"""Tiny-scale rehearsal of call shapes only a chip-sized run takes.

The 7B-width weight paths, the wide slot counts, the overcommitted pool
and the speculation arms run at real size only on the chip — a signature
typo there would surface in a chip window.  These tests execute the same
API sequences at toy sizes on CPU.
"""

import jax

from docqa_tpu.config import DecoderConfig, GenerateConfig


TINY = DecoderConfig(
    vocab_size=256, hidden_dim=32, num_layers=1, num_heads=4,
    num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=128,
)


class TestBenchSevenBShapes:
    def test_quantized_host_init_engine_path(self):
        """int8 weights drawn on the host:
        init_quantized_decoder_params(host_init=True) ->
        GenerateEngine(cfg, GenerateConfig, params=...) -> generate_ids."""
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.models.quant import init_quantized_decoder_params

        params8 = init_quantized_decoder_params(
            jax.random.PRNGKey(0), TINY, host_init=True
        )
        eng = GenerateEngine(
            TINY,
            GenerateConfig(max_new_tokens=8, prefill_buckets=(16,)),
            params=params8,
        )
        out = eng.generate_ids([[5, 9, 11]], max_new_tokens=8)
        assert len(out[0]) <= 8

    def test_speculation_sweep_engine_variants(self):
        """Engines sharing one params tree with
        speculative_k in {0, 4, 8} must produce identical greedy output
        (speculation is output-exact by construction)."""
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.models.quant import init_quantized_decoder_params

        params8 = init_quantized_decoder_params(
            jax.random.PRNGKey(0), TINY, host_init=True
        )
        outs = []
        for spec_k in (0, 4, 8):
            eng = GenerateEngine(
                TINY,
                GenerateConfig(
                    max_new_tokens=12,
                    prefill_buckets=(16,),
                    speculative_k=spec_k,
                ),
                params=params8,
            )
            outs.append(eng.generate_ids([[5, 9, 11]], max_new_tokens=12)[0])
            del eng
        assert outs[0] == outs[1] == outs[2]

    def test_bf16_device_init_engine_path(self):
        """bf16 weights: init_decoder_params(param_dtype=bf16) ->
        engine -> generate_ids."""
        import jax.numpy as jnp

        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.models.decoder import init_decoder_params

        params7 = init_decoder_params(
            jax.random.PRNGKey(0), TINY, param_dtype=jnp.bfloat16
        )
        eng = GenerateEngine(
            TINY,
            GenerateConfig(max_new_tokens=8, prefill_buckets=(16,)),
            params=params7,
        )
        assert eng.generate_ids([[5, 9, 11]], max_new_tokens=8)


class TestBenchLoadSweepShapes:
    def test_batcher_32_slots_and_spec(self):
        """n_slots up to 32 and a speculative engine through the same
        ContinuousBatcher kwargs."""
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.serve import ContinuousBatcher

        eng = GenerateEngine(
            TINY,
            GenerateConfig(
                max_new_tokens=8, prefill_buckets=(16,), speculative_k=4
            ),
        )
        b = ContinuousBatcher(eng, n_slots=32, chunk=32, cache_len=128)
        try:
            prompts = [[7 + i % 13, 5, 9, 11, 3 + i % 7] for i in range(40)]
            handles = [b.submit_ids(p, max_new_tokens=8) for p in prompts]
            results = [h.result(timeout=120) for h in handles]
            assert len(results) == 40
            assert all(len(r) <= 8 for r in results)
        finally:
            b.stop()

    def test_kv_paging_sweep_call_shape(self):
        """A ContinuousBatcher with a FIXED kv_pool_tokens overcommit, a
        live sampler, and the serve_kv_blocks_used series whose peak is
        the pool's occupancy."""
        from docqa_tpu import obs
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.serve import ContinuousBatcher

        eng = GenerateEngine(
            TINY, GenerateConfig(max_new_tokens=8, prefill_buckets=(16,))
        )
        b = ContinuousBatcher(
            eng, n_slots=4, chunk=8, cache_len=128,
            kv_pool_tokens=2 * 128,  # half of the 4-slot worst case
        )
        tstore = obs.TelemetryStore(interval_s=0.2, points=100)
        sampler = obs.TelemetrySampler(
            tstore, batcher=b, sample_every_s=0.02, hbm_refresh_s=0
        ).start()
        try:
            prompts = [[7 + i % 13, 5, 9, 11, 3 + i % 7] for i in range(12)]
            handles = [b.submit_ids(p, max_new_tokens=8) for p in prompts]
            results = [h.result(timeout=120) for h in handles]
            assert all(len(r) <= 8 for r in results)
            occ = b.kv_block_occupancy()
            assert occ["blocks_total"] == (2 * 128) // occ["block_size"]
        finally:
            sampler.stop()
            b.stop()
        series = tstore.series("serve_kv_blocks_used")
        vals = [
            p.get("value") for p in (series or {}).get("points", [])
            if isinstance(p.get("value"), (int, float))
        ]
        assert vals and max(vals) > 0  # peak occupancy was observable
        assert max(vals) <= occ["blocks_total"]

    def test_prefix_reuse_ab_call_shape(self):
        """The SAME repeat-heavy session mix through two batchers
        (sharing disabled, then enabled) and the serve_prefix_* counter
        deltas.  The enabled arm must record warm
        hits; the disabled arm must record none."""
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.serve import ContinuousBatcher
        from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

        eng = GenerateEngine(
            TINY, GenerateConfig(max_new_tokens=8, prefill_buckets=(16,))
        )
        ctx = [(3 + i * 7) % 60 + 1 for i in range(140)]
        mix = [(ctx + [5 + q], "patient-0") for q in range(4)]
        hits = {}
        for label, enabled in (("off", False), ("on", True)):
            b = ContinuousBatcher(
                eng, n_slots=2, chunk=8, cache_len=256,
                prefix_cache=enabled,
            )
            h0 = DEFAULT_REGISTRY.counter("serve_prefix_hits").value
            try:
                assert b.prefix_cache_enabled is enabled
                # sequential like a session: later questions can hit
                for p, key in mix:
                    out = b.submit_ids(
                        p, max_new_tokens=8, prefix_key=key
                    ).result(timeout=120)
                    assert len(out) <= 8
            finally:
                b.stop()
            hits[label] = (
                DEFAULT_REGISTRY.counter("serve_prefix_hits").value - h0
            )
            assert b._alloc.blocks_in_use == 0
        assert hits["off"] == 0
        assert hits["on"] >= len(mix) - 1

    def test_delta_windowed_histogram_math(self):
        """The serve_tokens_per_chunk delta-mean formula
        (``decode_tokens_per_chunk`` in the benchmark)."""
        from docqa_tpu.runtime.metrics import Histogram

        h = Histogram("x")
        for v in (2.0, 4.0):
            h.observe(v)  # the "config 5" contamination
        count0 = h.count
        sum0 = (h.mean * count0) if count0 else 0.0
        for v in (10.0, 20.0, 30.0):
            h.observe(v)  # the "config 5b" window
        d_count = h.count - count0
        delta_mean = (h.mean * h.count - sum0) / d_count
        assert abs(delta_mean - 20.0) < 1e-9
