"""Int8 weight-only quantization (models/quant.py).

The capability this buys: a Mistral-7B-class decoder on ONE 16 GB v5e chip
(bf16 weights alone are ~14.5 GB and OOM with cache+workspace; int8 halves
both the tree and the bytes read per decode step).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.models.decoder import (
    _qmatmul,
    decoder_forward,
    init_decoder_params,
    init_kv_cache,
)
from docqa_tpu.models.quant import (
    init_quantized_decoder_params,
    is_quantized,
    quantize_array,
    quantize_decoder_params,
    should_quantize,
)

CFG = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=128,
    dtype="float32",
)


class TestQuantizeArray:
    def test_roundtrip_error_bounded(self):
        w = jnp.asarray(
            np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)
        )
        q, scale = quantize_array(w)
        assert q.dtype == jnp.int8 and scale.shape == (32,)
        deq = q.astype(jnp.float32) * scale[None, :]
        # per-column absmax: error ≤ scale/2 = absmax/254 per element
        err = np.abs(np.asarray(deq - w))
        bound = np.asarray(scale) / 2 + 1e-7
        assert (err <= bound[None, :]).all()

    def test_dead_column_no_nan(self):
        w = jnp.zeros((8, 4))
        q, scale = quantize_array(w)
        assert np.isfinite(np.asarray(scale)).all()
        assert (np.asarray(q) == 0).all()

    def test_should_quantize_selection(self):
        assert should_quantize("l0_wq") and should_quantize("lm_head")
        assert should_quantize("l11_w_down")
        assert not should_quantize("tok_emb")
        assert not should_quantize("l0_attn_norm_g")
        assert not should_quantize("final_norm_g")


class TestQuantizedForward:
    def test_logits_close_to_float(self):
        params = init_decoder_params(jax.random.PRNGKey(0), CFG)
        qparams = quantize_decoder_params(params)
        assert is_quantized(qparams) and not is_quantized(params)
        ids = np.array([[3, 9, 17, 4]], np.int32)
        lengths = np.array([4], np.int32)

        def run(p):
            cache = init_kv_cache(CFG, 1, max_len=32)
            logits, _ = decoder_forward(
                p, CFG, ids, cache, np.zeros((1,), np.int32),
                attn_lengths=lengths,
            )
            return np.asarray(logits)

        full = run(params)
        quant = run(qparams)
        # w8a16 per-channel: logits track closely relative to their spread
        denom = max(float(np.std(full)), 1e-6)
        rel = float(np.max(np.abs(full - quant))) / denom
        assert rel < 0.15, rel
        # greedy next-token choice is preserved on a comfortable margin
        assert int(full[0, -1].argmax()) == int(quant[0, -1].argmax())

    def test_generation_runs_and_matches_mostly(self):
        params = init_decoder_params(jax.random.PRNGKey(1), CFG)
        gen_cfg = GenerateConfig(max_new_tokens=16, prefill_buckets=(16,))
        full = GenerateEngine(CFG, gen_cfg, params=params)
        quant = GenerateEngine(
            CFG, gen_cfg, params=quantize_decoder_params(params)
        )
        a = full.generate_ids([[5, 9, 11]])[0]
        b = quant.generate_ids([[5, 9, 11]])[0]
        assert len(b) > 0
        # greedy paths may diverge after a near-tie; require a common prefix
        common = 0
        for x, y in zip(a, b):
            if x != y:
                break
            common += 1
        assert common >= 4, (a, b)

    def test_param_dtype_cast_preserves_int8(self):
        qparams = quantize_decoder_params(
            init_decoder_params(jax.random.PRNGKey(0), CFG)
        )
        eng = GenerateEngine(
            CFG, GenerateConfig(max_new_tokens=4, prefill_buckets=(16,)),
            params=qparams, param_dtype=jnp.bfloat16,
        )
        assert eng.params["l0_wq"].dtype == jnp.int8
        assert eng.params["l0_wq__scale"].dtype == jnp.float32
        assert eng.generate_ids([[3, 5]])[0] is not None


class TestInt8Branch:
    """``decoder._qmatmul``'s int8 branch (ISSUE 41): the per-column scale
    is applied to the dot's OUTPUT, in float32, so the dot's weight operand
    is a bare ``convert`` of the stored int8 array — nothing of the
    weight's shape is multiplied, and nothing is rounded per weight
    element."""

    @pytest.mark.parametrize("shape", [(4096, 1024), (4096, 4096),
                                       (4096, 14336)])
    @pytest.mark.parametrize("rows", [1, 4, 512])
    def test_scale_on_the_output(self, rows, shape):
        dtype = jnp.bfloat16
        rng = np.random.default_rng(rows * 31 + shape[1])
        fan_in, fan_out = shape
        w = rng.standard_normal(shape, np.float32) * fan_in ** -0.5
        q, scale = quantize_array(jnp.asarray(w))
        x = jnp.asarray(rng.standard_normal((1, rows, fan_in), np.float32),
                        dtype)
        params = {"w": q, "w__scale": scale}
        fn = jax.jit(lambda x, p: _qmatmul(x, p, "w", dtype))

        # numerics: against the float32 product, at least as close as the
        # operand form (scale on the weight, rounded to bf16 per element)
        want = np.asarray(x, np.float32) @ (
            np.asarray(q, np.float32) * np.asarray(scale)[None, :])
        out = fn(x, params)
        assert out.dtype == dtype and out.shape == (1, rows, fan_out)
        got = np.asarray(out, np.float32)
        operand_form = np.asarray(jax.jit(
            lambda x, q, s: x @ (q.astype(dtype) * s.astype(dtype)[None, :])
        )(x, q, scale), np.float32)
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        err_operand = np.linalg.norm(operand_form - want) / np.linalg.norm(want)
        assert err <= err_operand + np.finfo(np.float32).eps, (
            err, err_operand)
        assert err < 2.0 ** -8  # the output's two bf16 roundings

        # structure, on the lowered (platform-independent) text
        text = fn.lower(x, params).as_text()
        wt = f"tensor<{fan_in}x{fan_out}x"
        ops = [l for l in text.splitlines() if " = stablehlo." in l]
        made = [  # every op whose RESULT (its last type) has that shape
            l for l in ops if re.findall(r"tensor<[^>]*>", l)[-1].startswith(wt)
        ]
        assert made and all("stablehlo.convert" in m for m in made), made
        dots = [l for l in ops if "stablehlo.dot_general" in l]
        assert len(dots) == 1 and f"{wt}bf16>" in dots[0], dots
        # the ONE multiply is the scale, in float32, on the dot's result
        scaled = [l for l in ops if "stablehlo.multiply" in l]
        assert len(scaled) == 1 and scaled[0].rstrip().endswith(
            f"tensor<1x{rows}x{fan_out}xf32>"), scaled


class TestDirectInt8Init:
    def test_incremental_init_structure(self):
        qparams = init_quantized_decoder_params(jax.random.PRNGKey(0), CFG)
        assert is_quantized(qparams)
        assert qparams["l0_wq"].dtype == jnp.int8
        assert qparams["tok_emb"].dtype == jnp.bfloat16
        # int8 tree is ~half the bf16 bytes for the quantized weights
        qbytes = sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for k, v in qparams.items()
            if v.dtype == jnp.int8
        )
        assert qbytes > 0
        # forward runs
        cache = init_kv_cache(CFG, 1, max_len=32)
        logits, _ = decoder_forward(
            qparams, CFG, np.array([[3, 9]], np.int32), cache,
            np.zeros((1,), np.int32), attn_lengths=np.array([2], np.int32),
        )
        assert np.isfinite(np.asarray(logits)).all()


class TestConfigKnob:
    def test_quantize_weights_flag(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, quantize_weights=True)
        eng = GenerateEngine(
            cfg, GenerateConfig(max_new_tokens=4, prefill_buckets=(16,))
        )
        assert is_quantized(eng.params)
        assert eng.generate_ids([[3, 5, 9]])[0] is not None

    def test_flag_quantizes_supplied_float_params(self):
        # the path real HF checkpoints take: params= + quantize_weights=True
        import dataclasses

        cfg = dataclasses.replace(CFG, quantize_weights=True)
        params = init_decoder_params(jax.random.PRNGKey(0), CFG)
        eng = GenerateEngine(
            cfg, GenerateConfig(max_new_tokens=4, prefill_buckets=(16,)),
            params=params,
        )
        assert is_quantized(eng.params)
        assert eng.params["l0_wq"].dtype == jnp.int8

    def test_incremental_init_equals_quantized_float_init(self):
        # both consume decoder_param_schema with the same RNG stream, so
        # quantize(float_init) == incremental_int8_init exactly
        rng = jax.random.PRNGKey(7)
        a = quantize_decoder_params(init_decoder_params(rng, CFG))
        b = init_quantized_decoder_params(rng, CFG)
        assert set(a) == set(b)
        for k in a:
            if a[k].dtype == jnp.int8 or k.endswith("__scale"):
                np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))

    def test_host_init_coherent_with_host_float_init(self):
        # the serving engines' path: both host_init branches share one numpy
        # stream, so the int8 tree is the quantization of the float tree
        # (within numpy-vs-XLA rounding of the quantizer itself)
        rng = jax.random.PRNGKey(7)
        f = init_decoder_params(rng, CFG, param_dtype=jnp.float32,
                                host_init=True)
        q = init_quantized_decoder_params(rng, CFG, host_init=True)
        assert set(q) == {
            k2
            for k in f
            for k2 in (
                [k, k + "__scale"]
                if q.get(k) is not None and q[k].dtype == jnp.int8
                else [k]
            )
        }
        for k, w in f.items():
            if q[k].dtype != jnp.int8:
                continue
            deq = np.asarray(q[k], np.float32) * np.asarray(
                q[k + "__scale"], np.float32
            )[None, :]
            err = np.abs(deq - np.asarray(w, np.float32))
            # quantization error bounded by scale/2 per element
            bound = np.asarray(q[k + "__scale"], np.float32)[None, :] * 0.51
            assert (err <= bound).all()


class TestQuantizedTP:
    def test_sharded_quantized_generation(self, mesh_tp8):
        cfg = DecoderConfig(
            vocab_size=256, hidden_dim=64, num_layers=2, num_heads=8,
            num_kv_heads=8, head_dim=8, mlp_dim=128, max_seq_len=128,
            dtype="float32",
        )
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        qparams = quantize_decoder_params(params)
        gen_cfg = GenerateConfig(max_new_tokens=6, prefill_buckets=(16,))
        solo = GenerateEngine(cfg, gen_cfg, params=qparams).generate_ids(
            [[5, 9, 11]]
        )[0]
        tp = GenerateEngine(
            cfg, gen_cfg, params=qparams, mesh=mesh_tp8
        ).generate_ids([[5, 9, 11]])[0]
        assert tp == solo  # TP sharding of int8+scales is numerics-neutral


class TestInt4:
    def test_grouped_roundtrip_error_bounded(self):
        from docqa_tpu.models.quant import quantize_array_int4

        w = jnp.asarray(
            np.random.default_rng(0).normal(size=(256, 32)).astype(np.float32)
        )
        q, scale = quantize_array_int4(w)
        assert str(q.dtype) == "int4"
        assert q.shape == (2, 128, 32)  # 3-D grouped store (fusion-safe)
        assert scale.shape == (2, 32)  # 256 / group(128)
        deq = (
            np.asarray(q, np.float32) * np.asarray(scale)[:, None, :]
        ).reshape(256, 32)
        err = np.abs(deq - np.asarray(w))
        # per-group absmax: error bounded by half a step of that group
        bound = np.repeat(np.asarray(scale), 128, axis=0) * 0.5 + 1e-7
        assert np.all(err <= bound)

    def test_small_in_dim_group_clamps(self):
        from docqa_tpu.models.quant import quantize_array_int4

        w = jnp.ones((48, 8), jnp.float32)
        q, scale = quantize_array_int4(w)
        assert scale.shape[0] * (48 // scale.shape[0]) == 48

    def test_int4_forward_close(self):
        params = init_decoder_params(jax.random.PRNGKey(0), CFG)
        q4 = quantize_decoder_params(params, bits=4)
        ids = np.array([[3, 9, 17, 4]], np.int32)
        lengths = np.array([4], np.int32)

        def run(p):
            cache = init_kv_cache(CFG, 1, max_len=32)
            logits, _ = decoder_forward(
                p, CFG, ids, cache, np.zeros((1,), np.int32),
                attn_lengths=lengths,
            )
            return np.asarray(logits)

        full = run(params)
        quant = run(q4)
        denom = max(float(np.std(full)), 1e-6)
        rel = float(np.max(np.abs(full - quant))) / denom
        # grouped int4 at this TINY config degenerates to per-column
        # (hidden 64 < group 128 → one group), the worst case for 15
        # levels; real configs get 32+ groups per column.  The bound here
        # only guards against a broken dequant (order-of-magnitude blowup
        # or NaN), not production quality.
        assert np.isfinite(rel) and rel < 3.0, rel

    def test_int4_greedy_generation_deterministic(self):
        """Int4 generation must be internally deterministic (same engine,
        same prompt, same greedy tokens) and produce a non-trivial
        rollout — guards a dequant regression that a single loose
        forward-error bound would miss."""
        gen_cfg = GenerateConfig(max_new_tokens=16, prefill_buckets=(16,))
        params = init_decoder_params(jax.random.PRNGKey(3), CFG)
        eng = GenerateEngine(
            CFG, gen_cfg, params=quantize_decoder_params(params, bits=4)
        )
        a = eng.generate_ids([[5, 9, 11]])[0]
        b = eng.generate_ids([[5, 9, 11]])[0]
        assert a == b
        assert len(a) >= 4, a
        # no float-prefix expectation here: at this TINY config the group
        # degenerates to the whole 64-row column (15 levels), where greedy
        # divergence from float at token 1 is legitimate; the roundtrip
        # bound test above covers dequant numerics at real group shapes

    def test_int4_engine_via_config_knob(self):
        import dataclasses

        cfg4 = dataclasses.replace(CFG, quantize_weights=True, quant_bits=4)
        eng = GenerateEngine(
            cfg4, GenerateConfig(max_new_tokens=8, prefill_buckets=(16,))
        )
        assert any(str(v.dtype) == "int4" for v in eng.params.values())
        out = eng.generate_ids([[5, 9, 11]], max_new_tokens=8)[0]
        assert len(out) <= 8

    def test_int4_host_init_matches_device_init_structure(self):
        a = init_quantized_decoder_params(
            jax.random.PRNGKey(0), CFG, host_init=True, bits=4
        )
        b = init_quantized_decoder_params(
            jax.random.PRNGKey(0), CFG, host_init=False, bits=4
        )
        assert set(a) == set(b)
        for k in a:
            assert a[k].shape == b[k].shape, k
            assert a[k].dtype == b[k].dtype, k

    def test_int4_tree_is_half_of_int8_except_lm_head(self):
        p8 = init_quantized_decoder_params(jax.random.PRNGKey(0), CFG, bits=8)
        p4 = init_quantized_decoder_params(jax.random.PRNGKey(0), CFG, bits=4)

        def bits_total(p):
            total = 0
            for k, v in p.items():
                if str(v.dtype) == "int4":
                    total += int(np.prod(v.shape)) * 4
                elif v.dtype == jnp.int8:
                    total += int(np.prod(v.shape)) * 8
            return total

        # lm_head stays int8 in int4 mode (output-projection quality);
        # everything else halves
        assert str(p4["lm_head"].dtype) == "int8"
        lm_bits = int(np.prod(p8["lm_head"].shape)) * 8
        assert bits_total(p4) == (bits_total(p8) - lm_bits) // 2 + lm_bits

    def test_int4_tp_sharding_compiles(self):
        import dataclasses

        from docqa_tpu.runtime.mesh import host_cpu_mesh

        mesh = host_cpu_mesh(8, data=1)
        cfg4 = dataclasses.replace(
            CFG,
            quantize_weights=True,
            quant_bits=4,
            num_heads=8,
            num_kv_heads=8,
            head_dim=8,
        )
        eng = GenerateEngine(
            cfg4,
            GenerateConfig(max_new_tokens=4, prefill_buckets=(16,)),
            mesh=mesh,
        )
        out = eng.generate_ids([[5, 9, 11]], max_new_tokens=4)[0]
        assert len(out) <= 4
