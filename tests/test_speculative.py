"""Prompt-lookup speculative decoding must be OUTPUT-EXACT with plain
greedy — drafts only decide how many argmax tokens one weight-read yields,
never which tokens.  Covered regimes:

* near-zero acceptance (random weights: drafts almost never match);
* full acceptance (a constant-output model: every draft chain matches);
* EOS at the very first token, EOS mid-stream, and budget truncation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.models.decoder import init_decoder_params

CFG = DecoderConfig(
    vocab_size=128, hidden_dim=32, num_layers=2, num_heads=4,
    num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=256,
    dtype="float32",
)

PROMPTS = [
    [5, 9, 11, 7, 9, 11, 7, 9],  # repetitive: bigrams predict continuation
    [3, 4, 5],
    [88, 17, 88, 17, 88],
]


def _engines(gen_cfg, params=None, spec_k=4):
    plain = GenerateEngine(CFG, gen_cfg, params=params, seed=3)
    spec = GenerateEngine(
        CFG,
        dataclasses.replace(gen_cfg, speculative_k=spec_k),
        params=params if params is not None else plain.params,
        seed=3,
    )
    if params is None:
        spec.params = plain.params  # identical weights either way
    return plain, spec


class TestExactness:
    def test_random_weights_low_acceptance(self):
        gen_cfg = GenerateConfig(max_new_tokens=12, prefill_buckets=(16,))
        plain, spec = _engines(gen_cfg)
        for batch in ([PROMPTS[0]], PROMPTS):
            assert spec.generate_ids(batch) == plain.generate_ids(batch)

    def test_constant_model_full_acceptance(self):
        # zero attention/MLP, all-ones embeddings, lm_head favoring token 7:
        # every position greedily emits 7, so the self-lookup chain 7->7
        # accepts every draft — the accepted-prefix path does the emitting
        params = init_decoder_params(jax.random.PRNGKey(0), CFG)
        params = {k: jnp.zeros_like(v) for k, v in params.items()}
        params["tok_emb"] = jnp.ones_like(params["tok_emb"])
        params["final_norm_g"] = jnp.ones_like(params["final_norm_g"])
        lm = np.zeros((CFG.hidden_dim, CFG.vocab_size), np.float32)
        lm[:, 7] = 1.0
        params["lm_head"] = jnp.asarray(lm)
        gen_cfg = GenerateConfig(max_new_tokens=10, prefill_buckets=(16,))
        plain, spec = _engines(gen_cfg, params=params)
        out_p = plain.generate_ids([[5, 9, 11]])
        out_s = spec.generate_ids([[5, 9, 11]])
        assert out_s == out_p
        assert out_p[0] == [7] * 10  # the constant model really is constant

    def test_eos_first_token(self):
        # constant model whose constant IS eos: zero tokens emitted
        params = init_decoder_params(jax.random.PRNGKey(0), CFG)
        params = {k: jnp.zeros_like(v) for k, v in params.items()}
        params["tok_emb"] = jnp.ones_like(params["tok_emb"])
        params["final_norm_g"] = jnp.ones_like(params["final_norm_g"])
        lm = np.zeros((CFG.hidden_dim, CFG.vocab_size), np.float32)
        lm[:, 2] = 1.0  # default eos_id == 2
        params["lm_head"] = jnp.asarray(lm)
        gen_cfg = GenerateConfig(max_new_tokens=8, prefill_buckets=(16,))
        plain, spec = _engines(gen_cfg, params=params)
        assert spec.generate_ids([[5, 9]]) == plain.generate_ids([[5, 9]]) == [[]]

    @pytest.mark.parametrize("max_new", [1, 3])
    def test_budget_smaller_than_verify_width(self, max_new):
        gen_cfg = GenerateConfig(max_new_tokens=max_new, prefill_buckets=(16,))
        plain, spec = _engines(gen_cfg, spec_k=6)
        out_p = plain.generate_ids(PROMPTS)
        out_s = spec.generate_ids(PROMPTS)
        assert out_s == out_p
        assert all(len(r) <= max_new for r in out_s)

    def test_default_promotion_gated_by_token_equality(self):
        """speculative_k=4 is the SHIPPED default (promoted from a bench
        knob)
        — this is its quality gate: the default config's output must
        equal speculative_k=0 token for token, both solo and through
        the continuous batcher."""
        from docqa_tpu.engines.serve import ContinuousBatcher

        assert GenerateConfig().speculative_k == 4
        default_cfg = GenerateConfig(
            max_new_tokens=12, prefill_buckets=(16,)
        )
        plain_cfg = dataclasses.replace(default_cfg, speculative_k=0)
        default_eng = GenerateEngine(CFG, default_cfg, seed=3)
        plain_eng = GenerateEngine(
            CFG, plain_cfg, params=default_eng.params
        )
        assert default_eng.generate_ids(PROMPTS) == plain_eng.generate_ids(
            PROMPTS
        )
        b = ContinuousBatcher(default_eng, n_slots=2, chunk=4, cache_len=64)
        try:
            assert b.spec_k == 4  # the default reaches the served path
            handles = [b.submit_ids(p, max_new_tokens=12) for p in PROMPTS]
            got = [h.result(timeout=300) for h in handles]
        finally:
            b.stop()
        assert got == plain_eng.generate_ids(PROMPTS)

    def test_sampling_falls_back_to_plain(self):
        # speculation is greedy-only; temperature>0 must route to the
        # stochastic program, not silently ignore the temperature
        gen_cfg = GenerateConfig(max_new_tokens=6, prefill_buckets=(16,))
        plain, spec = _engines(gen_cfg)
        a = spec.generate_ids([PROMPTS[0]], temperature=1.0, seed=11)
        b = plain.generate_ids([PROMPTS[0]], temperature=1.0, seed=11)
        assert a == b
