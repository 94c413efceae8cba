"""ISSUE 40: the device scopes (``docqa_tpu/ops/scopes.py``) — a closed
vocabulary, opened where the work is in all three trunks, and HLO
metadata only.  Per block kind, at toy sizes on the CPU: the compiled
text of the batcher's prefill AND decode program holds every scope the
issue's table gives that block, and no ``dq.`` name outside the tuple."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from docqa_tpu.config import DecoderConfig, GenerateConfig  # noqa: E402
from docqa_tpu.engines import paged  # noqa: E402
from docqa_tpu.engines.generate import GenerateEngine  # noqa: E402
from docqa_tpu.ops.scopes import DEVICE_SCOPES, PREFIX, scope  # noqa: E402
from harness import arch  # noqa: E402

COMMON = {"embed", "proj", "cache_write", "attend", "mlp", "head", "sample"}
BLOCKS = {
    "gqa": (None, COMMON, DecoderConfig(
        vocab_size=256, hidden_dim=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=256)),
    "latent": ("deepseek_v2", COMMON | {"route", "experts"}, DecoderConfig(
        vocab_size=512, hidden_dim=128, num_layers=3, num_heads=4,
        num_kv_heads=1, head_dim=48, mlp_dim=256, max_seq_len=256,
        norm_eps=1e-6, block="mla_moe", q_lora_rank=64, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        rope_scaling_factor=40.0, rope_original_max_len=64,
        rope_mscale=0.707, rope_mscale_all_dim=0.707, first_dense_layers=1,
        num_experts=32, experts_per_token=4, expert_dim=64,
        num_shared_experts=2, expert_groups=4, expert_groups_per_token=2,
        routed_scale=16.0, experts_held_start=8, experts_held=8)),
    "hybrid": (None, COMMON | {"select", "state"}, DecoderConfig(
        vocab_size=256, hidden_dim=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=256,
        norm_eps=1e-6, block="sparse_linear", dtype="float32",
        mixer_types=("sparse", "linear", "linear", "sparse"),
        linear_heads=4, linear_head_dim=16, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=16, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_block_size=8, sparse_topk=4, sparse_init_blocks=1,
        sparse_window_size=8, sparse_dense_len=40)),
}


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def compiled(request):
    """(block, {"prefill" | "decode": compiled HLO text}) of a toy
    batcher's two programs."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    package, _scopes, cfg = BLOCKS[request.param]
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False,
        max_concurrent=4, decode_chunk=4)
    kw = {"seed": 0}
    if package:
        kw = {"params": arch.load(
            {"architecture": package}).weights.make_decoder_params(cfg, 1)}
    engine = GenerateEngine(cfg, gen=gen, use_flash=False, **kw)
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16, prefix_cache=False)
    try:
        pools = jax.eval_shape(lambda: paged.init_paged_pools(
            b.cfg, b.n_blocks, b.block_size))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), engine.params)
        rng = sds((2,), jnp.uint32)
        lane, flag = sds((4,), i32), sds((4,), jnp.bool_)
        packed = (sds((256,), i32),) * 4 + (lane,) * 2
        tables = sds((4, b.blocks_per_seq), i32)
        return request.param, {
            "prefill": b._get_prefill_fn().lower(
                params, pools, *packed, rng).compile().as_text(),
            "decode": b._get_decode_fn().lower(
                params, pools, tables, lane, lane, lane, flag, rng
            ).compile().as_text(),
        }
    finally:
        b.stop()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_blocks_program_holds_its_scopes_and_no_other(compiled, program):
    block, texts = compiled
    found = set(re.findall(re.escape(PREFIX) + r"([A-Za-z_0-9]+)",
                           texts[program]))
    assert found <= set(DEVICE_SCOPES), found - set(DEVICE_SCOPES)
    assert found == BLOCKS[block][1]


def test_a_decode_steps_scopes_are_inside_the_chunks_loop(compiled):
    _block, texts = compiled
    names = re.findall(r'op_name="([^"]*)"', texts["decode"])
    inside = {n for n in names if "/while/body/" in n and PREFIX in n}
    assert {re.search(r"dq\.([a-z_]+)", n).group(1) for n in inside} >= {
        "proj", "attend", "mlp", "head", "sample"}


def test_every_blocks_scopes_are_the_vocabulary_between_them():
    assert set().union(*(s for _p, s, _c in BLOCKS.values())) == set(
        DEVICE_SCOPES)
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES) == 11


@pytest.mark.parametrize("name", ["x", "", "Attend", "dq.attend", "layer0"])
def test_a_name_outside_the_vocabulary_is_refused(name):
    with pytest.raises(ValueError, match="no device scope"):
        scope(name)


@pytest.mark.parametrize("name", DEVICE_SCOPES)
def test_a_scope_is_metadata_only(name):
    """The lowered text is the same with and without it; the compiled
    text carries it, inside a loop's body too."""
    def step(x, scoped):
        def body(_i, x):
            if scoped:
                with scope(name):
                    return jnp.tanh(x @ x)
            return jnp.tanh(x @ x)
        return jax.lax.fori_loop(0, 3, body, x)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    plain = jax.jit(lambda x: step(x, False)).lower(x)
    scoped = jax.jit(lambda x: step(x, True)).lower(x)
    assert plain.as_text() == scoped.as_text()
    assert PREFIX + name not in plain.compile().as_text()
    assert f"/while/body/closed_call/{PREFIX}{name}/" in (
        scoped.compile().as_text())


@pytest.mark.parametrize("document", ["PERF.md", "docs/OBSERVABILITY.md"])
def test_every_scope_is_in_the_documents(document):
    """A scope added to the tuple is added to PERF.md 3's table (beside
    the metric that reads it) and to the operator's table in the same
    PR."""
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    at = text.index("Device scopes")
    assert [n for n in DEVICE_SCOPES if f"`{n}`" not in text[at:]] == []
