"""ISSUE 40 (and, at the end, ISSUE 53: the second axis, the layer
kind): the device scopes (``docqa_tpu/ops/scopes.py``) — a closed
vocabulary, opened where the work is in all three trunks, and HLO
metadata only.  Per block kind, at toy sizes on the CPU: the compiled
text of the batcher's prefill AND decode program holds every scope the
issue's table gives that block, and no ``dq.`` name outside the tuple."""

import contextlib
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
from docqa_tpu.models import hybrid  # noqa: E402
from docqa_tpu.ops.scopes import (  # noqa: E402
    DEVICE_SCOPES,
    FFN_KINDS,
    KIND_PREFIX,
    PREFIX,
    layer_kind,
    scope,
)
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


def lowered_programs(cfg, package=None):
    """{"prefill" | "decode": ``Lowered``} of a toy batcher's two
    programs, traced now."""
    from docqa_tpu.engines.serve import ContinuousBatcher

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
        return {
            "prefill": b._get_prefill_fn().lower(
                params, pools, *packed, rng),
            "decode": b._get_decode_fn().lower(
                params, pools, tables, lane, lane, lane, flag, rng),
        }
    finally:
        b.stop()


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def compiled(request):
    """(block, {"prefill" | "decode": compiled HLO text}) of a toy
    batcher's two programs."""
    package, _scopes, cfg = BLOCKS[request.param]
    return request.param, {
        program: lowered.compile().as_text()
        for program, lowered in lowered_programs(cfg, package).items()}


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


# ---- ISSUE 53: the layer KIND, the second axis ------------------------------

KINDS = tuple(hybrid.MIXERS) + FFN_KINDS
FFN_PHASES = {"mlp", "route", "experts"}
# the two stacks whose cells read the step by kind, at toy widths: window
# + global attention over a dense and two routed layers (Trinity), and
# state-space + attention over dense layers (Jamba2)
STACKS = {
    "window_attention_routed": (
        {"window", "attention", "dense", "routed"}, DecoderConfig(
            vocab_size=256, hidden_dim=64, num_layers=3, num_heads=4,
            num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=256,
            norm_eps=1e-5, block="sparse_linear", dtype="float32",
            mixer_types=("window", "attention", "window"),
            sliding_window=48, qk_norm=True, use_output_gate=True,
            use_output_norm=False, sandwich_norm=True, scale_emb=8.0,
            first_dense_layers=1, num_experts=16, experts_held=4,
            experts_held_start=4, experts_per_token=4, expert_dim=32,
            num_shared_experts=1, routed_scale=2.826,
            router_score="sigmoid", router_bias=True, router_norm=True)),
    "mamba_attention": (
        {"mamba", "attention", "dense"}, DecoderConfig(
            vocab_size=256, hidden_dim=64, num_layers=2, num_heads=4,
            num_kv_heads=1, head_dim=16, mlp_dim=128, max_seq_len=256,
            norm_eps=1e-6, block="sparse_linear", dtype="float32",
            mixer_types=("mamba", "attention"), qk_norm=False,
            use_output_gate=False, use_output_norm=False,
            tie_embeddings=True, ssm_state_dim=8, ssm_conv_width=4,
            ssm_dt_rank=8, ssm_expand=2)),
}


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request):
    """(the stack's kinds, {program: (lowered text, lowered text with
    ``layer_kind`` a no-op, the ``op_name``s of the compiled text)})."""
    kinds, cfg = STACKS[request.param]
    real = lowered_programs(cfg)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid, "layer_kind",
                      lambda name: contextlib.nullcontext())
        bare = lowered_programs(cfg)
    return kinds, {
        program: (
            real[program].as_text(), bare[program].as_text(),
            # XLA joins the names of ops it merged with ";"
            [part for name in re.findall(
                r'op_name="([^"]*)"', real[program].compile().as_text())
             for part in name.split(";")])
        for program in real
    }


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_kind_scope_is_metadata_only(stack, program):
    """The lowered text of a served program is the parent's, byte for
    byte: what the compile cache keys and what the chip runs."""
    _kinds, programs = stack
    real, bare, names = programs[program]
    assert real == bare
    assert KIND_PREFIX not in real
    assert any(KIND_PREFIX in n for n in names)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_layers_ops_carry_one_kind_and_the_phase_inside_it(stack, program):
    kinds, programs = stack
    names = programs[program][2]
    found = {}
    for name in names:
        kind = re.findall(r"dk\.([a-z_]+)", name)
        phase = re.findall(r"dq\.([a-z_]+)", name)
        assert len(kind) <= 1, name
        if kind and phase:
            assert name.index(KIND_PREFIX) < name.index(PREFIX), name
        if phase and phase[-1] in FFN_PHASES | {"attend", "select"}:
            assert kind, name  # a layer's body: never outside a kind
        if phase and phase[-1] in {"embed", "head", "sample"}:
            assert not kind, name
        if kind:
            found.setdefault(kind[0], set()).update(phase[-1:])
    assert set(found) == kinds
    for kind, phases in found.items():
        if kind in FFN_KINDS:
            assert phases <= FFN_PHASES, (kind, phases)
        else:  # a mixer half: its projections and what the engine's
            # ``mix`` closure does
            assert "proj" in phases and not phases & FFN_PHASES, (
                kind, phases)
    assert found["dense"] == {"mlp"}
    if "routed" in kinds:
        assert found["routed"] == FFN_PHASES
        assert found["window"] == found["attention"] == {
            "proj", "cache_write", "attend"}
    if "mamba" in kinds:
        assert found["mamba"] == {"proj", "state"}
    if program == "decode":  # inside the chunk's loop (XLA may hoist a
        # constant of the weights, a state-space layer's ``-exp(A_log)``)
        # (a reducer's own ops carry the tail of the name alone)
        assert all("/while/body/" in n for n in names if KIND_PREFIX in n
                   and PREFIX in n and n.startswith("jit("))


def test_the_kinds_are_the_mixers_and_the_two_feed_forwards():
    assert KINDS == ("sparse", "attention", "window", "linear", "retention",
                     "mamba", "dense", "routed")
    assert KIND_PREFIX == "dk." and KIND_PREFIX != PREFIX
    assert set().union(*(k for k, _c in STACKS.values())) <= set(KINDS)
    assert not set(KINDS) & set(DEVICE_SCOPES)
    # ``benchmark/harness/xplane_kinds.py`` reads ``dk\.([a-z_]+)``
    assert all(re.fullmatch(r"[a-z_]+", k) for k in KINDS)
    # one list of mixer kinds: ``scopes.py`` names none of them in code
    with open(os.path.join(ROOT, "docqa_tpu", "ops", "scopes.py"),
              encoding="utf-8") as f:
        code = f.read().split('"""', 2)[2]
    assert [k for k in hybrid.MIXERS if f'"{k}"' in code] == []


@pytest.mark.parametrize("name", [
    "x", "", "Window", "dk.window", "layer0", "mlp", "attend", "global"])
def test_a_kind_outside_the_vocabulary_is_refused(name):
    with pytest.raises(ValueError, match="no layer kind"):
        layer_kind(name)


@pytest.mark.parametrize("name", KINDS)
def test_a_kind_encloses_a_phase(name):
    def step(x):
        with layer_kind(name):
            with scope("proj"):
                return jnp.tanh(x @ x)

    text = jax.jit(step).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    assert f"/{KIND_PREFIX}{name}/{PREFIX}proj/" in text


@pytest.mark.parametrize("document", ["PERF.md", "docs/OBSERVABILITY.md"])
def test_every_kind_is_in_the_documents(document):
    """PERF.md 3's row of kind scopes (beside the metric that reads
    each) and the operator's second axis, in the same PR as a kind."""
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    at = text.index("Device scopes")
    assert [k for k in KINDS if f"`{KIND_PREFIX}{k}`" not in text[at:]] == []
