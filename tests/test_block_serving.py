"""A block kind is one record, the choice of kernel one function (ISSUE 47).

* ``models/decoder.kernel_forms``: every rule of the four forms, the cases
  the three deleted predicates' tests held (the sparse pages' nine, the
  scan's four, the grouped product's), the paged geometry through it and
  (ISSUE 50) the latent block's own answer to ``paged``;
* the five benchmarked block settings at toy widths (the compile audit's
  own toy configurations): what each record says, that the batcher, the
  solo engine and the sharding rules import no ``is_latent`` /
  ``is_hybrid``, and that the batcher's refusals are the parent's, word
  for word.
"""

import ast
import dataclasses
import os
import types

import jax
import pytest

from docqa_tpu.analysis.compile_audit import SERVE_CFGS
from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.models.decoder import (
    block_serving,
    decoder_param_schema,
    kernel_forms,
)
from docqa_tpu.models.hybrid import SPARSE_SUMS
from docqa_tpu.models.latent import MOE_PREFILL_SUMS, MOE_SUMS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# kernel-sized geometries (nothing is traced: a configuration is enough)
GQA = DecoderConfig(num_kv_heads=8, head_dim=128)
SELECTS = dataclasses.replace(
    GQA, block="sparse_linear", num_layers=2, num_kv_heads=2,
    mixer_types=("sparse", "linear"), sparse_block_size=64)
SCANS = dataclasses.replace(
    GQA, block="sparse_linear", num_layers=2, num_kv_heads=1,
    mixer_types=("mamba", "attention"))
ROUTES = dataclasses.replace(
    GQA, block="mla_moe", num_layers=2, num_kv_heads=1, first_dense_layers=1,
    num_experts=8)
LATENT = dataclasses.replace(ROUTES, head_dim=192, kv_lora_rank=512)
MESH = types.SimpleNamespace(n_devices=4, n_model=4)


@pytest.mark.parametrize("form, cfg, change, kw, chosen", [
    # a sparse layer's blocks as pages (nine cases of the predicate that went)
    ("sparse_paged", SELECTS, {}, {}, True),
    ("sparse_paged", SELECTS, {}, dict(on_tpu=False), False),  # a CPU
    ("sparse_paged", SELECTS, {}, dict(on_tpu=None), False),  # asks: a CPU
    ("sparse_paged", SELECTS, {}, dict(mesh=MESH), False),  # GSPMD: XLA
    ("sparse_paged", SELECTS, dict(head_dim=16), {}, False),  # geometry
    ("sparse_paged", SELECTS, dict(num_kv_heads=3), {}, False),
    ("sparse_paged", SELECTS, dict(sparse_block_size=8), {}, False),
    ("sparse_paged", SELECTS, dict(sparse_block_size=24), {}, False),
    ("sparse_paged", SELECTS, dict(dtype="float32", num_kv_heads=3), {},
     True),
    ("sparse_paged", SELECTS, {}, dict(block_size=None), False),  # a prefill
    ("sparse_paged", GQA, {}, {}, False),  # no layer selects
    # a state-space layer's prefill scan (four cases of the predicate that went)
    ("scan", SCANS, {}, {}, True),
    ("scan", SCANS, {}, dict(on_tpu=False), False),
    ("scan", SCANS, {}, dict(on_tpu=None), False),
    ("scan", SCANS, {}, dict(mesh=MESH), False),
    ("scan", SELECTS, {}, {}, False),  # no layer scans
    # a routed layer's grouped product (the predicate that went read the backend itself)
    ("grouped", ROUTES, {}, {}, True),
    ("grouped", ROUTES, {}, dict(on_tpu=False), False),
    ("grouped", ROUTES, {}, dict(on_tpu=None), False),
    ("grouped", ROUTES, {}, dict(mesh=MESH), False),
    ("grouped", ROUTES, dict(num_experts=0), {}, False),  # does not route
    ("grouped", GQA, {}, {}, False),
    # live pages in place: the geometry rule, per device of the mesh
    ("paged", GQA, {}, {}, True),
    ("paged", GQA, {}, dict(on_tpu=False), False),
    ("paged", GQA, {}, dict(mesh=MESH), True),  # 2 kv heads a device
    ("paged", GQA, dict(num_kv_heads=6), dict(mesh=MESH), False),
    ("paged", GQA, dict(head_dim=64), {}, False),
    ("paged", SCANS, {}, {}, True),  # the plain attention layer's one head
    # the latent block's one shared row a token, through a kernel of its
    # own (ISSUE 50): a TPU, NO mesh, pages that are whole tiles of the
    # pool's type, a latent of whole registers — never the GQA geometry
    ("paged", LATENT, {}, {}, True),
    ("paged", LATENT, {}, dict(on_tpu=False), False),  # a CPU
    ("paged", LATENT, {}, dict(on_tpu=None), False),  # asks: a CPU
    ("paged", LATENT, {}, dict(mesh=MESH), False),  # GSPMD: XLA
    ("paged", LATENT, {}, dict(block_size=None), False),  # a prefill
    ("paged", LATENT, {}, dict(block_size=8), False),  # half a bf16 tile
    ("paged", LATENT, dict(dtype="float32"), dict(block_size=8), True),
    ("paged", LATENT, dict(kv_lora_rank=32), {}, False),  # a toy's latent
    ("paged", LATENT, dict(head_dim=128), {}, True),  # not the GQA rule
    ("grouped", LATENT, {}, {}, True),  # and its routed product as before
])
def test_kernel_forms_holds_every_rule(form, cfg, change, kw, chosen):
    """``on_tpu`` None: a caller that observed nothing enters through the
    paged forwards, which ask the backend for it (a CPU here)."""
    from docqa_tpu.engines.paged import _forms

    cfg = dataclasses.replace(cfg, **change)
    args = {**dict(on_tpu=True, mesh=None, block_size=16), **kw}
    if args["on_tpu"] is None:
        assert jax.default_backend() == "cpu"
        forms = _forms(cfg, None, None, args["mesh"], args["block_size"])
    else:
        forms = kernel_forms(cfg, **args)
    assert getattr(forms, form) is chosen


def test_the_engine_asks_once_and_the_batcher_reads_its_answer():
    """``use_flash`` handed to the engine is the observation; the batcher's
    tuple is the engine's at the batcher's page size."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    cfg = SERVE_CFGS["serve_ssm"]()
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False)
    for flash in (True, False):
        engine = GenerateEngine(cfg, gen, use_flash=flash)
        assert engine.use_flash is flash
        assert engine.kernel_forms(block_size=16) == kernel_forms(
            cfg, on_tpu=flash, mesh=None, block_size=16)
        assert engine.kernel_forms(block_size=16).scan is flash
    b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128,
                          kv_block_size=8, prefix_cache=False)
    try:
        assert b._kernels == engine.kernel_forms(block_size=8)
        assert b._block is engine.block
    finally:
        b.stop()


# ---- the five benchmarked block settings ---------------------------------------

# the batcher's refusal of each setting with everything on, as the parent
# commit (5c6328d) worded it
REFUSALS = {
    "serve": None,
    "serve_latent": (
        'DecoderConfig(block="mla_moe") is served without '
        "generate.prefix_cache and generate.speculative_k: set prefix_cache "
        "false and speculative_k 0"),
    "serve_hybrid": (
        'DecoderConfig(block="sparse_linear") is served without '
        "generate.prefix_cache and generate.speculative_k and "
        "qos.preemption: set prefix_cache false, speculative_k 0 and "
        "qos.preemption off"),
    "serve_ssm": (
        'DecoderConfig(block="sparse_linear") is served without '
        "generate.prefix_cache and generate.speculative_k and "
        "qos.preemption: set prefix_cache false, speculative_k 0 and "
        "qos.preemption off"),
    "serve_loop": (
        "DecoderConfig(loop_steps=4) is served without "
        "generate.prefix_cache and generate.speculative_k: set prefix_cache "
        "false and speculative_k 0"),
    "serve_retention": (
        'DecoderConfig(block="sparse_linear") is served without '
        "generate.prefix_cache and generate.speculative_k and "
        "qos.preemption: set prefix_cache false, speculative_k 0 and "
        "qos.preemption off"),
}
# (decode sums, prefill sums, lane state, own parameter rules, solo engine)
RECORDS = {
    "serve": ((), (), False, False, True),
    "serve_latent": (MOE_SUMS, MOE_PREFILL_SUMS, False, True, False),
    "serve_hybrid": (SPARSE_SUMS, (), True, True, False),
    "serve_ssm": ((), (), True, True, False),
    "serve_loop": ((), (), False, False, False),
    "serve_retention": ((), (), True, True, False),
}


def _imported_names(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    return {
        alias.name for node in ast.walk(tree)
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    } | {node.id for node in ast.walk(tree) if isinstance(node, ast.Name)}


@pytest.mark.parametrize("path", [
    "docqa_tpu/engines/serve.py", "docqa_tpu/engines/generate.py",
    "docqa_tpu/parallel/sharding.py"])
def test_the_surroundings_import_no_kind_predicate(path):
    names = _imported_names(path)
    assert not names & {"is_latent", "is_hybrid", "kv_entries"}


@pytest.mark.parametrize("workload", sorted(SERVE_CFGS))
def test_a_block_settings_record_and_its_refusal_word_for_word(workload):
    from docqa_tpu.engines.paged import init_paged_pools
    from docqa_tpu.engines.qos import QoSPolicy
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.parallel.sharding import (
        decoder_param_pspecs,
        paged_pool_pspecs,
    )
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    cfg = SERVE_CFGS[workload]()
    block = block_serving(cfg)
    sums, prefill_sums, lane_state, own_rules, solo = RECORDS[workload]
    assert block.step_sum_names == sums
    assert block.prefill_sum_names == prefill_sums
    assert (block.step_sums is None) == (not sums)
    assert (block.prefill_sums is None) == (not prefill_sums)
    assert block.lane_state is lane_state
    assert (block.param_pspecs is not None) is own_rules
    assert (block.pool_pspecs is not None) is own_rules
    assert (block.solo is None) is solo
    # the merged rules name the tree's parameters and the pools
    # (a kind's own rules may name what a tree of other options holds)
    named = set(decoder_param_pspecs(cfg, "model"))
    tree = {name for name, *_ in decoder_param_schema(cfg)}
    assert named >= tree and (own_rules or named == tree)
    pools = jax.eval_shape(lambda: init_paged_pools(cfg, 4, 8))
    assert set(paged_pool_pspecs(cfg, host_cpu_mesh(2))) == set(pools)

    # everything a kind can be refused with, on
    gen = dataclasses.replace(
        GenerateConfig(), prefix_cache=True, speculative_k=4,
        temperature=0.0, max_concurrent=2)
    engine = GenerateEngine(cfg, gen, seed=0)
    make = lambda: ContinuousBatcher(  # noqa: E731
        engine, n_slots=2, chunk=4, cache_len=256, kv_block_size=8,
        qos=QoSPolicy(preemption="on"))
    if REFUSALS[workload] is None:
        make().stop()
        return
    with pytest.raises(ValueError) as refused:
        make()
    assert str(refused.value) == REFUSALS[workload]
    with pytest.raises(NotImplementedError, match="solo dense-cache engine"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=2)
