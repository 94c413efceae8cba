"""docqa-shardcheck Tier B: lower the device-plane programs, count their
collectives, and hold the counts to a checked-in budget.

Tier A (mesh-axes / spec-shape / donation, ``analysis/*.py``) proves the
*annotations* are coherent; this module proves what GSPMD actually
*derives* from them.  Each audited program is lowered AOT — abstract
``ShapeDtypeStruct`` inputs, no weights materialized — on three virtual
CPU meshes (1x1, 2x4, 1x8; ``--xla_force_host_platform_device_count=8``),
the partitioned module text is parsed, and every collective op is counted
against ``shard_budget.json``.  The contracts that previously lived only
in comments become red builds:

* **decoder (Megatron TP)** — exactly ONE all-reduce per Megatron block
  (the row-parallel ``wo`` and ``w_down`` projections: two blocks per
  layer), zero all-gathers: the column/row split keeps every other edge
  local.  A spec edit that replicates a weight or reshards an activation
  shows up as an extra all-gather/all-reduce here, not as a mystery 8x
  step-time regression on the pod.
* **ring attention** — exactly n-1 ``ppermute`` rotation rounds on an
  n-device ring (measured from the lowered loop trip count), two
  ppermutes (K and V) per round, nothing else.
* **fused retrieve** — exactly the two tiny all-gathers of the top-k
  merge (values + ids), zero all-reduces/all-gathers anywhere else on
  the path: the corpus scan itself never leaves the shard.

The budget also carries a **jit-root ledger**: every traced root the
package declares (enumerated by jit-purity's discovery pass, so the two
tiers can't disagree about what "traced" means) must be either covered by
an audit program or explicitly waived with a reason.  A new ``jax.jit``
site therefore fails the gate until its collective story is stated.

Entry points: ``scripts/shard_audit.py`` (CLI; CI uploads its ``--report``
JSON as the collective-count trend artifact) and ``pytest -m lint``
(tests/test_shard_audit.py).  See docs/SHARDING.md for the budget file
format and how to amend it deliberately.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# HLO instruction names counted from the partitioned module (sync and
# async-start forms; ``-done`` completes a counted start and is skipped).
HLO_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# mesh name -> (data, model); the three shapes every program must lower on
MESH_SHAPES: Dict[str, Tuple[int, int]] = {
    "1x1": (1, 1),
    "2x4": (2, 4),
    "1x8": (1, 8),
}

def default_budget_path() -> str:
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg_dir), "shard_budget.json")


# ---------------------------------------------------------------------------
# counting
# ---------------------------------------------------------------------------


def count_hlo_collectives(hlo_text: str) -> Dict[str, int]:
    """Collective instruction counts from (partitioned, optimized) HLO
    module text — ``%x = bf16[...] all-reduce(...)`` and the async
    ``all-reduce-start`` form; ``-done`` ops are completions, not new
    collectives."""
    out: Dict[str, int] = {}
    for op in HLO_COLLECTIVES:
        # result type may be a spacey tuple — `= (f32[..], f32[..])
        # all-to-all(` — so match anything between `=` and the opcode;
        # metadata op_names use the jax (underscore) spellings and cannot
        # collide with the hyphenated HLO opcodes
        out[op] = len(
            re.findall(rf"= .*? {re.escape(op)}(?:-start)?\(", hlo_text)
        )
    return out


def _walk_jaxprs(jaxpr) -> "list":
    """Depth-first eqn list over nested jaxprs (duck-typed: anything with
    ``.eqns`` or a ``.jaxpr`` attribute recurses)."""
    pairs = []
    stack = [jaxpr]
    seen = set()
    while stack:
        jx = stack.pop()
        jx = getattr(jx, "jaxpr", jx)  # ClosedJaxpr -> Jaxpr
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            pairs.append(eqn)
            for value in eqn.params.values():
                for sub in (
                    value if isinstance(value, (list, tuple)) else [value]
                ):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        stack.append(sub)
    return pairs


def jaxpr_ring_rounds(closed_jaxpr) -> List[int]:
    """Trip counts of every lowered loop whose body rotates KV shards
    (contains a ppermute) — the ring rounds the device actually runs, as
    opposed to the static op count in the module text."""
    rounds: List[int] = []
    for eqn in _walk_jaxprs(closed_jaxpr):
        if eqn.primitive.name not in ("scan", "while"):
            continue
        body = eqn.params.get("jaxpr") or eqn.params.get("body_jaxpr")
        if body is None:
            continue
        inner = [e.primitive.name for e in _walk_jaxprs(body)]
        if "ppermute" in inner:
            length = eqn.params.get("length")
            if length is not None:
                rounds.append(int(length))
    return rounds


# ---------------------------------------------------------------------------
# audit configs (small enough to lower in seconds, shardable on 1x8)
# ---------------------------------------------------------------------------


def _audit_decoder_cfg():
    from docqa_tpu.config import DecoderConfig

    # every sharded dim divisible by 8 (the largest model-axis size)
    return DecoderConfig(
        vocab_size=128,
        hidden_dim=64,
        num_layers=2,
        num_heads=8,
        num_kv_heads=8,
        head_dim=8,
        mlp_dim=128,
        max_seq_len=64,
    )


def _audit_latent_cfg():
    """The latent-attention / routed-expert block (models/latent.py):
    heads, the dense and shared MLP widths and the experts HELD all
    divisible by 8 — the held range is what the ``model`` axis divides."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=128, hidden_dim=64, num_layers=2, num_heads=8,
        num_kv_heads=1, head_dim=24, mlp_dim=128, max_seq_len=64,
        block="mla_moe", q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling_factor=40.0, rope_original_max_len=32,
        rope_mscale=0.707, rope_mscale_all_dim=0.707, first_dense_layers=1,
        num_experts=32, experts_per_token=2, expert_dim=16,
        num_shared_experts=1, expert_groups=4, expert_groups_per_token=2,
        routed_scale=4.0, experts_held_start=0, experts_held=8,
    )


def _audit_ssm_cfg():
    """The same stack's other two mixer kinds: a state-space layer (its
    inner channels and the MLP width divisible by 8) and a plain attention
    layer whose ONE kv head is replicated; tied head."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=128, hidden_dim=64, num_layers=2, num_heads=8,
        num_kv_heads=1, head_dim=8, mlp_dim=128, max_seq_len=32,
        block="sparse_linear", mixer_types=("mamba", "attention"),
        qk_norm=False, use_output_gate=False, use_output_norm=False,
        tie_embeddings=True, ssm_state_dim=4, ssm_conv_width=4,
        ssm_dt_rank=8, ssm_expand=2,
    )


def _audit_hybrid_cfg():
    """The stack of mixer kinds (models/hybrid.py): a sparse and a linear
    layer; query heads, the linear heads and the MLP width divisible by 8,
    the sparse layer's 2 kv heads replicated."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=128, hidden_dim=64, num_layers=2, num_heads=8,
        num_kv_heads=2, head_dim=8, mlp_dim=128, max_seq_len=32,
        block="sparse_linear", mixer_types=("sparse", "linear"),
        linear_heads=8, linear_head_dim=8, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=16, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_block_size=8, sparse_topk=2, sparse_init_blocks=1,
        sparse_window_size=8, sparse_dense_len=16,
    )


def _audit_retention_cfg():
    """The same stack with its state-keeping kind that shares kv heads:
    two retention layers (8 query heads over 2 kv heads; k, v and the
    decay projection replicated beside a state pool whole on every
    device) — a stack in which NO layer keeps a row."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=128, hidden_dim=64, num_layers=2, num_heads=8,
        num_kv_heads=2, head_dim=8, mlp_dim=128, max_seq_len=32,
        block="sparse_linear", mixer_types=("retention", "retention"),
        use_output_gate=False, use_output_norm=False,
    )


def _audit_loop_cfg():
    """The GQA block's looped trunk, with the sandwich norms."""
    return dataclasses.replace(
        _audit_decoder_cfg(), loop_steps=4, sandwich_norm=True)


# the paged programs' toy configurations, one a block kind the batcher
# serves (``_audit_paged``: programs ``<name>_paged_decode`` /
# ``<name>_ragged_prefill``)
PAGED_CFGS = {
    "decoder": _audit_decoder_cfg,
    "latent": _audit_latent_cfg,
    "hybrid": _audit_hybrid_cfg,
    "ssm": _audit_ssm_cfg,
    "loop": _audit_loop_cfg,
    "retention": _audit_retention_cfg,
}


def _audit_encoder_cfg():
    from docqa_tpu.config import EncoderConfig

    return EncoderConfig(
        vocab_size=128,
        hidden_dim=32,
        num_layers=1,
        num_heads=4,
        mlp_dim=64,
        max_seq_len=16,
        embed_dim=32,
        dtype="float32",
    )


def _mesh(name: str):
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    data, model = MESH_SHAPES[name]
    return host_cpu_mesh(data * model, data=data)


def _decoder_abstract_args(cfg, batch: int, seq: int, cache_len: int):
    import jax
    import jax.numpy as jnp

    from docqa_tpu.models.decoder import decoder_param_schema

    params = {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if kind == "ones" else jnp.bfloat16
        )
        for name, kind, shape, _fan in decoder_param_schema(cfg)
    }
    cache = {
        f"{kv}{i}": jax.ShapeDtypeStruct(
            (batch, cache_len, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16
        )
        for i in range(cfg.num_layers)
        for kv in ("k", "v")
    }
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return params, cache, ids, lengths


def _audit_decoder(mesh_name: str, prefill: bool, pspec_fn=None):
    """Lower one decoder step under the Megatron layout; returns
    (collective counts, meta).  ``pspec_fn`` overrides
    ``decoder_param_pspecs`` so the mutation tests can audit a broken
    layout without editing the real one."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.models.decoder import decoder_forward
    from docqa_tpu.parallel.sharding import cache_pspecs, decoder_param_pspecs

    cfg = _audit_decoder_cfg()
    mesh = _mesh(mesh_name)
    batch, cache_len = 4, 32
    seq = 8 if prefill else 1
    params, cache, ids, lengths = _decoder_abstract_args(
        cfg, batch, seq, cache_len
    )
    pspecs = (pspec_fn or decoder_param_pspecs)(cfg, mesh.model_axis)
    cspecs = cache_pspecs(cfg, mesh)

    if prefill:

        def program(params, cache, ids, lengths):
            return decoder_forward(
                params, cfg, ids, cache,
                jax.numpy.zeros_like(lengths), attn_lengths=lengths,
                last_token_only=True,
            )

    else:

        def program(params, cache, ids, lengths):
            return decoder_forward(params, cfg, ids, cache, lengths)

    in_shardings = (
        {k: NamedSharding(mesh.mesh, pspecs[k]) for k in params},
        {k: NamedSharding(mesh.mesh, cspecs[k]) for k in cache},
        NamedSharding(mesh.mesh, P(mesh.data_axis, None)),
        NamedSharding(mesh.mesh, P(mesh.data_axis)),
    )
    compiled = (
        jax.jit(program, in_shardings=in_shardings)
        .lower(params, cache, ids, lengths)
        .compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    meta = {
        "num_layers": cfg.num_layers,
        # Megatron blocks: the row-parallel projections (attention wo,
        # MLP w_down) — each owes exactly one all-reduce on a TP mesh
        "megatron_blocks": 2 * cfg.num_layers,
        "model_parallel": mesh.n_model,
    }
    return counts, meta


def _audit_paged(mesh_name: str, prefill: bool, kind: str = "decoder"):
    """Lower the PAGED serving programs (engines/paged.py) under the
    same Megatron layout: the block-pool gather/scatter must not change
    the collective story — still exactly one all-reduce per Megatron
    block, zero all-gathers (the pool shards kv-heads over ``model``,
    its flat block-row axis is replicated, and every table index rides
    that unsharded axis).  This is the ISSUE's "unchanged collective
    budget" evidence for the paged KV tentpole.

    ``kind`` names the toy configuration (``PAGED_CFGS``); what its pools
    and its parameters take beyond the GQA rules is its record's
    (``models/serving.BlockServing``).

    ``latent``: the same two programs of the latent block, whose row pool
    is replicated and whose routed experts ride the ``model`` axis on
    their expert axis (models/latent.latent_param_pspecs).  They have
    to LOWER on every mesh; their collectives are recorded, not held to
    the Megatron count — GSPMD's handling of a per-expert loop over a
    sharded expert axis is not the exchange a deployment would run, and no
    cell runs this block across chips yet.

    ``hybrid``: the same two programs of the stack of mixer kinds
    (models/hybrid.py; its pools — rows, compressed keys, lane states, the
    slot map — replicated): lowered on every mesh, collectives recorded.
    ``ssm``: the same stack with a state-space and a plain attention layer
    (the state-space mixer divided along its inner channels, windows and
    states replicated with the rows of the one kv head).

    ``loop``: the GQA block's looped trunk (``loop_steps`` 4 with the
    sandwich norms): the step loop is ONE loop in the program, so the
    text holds each Megatron block's all-reduce once — the same count as
    the plain trunk, and held to it — and the pools, four ranges of rows
    along their unsharded row axis, keep their kv heads over ``model``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.engines.paged import (
        init_paged_pools,
        paged_decode_forward,
        ragged_prefill_forward,
    )
    from docqa_tpu.models.decoder import block_serving, kv_entries
    from docqa_tpu.parallel.sharding import (
        decoder_param_pspecs,
        paged_pool_pspecs,
    )

    cfg = PAGED_CFGS[kind]()
    mesh = _mesh(mesh_name)
    slots, block_size, n_blocks = 4, 8, 16
    rope_len = 32
    params, _cache, _ids, _lengths = _decoder_abstract_args(cfg, slots, 1, 8)
    pools = jax.eval_shape(
        lambda: init_paged_pools(cfg, n_blocks, block_size))
    pspecs = decoder_param_pspecs(cfg, mesh.model_axis)
    pool_specs = paged_pool_pspecs(cfg, mesh)
    replicated = NamedSharding(mesh.mesh, P())
    param_shardings = {
        k: NamedSharding(mesh.mesh, pspecs[k]) for k in params
    }
    pool_shardings = {
        k: NamedSharding(mesh.mesh, pool_specs[k]) for k in pools
    }

    if prefill:
        T = 128

        def program(params, pools, ids, seg, pos, dest, last_rows):
            return ragged_prefill_forward(
                params, cfg, pools, ids, seg, pos, dest, last_rows,
                rope_len=rope_len,
            )

        args = (
            params,
            pools,
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((T,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
        )
        in_shardings = (
            param_shardings, pool_shardings,
            replicated, replicated, replicated, replicated, replicated,
        )
    else:

        def program(params, pools, tables, tok, lengths):
            return paged_decode_forward(
                params, cfg, pools, tables, tok, lengths,
                block_size=block_size, rope_len=rope_len,
            )

        args = (
            params,
            pools,
            jax.ShapeDtypeStruct((slots, 4), jnp.int32),
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
        )
        in_shardings = (
            param_shardings, pool_shardings,
            replicated, replicated, replicated,
        )
    compiled = (
        jax.jit(program, in_shardings=in_shardings).lower(*args).compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    meta = {
        "num_layers": cfg.num_layers,
        "megatron_blocks": 2 * cfg.num_layers,
        "block_size": block_size,
        "model_parallel": mesh.n_model,
    }
    # by what the configuration says of itself: a kind with layers of its
    # own is not held to the Megatron count
    if block_serving(cfg).param_pspecs is not None:
        del meta["megatron_blocks"]
    if cfg.num_experts:
        meta["experts_held"] = cfg.experts_held
    if cfg.mixer_types:
        meta["mixer_types"] = list(cfg.mixer_types)
    if kv_entries(cfg) != 1:
        meta["loop_steps"] = cfg.loop_steps
    return counts, meta


def _attention_abstract_args():
    import jax
    import jax.numpy as jnp

    shape = (2, 16, 8, 8)  # [b, s, h, d]; s and h divisible by 8
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    return x, x, x


def _audit_ring(mesh_name: str):
    import jax

    from docqa_tpu.parallel.ring_attention import ring_attention

    mesh = _mesh(mesh_name)
    q, k, v = _attention_abstract_args()

    def program(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True)

    counts = count_hlo_collectives(
        jax.jit(program).lower(q, k, v).compile().as_text()
    )
    rounds = jaxpr_ring_rounds(jax.make_jaxpr(program)(q, k, v))
    meta = {
        "ring_size": mesh.n_model,
        "ring_rounds": sum(rounds),
        # K and V shards rotate per round; the static module has one loop
        "ppermute_per_round": 2,
    }
    return counts, meta


def _audit_ulysses(mesh_name: str):
    import jax

    from docqa_tpu.parallel.ring_attention import ulysses_attention

    mesh = _mesh(mesh_name)
    q, k, v = _attention_abstract_args()

    def program(q, k, v):
        return ulysses_attention(q, k, v, mesh, causal=True)

    counts = count_hlo_collectives(
        jax.jit(program).lower(q, k, v).compile().as_text()
    )
    return counts, {"group_size": mesh.n_model}


def _audit_retrieve(mesh_name: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.engines.retrieve import build_fused_search_program
    from docqa_tpu.models.encoder import init_encoder_params

    cfg = _audit_encoder_cfg()
    mesh = _mesh(mesh_name)
    params = jax.eval_shape(
        functools.partial(init_encoder_params, cfg=cfg),
        jax.random.PRNGKey(0),
    )
    batch, capacity = 4, 64
    ids = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    buf = jax.ShapeDtypeStruct((capacity, cfg.embed_dim), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)

    sharded = mesh.n_model > 1
    program = build_fused_search_program(
        cfg, mesh if sharded else None, k=4, masked=False
    )
    replicated = NamedSharding(mesh.mesh, P())
    in_shardings = (
        jax.tree_util.tree_map(lambda _: replicated, params),
        replicated,
        replicated,
        NamedSharding(
            mesh.mesh, P(mesh.model_axis, None) if sharded else P()
        ),
        replicated,
    )
    compiled = (
        jax.jit(program, in_shardings=in_shardings)
        .lower(params, ids, lengths, buf, count)
        .compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    return counts, {"row_shards": mesh.n_model if sharded else 1}


def _audit_retrieve_ivf(mesh_name: str):
    """Lower the mesh-native fused TIERED retrieve program
    (``engines/retrieve.py:build_tiered_search_program`` — encoder
    forward -> coarse probe over mesh-sharded int8 cell tiles -> exact
    tail scan): the cell tiles/scales/ids shard rows over ``model``,
    the coarse centroid score replicates, each shard scores its local
    tiles, and the only collective content is the 2-gather top-k merge
    (vals + ids) — the same budget the exact store's ``sharded_topk``
    pays.  1x1 lowers the single-device kernel and must be
    collective-free (docqa-meshindex; ROADMAP item 2)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.engines.retrieve import build_tiered_search_program
    from docqa_tpu.index.ivf import ivf_cell_specs
    from docqa_tpu.models.encoder import init_encoder_params

    cfg = _audit_encoder_cfg()
    mesh = _mesh(mesh_name)
    params = jax.eval_shape(
        functools.partial(init_encoder_params, cfg=cfg),
        jax.random.PRNGKey(0),
    )
    batch = 4
    n_cells, cap, n_spill, tail_rows = 16, 8, 4, 32  # cells divisible by 8
    ids = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cells = jax.ShapeDtypeStruct((n_cells, cap, cfg.embed_dim), jnp.int8)
    scale = jax.ShapeDtypeStruct((n_cells, cap), jnp.float32)
    cell_ids = jax.ShapeDtypeStruct((n_cells, cap), jnp.int32)
    centroids = jax.ShapeDtypeStruct((n_cells, cfg.embed_dim), jnp.float32)
    spill = jax.ShapeDtypeStruct((n_spill, cfg.embed_dim), jnp.float32)
    spill_ids = jax.ShapeDtypeStruct((n_spill,), jnp.int32)
    tail = jax.ShapeDtypeStruct((tail_rows, cfg.embed_dim), jnp.float32)
    n_live = jax.ShapeDtypeStruct((), jnp.int32)

    sharded = mesh.n_model > 1
    program = build_tiered_search_program(
        cfg, mesh if sharded else None,
        nprobe=4, fetch=8, k_tail=4, n_real_cells=n_cells,
    )
    replicated = NamedSharding(mesh.mesh, P())
    cell_specs = ivf_cell_specs(mesh.model_axis)
    in_shardings = (
        jax.tree_util.tree_map(lambda _: replicated, params),
        replicated,  # ids
        replicated,  # lengths
        NamedSharding(mesh.mesh, cell_specs[0] if sharded else P()),
        NamedSharding(mesh.mesh, cell_specs[1] if sharded else P()),
        NamedSharding(mesh.mesh, cell_specs[2] if sharded else P()),
        replicated,  # centroids
        replicated,  # spill
        replicated,  # spill_ids
        replicated,  # tail
        replicated,  # n_live
    )
    compiled = (
        jax.jit(program, in_shardings=in_shardings)
        .lower(
            params, ids, lengths, cells, scale, cell_ids, centroids,
            spill, spill_ids, tail, n_live,
        )
        .compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    return counts, {
        "row_shards": mesh.n_model if sharded else 1,
        "storage": "int8",
    }


def _lexical_operand_structs(rows: int = 64, width: int = 8, batch: int = 4,
                             q_terms: int = 16):
    """Abstract operands for the lexical impact-tile kernel (rows
    divisible by 8 so every audit mesh shards them evenly)."""
    import jax
    import jax.numpy as jnp

    return (
        jax.ShapeDtypeStruct((rows, width), jnp.int32),  # term_ids
        jax.ShapeDtypeStruct((rows, width), jnp.int8),  # impacts
        jax.ShapeDtypeStruct((rows,), jnp.bool_),  # row_live
        jax.ShapeDtypeStruct((batch, q_terms), jnp.int32),  # q_terms
        jax.ShapeDtypeStruct((batch, q_terms), jnp.float32),  # q_weights
    )


def _audit_retrieve_lexical(mesh_name: str):
    """Lower the lexical tier's search program
    (``index/lexical.py:build_lexical_search_program`` — impact-tile
    scoring over row-sharded int8 tiles -> top-k): tiles/liveness shard
    rows over ``model``, queries replicate, each shard scores its local
    rows in f32 (preferred_element_type) and the only collective content
    is the SAME 2-gather top-k merge (vals + ids) the dense tiers pay.
    1x1 lowers the single-device kernel and must be collective-free
    (docqa-lexroute)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.index.lexical import (
        build_lexical_search_program,
        lexical_specs,
    )

    mesh = _mesh(mesh_name)
    sharded = mesh.n_model > 1
    operands = _lexical_operand_structs()
    program = build_lexical_search_program(mesh if sharded else None, k=4)
    specs = lexical_specs(mesh.model_axis)
    in_shardings = tuple(
        NamedSharding(mesh.mesh, spec if sharded else P())
        for spec in specs
    )
    compiled = (
        jax.jit(program, in_shardings=in_shardings)
        .lower(*operands)
        .compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    return counts, {
        "row_shards": mesh.n_model if sharded else 1,
        "storage": "lexical_int8",
    }


def _audit_retrieve_hybrid(mesh_name: str):
    """Lower the single-dispatch HYBRID retrieve program
    (``engines/retrieve.py:build_hybrid_search_program`` — the audited
    tiered dense program PLUS the audited lexical kernel in one XLA
    program).  On a mesh both tier scans enter their ``shard_map`` merge
    kernels inside the same dispatch, so the program owes exactly TWO
    2-gather merge pairs (dense probe + lexical) and nothing else; 1x1
    must stay collective-free (docqa-lexroute)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from docqa_tpu.engines.retrieve import build_hybrid_search_program
    from docqa_tpu.index.ivf import ivf_cell_specs
    from docqa_tpu.index.lexical import lexical_specs
    from docqa_tpu.models.encoder import init_encoder_params

    cfg = _audit_encoder_cfg()
    mesh = _mesh(mesh_name)
    params = jax.eval_shape(
        functools.partial(init_encoder_params, cfg=cfg),
        jax.random.PRNGKey(0),
    )
    batch = 4
    n_cells, cap, n_spill, tail_rows = 16, 8, 4, 32  # cells divisible by 8
    ids = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cells = jax.ShapeDtypeStruct((n_cells, cap, cfg.embed_dim), jnp.int8)
    scale = jax.ShapeDtypeStruct((n_cells, cap), jnp.float32)
    cell_ids = jax.ShapeDtypeStruct((n_cells, cap), jnp.int32)
    centroids = jax.ShapeDtypeStruct((n_cells, cfg.embed_dim), jnp.float32)
    spill = jax.ShapeDtypeStruct((n_spill, cfg.embed_dim), jnp.float32)
    spill_ids = jax.ShapeDtypeStruct((n_spill,), jnp.int32)
    tail = jax.ShapeDtypeStruct((tail_rows, cfg.embed_dim), jnp.float32)
    n_live = jax.ShapeDtypeStruct((), jnp.int32)
    lex_operands = _lexical_operand_structs(batch=batch)

    sharded = mesh.n_model > 1
    program = build_hybrid_search_program(
        cfg, mesh if sharded else None,
        nprobe=4, fetch=8, k_tail=4, k_lex=4, n_real_cells=n_cells,
    )
    replicated = NamedSharding(mesh.mesh, P())
    cell_specs = ivf_cell_specs(mesh.model_axis)
    lex_specs = lexical_specs(mesh.model_axis)
    in_shardings = (
        jax.tree_util.tree_map(lambda _: replicated, params),
        replicated,  # ids
        replicated,  # lengths
        NamedSharding(mesh.mesh, cell_specs[0] if sharded else P()),
        NamedSharding(mesh.mesh, cell_specs[1] if sharded else P()),
        NamedSharding(mesh.mesh, cell_specs[2] if sharded else P()),
        replicated,  # centroids
        replicated,  # spill
        replicated,  # spill_ids
        replicated,  # tail
        replicated,  # n_live
    ) + tuple(
        NamedSharding(mesh.mesh, spec if sharded else P())
        for spec in lex_specs
    )
    compiled = (
        jax.jit(program, in_shardings=in_shardings)
        .lower(
            params, ids, lengths, cells, scale, cell_ids, centroids,
            spill, spill_ids, tail, n_live, *lex_operands,
        )
        .compile()
    )
    counts = count_hlo_collectives(compiled.as_text())
    return counts, {
        "row_shards": mesh.n_model if sharded else 1,
        "storage": "int8+lexical_int8",
    }


_AUDITS: Dict[str, Callable[[str], Tuple[Dict[str, int], Dict[str, Any]]]] = {
    "decoder_decode": functools.partial(_audit_decoder, prefill=False),
    "decoder_prefill": functools.partial(_audit_decoder, prefill=True),
    **{
        f"{kind}_{program}": functools.partial(
            _audit_paged, prefill=prefill, kind=kind)
        for kind in PAGED_CFGS
        for program, prefill in (
            ("paged_decode", False), ("ragged_prefill", True))
    },
    "ring_attention": _audit_ring,
    "ulysses_attention": _audit_ulysses,
    "retrieve_fused": _audit_retrieve,
    "retrieve_ivf_sharded": _audit_retrieve_ivf,
    "retrieve_lexical_sharded": _audit_retrieve_lexical,
    "retrieve_hybrid_sharded": _audit_retrieve_hybrid,
}

# every program the audit lowers, in report order
AUDIT_PROGRAMS = tuple(_AUDITS)


# ---------------------------------------------------------------------------
# jit-root ledger
# ---------------------------------------------------------------------------


def enumerate_jit_roots(package=None) -> List[str]:
    """Stable symbols for every traced root jit-purity discovers:
    ``<relpath>:<qualname>`` for defs, ``...<qualname>.<lambda>`` (with
    ``#n`` suffixes for siblings) for lambdas."""
    from docqa_tpu.analysis.core import Package
    from docqa_tpu.analysis.jit_purity import discover_jit_roots

    if package is None:
        pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        package = Package.load(pkg_dir)
    traced, lambdas = discover_jit_roots(package)
    # the audit's own lowering closures are harness, not serving code
    symbols = [
        f"{fn.module.relpath}:{fn.qualname}"
        for fn, _via in traced.values()
        if not fn.module.relpath.startswith("analysis/")
    ]
    seen: Dict[str, int] = {}
    for fn, _lam, _via in lambdas:
        if fn.module.relpath.startswith("analysis/"):
            continue
        base = f"{fn.module.relpath}:{fn.qualname}.<lambda>"
        n = seen.get(base, 0) + 1
        seen[base] = n
        symbols.append(base if n == 1 else f"{base}#{n}")
    return sorted(symbols)


# ---------------------------------------------------------------------------
# run + compare
# ---------------------------------------------------------------------------


def run_audit(
    mesh_names: Optional[Sequence[str]] = None,
    programs: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """Lower every audited program on every mesh; returns the report
    (the CI artifact): measured collective counts + meta + the discovered
    jit-root symbols."""
    mesh_names = list(mesh_names or MESH_SHAPES)
    programs = list(programs or AUDIT_PROGRAMS)
    report: Dict[str, Any] = {"programs": {}, "jit_roots": {}}
    for name in programs:
        per_mesh: Dict[str, Any] = {}
        meta: Dict[str, Any] = {}
        for mesh_name in mesh_names:
            counts, m = _AUDITS[name](mesh_name)
            entry = dict(counts)
            # mesh-dependent meta rides with the mesh entry
            for key in ("ring_rounds", "ring_size", "group_size",
                        "row_shards", "model_parallel"):
                if key in m:
                    entry[key] = m.pop(key)
            per_mesh[mesh_name] = entry
            meta.update(m)
        report["programs"][name] = {"meta": meta, "per_mesh": per_mesh}
    report["jit_roots"] = {"discovered": enumerate_jit_roots()}
    return report


def _model_dim(mesh_name: str) -> int:
    return MESH_SHAPES[mesh_name][1]


def semantic_violations(report: Dict[str, Any]) -> List[str]:
    """Invariants checked against the MEASUREMENT (not the budget), so an
    'update the budget to whatever it prints' workflow still cannot admit
    a layout that breaks the stated contracts."""
    out: List[str] = []
    progs = report.get("programs", {})

    for name in (
        "decoder_decode",
        "decoder_prefill",
        "decoder_paged_decode",
        "decoder_ragged_prefill",
        "loop_paged_decode",
        "loop_ragged_prefill",
    ):
        prog = progs.get(name)
        if not prog:
            continue
        blocks = prog["meta"].get("megatron_blocks", 0)
        for mesh_name, counts in prog["per_mesh"].items():
            tp = _model_dim(mesh_name) > 1
            want_ar = blocks if tp else 0
            if counts.get("all-reduce") != want_ar:
                out.append(
                    f"{name}/{mesh_name}: {counts.get('all-reduce')} "
                    f"all-reduce(s) for {blocks} Megatron block(s) — the "
                    f"layout owes exactly one per block on a TP mesh "
                    f"(expected {want_ar})"
                )
            for op in ("all-gather", "all-to-all", "collective-permute"):
                if counts.get(op, 0):
                    out.append(
                        f"{name}/{mesh_name}: unexpected {op} x"
                        f"{counts[op]} — the Megatron layout keeps every "
                        f"non-psum edge local"
                    )

    prog = progs.get("ring_attention")
    if prog:
        for mesh_name, counts in prog["per_mesh"].items():
            n = counts.get("ring_size", _model_dim(mesh_name))
            want = n - 1 if n > 1 else 0
            if counts.get("ring_rounds") != want:
                out.append(
                    f"ring_attention/{mesh_name}: {counts.get('ring_rounds')}"
                    f" ppermute round(s) on a {n}-device ring — a ring "
                    f"needs exactly n-1 (= {want}); the n-th rotation is "
                    f"pure wasted ICI"
                )
            for op in ("all-gather", "all-reduce", "all-to-all"):
                if counts.get(op, 0):
                    out.append(
                        f"ring_attention/{mesh_name}: unexpected {op} x"
                        f"{counts[op]} — the ring only rotates KV shards"
                    )

    prog = progs.get("ulysses_attention")
    if prog:
        for mesh_name, counts in prog["per_mesh"].items():
            grouped = _model_dim(mesh_name) > 1
            want = 4 if grouped else 0  # q/k/v reshuffle in + output back
            if counts.get("all-to-all") != want:
                out.append(
                    f"ulysses_attention/{mesh_name}: "
                    f"{counts.get('all-to-all')} all-to-all(s) — the "
                    f"seq<->head reshuffle owes exactly {want}"
                )
            for op in ("all-gather", "all-reduce", "collective-permute"):
                if counts.get(op, 0):
                    out.append(
                        f"ulysses_attention/{mesh_name}: unexpected {op} x"
                        f"{counts[op]}"
                    )

    # every retrieve program owes the SAME collective story: each tier
    # scan pays exactly one (vals, ids) all-gather pair for its top-k
    # merge, nothing else — the corpus scan itself never leaves the
    # shard, and 1x1 lowers the single-device kernel collective-free.
    # The hybrid program runs TWO tier scans (dense probe + lexical) in
    # one dispatch, so it owes two merge pairs (docqa-lexroute).
    for rname, merge_pairs in (
        ("retrieve_fused", 1),
        ("retrieve_ivf_sharded", 1),
        ("retrieve_lexical_sharded", 1),
        ("retrieve_hybrid_sharded", 2),
    ):
        prog = progs.get(rname)
        if not prog:
            continue
        for mesh_name, counts in prog["per_mesh"].items():
            want_ag = 2 * merge_pairs if _model_dim(mesh_name) > 1 else 0
            if counts.get("all-gather") != want_ag:
                out.append(
                    f"{rname}/{mesh_name}: {counts.get('all-gather')} "
                    f"all-gather(s) — the path owes exactly "
                    f"{merge_pairs} top-k merge pair(s) (vals + ids; "
                    f"expected {want_ag})"
                )
            for op in ("all-reduce", "collective-permute", "all-to-all"):
                if counts.get(op, 0):
                    out.append(
                        f"{rname}/{mesh_name}: unexpected {op} x"
                        f"{counts[op]} on the retrieve path"
                    )
    return out


def compare_budget(
    report: Dict[str, Any], budget: Dict[str, Any]
) -> List[str]:
    """Violations of the checked-in budget: any measured-vs-granted count
    drift, any program/mesh missing on either side, any jit root neither
    covered nor waived (or waived without a real reason), plus the
    semantic invariants on the measurement itself."""
    out: List[str] = list(semantic_violations(report))
    want_progs = budget.get("programs", {})
    got_progs = report.get("programs", {})
    for name in sorted(set(want_progs) | set(got_progs)):
        if name not in got_progs:
            out.append(f"budget program '{name}' was not audited (stale?)")
            continue
        if name not in want_progs:
            out.append(f"program '{name}' has no budget entry")
            continue
        want_meshes = want_progs[name].get("per_mesh", {})
        got_meshes = got_progs[name].get("per_mesh", {})
        for mesh_name in sorted(set(want_meshes) | set(got_meshes)):
            want = want_meshes.get(mesh_name)
            got = got_meshes.get(mesh_name)
            if want is None or got is None:
                out.append(
                    f"{name}/{mesh_name}: present in "
                    f"{'report' if want is None else 'budget'} only"
                )
                continue
            for key in sorted(set(want) | set(got)):
                if want.get(key) != got.get(key):
                    out.append(
                        f"{name}/{mesh_name}: {key} = {got.get(key)} "
                        f"(budget grants {want.get(key)})"
                    )

    ledger = budget.get("jit_roots", {})
    discovered = report.get("jit_roots", {}).get("discovered", [])
    for symbol in discovered:
        reason = ledger.get(symbol)
        if reason is None:
            out.append(
                f"new jit root '{symbol}' is neither audited nor waived "
                f"in shard_budget.json"
            )
        elif not str(reason).strip() or "TODO" in str(reason):
            out.append(
                f"jit root '{symbol}' has no real coverage/waiver reason"
            )
    for symbol in sorted(set(ledger) - set(discovered)):
        out.append(
            f"stale jit-root ledger entry '{symbol}' (root no longer "
            f"exists)"
        )
    return out


def load_budget(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or default_budget_path()
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budget(
    report: Dict[str, Any], path: Optional[str] = None
) -> Dict[str, Any]:
    """Regenerate the budget from a report, preserving existing jit-root
    reasons (new roots get a TODO the gate rejects until justified)."""
    path = path or default_budget_path()
    old: Dict[str, Any] = {}
    if os.path.exists(path):
        old = load_budget(path)
    old_ledger = old.get("jit_roots", {})
    budget = {
        "_comment": (
            "Collective budget for the device-plane programs "
            "(docs/SHARDING.md).  Counts are measured from lowered, "
            "partitioned HLO by scripts/shard_audit.py; amend ONLY via "
            "--write-budget plus a reviewed justification of the new "
            "collective.  jit_roots maps every traced root to the audit "
            "program covering it or a waiver reason."
        ),
        "programs": {
            name: {
                "meta": prog.get("meta", {}),
                "per_mesh": prog.get("per_mesh", {}),
            }
            for name, prog in report.get("programs", {}).items()
        },
        "jit_roots": {
            symbol: old_ledger.get(symbol, "TODO: justify")
            for symbol in report.get("jit_roots", {}).get("discovered", [])
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budget, f, indent=2, sort_keys=True)
        f.write("\n")
    return budget
