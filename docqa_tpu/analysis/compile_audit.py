"""docqa-numcheck Tier B: drive the canonical serving workloads under a
compile-counting hook, AOT-measure every root's HBM footprint, and hold
both to a checked-in budget.

The shard audit (``analysis/shard_audit.py``) proves each program's
COLLECTIVE content; this module proves two different compilation-class
contracts the ROADMAP previously enforced only by convention:

* **compile counts** — every jit root's admitted shape set is warmed
  ahead of the serving path, and a repeated steady-state round performs
  ZERO retraces.  The batcher's two-shape admission policy
  (``serve._admit_round``: 4-lane trickle + full ``n_slots`` per prompt
  bucket) is driven explicitly, so the exact compile count per root is a
  checked-in number (``compile_budget.json``) and a new shape sneaking
  into the serving path flips CI red instead of adding a silent
  multi-second compile to someone's request.
* **HBM budgets** — each root is AOT-lowered (``lower().compile()``)
  and its ``memory_analysis()`` bytes (argument/output/temp/generated
  code) recorded; per-root peak bytes gate against a budget CEILING.
  ``--write-budget`` preserves an existing ceiling when the measurement
  still fits and stamps any GROWTH with a ``TODO`` note the gate rejects
  until a human justifies it — regeneration cannot launder a memory
  regression, mirroring ``shard_audit``'s semantic-invariant design.

Workloads (tiny configs, CPU-lowerable in seconds):

* ``serve``          — decoder prefill across every admitted shape
  (both batch families x every bucket) + the decode chunk, through a
  real :class:`~docqa_tpu.engines.serve.ContinuousBatcher` (warmup, then
  a trickle round and a full round as the steady state);
* ``generate``       — the solo engine's fused prefill+decode program;
* ``retrieve_fused`` — the single-dispatch text→top-k program;
* ``seq2seq``        — the BART-class summarize program;
* ``encoder``        — the batched document/query encoder.

The budget also carries the same **jit-root ledger** as the shard
budget (enumerated by jit-purity's discovery pass): every traced root
must be covered by a workload or waived with a reason, so a new
``jax.jit`` site fails the gate until its compile story is stated.

Violations are re-derived from the MEASUREMENT (``semantic_violations``)
so an "accept whatever it prints" budget update still cannot admit a
steady-state retrace, a missing shape family, or a trickle shape that
stopped being cheaper than the full width.

Entry points: ``scripts/compile_audit.py`` (CLI; CI uploads its
``--report`` JSON as the compile/HBM trend artifact) and ``pytest -m
lint`` (tests/test_compile_audit.py).  docs/STATIC_ANALYSIS.md documents
the budget format and amendment workflow.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence

# one byte-accounting implementation, shared with the serving layer
# (GenerateEngine.decode_memory_analysis) — it lives in utils because
# engines must never import the lint tree
from docqa_tpu.utils import compiled_memory_stats as memory_of

# headroom factor applied when a ceiling must grow (or is first written):
# measured bytes wobble a few percent across jaxlib versions; a regression
# worth gating is a structural one (a materialized tree, a doubled cache)
CEILING_HEADROOM = 1.25


def default_budget_path() -> str:
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg_dir), "compile_budget.json")


# ---------------------------------------------------------------------------
# counting + memory helpers
# ---------------------------------------------------------------------------


def jit_cache_size(fn) -> int:
    """Compiled-specialization count of a ``jax.jit`` wrapper — the
    compile-counting hook.  One entry per traced (shape, dtype, sharding,
    static-args) signature, so a steady-state round that grows it by N
    performed exactly N retraces."""
    size = getattr(fn, "_cache_size", None)
    if size is None:  # pragma: no cover - jax pinned in CI
        raise RuntimeError(
            "jax.jit wrapper has no _cache_size(); the compile audit "
            "needs it (jax>=0.4.31)"
        )
    return int(size())


def lowered_memory(fn, *args, **kwargs) -> Optional[Dict[str, int]]:
    """AOT memory accounting for one root, augmented with the compiled
    program's ``cost_analysis()`` FLOPs / bytes-accessed — the cost
    model every ledgered jit root now carries (docqa-observatory).  The
    GATE stays compile-count/bytes-based; the cost columns are
    informational (they feed the same per-program accounting the
    dispatch spine's MFU attribution uses at runtime)."""
    from docqa_tpu.obs.observatory import parse_cost_analysis

    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception:
        return None
    out = memory_of(compiled)
    cost = parse_cost_analysis(compiled)
    if cost is not None:
        # backends without the estimate keep bytes-only rows
        out = dict(out or {})
        out.update(cost)
    return out


# ---------------------------------------------------------------------------
# audit configs (tiny: every workload lowers AND runs in seconds on CPU)
# ---------------------------------------------------------------------------


def _audit_decoder_cfg():
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=64,
        hidden_dim=32,
        num_layers=2,
        num_heads=2,
        num_kv_heads=2,
        head_dim=16,
        mlp_dim=64,
        max_seq_len=128,
    )


def _audit_latent_cfg():
    """The latent-attention / routed-expert block (models/latent.py) at
    audit widths: one dense layer, one routed layer of which a quarter of
    the experts is held."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=24, mlp_dim=64, max_seq_len=128,
        block="mla_moe", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling_factor=40.0, rope_original_max_len=64,
        rope_mscale=0.707, rope_mscale_all_dim=0.707, first_dense_layers=1,
        num_experts=16, experts_per_token=2, expert_dim=16,
        num_shared_experts=1, expert_groups=4, expert_groups_per_token=2,
        routed_scale=4.0, experts_held_start=0, experts_held=4,
    )


def _audit_ssm_cfg():
    """The same stack (models/hybrid.py) at audit widths with its other
    two mixer kinds: a state-space and a plain attention layer, tied
    head."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=1, head_dim=8, mlp_dim=64, max_seq_len=128,
        block="sparse_linear", mixer_types=("mamba", "attention"),
        qk_norm=False, use_output_gate=False, use_output_norm=False,
        tie_embeddings=True, ssm_state_dim=4, ssm_conv_width=4,
        ssm_dt_rank=4, ssm_expand=2,
    )


def _audit_hybrid_cfg():
    """The stack of mixer kinds (models/hybrid.py) at audit widths: a
    sparse and a linear layer."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=128,
        block="sparse_linear", mixer_types=("sparse", "linear"),
        linear_heads=4, linear_head_dim=8, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=16, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_block_size=8, sparse_topk=4, sparse_init_blocks=1,
        sparse_window_size=16, sparse_dense_len=48,
    )


def _audit_retention_cfg():
    """The same stack at audit widths with retention layers alone: pools
    that hold lane states and no K / V row."""
    from docqa_tpu.config import DecoderConfig

    return DecoderConfig(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=128,
        block="sparse_linear", mixer_types=("retention", "retention"),
        use_output_gate=False, use_output_norm=False,
    )


def _audit_loop_cfg():
    """The GQA block's looped trunk, with the sandwich norms."""
    return dataclasses.replace(
        _audit_decoder_cfg(), loop_steps=4, sandwich_norm=True)


# the serving workloads: one toy configuration a block kind the batcher
# serves (``_audit_serve``); what a kind is served without, the audit
# reads from the kind's record (``models/serving.BlockServing``)
SERVE_CFGS = {
    "serve": _audit_decoder_cfg,
    "serve_latent": _audit_latent_cfg,
    "serve_hybrid": _audit_hybrid_cfg,
    "serve_ssm": _audit_ssm_cfg,
    "serve_loop": _audit_loop_cfg,
    "serve_retention": _audit_retention_cfg,
}


def _audit_gen_cfg():
    from docqa_tpu.config import GenerateConfig

    return GenerateConfig(
        max_new_tokens=4,
        prefill_buckets=(16, 32),
        decode_chunk=4,
        max_concurrent=8,
    )


def _audit_encoder_cfg():
    from docqa_tpu.config import EncoderConfig

    return EncoderConfig(
        vocab_size=64,
        hidden_dim=32,
        num_layers=1,
        num_heads=2,
        mlp_dim=64,
        max_seq_len=16,
        embed_dim=32,
        dtype="float32",
    )


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def _audit_serve(workload: str = "serve") -> Dict[str, Any]:
    """The PAGED batcher's whole compile surface: one ragged prefill
    program per packed token budget (<= 2) plus the one block-table
    decode chunk — the collapse from the pre-paged (2 shape families x
    prompt buckets) matrix that ROADMAP item 1 demanded.  Steady state =
    a trickle round (1 request) and a full round (n_slots requests) of
    MIXED prompt lengths AFTER warmup; both must hit warm programs (mixed
    lengths sharing one program is the point of ragged prefill).

    ``workload`` names the toy configuration (``SERVE_CFGS``), and its
    roots carry the name.  What its block kind is not served with
    (``BlockServing.unserved``) is turned off, so a kind that prefills
    cold only has no warm family; its decode chunk carries the kind's
    sums, advances its lane states, nests its step loop."""
    import jax
    import jax.numpy as jnp

    from docqa_tpu.engines.generate import GenerateEngine
    from docqa_tpu.engines.paged import kv_bytes_per_token
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.models.decoder import block_serving

    cfg, gen = SERVE_CFGS[workload](), _audit_gen_cfg()
    off = {"generate.prefix_cache": {"prefix_cache": False},
           "generate.speculative_k": {"speculative_k": 0}}
    for setting in block_serving(cfg).unserved:
        gen = dataclasses.replace(gen, **off.get(setting, {}))
    engine = GenerateEngine(cfg, gen)
    # cache_len 256: large enough that the 128-aligned prefix cache is
    # ENABLED (share_alignment < seq_capacity), so the warm prefill
    # program family is part of the audited surface
    batcher = ContinuousBatcher(engine, n_slots=8, chunk=4, cache_len=256)
    try:
        batcher.warmup()
        prefill_fn = batcher._get_prefill_fn()
        prefill_warm_fn = batcher._get_prefill_warm_fn()
        decode_fn = batcher._get_decode_fn()
        warm_prefill = jit_cache_size(prefill_fn)
        warm_prefill_w = jit_cache_size(prefill_warm_fn)
        warm_decode = jit_cache_size(decode_fn)

        # steady state: a trickle round, then a full round of MIXED
        # lengths (the shape-family x bucket matrix this would have
        # retraced across before paging), against warm programs
        batcher.submit_ids([1] * 10, max_new_tokens=3).result(timeout=120)
        handles = [
            batcher.submit_ids([1] * (4 + 5 * (i % 5)), max_new_tokens=3)
            for i in range(batcher.n_slots)
        ]
        for h in handles:
            h.result(timeout=120)
        # warm-prefix steady state: the same session key twice — the
        # second admission maps the cached prefix in and dispatches the
        # WARM program, which warmup must already have compiled
        warm_prompt = [1 + i % 60 for i in range(140)]
        for tail in ([3, 5], [7, 9]):
            batcher.submit_ids(
                warm_prompt + tail, max_new_tokens=3, prefix_key="audit"
            ).result(timeout=120)
        retrace_prefill = jit_cache_size(prefill_fn) - warm_prefill
        retrace_prefill_w = (
            jit_cache_size(prefill_warm_fn) - warm_prefill_w
        )
        retrace_decode = jit_cache_size(decode_fn) - warm_decode

        # AOT memory per packed token budget (counting is done —
        # lowering can no longer pollute the numbers).  Shapes mirror
        # warmup() EXACTLY so expected_shapes can never drift from what
        # warmup compiles.
        S = batcher.n_slots
        pool_struct = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in batcher._pools.items()
        }
        spec_table = (
            jax.ShapeDtypeStruct((S, cfg.vocab_size), jnp.int32)
            if batcher.spec_k
            else None
        )
        rng = jax.random.PRNGKey(0)

        def prefill_mem(T: int, warm: bool = False):
            vec = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)  # noqa: E731
            packed = (vec(T), vec(T), vec(T), vec(T), vec(S), vec(S))
            if warm:
                tabs = jax.ShapeDtypeStruct(
                    (S, batcher.blocks_per_seq), jnp.int32
                )
                packed = packed + (tabs, vec(S))
                use = prefill_warm_fn
            else:
                use = prefill_fn
            packed = packed + (rng,)
            if batcher.spec_k:
                return lowered_memory(
                    use, engine.params, pool_struct, spec_table, *packed,
                )
            return lowered_memory(use, engine.params, pool_struct, *packed)

        per_shape = {
            f"tokens_{T}": prefill_mem(T) for T in batcher._token_buckets
        }
        per_shape_warm = {
            f"tokens_{T}": prefill_mem(T, warm=True)
            for T in batcher._token_buckets
            if batcher.prefix_cache_enabled
        }
        tables = jax.ShapeDtypeStruct(
            (S, batcher.blocks_per_seq), jnp.int32
        )
        caps = jax.ShapeDtypeStruct((S,), jnp.int32)
        tok = jax.ShapeDtypeStruct((S,), jnp.int32)
        lens = jax.ShapeDtypeStruct((S,), jnp.int32)
        active = jax.ShapeDtypeStruct((S,), jnp.bool_)
        if batcher.spec_k:
            decode_mem = lowered_memory(
                decode_fn, engine.params, pool_struct, tables, caps,
                spec_table, tok, lens, active,
            )
        else:
            decode_mem = lowered_memory(
                decode_fn, engine.params, pool_struct, tables, caps,
                tok, lens, active, rng,
            )
        report = {
            "meta": {
                "n_slots": S,
                "paged": True,
                "prefix_cache": batcher.prefix_cache_enabled,
                "token_buckets": list(batcher._token_buckets),
                "kv_block_size": batcher.block_size,
                "kv_pool_blocks": batcher.n_blocks,
                "kv_bytes_per_token": kv_bytes_per_token(cfg),
                "kv_pool_bytes": (
                    batcher.n_blocks * batcher.block_size
                    * kv_bytes_per_token(cfg)
                ),
            },
            "roots": {
                "serve_prefill": {
                    "compiles": warm_prefill,
                    "expected_shapes": len(batcher._token_buckets),
                    "steady_state_retraces": retrace_prefill,
                    "per_shape": per_shape,
                    "peak_bytes": max(
                        (m or {}).get("peak_bytes", 0)
                        for m in per_shape.values()
                    ),
                    # cost model (informational; gate stays bytes-based)
                    "flops": max(
                        (m or {}).get("flops", 0)
                        for m in per_shape.values()
                    ),
                    "bytes_accessed": max(
                        (m or {}).get("bytes_accessed", 0)
                        for m in per_shape.values()
                    ),
                },
                "serve_prefill_warm": {
                    "compiles": warm_prefill_w,
                    "expected_shapes": len(batcher._token_buckets),
                    "steady_state_retraces": retrace_prefill_w,
                    "per_shape": per_shape_warm,
                    "peak_bytes": max(
                        ((m or {}).get("peak_bytes", 0)
                         for m in per_shape_warm.values()), default=0,
                    ),
                    "flops": max(
                        ((m or {}).get("flops", 0)
                         for m in per_shape_warm.values()), default=0,
                    ),
                    "bytes_accessed": max(
                        ((m or {}).get("bytes_accessed", 0)
                         for m in per_shape_warm.values()), default=0,
                    ),
                },
                "serve_decode": {
                    "compiles": warm_decode,
                    "expected_shapes": 1,
                    "steady_state_retraces": retrace_decode,
                    "memory": decode_mem,
                    "peak_bytes": (decode_mem or {}).get("peak_bytes", 0),
                    "flops": (decode_mem or {}).get("flops", 0),
                    "bytes_accessed": (
                        (decode_mem or {}).get("bytes_accessed", 0)
                    ),
                },
            },
        }
        if not batcher.prefix_cache_enabled:
            del report["roots"]["serve_prefill_warm"]
        report["roots"] = {
            name.replace("serve", workload, 1): root
            for name, root in report["roots"].items()
        }
        return report
    finally:
        batcher.stop()


def _audit_generate() -> Dict[str, Any]:
    from docqa_tpu.engines.generate import GenerateEngine

    cfg, gen = _audit_decoder_cfg(), _audit_gen_cfg()
    engine = GenerateEngine(cfg, gen)
    engine.generate_ids([[1, 2, 3]], max_new_tokens=4)
    warm = sum(jit_cache_size(fn) for fn in engine._fns.values())
    engine.generate_ids([[1, 2, 3]], max_new_tokens=4)
    after = sum(jit_cache_size(fn) for fn in engine._fns.values())
    mem = engine.decode_memory_analysis(prompt_len=3, max_new_tokens=4)
    return {
        "meta": {"programs": len(engine._fns)},
        "roots": {
            "generate_decode": {
                "compiles": warm,
                "expected_shapes": 1,
                "steady_state_retraces": after - warm,
                "memory": mem,
                "peak_bytes": (mem or {}).get("peak_bytes", 0),
                "flops": (mem or {}).get("flops", 0),
                "bytes_accessed": (mem or {}).get("bytes_accessed", 0),
            }
        },
    }


def _audit_retrieve() -> Dict[str, Any]:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from docqa_tpu.config import StoreConfig
    from docqa_tpu.engines.encoder import EncoderEngine
    from docqa_tpu.engines.retrieve import (
        FusedRetriever,
        build_fused_search_program,
    )
    from docqa_tpu.index.store import VectorStore

    enc_cfg = _audit_encoder_cfg()
    encoder = EncoderEngine(enc_cfg)
    store = VectorStore(
        StoreConfig(dim=enc_cfg.embed_dim, shard_capacity=64)
    )
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((8, enc_cfg.embed_dim)).astype(np.float32)
    store.add(vecs, [{"doc_id": f"d{i}"} for i in range(len(vecs))])

    retriever = FusedRetriever(encoder, store)
    retriever.search_texts(["alpha beta"], k=3)
    warm = sum(jit_cache_size(fn) for fn in retriever._fns.values())
    retriever.search_texts(["gamma delta"], k=3)
    after = sum(jit_cache_size(fn) for fn in retriever._fns.values())

    # canonical-program memory at controlled shapes (the same program the
    # shard audit lowers, single-shard here)
    program = jax.jit(build_fused_search_program(
        enc_cfg, None, k=3, masked=False
    ))
    batch, capacity = 1, 64
    mem = lowered_memory(
        program,
        encoder.params,
        jax.ShapeDtypeStruct((batch, enc_cfg.max_seq_len), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct(
            (capacity, enc_cfg.embed_dim),
            jnp.dtype(store.cfg.dtype),
        ),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    return {
        "meta": {"programs": len(retriever._fns)},
        "roots": {
            "retrieve_fused": {
                "compiles": warm,
                "expected_shapes": 1,
                "steady_state_retraces": after - warm,
                "memory": mem,
                "peak_bytes": (mem or {}).get("peak_bytes", 0),
                "flops": (mem or {}).get("flops", 0),
                "bytes_accessed": (mem or {}).get("bytes_accessed", 0),
            }
        },
    }


def _audit_seq2seq() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from docqa_tpu.config import Seq2SeqConfig
    from docqa_tpu.engines.seq2seq import Seq2SeqEngine

    engine = Seq2SeqEngine(Seq2SeqConfig())
    engine.generate_ids([[5, 9, 11]], max_new_tokens=4)
    warm = sum(jit_cache_size(fn) for fn in engine._fns.values())
    engine.generate_ids([[5, 9, 11]], max_new_tokens=4)
    after = sum(jit_cache_size(fn) for fn in engine._fns.values())
    fn = engine._get_fn(4)
    mem = lowered_memory(
        fn,
        engine.params,
        src_ids=jax.ShapeDtypeStruct((1, 64), jnp.int32),
        src_lengths=jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    return {
        "meta": {"programs": len(engine._fns)},
        "roots": {
            "seq2seq_summarize": {
                "compiles": warm,
                "expected_shapes": 1,
                "steady_state_retraces": after - warm,
                "memory": mem,
                "peak_bytes": (mem or {}).get("peak_bytes", 0),
                "flops": (mem or {}).get("flops", 0),
                "bytes_accessed": (mem or {}).get("bytes_accessed", 0),
            }
        },
    }


def _audit_encoder() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from docqa_tpu.engines.encoder import EncoderEngine

    enc_cfg = _audit_encoder_cfg()
    engine = EncoderEngine(enc_cfg)
    engine.encode_texts(["alpha beta"])
    warm = jit_cache_size(engine._encode)
    engine.encode_texts(["gamma delta"])
    after = jit_cache_size(engine._encode)
    mem = lowered_memory(
        engine._encode,
        params=engine.params,
        ids=jax.ShapeDtypeStruct((8, enc_cfg.max_seq_len), jnp.int32),
        lengths=jax.ShapeDtypeStruct((8,), jnp.int32),
    )
    return {
        "meta": {},
        "roots": {
            "encoder_encode": {
                "compiles": warm,
                "expected_shapes": 1,
                "steady_state_retraces": after - warm,
                "memory": mem,
                "peak_bytes": (mem or {}).get("peak_bytes", 0),
                "flops": (mem or {}).get("flops", 0),
                "bytes_accessed": (mem or {}).get("bytes_accessed", 0),
            }
        },
    }


_AUDITS = {
    "serve": _audit_serve,
    **{name: functools.partial(_audit_serve, name)
       for name in SERVE_CFGS if name != "serve"},
    "generate": _audit_generate,
    "retrieve_fused": _audit_retrieve,
    "seq2seq": _audit_seq2seq,
    "encoder": _audit_encoder,
}

# every workload the audit drives, in report order
WORKLOADS = tuple(_AUDITS)


# ---------------------------------------------------------------------------
# run + compare
# ---------------------------------------------------------------------------


def run_audit(
    workloads: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """Drive every workload; returns the report (the CI artifact)."""
    from docqa_tpu.analysis.shard_audit import enumerate_jit_roots

    names = list(workloads or WORKLOADS)
    report: Dict[str, Any] = {"workloads": {}}
    for name in names:
        report["workloads"][name] = _AUDITS[name]()
    report["jit_roots"] = {"discovered": enumerate_jit_roots()}
    return report


def _iter_roots(section: Dict[str, Any]):
    for wname, wl in section.get("workloads", {}).items():
        for rname, root in wl.get("roots", {}).items():
            yield wname, rname, root


def semantic_violations(report: Dict[str, Any]) -> List[str]:
    """Invariants checked against the MEASUREMENT, so regenerating the
    budget from a broken run still fails the gate."""
    out: List[str] = []
    for wname, rname, root in _iter_roots(report):
        retraces = root.get("steady_state_retraces")
        if retraces != 0:
            out.append(
                f"{wname}/{rname}: {retraces} steady-state retrace(s) — "
                "every admitted shape must be compiled at warmup, never "
                "inside a serving round"
            )
        expected = root.get("expected_shapes")
        if expected is not None and root.get("compiles") != expected:
            out.append(
                f"{wname}/{rname}: {root.get('compiles')} compiled "
                f"specialization(s) for {expected} admitted shape(s) — "
                "the warmed shape set drifted from the admission policy"
            )
        if not root.get("peak_bytes"):
            out.append(
                f"{wname}/{rname}: no memory_analysis measurement — the "
                "HBM gate cannot be satisfied by an empty measurement"
            )
    serve = report.get("workloads", {}).get("serve", {})
    prefill = serve.get("roots", {}).get("serve_prefill", {})
    shapes = prefill.get("per_shape") or {}
    trickle = (shapes.get("trickle") or {}).get("peak_bytes")
    full = (shapes.get("full") or {}).get("peak_bytes")
    if trickle is not None and full is not None and trickle >= full:
        out.append(
            f"serve_prefill: trickle-shape peak ({trickle}B) is not "
            f"smaller than the full-width peak ({full}B) — the narrow "
            "admission shape exists to make trickle rounds cheaper; this "
            "layout broke that"
        )
    meta = serve.get("meta", {})
    if meta.get("paged"):
        # the paged tentpole's headline contract, extended by
        # docqa-prefix: the whole batcher compile matrix is bounded by
        # the ragged token budgets — one COLD program per budget, one
        # WARM (prefix-gather) program per budget when the prefix cache
        # is on, plus the one decode chunk.  Re-derived from the
        # MEASUREMENT so a budget regeneration cannot launder a matrix
        # regrowth toward the per-bucket shape families.
        n_buckets = max(len(meta.get("token_buckets") or ()), 1)
        families = 2 if meta.get("prefix_cache") else 1
        allowed = families * n_buckets + 1
        total = sum(
            int(root.get("compiles") or 0)
            for root in serve.get("roots", {}).values()
        )
        if total > allowed:
            out.append(
                f"serve: {total} compiled programs across prefill+decode "
                f"— the paged batcher's whole matrix must stay <= "
                f"{allowed} ({families} prefill family(ies) x "
                f"{n_buckets} token budget(s) + one decode chunk); a "
                "regrowth toward the per-bucket shape families is a "
                "regression"
            )
    return out


def compare_budget(
    report: Dict[str, Any], budget: Dict[str, Any]
) -> List[str]:
    """Budget-gate violations: semantic invariants on the measurement,
    exact compile counts, per-root HBM ceilings (with TODO growth notes
    rejected), and the jit-root ledger in exact sync."""
    out: List[str] = list(semantic_violations(report))
    want = {
        (w, r): root for w, r, root in _iter_roots(budget)
    }
    got = {
        (w, r): root for w, r, root in _iter_roots(report)
    }
    for key in sorted(set(want) | set(got)):
        wname, rname = key
        if key not in got:
            out.append(
                f"budget root '{wname}/{rname}' was not audited (stale?)"
            )
            continue
        if key not in want:
            out.append(f"root '{wname}/{rname}' has no budget entry")
            continue
        g, w = got[key], want[key]
        if g.get("compiles") != w.get("compiles"):
            out.append(
                f"{wname}/{rname}: {g.get('compiles')} compile(s) "
                f"(budget grants exactly {w.get('compiles')})"
            )
        ceiling = w.get("peak_bytes_ceiling")
        if ceiling is None:
            out.append(
                f"{wname}/{rname}: budget entry lacks peak_bytes_ceiling"
            )
        elif g.get("peak_bytes", 0) > ceiling:
            peak = g.get("peak_bytes", 0)
            pct = 100.0 * (peak - ceiling) / max(ceiling, 1)
            out.append(
                f"{wname}/{rname}: peak {peak}B exceeds the HBM ceiling "
                f"{ceiling}B (+{pct:.0f}%) — justify and regrow the "
                "ceiling via --write-budget + an edited ceiling_note, or "
                "fix the regression"
            )
        note = str(w.get("ceiling_note", ""))
        if "TODO" in note:
            out.append(
                f"{wname}/{rname}: ceiling_note is an unjustified TODO — "
                "a grown ceiling needs a human-written reason"
            )

    ledger = budget.get("jit_roots", {})
    discovered = report.get("jit_roots", {}).get("discovered", [])
    for symbol in discovered:
        reason = ledger.get(symbol)
        if reason is None:
            out.append(
                f"new jit root '{symbol}' is neither covered by a "
                "compile-audit workload nor waived in compile_budget.json"
            )
        elif not str(reason).strip() or "TODO" in str(reason):
            out.append(
                f"jit root '{symbol}' has no real coverage/waiver reason"
            )
    for symbol in sorted(set(ledger) - set(discovered)):
        out.append(
            f"stale jit-root ledger entry '{symbol}' (root no longer "
            "exists)"
        )
    return out


def load_budget(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or default_budget_path()
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budget(
    report: Dict[str, Any], path: Optional[str] = None
) -> Dict[str, Any]:
    """Regenerate the budget from a report.  Compile counts are copied
    (the semantic gate separately forbids steady-state retraces), HBM
    ceilings are PRESERVED while the measurement still fits and only grow
    through a TODO note the gate rejects until a human edits it, and
    jit-root reasons are preserved (new roots get a TODO)."""
    path = path or default_budget_path()
    old: Dict[str, Any] = {}
    if os.path.exists(path):
        old = load_budget(path)
    old_roots = {(w, r): root for w, r, root in _iter_roots(old)}
    old_ledger = old.get("jit_roots", {})

    workloads: Dict[str, Any] = {}
    for wname, wl in report.get("workloads", {}).items():
        roots_out = {}
        for rname, root in wl.get("roots", {}).items():
            peak = int(root.get("peak_bytes", 0))
            prior = old_roots.get((wname, rname), {})
            prior_ceiling = prior.get("peak_bytes_ceiling")
            if prior_ceiling is not None and peak <= prior_ceiling:
                ceiling = prior_ceiling
                note = prior.get("ceiling_note", "")
            else:
                ceiling = int(math.ceil(peak * CEILING_HEADROOM))
                if prior_ceiling is None:
                    note = prior.get(
                        "ceiling_note",
                        "TODO: justify the initial ceiling",
                    )
                else:
                    note = (
                        f"TODO: justify growth from {prior_ceiling} to "
                        f"{ceiling} bytes"
                    )
            roots_out[rname] = {
                "compiles": root.get("compiles"),
                "steady_state_retraces": 0,
                "peak_bytes_ceiling": ceiling,
                "ceiling_note": note,
            }
        workloads[wname] = {
            "meta": wl.get("meta", {}),
            "roots": roots_out,
        }

    budget = {
        "_comment": (
            "Compile-count + HBM budget for the serving jit roots "
            "(docs/STATIC_ANALYSIS.md).  Counts and memory_analysis "
            "bytes are measured by scripts/compile_audit.py; amend ONLY "
            "via --write-budget plus a reviewed ceiling_note for any "
            "grown ceiling.  jit_roots maps every traced root to the "
            "workload covering it or a waiver reason."
        ),
        "workloads": workloads,
        "jit_roots": {
            symbol: old_ledger.get(symbol, "TODO: justify")
            for symbol in report.get("jit_roots", {}).get("discovered", [])
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budget, f, indent=2, sort_keys=True)
        f.write("\n")
    return budget
