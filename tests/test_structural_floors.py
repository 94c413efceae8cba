"""Structural counts of the serving path, held to fixed limits.

Counts, not timings: each is fully determined by shapes, seeds and the
program's own bookkeeping, so a CPU run decides it.  Limits are the
value measured when each was introduced, less (or plus) the slack stated
beside it.  Counts of the same kind that other suites already hold:
compiled serve programs (``test_compile_audit``), routing precision and
recall on the labeled mix (``test_router``), the preemption counter
under a block-pool collision (``test_qos``), zero off-mesh fallbacks on
the 1x8 mesh (``test_ivf_sharded``).
"""

import numpy as np
import pytest

from docqa_tpu.config import (
    DecoderConfig,
    GenerateConfig,
    StoreConfig,
)
from docqa_tpu.index.store import VectorStore
from docqa_tpu.index.tiered import TieredIndex
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY


def _clustered_corpus():
    """6000 unit vectors around 60 seeded centres (dim 32)."""
    rng = np.random.default_rng(11)
    sup = rng.standard_normal((60, 32)).astype(np.float32)
    sup /= np.linalg.norm(sup, axis=1, keepdims=True)
    assign = rng.integers(0, len(sup), 6000)
    noise = rng.standard_normal((6000, 32)).astype(np.float32)
    noise /= np.linalg.norm(noise, axis=1, keepdims=True)
    vecs = sup[assign] + 0.5 * noise
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    return sup, assign, vecs


def _tiered(vecs, tag, mesh=None):
    store = VectorStore(
        StoreConfig(dim=32, shard_capacity=8192, dtype="float32"), mesh=mesh
    )
    store.add(vecs, [{"doc_id": f"{tag}{i}"} for i in range(len(vecs))])
    tiered = TieredIndex(
        store, nprobe=8, min_rows=1000, rebuild_tail_rows=10**6,
        n_clusters=64, seed=0,
    )
    assert tiered.rebuild()
    return tiered


@pytest.fixture(scope="module")
def serve_counts():
    """One session's context asked six questions through a 4-slot batcher:
    the first alone (cold: it inserts the prefix), five together after it."""
    from docqa_tpu.engines.generate import GenerateEngine
    from docqa_tpu.engines.serve import ContinuousBatcher

    cfg = DecoderConfig(
        vocab_size=256, hidden_dim=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, mlp_dim=256, max_seq_len=512,
        dtype="float32",
    )
    gen = GenerateConfig(
        temperature=0.0, prefill_buckets=(32, 64), eos_id=2,
        max_new_tokens=32,
    )
    b = ContinuousBatcher(
        GenerateEngine(cfg, gen, seed=7), n_slots=4, chunk=8, cache_len=256
    )
    try:
        ctx = [(3 + i * 7) % 250 + 1 for i in range(160)]
        hits0 = DEFAULT_REGISTRY.counter("serve_prefix_hits").value
        av0 = DEFAULT_REGISTRY.counter("serve_prefix_tokens_avoided").value
        b.submit_ids(
            ctx + [5, 9], max_new_tokens=8, prefix_key="smoke-patient"
        ).result(timeout=300)
        warm = [
            b.submit_ids(
                ctx + [6 + q, 4], max_new_tokens=8,
                prefix_key="smoke-patient",
            )
            for q in range(5)
        ]
        for h in warm:
            h.result(timeout=300)
        return {
            "kv_bytes_per_token": b.kv_block_occupancy()["bytes_per_token"],
            "warm_prefix_hit_rate": (
                DEFAULT_REGISTRY.counter("serve_prefix_hits").value - hits0
            ) / len(warm),
            "warm_prefill_tokens_avoided": (
                DEFAULT_REGISTRY.counter("serve_prefix_tokens_avoided").value
                - av0
            ),
        }
    finally:
        b.stop()


@pytest.fixture(scope="module")
def recall_counts():
    """The shadow estimator's recall over 40 seeded probes of the tiered
    index (nprobe 8 of 64 cells)."""
    from docqa_tpu.obs.retrieval_observatory import (
        RetrievalObservatory,
        set_retrieval_observatory,
    )

    sup, assign, vecs = _clustered_corpus()
    tiered = _tiered(vecs, "q")
    robs = RetrievalObservatory(
        sample_every=1, seed=0, frontier_every=4, min_frontier_n=1,
        registry=DEFAULT_REGISTRY,
    ).start()
    prev = set_retrieval_observatory(robs)
    try:
        qidx = np.arange(0, 6000, 150)
        q = vecs[qidx] + 0.05 * sup[assign[qidx]]
        for start in range(0, len(q), 8):
            tiered.search(q[start : start + 8], k=10)
        assert robs.drain(120)
        est = robs.status().get("estimate") or {}
    finally:
        set_retrieval_observatory(prev)
        robs.stop()
    return {"retrieve_recall_smoke": est.get("recall")}


@pytest.fixture(scope="module")
def sharded_tier_counts(mesh_tp8):
    """The int8 tier's device bytes per chunk on the 1x8 mesh."""
    stats = _tiered(_clustered_corpus()[2], "m", mesh=mesh_tp8).index_stats()
    assert stats["shards"] == 8 and stats["storage"] == "int8"
    return {"index_bytes_per_chunk": stats["bytes_per_chunk"]}


@pytest.mark.parametrize(
    "group, name, holds",
    [
        # 2 layers x (K, V) x 2 kv heads x 32 x 4 B: per token, not per
        # bucket (a return to per-slot reservation grows it)
        ("serve_counts", "kv_bytes_per_token", lambda v: v <= 1024 * 1.1),
        # every question after the first reuses the session's prefix
        ("serve_counts", "warm_prefix_hit_rate", lambda v: v >= 0.9),
        # 5 warm admissions x the 128 aligned tokens of the 160 shared
        ("serve_counts", "warm_prefill_tokens_avoided",
         lambda v: v >= 640 * 0.9),
        # measured 0.995-1.0 at this geometry; an IVF placement or probe
        # regression collapses it
        ("recall_counts", "retrieve_recall_smoke",
         lambda v: v is not None and v >= 0.9),
        # 32 B tile + 4 B scale + 4 B id, x n_assign 2, + padding: ~122;
        # float cells would read ~4x
        ("sharded_tier_counts", "index_bytes_per_chunk",
         lambda v: v <= 121.71 * 1.1),
    ],
)
def test_structural_floor(request, group, name, holds):
    value = request.getfixturevalue(group)[name]
    assert holds(value), f"{name} = {value}"
