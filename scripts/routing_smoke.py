#!/usr/bin/env python
"""Answer-routing smoke (docqa-lexroute; docs/OPERATIONS.md "Tune the
answer router") — the CI-blocking proof that the confidence-gated
router actually ships the decoder-skip fast path over the REAL wire.

A tiny-but-real runtime (real decoder + continuous batcher, hash-embed
fake encoder, lexical tier + router on their defaults) serves the
checked-in labeled query mix (``data/routing_mix.jsonl``, EN+FR,
20 extractive + 20 generative — authored like the deid HELDOUT set and
never tuned against) over real HTTP ``POST /ask/``.  The corpus is the
mix's own evidence docs, seeded through ``store.add`` so the lexical
sink indexes the raw text (the pipeline's deid stage would mask the
very MRN/phone tokens the lookups target — correct for PHI, wrong for
a routing measurement; the journal-replay ingest convergence has its
own regression test in ``tests/test_lexical.py``).

Blocking assertions, all structural (the only timing claim is the
route split's ORDERING, which the decoder-skip geometry forces):

1. routing precision >= 0.95 from the WIRE ``route`` key vs the mix's
   labels — an extractive-routed generative question ships a
   wrong-shaped answer, so precision is the hard floor;
2. enough extractive routes landed (>= 10 of 20) for the split to mean
   anything — an evidence-gate collapse silently demoting every lookup
   to the generative path would otherwise pass assertion 1 vacuously;
3. ZERO decode-stage spine dispatches across every routed-extractive
   request: the requests run sequentially, so per-request deltas of the
   spine's ``serve_decode`` / ``serve_alloc`` stage counters are exact
   — the fast path must never touch a batcher lane or allocate KV;
4. wire shape: every answer keeps ``{"answer", "sources"}``; ``route``
   appears ONLY on routed-extractive responses (api_contract.json v2);
5. route split: routed-extractive p50 < generative p50 (the ~600ms ->
   ~50ms shape, asserted as an ordering so CI hosts can't flake it).

Writes a ``routing_report.json`` trend artifact (per-request rows,
precision/recall, per-route p50s, live counters) for the CI upload.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX_PATH = os.path.join(REPO, "data", "routing_mix.jsonl")

# tiny REAL decoder (the qos smoke's shape): the generative arm
# must pay genuine prefill+decode dispatches or the split proves nothing
OVERRIDES = {
    "encoder.hidden_dim": 64,
    "encoder.num_layers": 1,
    "encoder.num_heads": 4,
    "encoder.mlp_dim": 128,
    "encoder.embed_dim": 64,
    "store.dim": 64,
    "store.shard_capacity": 256,
    "store.serving_index": "tiered",
    "ner.train_steps": 0,
    "decoder.vocab_size": 256,
    "decoder.hidden_dim": 128,
    "decoder.num_layers": 2,
    "decoder.num_heads": 4,
    "decoder.num_kv_heads": 2,
    "decoder.head_dim": 32,
    "decoder.mlp_dim": 256,
    "decoder.max_seq_len": 512,
    "decoder.dtype": "float32",
    "generate.max_new_tokens": 24,
    "generate.prefill_buckets": (64, 128, 256),
    "flags.use_fake_encoder": True,  # retrieval exercised, hash embed
    # first-touch compiles on a loaded CI host can exceed the 8 s
    # production deadline; the smoke measures routing, not cold-start
    "resilience.request_deadline_s": 30.0,
    # the pool's liveness canary is a background 2-token generate — it
    # would race the per-request serve_decode deltas assertion 3 reads,
    # so push it past the smoke's horizon (liveness has its own tests)
    "pool.canary_interval_s": 3600.0,
}

MIN_PRECISION = 0.95
MIN_EXTRACTIVE_ROUTED = 10


def load_mix() -> list:
    mix = []
    with open(MIX_PATH, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                mix.append(json.loads(line))
    return mix


def seed_corpus(rt, mix: list) -> int:
    """The mix's evidence docs straight into the store — the lexical
    sink indexes them via the registered index-sink path."""
    texts = [ex["doc"] for ex in mix if "doc" in ex]
    ids = [ex["id"] for ex in mix if "doc" in ex]
    emb = rt.encoder.encode_texts(texts)
    rt.store.add(
        emb,
        [
            {"doc_id": i, "source": f"mix/{i}", "text_content": t}
            for i, t in zip(ids, texts)
        ],
    )
    return len(texts)


def _p50(xs: list):
    xs = sorted(xs)
    return round(xs[len(xs) // 2], 1) if xs else None


async def drive(rt, mix: list, errs: list) -> dict:
    import asyncio

    import aiohttp
    from aiohttp import web

    from docqa_tpu.engines.spine import get_spine
    from docqa_tpu.service.app import make_app

    def stage_count(name: str) -> int:
        row = get_spine().stats()["stages"].get(name) or {}
        return int(row.get("count", 0))

    app = make_app(rt)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    rows = []
    try:
        async with aiohttp.ClientSession() as s:

            async def one(question: str):
                t0 = time.perf_counter()
                async with s.post(
                    f"{base}/ask/", json={"question": question}
                ) as r:
                    body = await r.json()
                    return r.status, body, (time.perf_counter() - t0) * 1e3

            # warm BOTH arms until the real (non-degraded) paths serve:
            # generative pays the prefill/decode compiles, extractive
            # pays the lexical/hybrid program compile
            t_end = time.monotonic() + 300
            while time.monotonic() < t_end:
                st, body, _ = await one("Summarize the admission note.")
                if st == 200 and not body.get("degraded"):
                    break
            else:
                errs.append("generative warmup never served un-degraded")
            while time.monotonic() < t_end:
                st, body, _ = await one(
                    "What is the MRN of patient Okafor?"
                )
                if st == 200 and body.get("route") == "extractive":
                    break
            else:
                errs.append("extractive warmup never served the route")

            # quiescence barrier: a warmup decode whose HTTP answer
            # already resolved can still be retiring chunks on the
            # batcher worker — wait for the decode counter to go flat so
            # the per-request deltas below are attributable
            stable_since, last = time.monotonic(), stage_count(
                "serve_decode"
            )
            while time.monotonic() < t_end:
                await asyncio.sleep(0.1)
                now = stage_count("serve_decode")
                if now != last:
                    stable_since, last = time.monotonic(), now
                elif time.monotonic() - stable_since > 2.0:
                    break

            for ex in mix:
                d0, a0 = stage_count("serve_decode"), stage_count(
                    "serve_alloc"
                )
                st, body, lat_ms = await one(ex["question"])
                if st != 200:
                    errs.append(f"{ex['id']}: HTTP {st}: {body}")
                    continue
                if not ({"answer", "sources"} <= set(body)):
                    errs.append(
                        f"{ex['id']}: wire shape broken: {sorted(body)}"
                    )
                route = body.get("route")
                if route not in (None, "extractive"):
                    errs.append(f"{ex['id']}: unexpected route {route!r}")
                routed_ex = route == "extractive"
                decode_d = stage_count("serve_decode") - d0
                alloc_d = stage_count("serve_alloc") - a0
                if routed_ex and (decode_d or alloc_d):
                    errs.append(
                        f"{ex['id']}: routed-extractive paid device "
                        f"dispatches (serve_decode +{decode_d}, "
                        f"serve_alloc +{alloc_d}) — the decoder-skip "
                        "path regressed"
                    )
                rows.append(
                    {
                        "id": ex["id"],
                        "lang": ex["lang"],
                        "label": ex["label"],
                        "routed": "extractive" if routed_ex else
                        "generative",
                        "latency_ms": round(lat_ms, 1),
                        "degraded": bool(body.get("degraded")),
                        "serve_decode_delta": decode_d,
                        "serve_alloc_delta": alloc_d,
                    }
                )
            async with s.get(f"{base}/api/retrieval") as r:
                routing_live = (await r.json()).get("routing") \
                    if r.status == 200 else None
    finally:
        await runner.cleanup()
    return {"rows": rows, "routing_live": routing_live}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="routing_report.json")
    args = ap.parse_args()

    import asyncio

    from docqa_tpu.config import load_config
    from docqa_tpu.service.app import DocQARuntime

    mix = load_mix()
    cfg = load_config(env={}, overrides=dict(OVERRIDES))
    rt = DocQARuntime(cfg).start()
    errs: list = []
    try:
        n_docs = seed_corpus(rt, mix)
        driven = asyncio.run(drive(rt, mix, errs))
    finally:
        rt.stop()
    rows = driven["rows"]

    tp = sum(
        1 for r in rows
        if r["label"] == "extractive" and r["routed"] == "extractive"
    )
    fp = sum(
        1 for r in rows
        if r["label"] == "generative" and r["routed"] == "extractive"
    )
    fn = sum(
        1 for r in rows
        if r["label"] == "extractive" and r["routed"] == "generative"
    )
    precision = tp / max(tp + fp, 1)
    recall = tp / max(tp + fn, 1)
    if len(rows) != len(mix):
        errs.append(f"only {len(rows)}/{len(mix)} requests measured")
    if precision < MIN_PRECISION:
        errs.append(
            f"routing precision {precision:.3f} < {MIN_PRECISION} "
            f"(tp={tp} fp={fp}) — extractive-routed generative "
            "questions are shipping wrong-shaped answers"
        )
    if tp + fp < MIN_EXTRACTIVE_ROUTED:
        errs.append(
            f"only {tp + fp} routed-extractive answers (< "
            f"{MIN_EXTRACTIVE_ROUTED}): the evidence gate is demoting "
            "the lookup traffic and the decoder-skip win is gone"
        )
    ex_lat = [r["latency_ms"] for r in rows if r["routed"] == "extractive"]
    gen_lat = [r["latency_ms"] for r in rows if r["routed"] == "generative"]
    p50_ex, p50_gen = _p50(ex_lat), _p50(gen_lat)
    if p50_ex is not None and p50_gen is not None and p50_ex >= p50_gen:
        errs.append(
            f"route split inverted: routed-extractive p50 {p50_ex}ms >= "
            f"generative p50 {p50_gen}ms — the fast path is not fast"
        )

    report = {
        "n_docs": n_docs,
        "n_requests": len(rows),
        "routing_precision": round(precision, 3),
        "routing_recall": round(recall, 3),
        "confusion": {"tp": tp, "fp": fp, "fn": fn,
                      "tn": len(rows) - tp - fp - fn},
        "p50_ms": {"extractive": p50_ex, "generative": p50_gen},
        "split_ratio": (
            round(p50_gen / p50_ex, 1) if p50_ex and p50_gen else None
        ),
        "routing_live": driven["routing_live"],
        "rows": rows,
        "errors": errs,
        "pass": not errs,
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(
        f"routing_smoke: precision {precision:.3f} recall {recall:.3f} "
        f"(tp={tp} fp={fp} fn={fn}); p50 extractive {p50_ex}ms vs "
        f"generative {p50_gen}ms (x{report['split_ratio']}); "
        f"report -> {args.out}"
    )
    if errs:
        for e in errs:
            print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(
        "routing_smoke PASS: precision floor held, decoder-skip paid "
        "zero decode/alloc dispatches, route split ordered"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
