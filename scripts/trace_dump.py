#!/usr/bin/env python
"""Dump flight-recorder timelines (docs/OBSERVABILITY.md).

Against a running service:

    # summaries + full JSON timelines of the recent (or anomalous) ring
    python scripts/trace_dump.py http://127.0.0.1:8000 --out traces.json
    python scripts/trace_dump.py http://127.0.0.1:8000 --anomalous --out bad.json

    # ONE request's Chrome trace — load the file at https://ui.perfetto.dev
    python scripts/trace_dump.py http://127.0.0.1:8000 t-000007 --out one.json

Self-contained smoke (the CI artifact): boot a fake-mode runtime in
process, drive one ingest + one /ask over real HTTP, and export the
/ask request's Chrome trace:

    python scripts/trace_dump.py --smoke --out ask_trace.json

Exits non-zero when the smoke trace is structurally broken (no events,
no linked spans), when ``GET /metrics`` fails the strict Prometheus
line-lint (``obs/expo.py``), or when ``GET /api/telemetry`` serves no
series — so CI fails loudly instead of archiving an empty file.
"""

import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def fetch_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def dump_from_service(base: str, trace_id, anomalous: bool, out: str) -> int:
    if trace_id:
        payload = fetch_json(f"{base}/api/trace/{trace_id}?format=chrome")
        kind = "chrome-trace"
    else:
        flag = "?anomalous=1&limit=100" if anomalous else "?limit=100"
        summaries = fetch_json(f"{base}/api/traces{flag}")
        payload = {
            "summaries": summaries,
            "timelines": [
                fetch_json(f"{base}/api/trace/{row['trace_id']}")
                for row in summaries
            ],
        }
        kind = f"{len(summaries)} timeline(s)"
    with open(out, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {kind} to {out}")
    return 0


def smoke(out: str) -> int:
    """Fake-mode runtime, one /ask over real HTTP, Chrome trace out."""
    import asyncio

    import jax

    jax.config.update("jax_platforms", "cpu")

    from docqa_tpu.config import load_config
    from docqa_tpu.service.app import DocQARuntime, make_app

    cfg = load_config(env={}, overrides={
        "flags.use_fake_llm": True,
        "flags.use_fake_encoder": True,
        "encoder.embed_dim": 64,
        "store.dim": 64,
        "store.shard_capacity": 256,
        "ner.hidden_dim": 32,
        "ner.num_layers": 1,
        "ner.num_heads": 2,
        "ner.mlp_dim": 64,
        "ner.train_steps": 0,
    })
    rt = DocQARuntime(cfg).start()

    async def drive():
        import aiohttp
        from aiohttp import web

        app = make_app(rt)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"{base}/ingest/?wait=1",
                    json={
                        "filename": "smoke.txt",
                        "text": "Aspirin 100 mg daily. BP 130/85 mmHg.",
                        "patient_id": "p-smoke",
                    },
                ) as r:
                    assert r.status == 200, await r.text()
                async with s.post(
                    f"{base}/ask/", json={"question": "aspirin dose?"}
                ) as r:
                    assert r.status == 200, await r.text()
                    trace_id = r.headers.get("X-Trace-Id")
                assert trace_id, "no X-Trace-Id on the /ask response"
                timeline = await (
                    await s.get(f"{base}/api/trace/{trace_id}")
                ).json()
                chrome = await (
                    await s.get(
                        f"{base}/api/trace/{trace_id}?format=chrome"
                    )
                ).json()
                listing = await (await s.get(f"{base}/api/traces")).json()
                # Prometheus exposition over REAL HTTP bytes, strict
                # line-lint (CI has no promtool; the grammar lives in
                # obs/expo.py and tests/test_telemetry.py pins it)
                async with s.get(f"{base}/metrics") as r:
                    assert r.status == 200, await r.text()
                    prom = await r.text()
                tele = await (await s.get(f"{base}/api/telemetry")).json()
        finally:
            await runner.cleanup()
        return timeline, chrome, listing, prom, tele

    try:
        timeline, chrome, listing, prom, tele = asyncio.run(drive())
    finally:
        rt.stop()

    from docqa_tpu.obs.expo import lint_prometheus_text

    problems = lint_prometheus_text(prom)
    n_series = len(tele.get("series", {}))
    print(
        f"/metrics: {len(prom.splitlines())} line(s), "
        f"{len(problems)} lint problem(s); /api/telemetry: "
        f"{n_series} series"
    )
    if problems:
        for p in problems[:10]:
            print(f"  {p}", file=sys.stderr)
        return 1
    if n_series == 0:
        print("telemetry served no series", file=sys.stderr)
        return 1

    with open(out, "w", encoding="utf-8") as f:
        json.dump(chrome, f, indent=1)
    n_events = len(chrome.get("traceEvents", []))
    n_spans = len(timeline.get("spans", []))
    print(
        f"smoke /ask trace {timeline.get('trace_id')}: {n_spans} span(s), "
        f"coverage {timeline.get('coverage')}, {n_events} Chrome event(s), "
        f"{len(listing)} trace(s) in the recorder -> {out}"
    )
    # structural gates only: the fake-llm path is sub-millisecond, so a
    # coverage threshold would gate on scheduler noise
    if n_events == 0 or n_spans < 2:
        print("smoke trace is structurally empty", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("base_url", nargs="?", help="running service base URL")
    ap.add_argument("trace_id", nargs="?", help="one trace id (Chrome out)")
    ap.add_argument("--anomalous", action="store_true",
                    help="dump the always-keep anomalous ring")
    ap.add_argument("--smoke", action="store_true",
                    help="self-contained fake-mode /ask trace export")
    ap.add_argument("--out", default="traces.json")
    args = ap.parse_args()
    if args.smoke:
        return smoke(args.out)
    if not args.base_url:
        ap.error("base_url required (or --smoke)")
    return dump_from_service(
        args.base_url.rstrip("/"), args.trace_id, args.anomalous, args.out
    )


if __name__ == "__main__":
    sys.exit(main())
