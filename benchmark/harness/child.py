"""The one process that holds the chip(s): the program's own runtime and
HTTP surface, booted for a benchmark cell.

What differs from ``scripts/start_all.py`` is set-up only, and none of it
is on a request's path:

* decoder weights are made on the device from the configuration's
  ``weights_seed`` (never from ``--seed``) by the
  ``weights.make_decoder_params`` of the configuration's architecture
  package (``arch.load``) and handed to the program's
  ``GenerateEngine(params=...)`` (the program's own default draws 7e9
  normals on one host thread, 280 s);
* the encoder keeps the program's initialiser but takes the run's seed;
* the PHI tagger is the untrained plumbing tagger (``ner.train_steps=0``):
  no cell ingests, so nothing reads it;
* no bootstrap CSV index: the corpus of ``corpus.py`` is encoded by the
  cell's encoder and appended through ``VectorStore.add``, then filled to
  the configured row count with seeded unit vectors that carry no text;
* routes under ``/bench/`` are added to the program's app for the parent
  (state, light samples, the correctness comparisons, the profiler).

Usage (the parent, ``run.py``, is the only caller):
    python child.py --config FILE --seed N --port P --work DIR
                    [--trace 1] [--overlay FILE]
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import arch  # noqa: E402  (standard library)


def program_overrides(conf: dict) -> dict:
    """Dotted-path overrides for the program's ``load_config``: the
    published keys as the architecture's package maps them, the file's
    ``serving`` block over them, then what every benchmark child fixes."""
    out = dict(arch.load_shapes(conf).keys.program_overrides(conf))
    out.update(conf.get("serving", {}))
    out.update({
        "data.work_dir": None,  # nothing persisted, nothing restored
        "data.bootstrap_dir": None,
        "ner.train_steps": 0,
        "ner.params_path": None,
        "service.host": "127.0.0.1",
    })
    return out


class State:
    """What ``/bench/state`` serves."""

    def __init__(self):
        self.t_start = time.monotonic()
        self.phase = "boot"
        self.error = None
        self.setup: dict = {}
        self.compiles = 0  # persistent-cache misses: real compilations
        self.programs_built = 0  # compile requests, cache hits included
        self.lock = threading.Lock()

    def mark(self, name: str, t0: float) -> float:
        now = time.monotonic()
        self.setup[name] = round(now - t0, 3)
        return now


def count_compiles(state: State) -> None:
    import jax

    def on_duration(event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with state.lock:
                state.programs_built += 1

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            with state.lock:
                state.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def profile_program_spans() -> None:
    """Traced runs only: make the program's own ``span()`` sites open a
    profiler annotation (``profile=True``, an argument they already take),
    so that idle gaps in the device trace can be named after the host span
    open during them.  Must run before the program's modules import."""
    import functools

    from docqa_tpu.runtime import metrics

    plain = metrics.span

    @functools.wraps(plain)
    def span(name, registry=None, profile=False):
        return plain(name, registry, True)

    metrics.span = span


def build_corpus(rt, conf: dict, seed: int, state: State):
    """Encode and append the text corpus, then fill with seeded unit rows.
    Returns the bfloat16 copy of every row handed to the store (the
    retrieval reference scans it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import check, corpus

    spec = conf["corpus"]
    n_patients, total = int(spec["patients"]), int(spec["rows"])
    t0 = time.monotonic()
    meta = []
    for i in range(n_patients):
        meta.extend(corpus.patient_chunks(seed, i))
    if len(meta) > total:
        raise ValueError("corpus.rows is smaller than the text corpus")
    t0 = state.mark("corpus_text", t0)
    vectors = rt.encoder.encode_texts([m["text_content"] for m in meta])
    t0 = state.mark("corpus_encode", t0)
    stored = [check.to_bf16(check.unit_rows(vectors))]
    rt.store.add(vectors, meta)
    t0 = state.mark("corpus_add_text", t0)
    n_fill = total - len(meta)
    if n_fill > 0:
        dim = rt.cfg.store.dim

        @jax.jit
        def fill(key):
            x = jax.random.normal(key, (n_fill, dim), jnp.float32)
            return x / jnp.linalg.norm(x, axis=1, keepdims=True)

        rows = np.asarray(fill(jax.random.key(seed % (2**31), impl="rbg")))
        t0 = state.mark("corpus_fill_draw", t0)
        blank = {"doc_id": "fill", "doc_type": "fill", "source": "fill",
                 "text_content": ""}
        rt.store.add(rows, [blank] * n_fill)
        stored.append(check.to_bf16(check.unit_rows(rows)))
        del rows
        t0 = state.mark("corpus_add_fill", t0)
    return np.concatenate(stored, axis=0)


def run_check(rt, conf: dict, package, seed: int, stored) -> dict:
    """What ``/bench/check`` answers: every number ``harness/check.py``
    compares, each beside its limit from the file's ``correct``; a missing
    limit fails.  A package that routes adds ``router_choice_gap``."""
    from harness import check

    limits = conf.get("correct", {})
    out = {"numbers": []}
    batcher = rt.batcher._replicas[0].batcher
    dec = check.decoder_check(
        package, conf["check"], rt.generator, seed,
        n_blocks=batcher.n_blocks, block_size=batcher.block_size,
        seq_capacity=batcher.seq_capacity, n_lanes=batcher.n_slots,
        step_width=max(1, int(rt.cfg.generate.speculative_k)),
        mesh=rt.mesh,
    )
    out["decoder"] = dec
    out["numbers"].append({
        "name": "decoder_logit_rel_err",
        "value": dec["program"]["worst_row"],
        "limit": limits.get("decoder_logit_rel_err"),
    })
    out["numbers"].append({
        "name": "kv_cache_bits_missing",
        "value": check.kv_bits_missing(conf["kv_cache_bits"], dec["kv_bits"]),
        "limit": 0,
    })
    queries = check.retrieval_queries(stored, seed)
    k = int(rt.cfg.store.default_k)
    err = check.retrieval_error(
        stored, queries, k, check.store_search(rt.store)
    )
    out["numbers"].append({
        "name": "retrieval_score_err", "value": err,
        "limit": limits.get("retrieval_score_err"),
    })
    if arch.routes(package):
        out["numbers"].append({
            "name": "router_choice_gap",
            "value": dec["routing"]["worst_gap"],
            "limit": limits.get("router_choice_gap"),
        })
    out["correct"] = all(
        n["limit"] is not None and n["value"] <= n["limit"]
        for n in out["numbers"]
    )
    return out


def make_routes(rt, state: State, conf: dict, package, seed: int, stored,
                work: str):
    import jax
    from aiohttp import web

    from harness import xplane

    profile = {"dir": None, "t0": None}

    def device_block():
        devices = jax.devices()
        peaks = []
        for d in devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use") or 0))
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks),
        }

    async def get_state(_req):
        with state.lock:
            compiles, built = state.compiles, state.programs_built
        return web.json_response({
            "programs_built": built,
            "phase": state.phase,
            "error": state.error,
            "warmup": rt.warmup_status,
            "compiles": compiles,
            "setup": state.setup,
            "rows": rt.store.count,
            "device": device_block(),
        })

    async def get_sample(_req):
        """Light enough to poll at 10 Hz during a traced run."""
        b = rt.batcher
        occ = b.kv_block_occupancy() if b is not None else {}
        return web.json_response({
            "n_active": b.n_active if b is not None else 0,
            "n_queued": b.n_queued if b is not None else 0,
            "kv_utilization": occ.get("utilization"),
            "kv_tokens": occ.get("tokens_committed"),
        })

    async def post_check(_req):
        import asyncio

        loop = asyncio.get_running_loop()
        try:
            out = await loop.run_in_executor(
                None, run_check, rt, conf, package, seed, stored
            )
        except Exception as e:  # a crash is a failed check, with its cause
            import traceback

            traceback.print_exc()
            out = {"correct": False, "error": repr(e)[:400], "numbers": []}
        # the window's peak was read before this (run.py); what the
        # comparison itself reached is said beside it, never reported
        out["memory_peak_bytes_after"] = device_block()["memory_peak_bytes"]
        return web.json_response(out)

    async def trace_start(_req):
        logdir = os.path.join(work, "trace")
        import shutil

        shutil.rmtree(logdir, ignore_errors=True)
        os.makedirs(logdir)
        options = jax.profiler.ProfileOptions()
        # the runtime's own events and the program's spans, not a frame
        # per Python call: smaller trace, less tracing overhead
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        profile["dir"], profile["t0"] = logdir, time.monotonic()
        return web.json_response({"logdir": logdir})

    async def trace_stop(_req):
        import asyncio

        if profile["dir"] is None:
            return web.json_response({"error": "no trace open"}, status=409)
        window_s = time.monotonic() - profile["t0"]

        def stop_and_reduce():
            jax.profiler.stop_trace()
            path = xplane.find_xplane(profile["dir"])
            return xplane.reduce_file(path)

        loop = asyncio.get_running_loop()
        reduced = await loop.run_in_executor(None, stop_and_reduce)
        reduced["host_window_s"] = window_s
        profile["dir"] = None
        return web.json_response(reduced)

    return [
        web.get("/bench/state", get_state),
        web.get("/bench/sample", get_sample),
        web.post("/bench/check", post_check),
        web.post("/bench/trace/start", trace_start),
        web.post("/bench/trace/stop", trace_stop),
    ]


def seed_engines(package, conf: dict, seed: int, state: State) -> None:
    """Put the benchmark's seeded engines in the program's place, before
    the runtime is built.  The decoder's weights are the CONFIGURATION's:
    one draw (``weights_seed``) for every run, as a deployment serves one
    set of weights for months; ``--seed`` draws the encoder, the sampler
    and, elsewhere, the corpus, the questions and ``check``'s prompts
    (README.md, "What the seed may draw, and what it may not")."""
    import jax

    from docqa_tpu.engines import encoder as encoder_mod
    from docqa_tpu.engines import generate as generate_mod

    seed31 = seed % (2**31)
    weights_seed31 = int(conf["weights_seed"]) % (2**31)

    class SeededGenerateEngine(generate_mod.GenerateEngine):
        """The program's engine, given the benchmark's weights."""

        def __init__(self, dec_cfg, gen=None, mesh=None, params=None, **kw):
            if params is None:
                t = time.monotonic()
                params = package.weights.make_decoder_params(
                    dec_cfg, weights_seed31, mesh
                )
                jax.block_until_ready(params)
                state.mark("decoder_weights", t)
            kw.setdefault("seed", seed31)
            super().__init__(dec_cfg, gen=gen, mesh=mesh, params=params, **kw)

    class SeededEncoderEngine(encoder_mod.EncoderEngine):
        def __init__(self, enc_cfg, mesh=None, **kw):
            kw.setdefault("seed", seed31)
            super().__init__(enc_cfg, mesh=mesh, **kw)

    generate_mod.GenerateEngine = SeededGenerateEngine
    encoder_mod.EncoderEngine = SeededEncoderEngine


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--overlay", default="")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--work", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()
    state = State()
    t0 = state.t_start
    conf = arch.load_cell_config(args.config, args.overlay)

    if args.trace:
        profile_program_spans()
    import jax

    from docqa_tpu.runtime.compile_cache import configure_compile_cache

    configure_compile_cache()
    # every program, however quick to compile, is found again by the next
    # run: set-up then does the same work every time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_log_compiles", True)  # names, in the child's log
    count_compiles(state)

    from harness import peaks

    devices = jax.devices()  # no accelerator under JAX_PLATFORMS=tpu: raises
    if not args.overlay:
        peaks.peaks_of(devices[0].device_kind)  # unknown device: an error
        if devices[0].platform != "tpu" or len(devices) < args.chips:
            print(
                f"need {args.chips} TPU chip(s), found {len(devices)} x "
                f"{devices[0].platform}", file=sys.stderr,
            )
            return 3
    t0 = state.mark("jax_init", t0)

    from docqa_tpu.config import load_config

    cfg = load_config(env={}, overrides=program_overrides(conf))
    package = arch.load(conf)
    seed_engines(package, conf, args.seed, state)

    from aiohttp import web

    from docqa_tpu.service.app import DocQARuntime, make_app

    rt = DocQARuntime(cfg)
    t0 = state.mark("runtime", t0)
    state.setup["runtime_split"] = dict(rt.boot_s)
    stored = build_corpus(rt, conf, args.seed, state)
    t0 = time.monotonic()
    rt.start()
    state.phase = "warming"

    def watch_warmup():
        t = time.monotonic()
        while rt.warmup_status.get("state") in ("pending", "running"):
            time.sleep(0.2)
        state.mark("decode_warmup", t)
        if rt.warmup_status.get("state") != "ok":
            state.error = f"warm-up {rt.warmup_status}"
            state.phase = "failed"
        else:
            state.phase = "ready"

    threading.Thread(target=watch_warmup, daemon=True).start()
    app = make_app(rt)
    app.add_routes(make_routes(rt, state, conf, package, args.seed, stored, args.work))
    try:
        web.run_app(app, host="127.0.0.1", port=args.port, print=None)
    finally:
        rt.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
