#!/usr/bin/env python3
"""The benchmark: one cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A standard-library parent (it never imports JAX) that finds the cell in
``BENCHMARK.json``, starts ONE child that holds the chip(s)
(``harness/child.py``: the program's runtime and HTTP surface), warms every
shape the cell's traffic uses, offers the traffic of the cell's traffic
file over HTTP for ``--seconds``, asks the child for the out-of-window
correctness comparisons, and prints one JSON object as its last line.

Everything that belongs to one cell is data found by name: the
configuration (``configs/<config>.json``), the traffic mix
(``traffic/<traffic>.json``), each metric (``metrics/<name>.json`` naming a
reader module under ``readers/`` and its parameters).  See README.md.

Without a TPU (or with fewer chips than the cell asks for) the child
cannot start, and this exits non-zero without a result line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

T_START = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from harness import arch, client, stats, traffic  # noqa: E402

FIRST_RUN_BUDGET_S = 1150.0  # a run that compiles may take 1200 s


def say(msg: str) -> None:
    print(msg, flush=True)


def load_spec(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, config


def metrics_of(bench: dict, cell: dict, traced: bool):
    """The metrics this run reports: end-to-end without a trace, per-layer
    with one; each only in the cells it lists (all cells if it lists
    none — then, for a per-layer metric, the cells that report the
    end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell["name"] in m["workloads"] if "workloads" in m
            else m["moves"] in reported)
    ]


def read_metric(name: str, ctx: dict):
    """Run the metric's reader; None when it finds nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".json")
    with open(path, encoding="utf-8") as f:
        decl = json.load(f)
    reader = importlib.import_module("readers." + decl["reader"])
    return reader.read(ctx, **decl.get("params", {}))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def split_cores(cores):
    """(the server's cores, the load generator's) of the cores this process
    may run on: the last two are kept for the parent — its client threads,
    which no clinic runs on the server's own cores — and the rest go to the
    child.  None under four cores: then nothing is pinned."""
    cores = sorted(cores)
    if len(cores) < 4:
        return None
    return cores[:-2], cores[-2:]


class Child:
    """The server process, its whole process group, and its log."""

    def __init__(self, args, cell, config, work):
        self.port = free_port()
        self.log_path = os.path.join(work, "child.log")
        cmd = [
            sys.executable, os.path.join(BENCH_DIR, "harness", "child.py"),
            "--config", os.path.join(ROOT, config["file"]),
            "--seed", str(args.seed), "--port", str(self.port),
            "--work", work, "--trace", str(args.trace),
            "--chips", str(cell["chips"]),
        ]
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        if args.rehearsal:
            cmd += ["--overlay", args.rehearsal]
            env["JAX_PLATFORMS"] = "cpu"
        else:
            # no accelerator: the child's first JAX call raises and it
            # exits; there is no CPU fallback to print a number from
            env["JAX_PLATFORMS"] = "tpu"
        self.log = open(self.log_path, "wb")
        # the child inherits the cores this (still single) thread holds at
        # its start; the parent then moves to its own two, and the client
        # threads it starts later inherit those
        split = (split_cores(os.sched_getaffinity(0))
                 if hasattr(os, "sched_setaffinity") else None)
        if split:
            os.sched_setaffinity(0, split[0])
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        if split:
            os.sched_setaffinity(0, split[1])
        self.affinity = ({"child": split[0], "parent": split[1]} if split
                         else "left alone")
        self.conn = client.Connection("127.0.0.1", self.port, 120.0)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def _call(self, method: str, path: str, body=None):
        """A control call (never a timed request).  The server drops a
        keep-alive connection idle for 75 s, which a window with a long
        drain outlasts: a call that finds it gone is made once more."""
        for _ in range(2):
            status, _h, payload = self.conn.request(method, path, body)
            if status is not None:
                return payload if status == 200 else None
        return None

    def get(self, path: str):
        return self._call("GET", path)

    def post(self, path: str, body=None):
        return self._call("POST", path, body or {})

    def stop(self) -> None:
        self.conn.close()
        for sig, wait in ((signal.SIGTERM, 30), (signal.SIGKILL, 10)):
            if self.proc.poll() is not None:
                break
            try:
                os.killpg(self.proc.pid, sig)
                self.proc.wait(timeout=wait)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        try:  # anything of the group that outlived its leader
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.log.close()

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return ""


class Failed(Exception):
    pass


def wait_ready(child: Child, deadline: float) -> dict:
    while True:
        if not child.alive():
            raise Failed(f"the child exited with {child.proc.returncode}")
        if time.monotonic() > deadline:
            raise Failed("time budget spent before the server was ready")
        state = child.get("/bench/state")
        if state is not None:
            if state["phase"] == "failed":
                raise Failed(f"set-up failed: {state['error']}")
            if state["phase"] == "ready":
                return state
        time.sleep(0.5)


def warm(child: Child, mix: dict, seed: int, n_patients: int) -> int:
    """Send the mix's warm requests — one after another, then in bursts
    of each size in ``warm_bursts`` (the batcher's admission code builds a
    few small programs per number of requests admitted together); returns
    the requests sent."""
    stream = traffic.questions(mix, seed, n_patients, "warm")
    sent = [0]
    lock = threading.Lock()

    def one(kind, text):
        conn = client.Connection("127.0.0.1", child.port, 600.0)
        try:
            s = client.send(conn, mix["endpoint"], kind, text, time.monotonic())
        finally:
            conn.close()
        with lock:
            sent[0] += 1
        if s.failed:
            say(f"warm request failed: {s.failed}")

    for _ in range(int(mix.get("warm_requests", 4))):
        one(*next(stream))
    for size in mix.get("warm_bursts", []):
        threads = [threading.Thread(target=one, args=next(stream))
                   for _ in range(int(size))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return sent[0]


class Poller:
    """Samples ``/bench/sample`` during a traced run."""

    def __init__(self, child: Child, hz: float = 5.0):
        self.samples, self._stop = [], threading.Event()
        self._conn = client.Connection("127.0.0.1", child.port, 5.0)
        self._hz = hz
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(1.0 / self._hz):
            status, _h, payload = self._conn.request("GET", "/bench/sample")
            if status == 200:
                self.samples.append(payload)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)
        self._conn.close()


def run_traffic(child, mix, args, n_patients, traced):
    """Ramp, window, drain.  Returns the context the readers read."""
    ctx = {"before": {}, "after": {}}
    timeout = float(mix.get("timeout_s", 60.0))
    trace_s = min(float(mix.get("trace_s", 3.0)), args.seconds / 2)
    tracer = {"thread": None, "reduced": None}

    def trace_slice():
        time.sleep(max(0.0, (args.seconds - trace_s) / 2))
        tconn = client.Connection("127.0.0.1", child.port, 300.0)
        try:
            tconn.request("POST", "/bench/trace/start", {})
            time.sleep(trace_s)
            _s, _h, tracer["reduced"] = tconn.request(
                "POST", "/bench/trace/stop", {}
            )
        finally:
            tconn.close()

    def on_window(_t0):
        ctx["before"]["metrics"] = child.get("/api/metrics") or {}
        if traced:
            ctx["before"]["status"] = child.get("/api/status") or {}
            tracer["thread"] = threading.Thread(target=trace_slice, daemon=True)
            tracer["thread"].start()

    poller = Poller(child) if traced else None
    if poller:
        poller.start()
    streams = [
        traffic.questions(mix, args.seed, n_patients, f"client{i}")
        for i in range(int(mix["clients"]))
    ]
    samples, t0, t1 = client.closed_loop(
        "127.0.0.1", child.port, mix["endpoint"], timeout, streams,
        int(mix.get("ramp_requests", 1)), args.seconds, on_window,
        lockstep=bool(mix.get("lockstep")),
    )
    ctx["after"]["metrics"] = child.get("/api/metrics") or {}
    if poller:
        poller.stop()
        ctx["polled"] = poller.samples
    if traced:
        ctx["after"]["status"] = child.get("/api/status") or {}
        if tracer["thread"] is not None:
            tracer["thread"].join(timeout=300)
        ctx["trace"] = tracer["reduced"]
    ctx.update(samples=samples, t0=t0, t1=t1)
    return ctx


def fetch_request_traces(child: Child, samples, limit: int = 200) -> dict:
    """The program's own per-request timelines (``/api/trace/<id>``) for
    the requests of the window: spans and the cost summary."""
    out = {}
    for s in samples[:limit]:
        if s.trace_id:
            t = child.get(f"/api/trace/{s.trace_id}")
            if t:
                out[s.trace_id] = t
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearsal", default="",
        help="test only: a JSON overlay of tiny widths, run on the CPU "
        "backend; device metrics are then not reported",
    )
    args = ap.parse_args()
    bench, cell, config = load_spec(args.workload)
    try:
        conf = arch.load_cell_config(
            os.path.join(ROOT, config["file"]), args.rehearsal
        )
    except arch.ConfigError as e:
        raise SystemExit(f"FAIL: {e}")
    mix = traffic.load(
        os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    )
    n_patients = int(conf["corpus"]["patients"])
    work = os.path.join(ROOT, ".benchmark_work", cell["name"])
    os.makedirs(work, exist_ok=True)
    traced = bool(args.trace)

    # a run that has to compile may take 1200 s; a warm one ends long
    # before (PERF.md): one limit serves both
    deadline = T_START + FIRST_RUN_BUDGET_S

    child = Child(args, cell, config, work)
    try:
        state = wait_ready(child, deadline - args.seconds - 30)
        n_warm = warm(child, mix, args.seed, n_patients)
        built_before = child.get("/bench/state")
        say(f"warm after {time.monotonic() - T_START:.1f} s ({n_warm} warm "
            f"requests; child phases {json.dumps(state['setup'])})")
        ctx = run_traffic(child, mix, args, n_patients, traced)
        # process start -> the window's first instant: boot, weights,
        # corpus, warm-up, warm requests and the ramp are all set-up
        setup_s = ctx["t0"] - T_START
        # the device block (memory peak) is read here, at the window's
        # end: the comparison below allocates a KV pool and float32
        # layers of its own, which no deployment holds
        state = child.get("/bench/state")
        say("inside the window: "
            f"{state['programs_built'] - built_before['programs_built']} "
            "programs built, of which "
            f"{state['compiles'] - built_before['compiles']} compiled "
            "(the rest came from the cache); both should be 0")
        window = [s for s in ctx["samples"] if ctx["t0"] <= s.due < ctx["t1"]]
        if traced:
            ctx["request_traces"] = fetch_request_traces(child, window)
        verdict = child.post("/bench/check")
        if verdict is None:
            raise Failed("the correctness comparison gave no answer")
    except Failed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        print(f"---- end of {child.log_path} ----\n{child.log_tail()}",
              file=sys.stderr)
        return 1
    finally:
        child.stop()

    compared = [f"compared {n['name']}: {n['value']:.6g} (limit {n['limit']})"
                for n in verdict.get("numbers", [])]
    if verdict.get("error"):
        compared.append(f"comparison error: {verdict['error']}")
    for line in compared:
        say(line)
    say(f"device memory peak: {state['device']['memory_peak_bytes']} at the "
        f"window's end, {verdict.get('memory_peak_bytes_after')} after the "
        "comparison")
    failed = [s for s in window if s.failed]
    if failed:  # the program's own account of what it cut or refused
        print(f"---- end of {child.log_path} ----\n{child.log_tail()}",
              file=sys.stderr)
    reasons = {}
    for s in failed:
        reasons[s.failed] = reasons.get(s.failed, 0) + 1
    say(f"window {ctx['t1'] - ctx['t0']:.2f} s: {len(window)} requests sent, "
        f"{len(failed)} failed {reasons or ''}")
    ttfts = sorted(
        t for t in (stats.ttft_ms(s.sent, s.delta_times) for s in window)
        if t is not None
    )
    if ttfts:  # which requests the batcher admitted ahead shows here
        say("first token after, ms: " + " ".join(f"{t:.0f}" for t in ttfts))
    routes = {}
    for s in window:
        routes[s.route] = routes.get(s.route, 0) + 1
    say(f"routes as answered: {routes}")
    if traced and ctx.get("request_traces"):
        spans = {}
        tokens = []
        for t in ctx["request_traces"].values():
            for sp in t.get("spans", []):
                if sp.get("duration_ms") is not None:
                    spans.setdefault(sp["name"], []).append(sp["duration_ms"])
            cost = t.get("cost") or {}
            if cost.get("prefill_tokens"):
                tokens.append(cost["prefill_tokens"])
        say("request spans, ms (n, p50, max): " + json.dumps({
            k: [len(v), round(stats.percentile(v, 50), 1), round(max(v), 1)]
            for k, v in sorted(spans.items())
        }))
        if tokens:
            say(f"prompt tokens per request: {stats.summarize(tokens)}")
    if traced and ctx.get("trace"):
        say("traced slice: " + json.dumps({
            k: ctx["trace"].get(k)
            for k in ("devices", "busy_s", "window_s", "programs",
                      "collective_s", "layout")
        }))
        say("timeline of the slice [start s, seconds, where, what]: "
            + json.dumps(ctx["trace"].get("timeline")))
    ctx.update(
        window=window, conf=conf, cell=cell, mix=mix, setup_s=setup_s,
        device=state["device"], seconds=ctx["t1"] - ctx["t0"],
        rehearsal=bool(args.rehearsal),
    )
    metrics = {}
    for m in metrics_of(bench, cell, traced):
        if args.rehearsal and m["source"] == "device_trace":
            continue  # a CPU run is never written under a device metric
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(state["device"])
    result = {
        "correct": bool(verdict.get("correct")),
        "attempted": len(window),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
        "seed": args.seed,
        "weights_seed": conf["weights_seed"],
        "affinity": child.affinity,
    }
    if traced and ctx.get("trace") and not args.rehearsal:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": ctx["trace"]["device_ops"],
            "idle_gaps": ctx["trace"]["idle_gaps"],
        }
    # each number compared beside its limit, last in the line and again as
    # the last lines of standard error: the driver's record of a run that
    # is not correct keeps the end of each
    result["compared"] = {
        n["name"]: {"value": n["value"], "limit": n["limit"]}
        for n in verdict.get("numbers", [])
    }
    say(json.dumps(result))
    print("\n".join(compared), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
