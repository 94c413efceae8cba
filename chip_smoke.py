#!/usr/bin/env python3
"""Prove the serving path runs on the chip: ``python3 chip_smoke.py``.

Boots the normal launcher (``scripts/start_all.py``) at the full
Mistral-7B widths with int8 weights (seeded random weights, hash
tokenizer), ingests three notes, requests one patient synthesis, asks
seven questions (sequential, then four at once), and checks — from what the
SERVING PROCESS reports about itself, never from HTTP status alone —
that the decoder really generated on a TPU.  The four concurrent asks run
inside the program's own profiler window (``POST /api/profiler/start``):
the trace it leaves must name the batcher's host phases.  The last line
of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}

and the exit code is 0 only if every check held.  No TPU, a degraded
answer, zero decode tokens, a failed warm-up, an open breaker: non-zero.

One process per chip: this parent uses the standard library only (it
never imports JAX); the server child is the one process that holds the
device.  ``--cpu-rehearsal`` runs the same drive at tiny widths on the
CPU backend to rehearse the control flow; it says ``platform: cpu`` and
is never the default.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
# the driver allows 1200 s, compilation included
BUDGET_S = 1150.0

MAX_NEW_TOKENS = 64
# HBM arithmetic for one 16 GB v5e chip (a 1x4 mesh divides the weights
# and the pool's kv heads by four):
#   int8 weights + bf16 embedding                         7.38 GB
#   live KV pool: 16384 tokens x 131072 B/token            2.15 GB
#     (131072 = 2 x 32 layers x 8 kv heads x 128 x 2 B, paged.kv_bytes_per_token)
#   warm-up's throwaway pool, freed when it ends           2.15 GB
#   largest program's scratch (4096-token prefill)         0.67 GB
#   encoder + PHI tagger + index + drafting tables        <0.30 GB
#   peak                                                 ~12.65 GB
# (measured: peak_bytes_in_use 11.99 GB of a 16.9 GB limit — chip run, PR 21)
# 16384 tokens = 4 slots x 4096 positions: worst-case provisioning, no
# request mix can exhaust it.
KV_POOL_TOKENS = 16384
# Decode slots.  Four hold the four concurrent asks, and the pool above is
# provisioned for four: 16 slots x 4096 positions would be an 8.6 GB pool
# beside 7.4 GB of weights, more than one 16 GB chip holds.  (Speed no
# longer decides it: the paged decode kernel reads live pages only, so a
# step's attention does not grow with slots that are empty — PERF.md.)
DECODE_SLOTS = 4

DOCUMENTS = [
    {
        "filename": "consultation_cardio.txt",
        "patient_id": "P-1001",
        "doc_type": "consultation",
        "doc_date": "2024-03-12",
        "text": (
            "Compte rendu de consultation de cardiologie. Le patient est suivi "
            "pour une hypertension artérielle connue depuis cinq ans. Tension "
            "artérielle mesurée à 142/88 mmHg au cabinet, céphalées "
            "intermittentes depuis deux semaines, pas de douleur thoracique. "
            "Un traitement par amlodipine a été instauré. Posologie de "
            "l'amlodipine : 5 mg par jour, le matin. Surveillance de la "
            "tension à domicile matin et soir pendant trois semaines. "
            "Conseils hygiéno-diététiques rappelés : réduction du sel, "
            "activité physique régulière, arrêt du tabac. Prochain contrôle "
            "prévu dans un mois avec un bilan rénal et un ionogramme."
        ),
    },
    {
        "filename": "suivi_diabete.txt",
        "patient_id": "P-1001",
        "doc_type": "suivi",
        "doc_date": "2024-06-03",
        "text": (
            "Consultation de suivi. Bilan biologique sans anomalie notable, "
            "HbA1c à 6,1 %, créatinine normale, cholestérol LDL à 1,1 g/L. "
            "La tension artérielle est redescendue à 128/80 mmHg sous "
            "amlodipine, bonne tolérance, pas d'œdème des membres "
            "inférieurs. Poursuite du traitement en cours sans modification. "
            "Le patient signale une fatigue modérée en fin de journée. "
            "Contrôle clinique et biologique dans trois mois, avec un fond "
            "d'œil annuel à programmer."
        ),
    },
    {
        "filename": "hospitalisation_pneumo.txt",
        "patient_id": "P-1002",
        "doc_type": "hospitalisation",
        "doc_date": "2024-05-20",
        "text": (
            "Compte rendu d'hospitalisation en pneumologie. Patiente admise "
            "pour une pneumopathie du lobe inférieur droit, fièvre à 39 °C, "
            "toux productive, saturation à 91 % en air ambiant. Mise sous "
            "amoxicilline et acide clavulanique 3 g par jour et "
            "oxygénothérapie à 2 L/min. Évolution favorable en quatre jours, "
            "apyrexie, saturation à 97 %. Sortie à domicile avec poursuite de "
            "l'antibiothérapie pendant sept jours et radiographie de contrôle "
            "à six semaines. Points de vigilance : terrain asthmatique "
            "ancien, allergie signalée aux macrolides."
        ),
    },
]

# worded with the router's reasoning cues (engines/router.py), so they
# take the generative path and reach the decoder
SEQUENTIAL_ASKS = [
    "Pourquoi l'amlodipine a-t-elle été instaurée et comment la tension "
    "a-t-elle évolué ?",
    "Explique l'évolution du bilan biologique du patient suivi pour "
    "hypertension.",
]
# a lookup: the router may answer it from retrieval with no decode at all
LOOKUP_ASK = "Quelle est la posologie de l'amlodipine ?"
CONCURRENT_ASKS = [
    "Comment interpréter une HbA1c à 6,1 % dans ce contexte ?",
    "Pourquoi un contrôle dans trois mois est-il recommandé ?",
    "Résume les points de vigilance pour la patiente hospitalisée en "
    "pneumologie.",
    "Compare les traitements en cours et explique les risques associés.",
]


def smoke_config(rehearsal: bool, work_dir: str) -> dict:
    """Dotted-path overrides for ``scripts/start_all.py --config``."""
    cfg = {
        "generate.max_new_tokens": MAX_NEW_TOKENS,
        "generate.kv_pool_tokens": KV_POOL_TOKENS,
        "generate.max_concurrent": DECODE_SLOTS,
        # compile every prefill budget and the decode chunk before traffic
        "generate.startup_warm_buckets": -1,
        "summarizer.max_summary_tokens": MAX_NEW_TOKENS,
        "data.work_dir": work_dir,
        "service.host": "127.0.0.1",
    }
    if rehearsal:
        # the default tiny widths, cut further to what a CPU decodes inside
        # the request deadline: 1024 positions a slot, a 30-step tagger
        cfg.update({
            "ner.train_steps": 30,
            "decoder.max_seq_len": 1024,
            "generate.kv_pool_tokens": 4096,
        })
        return cfg
    from docqa_tpu.config import DecoderConfig

    widths = dataclasses.asdict(DecoderConfig.mistral_7b())
    widths.update(quantize_weights=True, quant_bits=8)
    cfg.update({f"decoder.{k}": v for k, v in widths.items()})
    return cfg


def http(method: str, url: str, body=None, timeout: float = 60.0):
    """(status, parsed JSON or None).  HTTP error statuses are returned,
    not raised; no connection or no answer in time gives (None, None)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    except OSError:
        return None, None
    try:
        return status, json.loads(raw.decode() or "null")
    except ValueError:
        return status, None


class Failed(Exception):
    """The smoke cannot go on (server died, budget spent)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def drive(
    base: str, deadline: float, alive=lambda: True, profile_dir: str = ""
) -> dict:
    """Run the whole request script against a booted server and return
    what the serving process reported, for :func:`verdict` to judge."""

    def get(path: str):
        status, payload = http("GET", base + path)
        if status != 200:
            raise Failed(f"GET {path} answered {status}")
        return payload

    def wait_for(what: str, probe, every: float = 1.0):
        while True:
            if not alive():
                raise Failed(f"server exited while waiting for {what}")
            if time.monotonic() > deadline:
                raise Failed(f"time budget spent waiting for {what}")
            out = probe()
            if out is not None:
                return out
            time.sleep(every)

    t0 = time.monotonic()
    wait_for("the server to listen", lambda: http("GET", base + "/health")[1])
    t_listen = time.monotonic()
    boot = get("/api/status")
    wait_for(
        "the decode warm-up",
        lambda: (
            (get("/api/status")["warmup"]["state"] in ("ok", "failed", "skipped"))
            or None
        ),
    )
    t_warm = time.monotonic()
    obs: dict = {"responses": []}

    # ---- ingest, then wait until each document is INDEXED
    docs = []
    for doc in DOCUMENTS:
        status, payload = http("POST", base + "/ingest/?wait=1", doc, 180.0)
        obs["responses"].append(("ingest", status, payload))
        if status == 200:
            docs.append(payload["doc_id"])
    for doc_id in docs:
        wait_for(
            f"document {doc_id} to be indexed",
            lambda: (
                (get(f"/documents/{doc_id}")["status"] not in
                 ("PENDING", "PROCESSED", "DEIDENTIFIED")) or None
            ),
            every=0.5,
        )
    obs["documents"] = [get(f"/documents/{d}") for d in docs]
    t_ingest = time.monotonic()

    # ---- one patient synthesis (batch class, rides the same batcher).
    # Before the asks on purpose: QoS defers batch work with a 503 while
    # the /ask latency SLO burns, which is policy, not a broken path —
    # report() prints whether it burned
    status, payload = http(
        "POST", base + "/api/synthese/patient", {"patient_id": "P-1001"},
        180.0,
    )
    obs["responses"].append(("synthese", status, payload))

    # ---- asks: two generative in sequence, a lookup, then four at once
    def ask(question: str):
        t = time.monotonic()
        status, payload = http(
            "POST", base + "/ask/", {"question": question}, 60.0
        )
        return ("ask", status, payload), time.monotonic() - t

    took = []
    for q in SEQUENTIAL_ASKS + [LOOKUP_ASK]:
        resp, seconds = ask(q)
        took.append(seconds)
        obs["responses"].append(resp)
    before = get("/api/status")
    peak_active = [0]
    polling = threading.Event()

    def poll_pool():
        while not polling.is_set():
            try:
                pool = get("/api/status")["pool"] or {}
                n = sum(r["n_active"] for r in pool.get("replicas", []))
                peak_active[0] = max(peak_active[0], n)
            except Failed:
                pass
            time.sleep(0.05)

    poller = threading.Thread(target=poll_pool, daemon=True)
    poller.start()
    # the operator's own window, around the asks that share admission
    # rounds: main() reads the trace once the server has let go of the chip
    profiling = profile_dir and http(
        "POST", base + "/api/profiler/start", {"logdir": profile_dir}
    )[0] == 200
    results = [None] * len(CONCURRENT_ASKS)

    def worker(i: int):
        results[i] = ask(CONCURRENT_ASKS[i])[0]

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(CONCURRENT_ASKS))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    if profiling:
        obs["profiled"] = http(
            "POST", base + "/api/profiler/stop", {}, 300.0
        )[0] == 200
    polling.set()
    poller.join(timeout=5)
    obs["responses"].extend(
        r if r is not None else ("ask", None, None) for r in results
    )
    obs["peak_active_slots"] = peak_active[0]
    after = get("/api/status")

    def prefills(st):
        return st["dispatch"]["spine"]["stages"].get(
            "serve_prefill", {}
        ).get("count", 0)

    obs["concurrent_prefill_dispatches"] = prefills(after) - prefills(before)

    t_done = time.monotonic()

    obs["status"] = get("/api/status")
    obs["metrics"] = get("/api/metrics")
    obs["setup_split_s"] = {
        **boot.get("boot", {}),
        "process_start_to_listening": round(t_listen - t0, 1),
        "warmup_compile": obs["status"]["warmup"].get("seconds"),
        "listening_to_warm": round(t_warm - t_listen, 1),
        "ingest_3_docs": round(t_ingest - t_warm, 1),
        "first_ask": round(took[0], 2),
        "all_requests": round(t_done - t_ingest, 1),
    }
    return obs


def verdict(obs: dict, rehearsal: bool) -> list:
    """Every way the run fell short, as sentences; empty means pass."""
    bad = []
    status, metrics = obs["status"], obs["metrics"]
    counters = metrics["counters"]
    dev = status["device"]

    want_platform = "cpu" if rehearsal else "tpu"
    if dev["platform"] != want_platform:
        bad.append(
            f"platform is {dev['platform']!r}, not {want_platform!r}"
        )

    # every response 200, and no answer served by the degraded fallback
    n_ask = n_generated = 0
    for kind, code, payload in obs["responses"]:
        n_ask += kind == "ask"
        if code != 200 or not isinstance(payload, dict):
            bad.append(f"{kind} answered {code}: {payload!r:.200}")
            continue
        if kind == "ingest":
            continue
        if kind == "ask":
            if payload.get("degraded"):
                bad.append(
                    "an /ask/ answer is degraded "
                    f"({payload.get('degrade_reason')})"
                )
            if not payload.get("answer") or not payload.get("sources"):
                bad.append(f"an /ask/ answer is empty: {payload!r:.200}")
            if payload.get("route") != "extractive":
                n_generated += 1
        elif not any(s.get("content") for s in payload.get("sections", [])):
            bad.append(f"the synthesis has no text: {payload!r:.200}")
    for doc in obs.get("documents", []):
        if doc["status"] != "INDEXED" or doc["n_chunks"] < 1:
            bad.append(f"document not indexed: {doc}")
    if len(obs.get("documents", [])) < len(DOCUMENTS):
        bad.append("fewer documents indexed than ingested")

    def counter(name: str) -> float:
        return counters.get(name, 0)

    if counter("qa_degraded"):
        bad.append(f"qa_degraded = {counter('qa_degraded')}")
    if counter("ask_failures"):
        bad.append(f"ask_failures = {counter('ask_failures')}")
    if counter("ask_requests") != n_ask:
        bad.append(
            f"ask_requests = {counter('ask_requests')}, {n_ask} were sent"
        )
    generative = counter("qa_routed_generative")
    if generative < 4 or generative != n_generated:
        bad.append(
            f"qa_routed_generative = {generative} "
            f"({n_generated} answers carry no extractive route; need >= 4)"
        )
    if generative + counter("qa_routed_extractive") != n_ask:
        bad.append("routed generative + extractive != asks sent")
    # each generative ask decodes 1..max_new tokens; a routed lookup none
    tokens = counter("cost_decode_tokens_interactive")
    if not generative <= tokens <= generative * MAX_NEW_TOKENS or not tokens:
        bad.append(
            f"interactive decode tokens = {tokens} for {generative} "
            f"generative asks of <= {MAX_NEW_TOKENS} tokens"
        )
    if not counter("cost_decode_tokens_batch"):
        bad.append("the synthesis decoded no tokens")
    if obs["peak_active_slots"] < 2:
        bad.append(
            "the concurrent asks never shared the decode batch "
            f"(peak active slots {obs['peak_active_slots']})"
        )

    for name, state in status["breakers"].items():
        if state != "closed":
            bad.append(f"breaker {name} is {state}")
    if any(status["dead_letters"].values()):
        bad.append(f"dead letters: {status['dead_letters']}")
    spine = status["dispatch"]["spine"]
    if spine["errors"]:
        failed = {
            k: v["errors"] for k, v in spine["stages"].items() if v["errors"]
        }
        bad.append(f"spine errors: {failed}")
    for rep in (status["pool"] or {}).get("replicas") or [None]:
        if (
            rep is None or rep["state"] != "healthy"
            or not rep["worker_alive"] or rep["breaker"] != "closed"
            or rep["deaths"]
        ):
            bad.append(f"decode replica not healthy: {rep}")

    warm = status["warmup"]
    if warm["state"] != "ok":
        bad.append(f"decode warm-up {warm['state']}: {warm.get('error')}")
    if not rehearsal:
        # the Pallas kernel, not the XLA reference, is in the program …
        if not warm.get("decode_kernel_calls"):
            bad.append(
                "no Mosaic custom call in the decode program "
                f"(decode_kernel_calls = {warm.get('decode_kernel_calls')})"
            )
        # … and agreed with that reference on this device
        if "kernel_check" not in warm:
            bad.append("the kernel-vs-reference check did not run")

    cache = dev["compile_cache"]
    if cache["entries"] <= cache["entries_at_boot"] == 0:
        # (a cache that was already filled at boot is being re-used and
        # may gain nothing)
        bad.append(f"the compile cache gained no entries: {cache}")

    in_use = [m["bytes_in_use"] for m in dev["memory"]]
    if not rehearsal:
        if not all(in_use):
            bad.append(f"a device reports no memory in use: {dev['memory']}")
        elif dev["count"] > 1:
            # weights, KV pool and index are divided over the mesh: no
            # chip holds the bulk, and none sits (nearly) empty
            if max(in_use) > 0.5 * sum(in_use) or min(in_use) < 0.5 * max(in_use):
                bad.append(f"memory is not divided over the mesh: {in_use}")
            if dev["mesh"] != {"data": 1, "model": dev["count"]}:
                bad.append(f"unexpected mesh {dev['mesh']}")
            if dev["index_devices"] != dev["count"]:
                bad.append(
                    f"the index sits on {dev['index_devices']} device(s)"
                )
    return bad


# the batcher phases every admission passes through (engines/serve.py):
# spans of the program, so annotations of its profiler window
PROFILED_PHASES = ("serve_admit_round", "serve_prefill",
                   "serve_first_token_fetch", "serve_decode_chunk")
_READ_PROFILE = """
import json, sys
from jax.profiler import ProfileData
from harness import xplane
profile = ProfileData.from_file(xplane.find_xplane(sys.argv[1]))
spans = {}
for plane in profile.planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(("serve_", "qa_", "dispatch:")):
                    spans[event.name] = spans.get(event.name, 0) + 1
reduced = xplane.reduce_profile(profile)
print(json.dumps({"host_spans": spans, "idle_gaps": reduced["idle_gaps"],
                  "programs": sorted(reduced["programs"])}))
"""


def read_profile(profile_dir: str) -> dict:
    """The host spans and the device's idle gaps of the window's trace,
    read in a process of its own (the reduction imports JAX; this parent
    does not).  Run after the server has exited."""
    proc = subprocess.run(
        [sys.executable, "-c", _READ_PROFILE, profile_dir],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(HERE, "benchmark")),
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise Failed(f"the profiler window's trace was not readable: "
                     f"{proc.stderr[-600:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def profile_verdict(obs: dict, profile_dir: str) -> list:
    """What the program's profiler window fell short of; empty means the
    trace names the batcher's phases."""
    if not obs.get("profiled"):
        return ["the program's profiler window did not open and close "
                "around the concurrent asks"]
    try:
        trace = read_profile(profile_dir)
    except Failed as e:
        return [str(e)]
    say(f"profiler window: host spans {trace['host_spans']}; programs "
        f"{trace['programs']}; device idle gaps by host span "
        f"{trace['idle_gaps']}")
    missing = [p for p in PROFILED_PHASES if p not in trace["host_spans"]]
    if missing:
        return [f"the window's trace lacks the host spans {missing}"]
    return []


def report(obs: dict) -> None:
    status = obs["status"]
    dev, warm = status["device"], status["warmup"]
    counters = obs["metrics"]["counters"]
    say(f"platform: {dev['platform']}")
    say(f"device_kind: {dev['device_kind']}  count: {dev['count']}  "
        f"mesh: {dev['mesh']}  index_devices: {dev['index_devices']}")
    for m in dev["memory"]:
        say(f"device {m['id']}: bytes_in_use={m['bytes_in_use']} "
            f"peak_bytes_in_use={m['peak_bytes_in_use']} "
            f"bytes_limit={m['bytes_limit']}")
    say(f"compile cache: {dev['compile_cache']}")
    say(f"warm-up: {warm}")
    say("set-up split (seconds; observations, not metrics): "
        + json.dumps(obs["setup_split_s"]))
    for name in (
        "ask_requests", "ask_failures", "qa_degraded",
        "qa_routed_generative", "qa_routed_extractive",
        "cost_decode_tokens_interactive", "cost_decode_tokens_batch",
        "serve_completed", "serve_prefix_hits",
    ):
        say(f"counter {name} = {counters.get(name, 0)}")
    say(f"peak active decode slots during the concurrent asks: "
        f"{obs['peak_active_slots']}; prefill dispatches for those "
        f"{len(CONCURRENT_ASKS)} asks: {obs['concurrent_prefill_dispatches']}")
    spine = status["dispatch"]["spine"]
    say(f"spine: lanes={spine['n_lanes']} completed={spine['completed']} "
        f"errors={spine['errors']} peak_depth={spine['peak_depth']}")
    say(f"breakers: {status['breakers']}")
    firing = [r["name"] for r in status.get("slo") or [] if r["firing"]]
    say(f"SLO alerts firing: {firing}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny widths on the CPU backend; rehearses control flow, "
        "proves nothing about the chip",
    )
    args = ap.parse_args()
    deadline = time.monotonic() + BUDGET_S
    launcher = os.path.join(HERE, "scripts", "start_all.py")
    if not os.path.exists(launcher):
        print(f"{launcher} not found: run from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    import shutil

    mode = "rehearsal" if args.cpu_rehearsal else "chip"
    run_dir = os.path.join(OUT_DIR, mode)  # config, server log, report
    # NER cache, index snapshots, registry: made by this run, in the
    # launcher's own git-ignored work root (too big for the output dir)
    work_dir = os.path.join(HERE, "docqa_work", f"chip_smoke_{mode}")
    for d in (run_dir, work_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    port = free_port()
    cfg_path = os.path.join(run_dir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(smoke_config(args.cpu_rehearsal, work_dir), f, indent=1)
    cmd = [sys.executable, launcher, "--config", cfg_path, "--port", str(port)]
    env = dict(os.environ)
    if args.cpu_rehearsal:
        cmd.append("--cpu")
    else:
        # no accelerator -> the child's first JAX call raises and it exits:
        # there is no CPU fallback to fool the checks below
        env["JAX_PLATFORMS"] = "tpu"
    log_path = os.path.join(run_dir, "server.log")
    say(f"launching {' '.join(cmd)}  (log: {log_path})")
    with open(log_path, "wb") as log:
        child = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    try:
        profile_dir = os.path.join(work_dir, "profile")
        obs = drive(
            f"http://127.0.0.1:{port}", deadline,
            alive=lambda: child.poll() is None, profile_dir=profile_dir,
        )
        with open(os.path.join(run_dir, "report.json"), "w") as f:
            json.dump(obs, f, indent=1)
        report(obs)
        bad = verdict(obs, args.cpu_rehearsal)
    except Failed as e:
        bad, obs = [str(e)], None
    finally:
        # stop everything this run started: the whole process group
        for sig, wait in ((signal.SIGTERM, 30), (signal.SIGKILL, 10)):
            if child.poll() is not None:
                break
            try:
                os.killpg(child.pid, sig)
                child.wait(timeout=wait)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
    if not bad:  # the server has exited: the trace can be read
        bad = profile_verdict(obs, profile_dir)
    if bad:
        for line in bad:
            print(f"FAIL: {line}", file=sys.stderr)
        with open(log_path, "rb") as f:
            tail = f.read()[-6000:].decode(errors="replace")
        print(f"---- end of {log_path} ----\n{tail}", file=sys.stderr)
        return 1
    dev = obs["status"]["device"]
    say(json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["count"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
