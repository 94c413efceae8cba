"""ISSUE 40: device time by scope — the reduction
(``benchmark/harness/xplane_scopes.py``) on fake profiles and on a small
trace file written here in the wire format, the reader
(``benchmark/readers/scope_time.py``), and the eight entries at the end of
``per_layer``.  Files and entries only; nothing that was there is edited."""

import ast
import json
import os
import struct
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import xplane_scopes as xs  # noqa: E402
from readers import scope_time  # noqa: E402


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["decode_attention_ms", "decode_projection_ms", "decode_mlp_ms",
       "decode_head_ms", "decode_other_ms", "prefill_attention_ms",
       "prefill_mlp_ms", "ask_lane_wait_p50_ms"]
US = 1000  # ns


# ---- a fake profile: what ``reduce_scopes`` is duck-typed against ----------

def ev(name, start_us, dur_us, tf_op=None):
    stats = [("hlo_category", "x"), ("flops", 7)]
    if tf_op is not None:
        stats.append(("tf_op", tf_op))
    return NS(name=name, start_ns=start_us * US, duration_ns=dur_us * US,
              stats=stats)


def profile(modules, ops, plane="/device:TPU:0", more=()):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("PjitFunction(f)", 0, 5)])]),
        NS(name=plane, lines=[NS(name="XLA Modules", events=modules),
                              NS(name="XLA Ops", events=ops)]),
        *more,
    ])


def decode_ops(t0):
    """One 100 us decode execution at ``t0``: a 90 us loop that holds an
    attention fusion (scope in a STAT), a conditional with an expert
    fusion inside it, an MLP fusion (scope in its NAME, as an HLO line
    with its metadata would carry it) and 5 us of its own; then a copy
    under no scope; 4 us of holes."""
    return [
        ev("%while.6 = (s32[], bf16[4,8]) while(%tuple.1), body=%b", t0, 90),
        ev("%fusion.1 = bf16[4,8] fusion(%p.1), kind=kOutput", t0, 30,
           "jit(_decode_program)/while/body/closed_call/dq.attend/dot:"),
        ev("%cond.2 = (bf16[4,8]) conditional(%p.2, %fusion.1)", t0 + 30, 30,
           "jit(_decode_program)/while/body/dq.mlp/dq.route/cond:"),
        ev("%fusion.3 = bf16[4,8] fusion(%p.3)", t0 + 35, 20,
           "jit(_decode_program)/while/body/dq.mlp/dq.experts/"
           "cond/branch_1_fun/dot_general:"),
        ev('%fusion.4 = bf16[4,8] fusion(%cond.2), metadata={op_name='
           '"jit(_decode_program)/while/body/dq.mlp/dot_general"}',
           t0 + 60, 25),
        ev("%copy.5 = bf16[4,8]{0,1} copy(%while.6)", t0 + 92, 6),
    ]


def test_a_loop_and_a_conditional_are_charged_their_self_time():
    mods = [ev("jit__decode_program(11)", 1000 * i, 100) for i in range(5)]
    ops = [o for i in range(5) for o in decode_ops(1000 * i)]
    row = xs.reduce_scopes(profile(mods, ops))["jit__decode_program"]
    assert (row["variants"], row["executions"], row["whole"]) == (1, 5, 3)
    assert row["median_s"] == pytest.approx(100e-6)
    assert row["scopes"] == pytest.approx({
        "attend": 30e-6, "route": 10e-6, "experts": 20e-6, "mlp": 25e-6,
        "-": 5e-6 + 6e-6})
    assert row["holes_s"] == pytest.approx(4e-6)
    assert sum(row["scopes"].values()) + row["holes_s"] == pytest.approx(
        row["median_s"])
    assert [r[:2] for r in row["rows"]] == [
        ["attend", "fusion"], ["mlp", "fusion"], ["experts", "fusion"],
        ["route", "cond"], ["-", "copy"], ["-", "while"]]


def test_the_scope_is_the_innermost_in_the_name_or_in_a_stat():
    assert xs.scope_of(ev("%f.1 = fusion()", 0, 1, "jit(f)/dq.mlp/dq.route/x:")
                       ) == "route"
    assert xs.scope_of(ev('%f.1 = fusion(), metadata={op_name="a/dq.head/b"}',
                          0, 1, "jit(f)/dq.mlp/x:")) == "head"
    assert xs.scope_of(ev("%f.1 = fusion()", 0, 1, "jit(f)/while/body/x:")
                       ) == "-"
    assert xs.scope_of(ev("%f.1 = fusion()", 0, 1)) == "-"
    assert xs.scope_of(NS(name="%f.1 = fusion()", stats=None)) == "-"


@pytest.mark.parametrize("line,stem", [
    ("%convert_multiply_fusion.12 = bf16[8]{0} fusion(%p)",
     "convert_multiply_fusion"),
    ("%slice-done.411 = s8[1024,1024]{1,0} async-done(%slice-start.411)",
     "slice-done"),
    ("%fusion.2963 = (f32[4], bf16[4,4096]) fusion(%gte.1)", "fusion"),
    ("%while.6 = (s32[]) while(%tuple.17), body=%b", "while"),
    ("%cond.0.clone.2 = (bf16[8]) conditional(%p)", "cond"),
    ("%_paged_decode_kernel.31 = bf16[4,32,128] custom-call(%q)",
     "_paged_decode_kernel"),
    ("%copy = bf16[8] copy(%p)", "copy"),
])
def test_the_stem_is_the_ops_name_without_its_number(line, stem):
    assert xs.op_stem(line) == stem


def test_two_programs_and_a_variant_that_ran_once():
    """The variant that ran most often stands for a program; the first
    execution to start and the last to end on the line are taken as cut
    by the slice's edge, whatever they are."""
    mods = (
        [ev("jit__prefill_program(5)", 0, 30)]  # first: cut
        + [ev("jit__decode_program(11)", 1000 * i, 100) for i in (1, 2, 3)]
        + [ev("jit__prefill_program(5)", 5000 + 100 * i, 50 + i)
           for i in range(4)]
        + [ev("jit__prefill_program(6)", 6000, 400)]  # ran once
        + [ev("jit__decode_program(11)", 7000, 40)]  # last: cut
    )
    ops = (
        [ev("%fusion.9 = f32[8] fusion(%p)", 1, 20, "jit(p)/dq.mlp/dot:")]
        + [o for i in (1, 2, 3) for o in decode_ops(1000 * i)]
        + [ev("%fusion.9 = f32[8] fusion(%p)", 5000 + 100 * i, 40 + i,
              "jit(_prefill_program)/dq.mlp/dot:") for i in range(4)]
        + [ev("%fusion.7 = f32[8] fusion(%p)", 6000, 400,
              "jit(_prefill_program)/dq.attend/dot:")]
        + [ev("%while.6 = (s32[]) while(%t)", 7000, 40)]
    )
    got = xs.reduce_scopes(profile(mods, ops))
    assert set(got) == {"jit__decode_program", "jit__prefill_program"}
    pre = got["jit__prefill_program"]
    assert (pre["variants"], pre["executions"], pre["whole"]) == (2, 5, 4)
    assert pre["median_s"] == pytest.approx(51e-6)  # of 50, 51, 52, 53
    assert pre["scopes"] == pytest.approx({"mlp": 41e-6})
    assert pre["holes_s"] == pytest.approx(10e-6)
    dec = got["jit__decode_program"]
    assert (dec["executions"], dec["whole"]) == (4, 3)
    assert dec["median_s"] == pytest.approx(100e-6)


def test_a_program_with_no_whole_execution_is_left_out():
    mods = [ev("jit__prefill_program(5)", 0, 30),
            ev("jit__decode_program(11)", 100, 100),
            ev("jit__prefill_program(5)", 300, 30)]
    got = xs.reduce_scopes(profile(mods, decode_ops(100)))
    assert set(got) == {"jit__decode_program"}


def test_an_op_the_compiler_put_in_is_charged_to_who_takes_its_result():
    """``slice-start`` -> ``slice-done`` -> ``ConcatBitcast`` -> the matmul
    fusion; one that feeds the NEXT iteration through the loop's carry
    finds nobody and stays under no scope."""
    def step(t0):
        return [
            ev("%slice-start.1 = ((s8[8]), s8[4]) async-start(%gte.1)",
               t0, 1),
            ev("%slice-done.1 = s8[4]{0:S(1)} async-done(%slice-start.1)",
               t0 + 1, 9),
            ev("%custom-call.2 = s8[8] custom-call(s8[4] %slice-done.1, s8[4]"
               ' %slice-done.1), custom_call_target="ConcatBitcast"',
               t0 + 10, 2),
            ev("%fusion.3 = bf16[4,8] fusion(bf16[4,8] %gte.2, s8[8] "
               "%custom-call.2), kind=kOutput", t0 + 12, 20,
               "jit(f)/while/body/dq.mlp/dot_general:"),
            ev("%slice-done.11 = s8[4]{0:S(1)} async-done(%slice-start.11)",
               t0 + 32, 5),  # for the next iteration: nobody takes it
        ]
    mods = [ev("jit__decode_program(11)", 1000 * i, 40) for i in range(3)]
    ops = [o for i in range(3) for o in step(1000 * i)]
    row = xs.reduce_scopes(profile(mods, ops))["jit__decode_program"]
    assert row["scopes"] == pytest.approx({"mlp": 32e-6, "-": 5e-6})
    assert ["mlp", "slice-done", pytest.approx(9e-6)] in row["rows"]
    assert ["-", "slice-done", pytest.approx(5e-6)] in row["rows"]


def test_chip_0_alone_and_nothing_without_a_device_plane():
    mods = [ev("jit__decode_program(11)", 1000 * i, 100) for i in range(3)]
    ops = [o for i in range(3) for o in decode_ops(1000 * i)]
    other = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_other(1)", 1000 * i, 10) for i in range(3)]),
        NS(name="XLA Ops", events=[])])
    assert set(xs.reduce_scopes(profile(mods, ops, more=[other]))) == {
        "jit__decode_program"}
    assert xs.reduce_scopes(profile(mods, ops, plane="/host:other")) == {}
    assert xs.reduce_scopes(NS(planes=[])) == {}


# ---- a trace FILE: the wire format ``read_xspace`` reads ---------------------

def varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, float):
        return varint(no << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


STAT_IDS = {"tf_op": 26, "flops": 3, "hlo_category": 4, "occupancy": 5,
            "kind": 6, "device_offset_ps": 1}


def stat(name, value, ref=False):
    body = field(1, STAT_IDS[name])
    if ref:
        body += field(7, STAT_IDS[value])
    elif isinstance(value, str):
        body += field(5, value)
    elif isinstance(value, float):
        body += field(2, value)
    else:
        body += field(4, value)
    return body


def write_trace(path, programs):
    """``programs``: [(module event name, [executions' start us], length
    us, [(op line, offset us, length us, tf_op or None)])] -> an XSpace
    with a host plane and one device plane, its events out of order."""
    metadata, lines = {}, {"XLA Modules": [], "XLA Ops": []}

    def meta(name, stats=b""):
        if name not in metadata:
            metadata[name] = (len(metadata) + 1, stats)
        return metadata[name][0]

    t0_ns = 5_000_000
    for module, starts, length, ops in programs:
        for start in starts:
            lines["XLA Modules"].append(
                field(1, meta(module)) + field(2, start * 10**6)
                + field(3, length * 10**6)
                + field(4, stat("device_offset_ps", start * 10**6)))
            for line, off, dur, tf_op in ops:
                stats = field(5, stat("hlo_category", "x")) + field(
                    5, stat("flops", 7)) + field(5, stat("occupancy", 0.5)
                    ) + field(5, stat("kind", "hlo_category", ref=True))
                if tf_op:
                    stats += field(5, stat("tf_op", tf_op))
                lines["XLA Ops"].append(
                    field(1, meta(line, stats))
                    + field(2, (start + off) * 10**6)
                    + field(3, dur * 10**6))
    device = field(1, 7) + field(2, "/device:TPU:0")
    for i, (name, events) in enumerate(sorted(lines.items())):
        body = field(1, i) + field(2, name) + field(3, t0_ns)
        for e in reversed(events):  # no order promised
            body += field(4, e)
        device += field(3, body + field(9, 123))
    device += field(3, field(2, "Steps") + field(4, field(1, 1)))
    for name, (mid, stats) in metadata.items():
        device += field(4, field(1, mid) + field(
            2, field(1, mid) + field(2, name) + stats))
    for name, sid in STAT_IDS.items():
        device += field(5, field(1, sid) + field(
            2, field(1, sid) + field(2, name)))
    host = field(2, "/host:CPU") + field(3, field(2, "python") + field(
        4, field(1, 1) + field(2, 5) + field(3, 5)))
    with open(path, "wb") as f:
        f.write(field(1, host) + field(1, device) + field(4, "a-host"))
    return t0_ns


DECODE = ("jit__decode_program(11)", [1000, 2000, 3000, 4000, 5000], 160, [
    ("%while.6 = (s32[]) while(%t.1), body=%b", 0, 150, None),
    ("%fusion.1 = bf16[4,8] fusion(%p.1)", 0, 32, "jit(d)/while/body/dq.proj/dot:"),
    ("%k.2 = bf16[4,8] custom-call(%fusion.1)", 32, 8,
     "jit(d)/while/body/dq.attend/pallas_call:"),
    ("%fusion.3 = bf16[4,8] fusion(%k.2)", 40, 80, "jit(d)/while/body/dq.mlp/dot:"),
    ("%fusion.4 = s32[4] fusion(%fusion.3)", 120, 16,
     "jit(d)/while/body/dq.head/dot:"),
    ("%fusion.5 = s32[4] fusion(%fusion.4)", 136, 8,
     "jit(d)/while/body/dq.sample/argmax:"),
])
PREFILL = ("jit__prefill_program(5)", [1200, 2200, 3200], 50, [
    ("%fusion.1 = bf16[512,8] fusion(%p.1)", 0, 10,
     "jit(p)/dq.cache_write/scatter:"),
    ("%fusion.2 = bf16[512,8] fusion(%fusion.1)", 10, 5, "jit(p)/dq.attend/dot:"),
    ("%fusion.3 = bf16[512,8] fusion(%fusion.2)", 15, 30, "jit(p)/dq.mlp/dot:"),
])
EDGES = ("jit_edge(1)", [0, 9000], 10, [])


@pytest.fixture()
def run_ctx(tmp_path, monkeypatch):
    """A run's context over a work directory that holds a trace where
    ``harness/child.py`` leaves one."""
    monkeypatch.setattr(scope_time, "ROOT", str(tmp_path))
    logdir = tmp_path / ".benchmark_work" / "a_cell" / "trace"
    folder = logdir / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    write_trace(str(folder / "host.xplane.pb"), [DECODE, PREFILL, EDGES])
    return {"cell": {"name": "a_cell"},
            "conf": {"serving": {"generate.decode_chunk": 16}}}


def test_the_file_reader_hands_back_the_metadatas_stats(run_ctx, tmp_path):
    path = xs.find_xplane(str(
        tmp_path / ".benchmark_work" / "a_cell" / "trace"))
    assert path.endswith("host.xplane.pb") and xs.find_xplane(path) == path
    space = xs.read_xspace(path)
    assert [p.name for p in space.planes] == ["/device:TPU:0"]
    lines = {ln.name: ln.events for ln in space.planes[0].lines}
    assert sorted(lines) == ["XLA Modules", "XLA Ops"]
    assert len(lines["XLA Modules"]) == 10 and len(lines["XLA Ops"]) == 39
    first = min(lines["XLA Ops"], key=lambda e: (e.start_ns, e.name))
    assert first.name.startswith("%fusion.1 = ")
    first = min(lines["XLA Ops"], key=lambda e: (e.start_ns, -e.duration_ns))
    assert first.name.startswith("%while.6 = ")
    assert first.start_ns == pytest.approx(5_000_000 + 1000 * US)
    assert first.duration_ns == pytest.approx(150 * US)
    kernel = next(e for e in lines["XLA Ops"] if e.name.startswith("%k.2"))
    assert dict(kernel.stats) == {
        "hlo_category": "x", "flops": 7, "occupancy": 0.5,
        "kind": "hlo_category",
        "tf_op": "jit(d)/while/body/dq.attend/pallas_call:"}
    with pytest.raises(FileNotFoundError):
        xs.find_xplane(str(tmp_path / "nothing"))


DEC = {"program": "decode", "exclude": "prefill"}


def test_the_reader_finds_the_file_and_sums_the_named_scopes(run_ctx):
    read = scope_time.read
    assert read(run_ctx, scopes=["proj"], **DEC) == pytest.approx(0.032)
    assert read(run_ctx, scopes=["cache_write", "attend", "select", "state"],
                **DEC) == pytest.approx(0.008)
    assert read(run_ctx, scopes=["embed", "head", "sample"], **DEC
                ) == pytest.approx(0.024)
    assert read(run_ctx, "prefill", ["cache_write", "attend"]
                ) == pytest.approx(0.015)


def test_rest_is_what_no_scope_holds_and_the_groups_add_up(run_ctx):
    """The loop's own 6 us and the 10 us of holes after it."""
    read = scope_time.read
    assert read(run_ctx, scopes=["*rest"], **DEC) == pytest.approx(0.016)
    groups = [load(os.path.join(BENCH_DIR, "metrics", n + ".json"))["params"]
              for n in NEW[:5]]
    assert sum(read(run_ctx, **g) for g in groups) == pytest.approx(
        0.160 / 16)
    assert read(run_ctx, "prefill", ["*rest"]) == pytest.approx(0.005)


def test_per_is_a_number_or_a_key_of_the_serving_block(run_ctx):
    read = scope_time.read
    assert read(run_ctx, scopes=["mlp"], per="generate.decode_chunk", **DEC
                ) == pytest.approx(0.080 / 16)
    assert read(run_ctx, scopes=["mlp"], per=4, **DEC) == pytest.approx(0.020)


def test_nothing_to_read_is_none(run_ctx, tmp_path, monkeypatch):
    read = scope_time.read
    assert read(run_ctx, "no_such_program", ["mlp"]) is None
    assert read(run_ctx, "decode", ["mlp"], exclude="decode") is None
    # a group no op of the program ran under reads 0, not nothing
    assert read(run_ctx, scopes=["route", "experts"], **DEC) == 0.0
    # no trace where the child leaves one
    assert read({"cell": {"name": "another_cell"}, "conf": run_ctx["conf"]},
                "decode", ["mlp"]) is None
    # a program compiled before the scopes (the parent's): nothing, and
    # no error, for every group and for the rest
    bare = tmp_path / ".benchmark_work" / "bare" / "trace" / "plugins" / (
        "profile") / "x"
    bare.mkdir(parents=True)
    name, starts, length, ops = DECODE
    write_trace(str(bare / "t.xplane.pb"), [
        (name, starts, length, [(o[0], o[1], o[2], None) for o in ops]),
        EDGES])
    ctx = {"cell": {"name": "bare"}, "conf": run_ctx["conf"]}
    assert read(ctx, scopes=["mlp"], **DEC) is None
    assert read(ctx, scopes=["*rest"], **DEC) is None
    assert ctx["scope_times"]["jit__decode_program"]["scopes"] == {
        "-": pytest.approx(150e-6)}


def test_one_reduction_serves_every_metric_of_a_run(run_ctx, monkeypatch,
                                                    capsys, tmp_path):
    calls = []
    reduce_file = xs.reduce_file
    monkeypatch.setattr(xs, "reduce_file",
                        lambda path: calls.append(path) or reduce_file(path))
    sys.path.insert(0, BENCH_DIR)
    import run

    values = {n: run.read_metric(n, run_ctx) for n in NEW}
    assert len(calls) == 1
    assert values["ask_lane_wait_p50_ms"] is None  # no request traces here
    assert all(values[n] is not None for n in NEW[:7])
    assert values["decode_mlp_ms"] == pytest.approx(0.005)
    assert values["prefill_mlp_ms"] == pytest.approx(0.030)
    err = capsys.readouterr().err
    assert err.count("device time by scope") == 1
    assert "jit__decode_program: the median of 5 whole executions" in err
    kept = load(str(tmp_path / ".benchmark_work" / "a_cell" / "scopes.json"))
    assert kept["jit__decode_program"]["scopes"]["mlp"] == pytest.approx(
        80e-6)


def test_the_span_metric_reads_the_lane_wait_of_each_request():
    from readers import request_span

    params = load(os.path.join(
        BENCH_DIR, "metrics", "ask_lane_wait_p50_ms.json"))
    assert params == {"reader": "request_span", "params": {
        "spans": ["ask_lane_wait"], "percentile": 50}}
    traces = {str(i): {"spans": [
        {"name": "ask_lane_wait", "duration_ms": float(ms)},
        {"name": "qa_retrieve", "duration_ms": 5.0}]}
        for i, ms in enumerate([9, 11, 30])}
    assert request_span.read({"request_traces": traces}, **params["params"]
                             ) == pytest.approx(11.0)
    with open(os.path.join(ROOT, "docqa_tpu", "service", "app.py"),
              encoding="utf-8") as f:
        assert '"ask_lane_wait"' in f.read()


def test_the_command_prints_the_table(run_ctx, tmp_path):
    logdir = str(tmp_path / ".benchmark_work" / "a_cell" / "trace")
    script = os.path.join(BENCH_DIR, "harness", "xplane_scopes.py")
    done = subprocess.run([sys.executable, script, logdir], check=True,
                          capture_output=True, text=True, timeout=60)
    assert "jit__decode_program: the median of 5 whole executions" in (
        done.stdout)
    assert "mlp" in done.stdout and "holes" in done.stdout
    assert subprocess.run([sys.executable, script], capture_output=True,
                          timeout=60).returncode == 2


# ---- the entries -------------------------------------------------------------

def test_the_eight_entries_sit_at_the_end_with_the_two_cells():
    tail = BENCH["per_layer"][-8:]
    assert [m["name"] for m in tail] == NEW
    for m in tail:
        assert m["workloads"] == ["rag_closed", "rag_closed8_dsv2"]
        assert (m["unit"], m["better"]) == ("ms", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["source"] for m in tail] == ["device_trace"] * 7 + [
        "program_span"]
    assert [m["layer"] for m in tail] == [
        "Kernels", "Model step", "Model step", "Model step", "Model step",
        "Kernels", "Model step", "HTTP surface"]
    assert [m["moves"] for m in tail] == ["tpot_p50_ms"] * 5 + [
        "ttft_p50_ms"] * 3
    assert len({m["name"] for m in BENCH["per_layer"]}) == len(
        BENCH["per_layer"])


def test_the_decode_groups_hold_every_scope_once():
    """So the five decode metrics add up to ``decode_step_ms``."""
    tree = ast.parse(open(os.path.join(
        ROOT, "docqa_tpu", "ops", "scopes.py"), encoding="utf-8").read())
    vocabulary = next(
        ast.literal_eval(n.value) for n in tree.body
        if isinstance(n, ast.Assign) and n.targets[0].id == "DEVICE_SCOPES")
    params = [load(os.path.join(BENCH_DIR, "metrics", n + ".json"))
              for n in NEW[:7]]
    assert all(p["reader"] == "scope_time" for p in params)
    decode = [p["params"] for p in params[:5]]
    step = load(os.path.join(BENCH_DIR, "metrics", "decode_step_ms.json"))
    for p in decode:
        assert {k: p[k] for k in ("program", "exclude", "per")} == step[
            "params"]
    named = [s for p in decode for s in p["scopes"]]
    assert sorted(named) == sorted(list(vocabulary) + ["*rest"])
    for p in params[5:]:
        assert (p["params"]["program"], p["params"]["per"]) == ("prefill", 1)
    assert params[5]["params"]["scopes"] == decode[0]["scopes"]
    assert params[6]["params"]["scopes"] == decode[2]["scopes"]


@pytest.mark.parametrize("module", ["harness/xplane_scopes.py",
                                    "readers/scope_time.py"])
def test_what_the_parent_imports_is_standard_library(module):
    tree = ast.parse(open(os.path.join(BENCH_DIR, module),
                          encoding="utf-8").read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module.split(".")[0])
    assert names <= {"__future__", "bisect", "json", "os", "re", "struct",
                     "sys", "types", "typing", "harness", "readers"}
