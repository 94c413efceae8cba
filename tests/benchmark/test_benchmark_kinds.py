"""ISSUE 53: device time by LAYER KIND — the reduction
(``benchmark/harness/xplane_kinds.py``) on fake profiles and on a small
trace file in the wire format, as ``test_benchmark_scopes.py`` builds them
(its builders are imported, not copied), the reader
(``benchmark/readers/kind_time.py``), and the twelve metric files with
their ``per_layer`` entries, found BY NAME.  Files and entries only;
nothing that was there is edited."""

import ast
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, HERE)

from harness import xplane_kinds as xk  # noqa: E402
from harness import xplane_scopes as xs  # noqa: E402
from readers import kind_time, scope_time  # noqa: E402
from test_benchmark_scopes import (  # noqa: E402
    EDGES,
    ev,
    load,
    profile,
    write_trace,
)

BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
TRINITY, JAMBA2 = "record_closed4_trinity", "record_closed4_jamba2"
# ISSUE 53's table: metric -> (program, kinds, moves, cells)
TABLE = {
    "decode_mixer_ms.window": ("decode", ["window"], "tpot_p50_ms",
                               [TRINITY]),
    "decode_mixer_ms.attention": ("decode", ["attention"], "tpot_p50_ms",
                                  [TRINITY, JAMBA2]),
    "decode_mixer_ms.mamba": ("decode", ["mamba"], "tpot_p50_ms", [JAMBA2]),
    "decode_ffn_ms.routed": ("decode", ["routed"], "tpot_p50_ms",
                             [TRINITY]),
    "decode_ffn_ms.dense": ("decode", ["dense"], "tpot_p50_ms",
                            [TRINITY, JAMBA2]),
    "decode_no_kind_ms": ("decode", ["*none"], "tpot_p50_ms",
                          [TRINITY, JAMBA2]),
    "prefill_mixer_ms.window": ("prefill", ["window"], "ttft_p50_ms",
                                [TRINITY]),
    "prefill_mixer_ms.attention": ("prefill", ["attention"], "ttft_p50_ms",
                                   [TRINITY, JAMBA2]),
    "prefill_mixer_ms.mamba": ("prefill", ["mamba"], "ttft_p50_ms",
                               [JAMBA2]),
    "prefill_ffn_ms.routed": ("prefill", ["routed"], "ttft_p50_ms",
                              [TRINITY]),
    "prefill_ffn_ms.dense": ("prefill", ["dense"], "ttft_p50_ms",
                             [TRINITY, JAMBA2]),
    "prefill_no_kind_ms": ("prefill", ["*none"], "ttft_p50_ms",
                           [TRINITY, JAMBA2]),
}
LOOP = "jit(d)/while/body/closed_call/"


# ---- the two axes of one name ------------------------------------------------

def test_kind_and_phase_are_read_independently_of_one_name():
    e = ev("%f.1 = fusion()", 0, 1, LOOP + "dk.window/dq.attend/dot:")
    assert (xk.kind_of(e), xs.scope_of(e)) == ("window", "attend")
    e = ev("%f.1 = fusion()", 0, 1, LOOP + "dk.routed/dq.mlp/dq.experts/x:")
    assert (xk.kind_of(e), xs.scope_of(e)) == ("routed", "experts")
    # in the NAME, as an HLO line with its metadata would carry it
    e = ev('%f.1 = fusion(), metadata={op_name="a/dk.mamba/dq.state/b"}',
           0, 1, "jit(f)/dk.dense/dq.mlp/x:")
    assert (xk.kind_of(e), xs.scope_of(e)) == ("mamba", "state")
    # a phase outside any kind, and a kind around no phase
    e = ev("%f.1 = fusion()", 0, 1, LOOP + "dq.head/dot:")
    assert (xk.kind_of(e), xs.scope_of(e)) == ("-", "head")
    e = ev("%c.1 = copy()", 0, 1, LOOP + "dk.window/copy:")
    assert (xk.kind_of(e), xs.scope_of(e)) == ("window", "-")
    assert xk.kind_of(ev("%c.1 = copy()", 0, 1)) == "-"
    assert xk.kind_of(type(e)(name="%c.1 = copy()", stats=None)) == "-"


def step_ops(t0):
    """One 104 us decode execution at ``t0``: a 100 us loop that holds an
    embedding, a copy the compiler put in FOR the window layer's
    projection, that layer's projection, kernel and a layout copy under
    the kind alone, a routed layer's two fusions, and the head."""
    return [
        ev("%while.6 = (s32[]) while(%t.1), body=%b", t0, 100),
        ev("%fusion.1 = bf16[4,8] fusion(%p.1)", t0 + 1, 4,
           LOOP + "dq.embed/gather:"),
        ev("%copy.9 = bf16[4,8]{0,1} copy(%p.7)", t0 + 6, 2),
        ev("%fusion.2 = bf16[4,8] fusion(%fusion.1, %copy.9)", t0 + 10, 10,
           LOOP + "dk.window/dq.proj/dot:"),
        ev("%k.3 = bf16[4,8] custom-call(%fusion.2)", t0 + 22, 8,
           LOOP + "dk.window/dq.attend/pallas_call:"),
        ev("%copy.4 = bf16[4,8] copy(%k.3)", t0 + 31, 3,
           LOOP + "dk.window/copy:"),
        ev("%fusion.5 = bf16[4,8] fusion(%copy.4)", t0 + 36, 20,
           LOOP + "dk.routed/dq.mlp/dq.experts/dot:"),
        ev("%fusion.6 = bf16[4,8] fusion(%fusion.5)", t0 + 58, 10,
           LOOP + "dk.routed/dq.mlp/dot:"),
        ev("%fusion.7 = s32[4] fusion(%fusion.6)", t0 + 70, 20,
           LOOP + "dq.head/dot:"),
    ]


@pytest.fixture()
def stepped():
    mods = [ev("jit__decode_program(11)", 1000 * i, 104) for i in range(5)]
    ops = [o for i in range(5) for o in step_ops(1000 * i)]
    return profile(mods, ops)


def test_self_time_holes_and_events_by_kind(stepped):
    row = xk.reduce_kinds(stepped)["jit__decode_program"]
    assert (row["variants"], row["executions"], row["whole"]) == (1, 5, 3)
    assert row["median_s"] == pytest.approx(104e-6)
    kinds = row["kinds"]
    assert set(kinds) == {"window", "routed", "-"}
    # the compiler's copy is the projection's (kind AND scope of its
    # taker); the copy under the kind alone keeps its kind and waits, as
    # in the by-scope table, for its taker's scope
    assert kinds["window"]["scopes"] == pytest.approx(
        {"proj": 12e-6, "attend": 8e-6, "experts": 3e-6})
    assert kinds["window"]["events"] == 4
    # copy.9 -> fusion.2 2 us, fusion.2 -> k.3 2 us, k.3 -> copy.4 1 us
    assert kinds["window"]["holes_s"] == pytest.approx(5e-6)
    assert kinds["routed"]["scopes"] == pytest.approx(
        {"experts": 20e-6, "mlp": 10e-6})
    assert (kinds["routed"]["events"], kinds["routed"]["holes_s"]) == (
        2, pytest.approx(2e-6))
    # every OTHER hole stays where it was: the loop's own time (embed ->
    # copy.9, window -> routed, routed -> head, head -> the loop's end)
    # and the execution's 4 us after the loop
    assert kinds["-"]["scopes"] == pytest.approx(
        {"embed": 4e-6, "head": 20e-6, "-": 16e-6})
    assert kinds["-"]["holes_s"] == pytest.approx(4e-6)
    assert kinds["-"]["events"] == 3  # the loop, embed, head
    for r in kinds.values():
        assert r["self_s"] == pytest.approx(sum(r["scopes"].values()))
    assert sum(r["self_s"] + r["holes_s"] for r in kinds.values()
               ) == pytest.approx(row["median_s"])
    assert row["rows"][:3] == [
        ["routed", "experts", "fusion", pytest.approx(20e-6)],
        ["-", "head", "fusion", pytest.approx(20e-6)],
        ["-", "-", "while", pytest.approx(16e-6)]]
    assert ["window", "proj", "copy", pytest.approx(2e-6)] in row["rows"]
    assert ["window", "experts", "copy", pytest.approx(3e-6)] in row["rows"]


def test_the_by_scope_table_of_the_same_trace_does_not_move(stepped):
    """``xplane_scopes`` reads ``attend`` under ``dk.window`` and ``-``
    for the op under the kind alone: the parent's eight metrics read what
    they read, and the two reductions differ by the kinds' own holes
    alone (there the loop's time)."""
    by_scope = xs.reduce_scopes(stepped)["jit__decode_program"]
    assert by_scope["scopes"] == pytest.approx({
        "embed": 4e-6, "proj": 12e-6, "attend": 8e-6, "experts": 23e-6,
        "mlp": 10e-6, "head": 20e-6, "-": 23e-6})
    by_kind = xk.reduce_kinds(stepped)["jit__decode_program"]
    assert by_kind["median_s"] == by_scope["median_s"]
    summed = {}
    for r in by_kind["kinds"].values():
        for scope, s in r["scopes"].items():
            summed[scope] = summed.get(scope, 0.0) + s
    moved = sum(r["holes_s"] for k, r in by_kind["kinds"].items()
                if k != "-")
    assert moved == pytest.approx(7e-6)
    summed["-"] += moved
    assert summed == pytest.approx(by_scope["scopes"])
    assert by_kind["kinds"]["-"]["holes_s"] == pytest.approx(
        by_scope["holes_s"])


def test_a_hole_is_a_kinds_only_between_two_of_its_ops():
    """Inside whatever op holds both — a conditional here — and never
    across another kind's op, an op of no kind, or the holding op's
    edge."""
    def one(t0):
        return [
            ev("%cond.1 = (bf16[8]) conditional(%p.1)", t0, 40,
               "jit(p)/dk.routed/dq.mlp/dq.route/cond:"),
            ev("%fusion.2 = bf16[8] fusion(%p.2)", t0 + 2, 5,
               "jit(p)/dk.routed/dq.mlp/dq.experts/a:"),
            ev("%fusion.3 = bf16[8] fusion(%fusion.2)", t0 + 10, 5,
               "jit(p)/dk.routed/dq.mlp/dq.experts/b:"),
            ev("%fusion.4 = bf16[8] fusion(%fusion.3)", t0 + 18, 5,
               "jit(p)/dq.head/c:"),
            ev("%fusion.5 = bf16[8] fusion(%fusion.4)", t0 + 26, 5,
               "jit(p)/dk.routed/dq.mlp/d:"),
            ev("%fusion.6 = bf16[8] fusion(%fusion.5)", t0 + 44, 5,
               "jit(p)/dk.routed/dq.mlp/e:"),
            ev("%fusion.7 = bf16[8] fusion(%fusion.6)", t0 + 50, 5,
               "jit(p)/dk.dense/dq.mlp/f:"),
        ]
    mods = [ev("jit__prefill_program(5)", 1000 * i, 60) for i in range(3)]
    ops = [o for i in range(3) for o in one(1000 * i)]
    row = xk.reduce_kinds(profile(mods, ops))["jit__prefill_program"]
    routed = row["kinds"]["routed"]
    # inside the conditional: fusion.2 -> fusion.3 (3 us); at the top:
    # the conditional -> fusion.6 (4 us).  Not the conditional's head
    # (2 us) or tail (9 us), nor the holes around fusion.4 (3 + 3 us):
    # those stay the conditional's own time
    assert routed["holes_s"] == pytest.approx(7e-6)
    assert routed["scopes"] == pytest.approx(
        {"route": 40e-6 - 20e-6 - 3e-6, "experts": 10e-6, "mlp": 10e-6})
    assert routed["events"] == 5
    assert row["kinds"]["dense"] == {
        "self_s": pytest.approx(5e-6), "holes_s": 0.0, "events": 1,
        "scopes": {"mlp": pytest.approx(5e-6)}}
    # fusion.6 -> fusion.7 (1 us, two kinds) and the last 5 us
    assert row["kinds"]["-"]["holes_s"] == pytest.approx(6e-6)
    assert sum(r["self_s"] + r["holes_s"] for r in row["kinds"].values()
               ) == pytest.approx(60e-6)


def test_a_compilers_op_takes_its_takers_kind_and_none_where_it_has_none():
    """``slice-start`` -> ``slice-done`` -> the matmul of a ``mamba``
    layer; a copy for ``embed`` (a scope and NO kind: it has its kind
    already) stays outside; one for the next iteration finds nobody."""
    def step(t0):
        return [
            ev("%copy.1 = bf16[8]{0} copy(%p.0)", t0, 2),
            ev("%fusion.2 = bf16[8] fusion(%copy.1)", t0 + 2, 4,
               "jit(f)/while/body/dq.embed/gather:"),
            ev("%slice-start.1 = ((s8[8]), s8[4]) async-start(%gte.1)",
               t0 + 6, 1),
            ev("%slice-done.1 = s8[4]{0:S(1)} async-done(%slice-start.1)",
               t0 + 7, 9),
            ev("%fusion.3 = bf16[4,8] fusion(%fusion.2, s8[4] "
               "%slice-done.1)", t0 + 16, 20,
               "jit(f)/while/body/dk.mamba/dq.proj/dot_general:"),
            ev("%slice-done.11 = s8[4]{0:S(1)} async-done(%slice-start.11)",
               t0 + 36, 4),
        ]
    mods = [ev("jit__decode_program(11)", 1000 * i, 40) for i in range(3)]
    ops = [o for i in range(3) for o in step(1000 * i)]
    row = xk.reduce_kinds(profile(mods, ops))["jit__decode_program"]
    assert row["kinds"]["mamba"]["scopes"] == pytest.approx({"proj": 30e-6})
    assert row["kinds"]["mamba"]["events"] == 3
    assert row["kinds"]["-"]["scopes"] == pytest.approx(
        {"embed": 6e-6, "-": 4e-6})
    assert ["mamba", "proj", "slice-done", pytest.approx(9e-6)] in row["rows"]
    assert ["-", "embed", "copy", pytest.approx(2e-6)] in row["rows"]
    assert ["-", "-", "slice-done", pytest.approx(4e-6)] in row["rows"]


def test_the_execution_is_xplane_scopes_choice():
    """The variant that ran most often, its median WHOLE execution; the
    first to start and the last to end are cut."""
    mods = (
        [ev("jit__prefill_program(5)", 0, 30)]  # first: cut
        + [ev("jit__prefill_program(5)", 5000 + 100 * i, 50 + i)
           for i in range(4)]
        + [ev("jit__prefill_program(6)", 6000, 400)]  # ran once
        + [ev("jit__decode_program(11)", 7000, 40)]  # last: cut
    )
    ops = (
        [ev("%fusion.9 = f32[8] fusion(%p)", 5000 + 100 * i, 40 + i,
            "jit(p)/dk.dense/dq.mlp/dot:") for i in range(4)]
        + [ev("%fusion.7 = f32[8] fusion(%p)", 6000, 400,
              "jit(p)/dk.window/dq.attend/dot:")]
    )
    prof = profile(mods, ops)
    got, by_scope = xk.reduce_kinds(prof), xs.reduce_scopes(prof)
    assert set(got) == set(by_scope) == {"jit__prefill_program"}
    pre = got["jit__prefill_program"]
    for key in ("variants", "executions", "whole", "median_s"):
        assert pre[key] == by_scope["jit__prefill_program"][key]
    assert pre["median_s"] == pytest.approx(51e-6)  # of 50, 51, 52, 53
    assert pre["kinds"]["dense"]["self_s"] == pytest.approx(41e-6)
    assert "window" not in pre["kinds"]
    assert xk.reduce_kinds(profile(mods, ops, plane="/host:other")) == {}
    assert xk.reduce_kinds(type(prof)(planes=[])) == {}


# ---- a trace FILE and the reader ---------------------------------------------

DECODE = ("jit__decode_program(11)", [1000, 2000, 3000, 4000, 5000], 160, [
    ("%while.6 = (s32[]) while(%t.1), body=%b", 0, 150, None),
    ("%fusion.1 = bf16[4,8] fusion(%p.1)", 0, 30,
     LOOP + "dk.window/dq.proj/dot:"),
    ("%k.2 = bf16[4,8] custom-call(%fusion.1)", 32, 8,
     LOOP + "dk.window/dq.attend/pallas_call:"),
    ("%fusion.3 = bf16[4,8] fusion(%k.2)", 40, 40,
     LOOP + "dk.routed/dq.mlp/dq.experts/dot:"),
    ("%fusion.4 = bf16[4,8] fusion(%fusion.3)", 80, 20,
     LOOP + "dk.attention/dq.attend/dot:"),
    ("%fusion.5 = bf16[4,8] fusion(%fusion.4)", 100, 20,
     LOOP + "dk.dense/dq.mlp/dot:"),
    ("%fusion.6 = s32[4] fusion(%fusion.5)", 120, 16, LOOP + "dq.head/dot:"),
    ("%fusion.7 = s32[4] fusion(%fusion.6)", 136, 8,
     LOOP + "dq.sample/argmax:"),
])
PREFILL = ("jit__prefill_program(5)", [1200, 2200, 3200], 50, [
    ("%fusion.1 = bf16[512,8] fusion(%p.1)", 0, 10,
     "jit(p)/dk.window/dq.cache_write/scatter:"),
    ("%fusion.2 = bf16[512,8] fusion(%fusion.1)", 10, 5,
     "jit(p)/dk.attention/dq.attend/dot:"),
    ("%fusion.3 = bf16[512,8] fusion(%fusion.2)", 15, 30,
     "jit(p)/dk.routed/dq.mlp/dot:"),
])
DEC = {"program": "decode", "exclude": "prefill"}


def trace_dir(root, cell, programs):
    folder = root / ".benchmark_work" / cell / "trace" / "plugins" / (
        "profile") / "2026_01_01"
    folder.mkdir(parents=True)
    write_trace(str(folder / "host.xplane.pb"), programs)
    return str(root / ".benchmark_work" / cell / "trace")


@pytest.fixture()
def run_ctx(tmp_path, monkeypatch):
    """A run's context over a work directory that holds a trace where
    ``harness/child.py`` leaves one."""
    monkeypatch.setattr(scope_time, "ROOT", str(tmp_path))
    trace_dir(tmp_path, "a_cell", [DECODE, PREFILL, EDGES])
    return {"cell": {"name": "a_cell"},
            "conf": {"serving": {"generate.decode_chunk": 16}}}


def params_of(name):
    return load(os.path.join(BENCH_DIR, "metrics", name + ".json"))["params"]


def test_the_reader_sums_a_kinds_ops_and_its_own_holes(run_ctx):
    read = kind_time.read
    # proj 30 + the 2 us before the kernel + the kernel 8
    assert read(run_ctx, kinds=["window"], **DEC) == pytest.approx(0.040)
    assert read(run_ctx, kinds=["routed"], **DEC) == pytest.approx(0.040)
    assert read(run_ctx, kinds=["attention", "dense"], **DEC
                ) == pytest.approx(0.040)
    assert read(run_ctx, "prefill", ["window"]) == pytest.approx(0.010)
    assert read(run_ctx, "prefill", ["routed", "attention"]
                ) == pytest.approx(0.035)
    # a kind no op of the program ran under reads 0, not nothing
    assert read(run_ctx, kinds=["mamba"], **DEC) == 0.0
    assert read(run_ctx, kinds=["window"], per="generate.decode_chunk",
                **DEC) == pytest.approx(0.040 / 16)
    assert read(run_ctx, kinds=["window"], per=4, **DEC
                ) == pytest.approx(0.010)


@pytest.mark.parametrize("program,total", [("decode", 0.160 / 16),
                                           ("prefill", 0.050)])
def test_the_groups_and_none_add_up_to_the_execution(run_ctx, program,
                                                     total):
    """``*none``: head 16 + sample 8 + the loop's 6 + 10 us of holes.  A
    program's six metrics hold every kind of the two stacks once, so a
    cell's — the kinds ITS stack has, the others read 0 in its trace —
    add up to ``decode_step_ms`` / the prefill program's time."""
    read = kind_time.read
    assert read(run_ctx, kinds=["*none"], **DEC) == pytest.approx(0.040)
    assert read(run_ctx, "prefill", ["*none"]) == pytest.approx(0.005)
    names = [n for n, row in TABLE.items() if row[0] == program]
    assert len(names) == 6
    assert sum(read(run_ctx, **params_of(n)) for n in names
               ) == pytest.approx(total)


def test_nothing_to_read_is_none(run_ctx, tmp_path):
    read = kind_time.read
    assert read(run_ctx, "no_such_program", ["window"]) is None
    assert read(run_ctx, "decode", ["window"], exclude="decode") is None
    # no trace where the child leaves one
    assert read({"cell": {"name": "another_cell"}, "conf": run_ctx["conf"]},
                "decode", ["window"]) is None
    # the parent's program, or one out of a compile cache filled before
    # the kinds: phases and no kind — nothing, and no error, for every
    # group and for ``*none``; the by-scope metrics read on
    name, starts, length, ops = DECODE
    trace_dir(tmp_path, "bare", [
        (name, starts, length, [
            (o[0], o[1], o[2], o[3] and o[3].replace(
                "dk.window/", "").replace("dk.routed/", "").replace(
                "dk.attention/", "").replace("dk.dense/", ""))
            for o in ops]),
        EDGES])
    ctx = {"cell": {"name": "bare"}, "conf": run_ctx["conf"]}
    assert read(ctx, kinds=["window"], **DEC) is None
    assert read(ctx, kinds=["*none"], **DEC) is None
    assert set(ctx["kind_times"]["jit__decode_program"]["kinds"]) == {"-"}
    assert scope_time.read(ctx, scopes=["attend"], **DEC
                           ) == pytest.approx(0.028)


def test_one_reduction_serves_the_twelve_metrics_of_a_run(
        run_ctx, monkeypatch, capsys, tmp_path):
    calls = []
    reduce_file = xk.reduce_file
    monkeypatch.setattr(xk, "reduce_file",
                        lambda path: calls.append(path) or reduce_file(path))
    import run

    values = {n: run.read_metric(n, run_ctx) for n in TABLE}
    assert len(calls) == 1
    assert all(v is not None for v in values.values())
    assert values["decode_mixer_ms.window"] == pytest.approx(0.040 / 16)
    assert values["decode_ffn_ms.routed"] == pytest.approx(0.040 / 16)
    assert values["decode_no_kind_ms"] == pytest.approx(0.040 / 16)
    assert values["decode_mixer_ms.mamba"] == 0.0
    assert values["prefill_ffn_ms.routed"] == pytest.approx(0.030)
    assert values["prefill_no_kind_ms"] == pytest.approx(0.005)
    err = capsys.readouterr().err
    assert err.count("device time by layer kind") == 1
    assert ("jit__decode_program: the median of 5 whole executions (5 in "
            "the slice, 1 variant(s)), 0.160 ms = 16 steps of 0.010 ms"
            ) in err
    assert "jit__prefill_program" not in err  # 0.05 ms: under the table's floor
    kept = load(str(tmp_path / ".benchmark_work" / "a_cell" / "kinds.json"))
    assert kept["jit__decode_program"]["kinds"]["window"] == {
        "self_s": pytest.approx(38e-6), "holes_s": pytest.approx(2e-6),
        "events": 2, "scopes": {"proj": pytest.approx(30e-6),
                                "attend": pytest.approx(8e-6)}}


def test_the_tables_are_kind_by_scope_and_the_largest_rows(run_ctx):
    reduced = kind_time.reduced_of(run_ctx)
    text = xk.table(reduced, xk.decode_steps(reduced, 16))
    lines = text.splitlines()
    head = next(ln for ln in lines if ln.lstrip().startswith("kind"))
    assert head.split()[-5:] == ["ops", "holes", "all", "%", "events"]
    window = next(ln for ln in lines if ln.split()[:1] == ["window"]
                  and len(ln.split()) > 6).split()
    columns = head.split()
    cell = dict(zip(columns, window))
    # ms a step, /16: 38 us of ops, 2 of holes
    assert float(cell["ops"]) == pytest.approx(0.038 / 16, abs=6e-4)
    assert float(cell["%"]) == pytest.approx(25.0)
    assert any(ln.split()[:3] == ["routed", "experts", "fusion"]
               for ln in lines)
    assert xk.decode_steps(reduced, None) == {}
    assert xk.decode_steps(reduced, 16) == {"jit__decode_program": 16.0}
    assert xk.TOP_ROWS == 30


def test_the_command_prints_the_tables(run_ctx, tmp_path):
    logdir = str(tmp_path / ".benchmark_work" / "a_cell" / "trace")
    script = os.path.join(BENCH_DIR, "harness", "xplane_kinds.py")
    done = subprocess.run([sys.executable, script, logdir, "16"], check=True,
                          capture_output=True, text=True, timeout=60)
    assert "jit__decode_program: the median of 5 whole executions" in (
        done.stdout)
    assert "16 steps of 0.010 ms" in done.stdout
    assert "window" in done.stdout and "holes" in done.stdout
    plain = subprocess.run([sys.executable, script, logdir], check=True,
                           capture_output=True, text=True, timeout=60)
    assert "steps of" not in plain.stdout
    assert subprocess.run([sys.executable, script], capture_output=True,
                          timeout=60).returncode == 2


# ---- the files and the entries, by name --------------------------------------

@pytest.mark.parametrize("name", sorted(TABLE))
def test_a_metric_file_and_its_entry_are_the_issues(name):
    program, kinds, moves, cells = TABLE[name]
    entries = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entries == [{
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Model step", "moves": moves,
        "workloads": cells}]
    decl = load(os.path.join(BENCH_DIR, "metrics", name + ".json"))
    step = load(os.path.join(BENCH_DIR, "metrics", "decode_step_ms.json"))
    want = dict(step["params"]) if program == "decode" else {
        "program": "prefill", "per": 1}
    assert decl == {"reader": "kind_time", "params": {**want,
                                                      "kinds": kinds}}
    # every cell that reports it reports the metric it moves
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(moved["workloads"])


def test_the_groups_hold_every_kind_of_the_two_stacks_once():
    """So a cell's decode metrics add up to its ``decode_step_ms``: the
    kinds its configuration's stack has, each in one metric, and
    ``*none``."""
    stacks = {TRINITY: {"window", "attention", "routed", "dense"},
              JAMBA2: {"mamba", "attention", "dense"}}
    for program in ("decode", "prefill"):
        for cell, kinds in stacks.items():
            named = [k for n, row in TABLE.items()
                     if row[0] == program and cell in row[3]
                     for k in params_of(n)["kinds"]]
            assert sorted(named) == sorted(kinds | {"*none"}), (program, cell)
    # ... which is what the two configuration files say of their layers
    for cell, kinds in stacks.items():
        config = next(w["config"] for w in BENCH["workloads"]
                      if w["name"] == cell)
        conf = load(os.path.join(ROOT, next(
            c["file"] for c in BENCH["configs"] if c["name"] == config)))
        if cell == TRINITY:
            assert set(conf["layer_types"]) == {
                "sliding_attention", "full_attention"}
            assert 0 < conf["num_dense_layers"] < conf["num_hidden_layers"]
        else:
            assert conf["attn_layer_period"] > 1


def test_nothing_else_of_the_declaration_moved():
    assert [m["bound"] for m in BENCH["end_to_end"]] == [0.01, 0.01, 0.1]
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "ttft_p50_ms", "tpot_p50_ms", "setup_s"]
    assert BENCH["run_seconds"] == 30
    assert len(BENCH["workloads"]) == 7 and len(BENCH["configs"]) == 7
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    # PR 40's eight by phase stay as they are, beside the twelve
    for name in ("decode_attention_ms", "decode_projection_ms",
                 "decode_mlp_ms", "decode_head_ms", "decode_other_ms",
                 "prefill_attention_ms", "prefill_mlp_ms"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == ["rag_closed", "rag_closed8_dsv2"]
        assert load(os.path.join(BENCH_DIR, "metrics", name + ".json"))[
            "reader"] == "scope_time"


def test_what_pr44s_pin_held_still_holds():
    """``test_benchmark_ouro.py::test_what_pr42s_two_pins_held_still_
    holds`` holds the SET of metrics that list ``record_closed4_jamba2``
    equal to what PR 44 found: false once ISSUE 53's eight list the cell
    (tests/conftest.py marks it, strictly).  Every other line of it as it
    stands there, and that one as what the file's own header means:
    membership."""
    import test_benchmark_ouro as ouro

    metrics = ouro._metrics()
    joined = {n for n, m in metrics.items()
              if JAMBA2 in m.get("workloads", [])}
    assert joined >= {
        *ouro.SEVENTEEN, "prefill_mfu", "lane_state_share_of_step_bytes",
        *ouro.PR42S_TWO}
    assert joined >= {n for n, row in TABLE.items() if JAMBA2 in row[3]}
    for name in ouro.SEVENTEEN[:15]:
        assert metrics[name]["workloads"][:4] == ouro.OLDER, name
    for name in ("lane_state_share_of_step_bytes", "prefill_mfu"):
        assert metrics[name]["workloads"][:2] == [
            "record_closed4_sala", JAMBA2]
    for name in ("decode_step_ms", "decode_step_roofline"):
        assert metrics[name]["workloads"][:3] == ouro.OLDER[:2] + [JAMBA2]
    for name in ouro.PR42S_TWO:
        assert metrics[name]["workloads"][:1] == [JAMBA2]
    for name in ["sparse_blocks_read_share", *ouro.PR40S_EIGHT,
                 *ouro.PINNED_TO_RAG_CLOSED]:
        assert JAMBA2 not in metrics[name]["workloads"], name
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("prefill_mfu")
    assert names[at + 1:at + 9] == ouro.PR40S_EIGHT
    assert names[at + 9:at + 11] == ouro.PR42S_TWO
    ms, share = BENCH["per_layer"][at + 9:at + 11]
    assert ms == {
        "name": "prefill_scan_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [JAMBA2]}
    assert share == {
        "name": "prefill_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [JAMBA2]}
    assert load(os.path.join(BENCH_DIR, "metrics", "prefill_scan_ms.json")) == {
        "reader": "scope_time",
        "params": {"program": "prefill", "per": 1, "scopes": ["state"]}}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "prefill_scan_roofline.json"))["reader"] == (
        "scan_roofline")
    assert [w["name"] for w in BENCH["workloads"]][:4] == ouro.OLDER
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:4]) == 0
    # the twelve stand behind everything that was there
    first = min(names.index(n) for n in TABLE)
    assert set(names[first:]) >= set(TABLE)
    assert names.index("prefill_retention_roofline") < first


def test_the_kinds_the_metrics_name_are_the_programs():
    """The vocabulary lives in the program; the benchmark's parent never
    imports it, so the files are held to it here."""
    tree = ast.parse(open(os.path.join(
        ROOT, "docqa_tpu", "ops", "scopes.py"), encoding="utf-8").read())
    consts = {n.targets[0].id: ast.literal_eval(n.value) for n in tree.body
              if isinstance(n, ast.Assign)
              and isinstance(n.value, (ast.Constant, ast.Tuple))}
    assert consts["KIND_PREFIX"] == xk.PREFIX == "dk."
    assert consts["PREFIX"] == xs.PREFIX == "dq."
    from docqa_tpu.models.hybrid import MIXERS

    vocabulary = set(MIXERS) | set(consts["FFN_KINDS"])
    named = {k for n in TABLE for k in params_of(n)["kinds"]} - {"*none"}
    assert named <= vocabulary
    assert all(xk.KIND.fullmatch(xk.PREFIX + k) for k in vocabulary)


@pytest.mark.parametrize("module", ["harness/xplane_kinds.py",
                                    "readers/kind_time.py"])
def test_what_the_parent_imports_is_standard_library(module):
    tree = ast.parse(open(os.path.join(BENCH_DIR, module),
                          encoding="utf-8").read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module.split(".")[0])
    assert names <= {"__future__", "bisect", "json", "os", "re", "sys",
                     "time", "types", "typing", "harness", "readers"}
    # the file reader, the stem, the consumer rule and the choice of
    # execution are ``xplane_scopes``': imported, not written again
    if module.startswith("harness"):
        source = open(os.path.join(BENCH_DIR, module),
                      encoding="utf-8").read()
        for name in ("read_xspace", "find_xplane", "op_stem",
                     "_by_consumer", "scope_of"):
            assert f"def {name}" not in source
            assert name in source
        assert "xplane_scopes.reduce_scopes(" in source
