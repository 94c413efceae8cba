"""The trace reduction on a small recorded trace checked in beside this
file (``data/tiny_trace.xplane.pb``; ``data/tiny_trace.textproto`` is the
same trace, readable): two chips, two decode programs and one prefill on
each, one all-reduce inside each decode program, two idle gaps on chip 0."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark"))

from harness import xplane  # noqa: E402

TRACE = os.path.join(HERE, "data", "tiny_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(TRACE)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(reduced):
    assert reduced["devices"] == 2
    # chip 0: 1000 + 700 + 1000 us; chip 1: 900 + 700 + 900 us
    assert reduced["busy_s"] == pytest.approx(2600e-6, rel=1e-6)
    assert reduced["window_s"] == pytest.approx(6000e-6, rel=1e-6)


def test_idle_share_follows(reduced):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark"))
    from readers import idle_share

    assert idle_share.read({"trace": reduced}) == pytest.approx(
        100 * (1 - 2600 / 6000), rel=1e-6
    )
    assert idle_share.read({"trace": None}) is None


@pytest.mark.parametrize(
    "program,count,total_us,median_us",
    [("jit__decode_spec_program", 2, 2000, 1000),
     ("jit__prefill_program", 1, 700, 700)],
)
def test_per_program_time(reduced, program, count, total_us, median_us):
    row = reduced["programs"][program]
    assert row["count"] == count
    assert row["total_s"] == pytest.approx(total_us * 1e-6, rel=1e-6)
    assert row["median_s"] == pytest.approx(median_us * 1e-6, rel=1e-6)


def test_collectives_are_counted_inside_their_program(reduced):
    assert reduced["collective_s"]["jit__decode_spec_program"] == pytest.approx(
        300e-6, rel=1e-6
    )
    assert reduced["collective_s"]["jit__prefill_program"] == 0.0


def test_gaps_are_named_after_the_host_span_open_during_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    # 2000..2300 us under serve_decode_chunk, 3000..4000 us under qa_retrieve;
    # the Python tracer's "$" frames and thread-long events never win
    assert gaps["serve_decode_chunk"] == pytest.approx(300e-6, rel=1e-6)
    assert gaps["qa_retrieve"] == pytest.approx(1000e-6, rel=1e-6)
    assert not any(name.startswith("$") for name in gaps)


def test_top_device_ops(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.2"] == pytest.approx(900e-6, rel=1e-6)
    assert ops["all-reduce.7"] == pytest.approx(300e-6, rel=1e-6)
    assert len(reduced["device_ops"]) <= 10


def test_timeline_lists_programs_and_host_events_in_order_of_start():
    from jax.profiler import ProfileData

    out = xplane.reduce_profile(ProfileData.from_file(TRACE), timeline_min_s=0.0)
    line = out["timeline"]
    assert [row[0] for row in line] == sorted(row[0] for row in line)
    on_device = [row[3] for row in line if row[2] == "device"]
    assert on_device == ["jit__decode_spec_program", "jit__prefill_program",
                         "jit__decode_spec_program"]
    assert {"serve_decode_chunk", "qa_retrieve"} <= {
        row[3] for row in line if row[2] == "host"
    }
    # the default leaves out what is shorter than TIMELINE_MIN_S: all but
    # the 6 ms thread-long event
    assert [r[3] for r in xplane.reduce_file(TRACE)["timeline"]] == ["idle"]


@pytest.mark.parametrize(
    "intervals,expected",
    [([(0, 1), (2, 3)], 2.0), ([(0, 2), (1, 3)], 3.0),
     ([(0, 5), (1, 2)], 5.0), ([], 0.0)],
)
def test_union_length(intervals, expected):
    assert xplane.union_length(intervals) == pytest.approx(expected)


def test_gaps_of_ignores_short_holes():
    assert xplane.gaps_of([(0, 1), (1.01, 2), (3, 4)], 0.5) == [(2, 3)]


def test_a_trace_without_a_device_plane_reports_no_device():
    from jax.profiler import ProfileData

    text = 'planes { id: 1 name: "/host:CPU" }'
    empty = xplane.reduce_profile(ProfileData.from_text_proto(text))
    assert empty["devices"] == 0 and empty["busy_s"] == 0.0
