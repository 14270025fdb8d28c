"""Trace reduction on a small synthetic event list (lib/tracing.py)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import tracing  # noqa: E402


def _events():
    # window [0, 10); device ops: [1, 3), [2, 4) overlap -> busy [1, 4);
    # [6, 7); an op [9, 12) is clipped to [9, 10). Busy 3 + 1 + 1 = 5.
    ops = [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
           ("copy.3", 6.0, 7.0), ("fusion.1", 9.0, 12.0)]
    # two steps: prefill steps (drafter, target), the round, the gather;
    # a round at [9, 12) runs past the window and is not counted
    modules = [("jit__unknown(11)", 0.5, 0.7), ("jit__unknown(12)", 0.7, 1.0),
               ("jit__unknown(13)", 1.0, 4.0),
               ("jit__window_gather(5)", 4.0, 4.1),
               ("jit_where(7)", 5.5, 5.6),
               ("jit__unknown(11)", 6.0, 6.2), ("jit__unknown(12)", 6.2, 7.0),
               ("jit__unknown(13)", 7.0, 8.0),
               ("jit__window_gather(5)", 8.0, 8.1),
               ("jit__unknown(13)", 9.0, 12.0)]
    host = [("bench_window", 0.0, 10.0), ("decode_round", 0.5, 4.5),
            ("prefill_chunk", 5.0, 7.5), ("step", 4.0, 9.0),
            ("admit", 8.5, 8.9)]
    return {"ops": ops, "modules": modules}, host


def _trace(planes=1):
    lines, host = _events()
    return tracing.reduce_events(
        {f"/device:TPU:{i}": lines for i in range(planes)}, host)


def test_busy_union_and_idle_share():
    t = _trace()
    assert t.window_s == 10.0
    assert tracing.busy_s(t) == pytest.approx(5.0)
    assert tracing.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert tracing.union_length([], 0, 10) == 0


@pytest.mark.parametrize("intervals,lo,hi,gaps", [
    ([(1, 3), (2, 4), (6, 7)], 0, 10, [(0, 1), (4, 6), (7, 10)]),
    ([(0, 10)], 0, 10, []),
    ([], 2, 5, [(2, 5)]),
])
def test_idle_intervals(intervals, lo, hi, gaps):
    assert tracing.idle_intervals(intervals, lo, hi) == gaps


def test_round_and_prefill_programs_by_their_place():
    t = _trace()
    assert tracing.round_program(t) == "jit__unknown(13)"
    assert tracing.round_time(t) == (pytest.approx(4.0), 2)   # 3 + 1
    assert tracing.prefill_time(t) == (pytest.approx(1.5), 4)
    assert tracing.host_count(t, "prefill_chunk") == 1
    assert tracing.host_count(t, "decode_round") == 1
    assert t.op_time["fusion.1"] == pytest.approx(3.0)   # 2 + clipped 1


def test_no_round_without_a_window_gather():
    t = tracing.reduce_events(
        {"/device:TPU:0": {"ops": [], "modules": [("jit_f(1)", 1.0, 2.0)]}},
        [("bench_window", 0.0, 10.0)])
    assert tracing.round_program(t) is None
    assert tracing.round_time(t) == (0.0, 0)
    assert tracing.prefill_time(t) == (0.0, 0)


def test_breakdown_labels_gaps_by_innermost_host_span():
    b = tracing.breakdown(_trace())
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    # (4, 6): midpoint 5 lies in "step" and "prefill_chunk", the shorter
    # wins; (7, 9): midpoint 8 only in "step"; (0, 1): in "decode_round"
    got = sorted((n, round(sec, 9)) for n, sec in b["idle_gaps"])
    assert got == [("decode_round", 1.0), ("prefill_chunk", 2.0),
                   ("step", 2.0)]


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench_window"):
        tracing.reduce_events({}, [("decode_round", 0.0, 1.0)])


def test_four_identical_chips_read_as_one():
    """A cell across four chips reads per chip: the same op times,
    breakdown and idle share as one chip running the same ops."""
    one, four = _trace(), _trace(planes=4)
    assert four.op_time == pytest.approx(one.op_time)
    assert tracing.breakdown(four) == tracing.breakdown(one)
    assert tracing.busy_s(four) == pytest.approx(tracing.busy_s(one))
    assert tracing.round_time(four) == tracing.round_time(one)


@pytest.mark.parametrize("plane,dev", [
    ("/device:TPU:0", 0), ("/device:TPU:3", 3), ("/host:CPU", None),
    ("/device:TPU:0 SparseCore 1", None), ("/device:CPU:0", None),
    ("#Chip0 Host Interface", None),
])
def test_device_planes_by_id(plane, dev):
    assert tracing._device_id(plane) == dev
