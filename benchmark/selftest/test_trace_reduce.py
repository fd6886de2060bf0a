"""The reduction from a trace to numbers: interval arithmetic on events with
known answers, and the small trace recorded on the chip beside this file
(``data/tp4_small.xplane.pb``, made by ``record_trace.py`` on the four-chip
host, PR 22: three annotated steps of a sharded matmul + all-reduce)."""

import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.harness.trace_reduce import Event, TraceEvents

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tp4_small.xplane.pb")


def test_union_clip_subtract():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert tr.total(u) == 5
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [(0, 1), (2, 3), (7, 8)]


def synthetic():
    # chip 0: ops at [0,2], nested [0.5,1], [3,4], collective [4,5] overlapped by compute [4.5,6]
    ops0 = [Event("while.1", 0.0, 2.0), Event("fusion.1", 0.5, 0.5), Event("fusion.2", 3.0, 1.0),
            Event("all-reduce.3", 4.0, 1.0), Event("fusion.2", 4.5, 1.5)]
    # chip 1: busy [0,1] only
    ops1 = [Event("fusion.1", 0.0, 1.0)]
    mods = [Event("jit_wrapped(1)", 0.0, 2.0), Event("jit_wrapped(2)", 3.0, 3.0),
            Event("jit__where(3)", 6.5, 0.1)]
    spans = [Event("step", 0.0, 2.5), Event("wait_for_arrival", 2.5, 0.4), Event("step", 2.9, 5.1)]
    return TraceEvents(ops={"/device:TPU:0": ops0, "/device:TPU:1": ops1},
                       modules={"/device:TPU:0": mods}, spans=spans)


def test_busy_idle_and_gap_attribution():
    t = synthetic()
    window = tr.window_of(t.spans)
    assert window == (0.0, 8.0)
    busy = tr.busy(t, window)
    assert busy["/device:TPU:0"] == pytest.approx(5.0)  # [0,2] + [3,6]
    assert busy["/device:TPU:1"] == pytest.approx(1.0)
    gaps = tr.idle_gaps(t, window)
    assert gaps == [(2.0, 3.0), (6.0, 8.0)]
    by = tr.attribute_gaps(gaps, t.spans)
    # [2,3]: step covers 0.5, wait 0.4, step 0.1 -> the first step; [6,8] -> the last step
    assert by == {"step": pytest.approx(3.0)}


def test_name_sums_matching_and_collectives():
    t = synthetic()
    sums = tr.name_sums(t.ops, (0.0, 8.0))
    assert sums["fusion.2"] == (2, pytest.approx(2.5))
    assert tr.matching(sums, r"^fusion") == (4, pytest.approx(4.0))
    assert tr.matching(tr.name_sums(t.modules, (0.0, 8.0)), r"^jit_(?!_)") == (2, pytest.approx(5.0))
    c = tr.collectives(t, (0.0, 8.0))
    # chip 0: collective [4,5], compute covers [4.5,5] -> exposed 0.5; averaged over 2 chips
    assert c["collective_s"] == pytest.approx(0.5) and c["exposed_s"] == pytest.approx(0.25)


def test_short_name_and_containers():
    long = "%copy.71 = bf16[28,1057,8,32,128]{4,3,2,1,0} copy(bf16[28,1057,8,32,128] %fusion.142)"
    assert tr.short_name(long) == "copy.71"
    assert tr.short_name("jit_wrapped(123)") == "jit_wrapped(123)"
    assert tr.CONTAINER.match("while.13") and not tr.CONTAINER.match("whilex")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace checked in")
def test_recorded_trace_from_the_chip():
    t = tr.read(RECORDED)
    assert len(t.ops) == 4, "one XLA Ops line per chip of the four-chip host"
    assert [e.name for e in t.spans].count("step") == 3
    red = tr.reduce_trace(RECORDED)
    assert red["chips"] == 4
    assert 0 < red["busy_s"] < red["window_s"]
    # the busy union against a brute-force count on a 1 us grid, chip by chip
    window = tr.window_of(t.spans)
    for plane, evs in t.ops.items():
        grid = set()
        for e in evs:
            a = max(e.start, window[0]); b = min(e.end, window[1])
            grid.update(range(int(a * 1e6), int(b * 1e6)))
        assert tr.busy(t, window)[plane] == pytest.approx(len(grid) * 1e-6, abs=2e-6 * len(evs) + 1e-5)
    c = red["collectives"]
    assert 0 < c["exposed_s"] <= c["collective_s"] <= red["busy_s"] + 1e-9
    # three steps on four chips, counted over the whole trace: the chips'
    # clocks sit within a millisecond of the host's, which is the length of
    # these steps, so the driver-span window cuts some of them off
    everything = (float("-inf"), float("inf"))
    assert tr.matching(tr.name_sums(t.ops, everything), "^all-reduce")[0] == 3 * 4
    assert tr.matching(tr.name_sums(t.modules, everything), r"^jit_(?!_)")[0] == 3 * 4
    assert tr.matching(red["op_sums"], "^all-reduce")[0] >= 4
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert sum(s for _, s in red["breakdown"]["idle_gaps"]) == pytest.approx(
        tr.total(tr.idle_gaps(t, window))
    )
