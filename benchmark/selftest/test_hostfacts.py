"""``harness/hostfacts.py``: what the host did while the window ran."""

import gc
import time

import pytest

from benchmark.harness import hostfacts


def test_a_watch_counts_the_collections_between_start_and_stop_and_no_other():
    watch = hostfacts.HostWatch()
    gc.collect()  # before start(): not counted
    watch.start()
    junk = [[i] for i in range(2000)]
    gc.collect()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.02:
        pass
    facts = watch.stop()
    del junk
    gc.collect()  # after stop(): not counted, and the callback is gone
    assert watch._on_gc not in gc.callbacks
    assert facts["gc"]["gen2"]["collections"] == 1
    assert 0.0 < facts["gc"]["gen2"]["longest_s"] <= facts["gc"]["gen2"]["seconds"]
    assert facts["wall_s"] >= 0.02 and facts["process_cores_busy"] > 0.5
    assert facts["switched"] >= 0 and facts["preempted"] >= 0 and facts["cores"] >= 1
    if "machine_busy_share" in facts:  # where /proc/stat is readable
        assert 0.0 <= facts["machine_steal_share"] <= facts["machine_busy_share"] <= 1.0


@pytest.mark.parametrize("spans,expect", [
    ([], {"count": 0}),
    # 8 plain steps of 40 ms, one stalled to 70 ms, one of 400 ms at t = 2
    ([("step", i * 0.1, i * 0.1 + 0.04) for i in range(8)]
     + [("step", 1.0, 1.07), ("step", 2.0, 2.4), ("admit", 0.0, 9.0)],
     {"count": 10, "classes": {"to_1.25": 8, "to_2": 1, "to_3": 0, "to_4": 0, "over_4": 1},
      "stalled_s": 0.07, "slowest": [2.0, 400.0], "p50": 40.0, "max": 400.0}),
    # a step that began after the window closed is not the window's
    ([("step", 0.0, 0.04), ("step", 60.0, 69.0)], {"count": 1}),
])
def test_step_facts(spans, expect):
    facts = hostfacts.step_facts(spans, window_s=51.0)
    assert facts["count"] == expect["count"]
    if "classes" in expect:
        assert {k: v["steps"] for k, v in facts["by_median"].items()} == expect["classes"]
        assert facts["by_median"]["to_2"]["seconds"] == pytest.approx(expect["stalled_s"])
        assert sum(v["seconds"] for v in facts["by_median"].values()) == pytest.approx(0.04 * 8 + 0.07 + 0.4)
        assert facts["slowest"][0] == pytest.approx(expect["slowest"])
        assert facts["ms"]["p50"] == pytest.approx(expect["p50"])
        assert facts["ms"]["max"] == pytest.approx(expect["max"])
