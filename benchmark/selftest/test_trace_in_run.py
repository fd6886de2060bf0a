"""``--trace 2`` rehearsed on the CPU for every cell: up to the closing of
the window it is a ``--trace 0`` run (same traffic, nothing compiled, every
token accounted for), and its last line carries both kinds of metric. On a
CPU only counts may be printed, so the per-layer metrics of a rehearsal's
line are the counter-sourced ones; the others are read (the ``trace`` fact
names them) and left out."""

import json
import os

import pytest

from benchmark.harness import catalog

from .test_rehearsal import cells, run


def facts(p):
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return {l["phase"]: l for l in lines if "phase" in l}, lines[-1]


@pytest.mark.parametrize("cell,chips", cells())
def test_trace_2_is_a_trace_0_run_followed_by_a_traced_phase(cell, chips):
    plain, traced = (run(cell, "--trace", t, devices=chips) for t in ("0", "2"))
    assert plain.returncode == 0, plain.stderr[-2000:]
    assert traced.returncode == 0, traced.stderr[-2000:]
    f0, last0 = facts(plain)
    f2, last2 = facts(traced)
    assert f2["warm_up"]["digest"] == f0["warm_up"]["digest"]
    assert f2["warm_up"]["programs"] == f0["warm_up"]["programs"]
    assert f2["warm_up"]["kernels"] == f0["warm_up"]["kernels"] == {}
    for f in (f0, f2):
        w = f["window"]
        assert w["compiled_in_window"] == 0 and w["tokens_counted"] == w["tokens_stamped"]
        assert w["summary"]["failed"] == 0
    if f0["warm_up"]["traffic"]["loop"] == "open":  # a closed loop sends by the machine's speed
        assert f2["window"]["summary"]["attempted"] == f0["window"]["summary"]["attempted"]
    assert set(last2) == set(last0) == {"correct", "attempted", "failed", "metrics", "device", "host", "compared"}
    assert last2["compared"] == last0["compared"]  # same seed: the same weights, probe and verdict
    assert last2["correct"] is True and last2["failed"] == 0
    # held at the closing of the window: the line's counts are the window's
    assert last2["attempted"] == f2["window"]["summary"]["attempted"]
    assert last2["metrics"]["out_tokens"]["value"] == f2["window"]["summary"]["out_tokens"]
    phase, trace = f2["traced_phase"], f2["trace"]
    assert phase["compiled_in_phase"] == 0
    assert phase["slice"][0] >= f2["window"]["window_s"] and phase["slice"][1] > phase["slice"][0]
    assert phase["step_ms_slice"]["count"] == trace["span_counts"]["step"] > 0
    # both kinds side by side: the window's counts, and the per-layer metrics a CPU may print
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if cell in m.get("workloads", [cell])]
    counts = {m["name"] for m in mine if m["source"] == "program_counter"}
    assert {"finished", "out_tokens"} <= set(last2["metrics"])
    assert set(last2["metrics"]) - {"finished", "out_tokens"} == counts & set(trace["read"])
    # every host-side reader found what it reads (the device-side ones need a chip)
    host_side = {m["name"] for m in mine if m["source"] in ("program_counter", "program_span")}
    sampled = {m["name"] for m in mine if m["source"] == "host_clock"}
    assert host_side <= set(trace["read"])
    assert sampled & set(trace["read"])
    assert not [k for k in last2["metrics"] if k.startswith(("step.", "kernel.", "device."))]


def test_the_trace_is_deleted_once_it_is_reduced():
    assert not os.path.exists(os.path.join(catalog.REPO_DIR, ".bench_cache", "trace"))


def test_no_statement_before_the_window_closes_tells_trace_2_from_trace_0():
    """Read off ``run.py`` itself: up to the reduction of the closed window,
    ``--trace`` is looked at only to ask whether it is 1 (the older separate
    traced run) and to print it; a ``--trace 2`` run therefore executes the
    statements of a ``--trace 0`` run, the same objects built and nothing of
    the profiler imported, started or allocated (PR 34's refusal round)."""
    import re

    with open(os.path.join(os.path.dirname(catalog.__file__), "..", "run.py")) as f:
        main = f.read().split("def main(", 1)[1]
    before, after = main.split("# ---- reduction", 1)
    uses = re.findall(r"args\.trace\b[^\n]{0,8}", before)
    assert uses and all(u.startswith(("args.trace == 1", "args.trace,")) for u in uses), uses
    assert "args.trace == 2" in after  # the traced phase comes after, and only there
