"""The readers of ISSUE 56: ``span_phase`` (the self time of the program's
``serving.*`` spans phase by phase, and the device's idle time by the span it
falls under) and ``counter_share`` (one label's share of a counter family).
Interval arithmetic on events with known answers, and the small trace
recorded on the chip beside this file
(``data/serving_phases_small.xplane.pb.gz``, made by
``record_serving_trace.py`` as it stands on a tree that has the spans
``serving.schedule``, ``serving.h2d``, ``serving.account`` and
``serving.prefill_chunk.fetch_start``: eight split serving steps of the tiny
rehearsal preset, an admission and its chunk passes among them;
``data/serving_small.xplane.pb.gz`` is the same recording of a tree before
them)."""

import gzip
import os
import random
import re

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.harness.readers import counter_share, program_span as ps, span_phase as sp
from benchmark.harness.trace_reduce import Event, TraceEvents

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED_GZ = os.path.join(DATA, "serving_phases_small.xplane.pb.gz")
OLDER_GZ = os.path.join(DATA, "serving_small.xplane.pb.gz")  # recorded before the three spans
PARAMS = {"step": "serving.step", "prefix": "serving.", "wait": "fetch_wait$",
          "dispatch": r"\.dispatch$", "modules": r"^jit_token_generation_model_(decode|chunk)\("}
#: the ``spans`` of the per-layer metrics, as their ``layer_metrics/*.json`` have them
PHASES = {
    "sched.schedule_ms": r"^serving\.(schedule|housekeeping)$",
    "sched.prepare_ms": r"\.prepare$",
    "sched.h2d_ms": r"^serving\.h2d$",
    "sched.dispatch_ms": r"\.(dispatch|fetch_start)$",
    "sched.commit_ms": r"\.commit$",
    "sched.account_ms": r"^serving\.account$",
}
UNNAMED = r"^serving\.(step|decode|prefill_chunk)$"
IN_WAIT, BETWEEN = r"fetch_wait$", r"^\(none\)$"


def unpack(gz: str, tmp_path) -> str:
    path = str(tmp_path / os.path.basename(gz)[: -len(".gz")])
    with gzip.open(gz, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    return path


def synthetic():
    """One step [0,10] on one chip: housekeeping [0,.5], schedule [.5,1], decode [1,5] holding
    prepare [1,3] (h2d [2,3] inside it), dispatch [3.25,4] and account [4,4.75], fetch_wait
    [5,8], commit [8,9.5]. The step's own self time: [9.5,10]; the decode span's: [3,3.25] and
    [4.75,5]. A second step [11,12] holds nothing. The chip is busy [3.5,7]."""
    spans = [
        Event("serving.step", 0.0, 10.0), Event("serving.housekeeping", 0.0, 0.5),
        Event("serving.schedule", 0.5, 0.5), Event("serving.decode", 1.0, 4.0),
        Event("serving.decode.prepare", 1.0, 2.0), Event("serving.h2d", 2.0, 1.0),
        Event("serving.decode.dispatch", 3.25, 0.75), Event("serving.account", 4.0, 0.75),
        Event("serving.fetch_wait", 5.0, 3.0), Event("serving.commit", 8.0, 1.5),
        Event("serving.step", 11.0, 1.0),
    ]
    spans.sort(key=lambda e: (e.start, -e.dur))
    trace = TraceEvents(
        ops={"/device:TPU:0": [Event("fusion.1", 3.5, 3.5)]},
        modules={"/device:TPU:0": [Event("jit_token_generation_model_decode(1)", 3.5, 3.5)]},
        spans=[Event("step", 0.0, 10.0), Event("step", 11.0, 1.0)],
    )
    return spans, trace


@pytest.fixture
def fake(monkeypatch):
    spans, trace = synthetic()
    for reader in (sp, ps):
        monkeypatch.setattr(reader, "program_spans", lambda path, prefix: list(spans))
    monkeypatch.setattr(tr, "read", lambda path: trace)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "fake.xplane.pb")
    return spans, trace


def read(kind, spans, ctx=None):
    ctx = {"trace": {"chips": 1}} if ctx is None else ctx
    return sp.read({**PARAMS, "kind": kind, "spans": spans}, ctx)


def test_self_time_by_phase_and_what_no_leaf_names(fake):
    table = sp.phases("x", PARAMS)
    assert table["steps"] == 2
    assert table["host_s"] == pytest.approx(10.0 - 3.0 + 1.0)
    own = table["self_s"]
    assert own["serving.decode.prepare"] == pytest.approx(1.0)  # without the copies inside it
    assert own["serving.h2d"] == pytest.approx(1.0)
    assert own["serving.decode"] == pytest.approx(0.5) and own["serving.step"] == pytest.approx(1.5)
    ms = {name: read("self_ms_per_step", rx) for name, rx in PHASES.items()}
    assert ms == pytest.approx({
        "sched.schedule_ms": 500.0, "sched.prepare_ms": 500.0, "sched.h2d_ms": 500.0,
        "sched.dispatch_ms": 375.0, "sched.commit_ms": 750.0, "sched.account_ms": 375.0})
    unnamed = read("unnamed_share", UNNAMED)
    assert unnamed == pytest.approx(100 * 2.0 / 8.0)
    # the six parts and what no leaf names are the steps' host time
    assert sum(ms.values()) + unnamed / 100 * 4000.0 == pytest.approx(table["host_s"] / 2 * 1e3)


def test_idle_under_the_waits_between_the_steps_and_under_the_host(fake):
    table = sp.phases("x", PARAMS)
    # idle: [0,3.5], [7,12] of the window [0,12]
    assert table["idle_total_s"] == pytest.approx(8.5)
    assert read("idle_ms_per_step", IN_WAIT) == pytest.approx(1.0 / 2 * 1e3)  # [7,8]
    assert read("idle_ms_per_step", BETWEEN) == pytest.approx(1.0 / 2 * 1e3)  # [10,11]
    # what program_span reads as the idle under the host's own work is the rest
    assert ps.idle_by_program_span("x", PARAMS)["host_idle_s"] == pytest.approx(8.5 - 1.0 - 1.0)
    assert read("idle_ms_per_step", r"^serving\.h2d$") == pytest.approx(500.0)


def test_what_a_trace_does_not_hold_reads_as_nothing(fake, monkeypatch):
    spans, _ = fake
    ctx = {"trace": {"chips": 1}}
    assert read("self_ms_per_step", r"^serving\.nothing$", ctx) is None
    assert read("idle_ms_per_step", r"^serving\.nothing$", ctx) is None
    assert read("unnamed_share", r"^serving\.nothing$", ctx) is None
    assert len(ctx["span_phase_tables"]) == 1  # one reduction a run, shared
    with pytest.raises(ValueError):
        read("nope", r"\.commit$", ctx)
    assert read("self_ms_per_step", r"\.commit$", {"trace": None}) is None
    # a commit older than the three spans: the copies are in the prepare span, nothing is h2d
    older = [e for e in spans if e.name not in ("serving.h2d", "serving.account", "serving.schedule")]
    monkeypatch.setattr(sp, "program_spans", lambda path, prefix: older)
    assert read("self_ms_per_step", PHASES["sched.h2d_ms"]) is None
    assert read("self_ms_per_step", PHASES["sched.account_ms"]) is None
    assert read("self_ms_per_step", PHASES["sched.prepare_ms"]) == pytest.approx(1000.0)
    monkeypatch.setattr(sp, "program_spans", lambda path, prefix: [])
    assert read("self_ms_per_step", r"\.commit$") is None

    def missing(d):
        raise FileNotFoundError(d)

    monkeypatch.setattr(tr, "find_xplane", missing)
    assert read("self_ms_per_step", r"\.commit$") is None


def test_span_and_module_counts_that_differ_fail_loudly(fake):
    _, trace = fake
    trace.modules["/device:TPU:0"].append(Event("jit_token_generation_model_chunk(2)", 8.0, 0.5))
    with pytest.raises(ValueError, match="1 spans match .* but 2 XLA Modules"):
        sp.phases("x", PARAMS)


def random_spans(rng: random.Random, threads: int):
    """Properly nested spans of ``threads`` threads on one clock (siblings may touch, a child
    may share an end with its parent, two spans may be the same interval)."""
    out = []

    def fill(t0, t1, depth, cap):
        t = t0
        while t < t1 and len(out) < cap and rng.random() < (0.8 if depth else 0.98):
            a = t + rng.choice((0.0, rng.random() * (t1 - t) * 0.3))
            b = min(t1, a + rng.random() * (t1 - a))
            if b <= a:
                break
            out.append(Event(f"serving.d{depth}.{rng.randrange(3)}", a, b - a))
            if rng.random() < 0.2:
                out.append(Event(f"serving.twin{depth}", a, b - a))
            if depth < 4:
                fill(a, b, depth + 1, cap)
            t = b
    for k in range(threads):  # some 250 spans a thread
        fill(k * 0.37, 100.0 + k, 0, 250 * (k + 1))
    out.sort(key=lambda e: (e.start, -e.dur))
    return out


@pytest.mark.parametrize("seed,threads", [(1, 1), (2, 1), (3, 1), (4, 2), (5, 3)])
def test_the_stack_nests_as_the_pairwise_comparison_does(seed, threads):
    spans = random_spans(random.Random(seed), threads)
    assert len(spans) > 20
    want = ps.self_intervals(spans)
    got = sp.self_intervals(spans)
    assert got.keys() == want.keys()
    for name in want:
        assert got[name] == pytest.approx(want[name]), name


@pytest.mark.parametrize("gz", [RECORDED_GZ, OLDER_GZ], ids=["with_the_spans", "older"])
def test_recorded_traces_nest_equally_and_add_up(gz, tmp_path, monkeypatch):
    path = unpack(gz, tmp_path)
    spans = ps.program_spans(path, "serving.")
    assert sp.self_intervals(spans) == ps.self_intervals(spans)
    ctx = {"trace": {"chips": 1}}
    monkeypatch.setattr(tr, "find_xplane", lambda d: path)
    ms = {name: read("self_ms_per_step", rx, ctx) for name, rx in PHASES.items()}
    unnamed = read("unnamed_share", UNNAMED, ctx)
    in_wait = read("idle_ms_per_step", IN_WAIT, ctx)
    between = read("idle_ms_per_step", BETWEEN, ctx)
    older = gz == OLDER_GZ
    assert (ms["sched.h2d_ms"] is None) == (ms["sched.account_ms"] is None) == older
    table = ps.idle_by_program_span(path, PARAMS)
    host_ms = table["host_ms_per_step"]
    # the parts and what no leaf names are the host's time on a step
    parts = sum(v for v in ms.values() if v is not None)
    assert parts + unnamed / 100 * host_ms == pytest.approx(host_ms, rel=0.01)
    # the three idle metrics are the slice's idle time
    under_host = table["host_idle_s"] / table["steps"] * 1e3
    assert under_host + in_wait + between == pytest.approx(
        table["idle_total_s"] / table["steps"] * 1e3, rel=1e-6)
    assert in_wait > 0 and between > 0


def test_the_recorded_trace_holds_the_three_spans_under_their_parents(tmp_path):
    path = unpack(RECORDED_GZ, tmp_path)
    spans = ps.program_spans(path, "serving.")
    names = [e.name for e in spans]
    assert names.count("serving.step") == 8 and names.count("serving.decode") == 8
    assert names.count("serving.h2d") == names.count("serving.decode.prepare") + names.count(
        "serving.prefill_chunk.prepare")
    assert names.count("serving.account") == names.count("serving.decode") + names.count(
        "serving.prefill_chunk")

    def parent(e):
        holders = [o for o in spans if o is not e and o.start <= e.start and e.end <= o.end]
        return min(holders, key=lambda o: o.dur).name if holders else None

    parents = {"serving.h2d": r"\.prepare$", "serving.account": r"^serving\.(decode|prefill_chunk)$",
               "serving.schedule": r"^serving\.(step|admit)$"}
    for e in spans:
        if e.name in parents:
            assert re.search(parents[e.name], parent(e) or ""), (e, parent(e))
    # the phases leave little of a step unnamed even at this size (on the chip: PERF.md)
    table = sp.phases(path, PARAMS)
    unnamed = sum(v for k, v in table["self_s"].items() if re.search(UNNAMED, k))
    assert unnamed < 0.25 * table["host_s"]


def snapshot(**families):
    return {name: {"samples": [{"labels": {"kind": k}, "value": v} for k, v in kinds.items()]}
            for name, kinds in families.items()}


def test_counter_share():
    params = {"counter": "rows", "labels": {"kind": "live"}, "scale": 100}
    ctx = {"counters": {"before": snapshot(rows={"live": 10.0, "empty": 30.0}),
                        "after": snapshot(rows={"live": 13.0, "empty": 35.0})}}
    assert counter_share.read(params, ctx) == pytest.approx(100 * 3 / 8)
    assert counter_share.read(params, {"counters": None}) is None
    # a program without the family (an older commit); a phase in which it did not move
    assert counter_share.read(params, {"counters": {"before": {}, "after": {}}}) is None
    still = snapshot(rows={"live": 1.0, "empty": 7.0})
    assert counter_share.read(params, {"counters": {"before": still, "after": still}}) is None
    fresh = {"counters": {"before": {}, "after": snapshot(rows={"live": 11.0, "empty": 5.0})}}
    assert counter_share.read(params, fresh) == pytest.approx(100 * 11 / 16)
    # every row live: the other label was never minted
    full = {"counters": {"before": {}, "after": snapshot(rows={"live": 8.0})}}
    assert counter_share.read(params, full) == pytest.approx(100.0)
