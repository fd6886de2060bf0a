"""The two readers of what the PROGRAM records: ``program_span`` (the
``serving.*`` spans in the profiler's trace) and ``counter_ratio`` (its
registry). Interval arithmetic on events with known answers, and the small
trace recorded on the chip beside this file (``data/serving_small.xplane.pb.gz``,
made by ``record_serving_trace.py`` and gzipped: eight split serving steps
of the tiny rehearsal preset, an admission and its chunk passes among
them)."""

import gzip
import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.harness.readers import counter_ratio, program_span as ps
from benchmark.harness.trace_reduce import Event, TraceEvents

RECORDED_GZ = os.path.join(os.path.dirname(__file__), "data", "serving_small.xplane.pb.gz")
PARAMS = {"step": "serving.step", "prefix": "serving.", "wait": "fetch_wait$",
          "dispatch": r"\.dispatch$", "modules": r"^jit_token_generation_model_(decode|chunk)\("}


def synthetic():
    """Two steps on one chip. Step 1 [0,4]: housekeeping [0,.5], decode [.5,1.5] (prepare
    [.5,1], dispatch [1,1.5]), fetch_wait [1.5,3.5], commit [3.5,4]. Step 2 [5,9]: a chunk pass
    [5,8] (prepare [5,6], dispatch [6,6.5], fetch_wait [6.5,8]), commit [8,9]. The chip is busy
    [1.25,3.5] and [6.25,7.5]."""
    spans = [
        Event("serving.step", 0.0, 4.0), Event("serving.housekeeping", 0.0, 0.5),
        Event("serving.decode", 0.5, 1.0), Event("serving.decode.prepare", 0.5, 0.5),
        Event("serving.decode.dispatch", 1.0, 0.5), Event("serving.fetch_wait", 1.5, 2.0),
        Event("serving.commit", 3.5, 0.5),
        Event("serving.step", 5.0, 4.0), Event("serving.prefill_chunk", 5.0, 3.0),
        Event("serving.prefill_chunk.prepare", 5.0, 1.0),
        Event("serving.prefill_chunk.dispatch", 6.0, 0.5),
        Event("serving.prefill_chunk.fetch_wait", 6.5, 1.5), Event("serving.commit", 8.0, 1.0),
    ]
    spans.sort(key=lambda e: (e.start, -e.dur))
    trace = TraceEvents(
        ops={"/device:TPU:0": [Event("fusion.1", 1.25, 2.25), Event("fusion.2", 6.25, 1.25)]},
        modules={"/device:TPU:0": [Event("jit_token_generation_model_decode(1)", 1.25, 2.25),
                                   Event("jit_token_generation_model_chunk(2)", 6.25, 1.25),
                                   Event("jit__where(3)", 0.9, 0.01)]},
        spans=[Event("step", 0.0, 4.0), Event("step", 5.0, 4.0)],
    )
    return spans, trace


@pytest.fixture
def fake(monkeypatch):
    spans, trace = synthetic()
    monkeypatch.setattr(ps, "program_spans", lambda path, prefix: list(spans))
    monkeypatch.setattr(tr, "read", lambda path: trace)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "fake.xplane.pb")
    return spans, trace


def test_self_intervals_leave_out_what_nested_spans_cover():
    spans, _ = synthetic()
    own = ps.self_intervals(spans)
    assert own["serving.step"] == []  # its children cover both steps
    assert own["serving.decode"] == []  # prepare + dispatch cover it
    assert own["serving.fetch_wait"] == [(1.5, 3.5)]
    assert own["serving.commit"] == [(3.5, 4.0), (8.0, 9.0)]
    assert own["serving.prefill_chunk"] == []


def test_host_time_and_idle_under_host_spans(fake):
    table = ps.idle_by_program_span("x", PARAMS)
    assert table["steps"] == 2 and table["window_s"] == pytest.approx(9.0)
    # step 1: 4 - 2 of waiting; step 2: 4 - 1.5
    assert table["host_ms_per_step"] == pytest.approx((2.0 + 2.5) / 2 * 1e3)
    # idle: [0,1.25] [3.5,6.25] [7.5,9]; by innermost span
    idle = table["idle_s"]
    assert idle["serving.housekeeping"] == pytest.approx(0.5)
    assert idle["serving.decode.prepare"] == pytest.approx(0.5)
    assert idle["serving.decode.dispatch"] == pytest.approx(0.25)
    assert idle["serving.commit"] == pytest.approx(0.5 + 1.0)
    assert idle["(none)"] == pytest.approx(1.0)  # [4,5] between the steps
    assert idle["serving.prefill_chunk.prepare"] == pytest.approx(1.0)
    assert idle["serving.prefill_chunk.dispatch"] == pytest.approx(0.25)
    assert idle["serving.prefill_chunk.fetch_wait"] == pytest.approx(0.5)  # [7.5,8]
    assert sum(idle.values()) == pytest.approx(table["idle_total_s"]) == pytest.approx(5.5)
    assert table["host_idle_s"] == pytest.approx(5.5 - 1.0 - 0.5)
    ctx = {"trace": {"chips": 1}}
    assert ps.read({**PARAMS, "kind": "host_ms_per_step"}, ctx) == pytest.approx(2250.0)
    assert ps.read({**PARAMS, "kind": "host_idle_ms_per_step"}, ctx) == pytest.approx(2000.0)
    with pytest.raises(ValueError):
        ps.read({**PARAMS, "kind": "nope"}, ctx)


def test_span_and_module_counts_that_differ_fail_loudly(fake):
    spans, trace = fake
    trace.modules["/device:TPU:0"].append(Event("jit_token_generation_model_decode(1)", 8.0, 0.5))
    with pytest.raises(ValueError, match="2 spans match .* but 3 XLA Modules"):
        ps.idle_by_program_span("x", PARAMS)
    only_decode = {**PARAMS, "modules": r"^jit_token_generation_model_decode\("}
    trace.modules["/device:TPU:0"].pop()
    with pytest.raises(ValueError, match="2 spans match .* but 1 XLA Modules"):
        ps.idle_by_program_span("x", only_decode)
    unchecked = {k: v for k, v in PARAMS.items() if k not in ("dispatch", "modules")}
    assert ps.idle_by_program_span("x", unchecked)["steps"] == 2


def test_a_trace_without_the_programs_spans_gives_nothing(monkeypatch, fake):
    monkeypatch.setattr(ps, "program_spans", lambda path, prefix: [])
    assert ps.idle_by_program_span("x", PARAMS) is None
    assert ps.read({**PARAMS, "kind": "host_ms_per_step"}, {"trace": {"chips": 1}}) is None
    assert ps.read({**PARAMS, "kind": "host_ms_per_step"}, {"trace": None}) is None

    def missing(d):
        raise FileNotFoundError(d)

    monkeypatch.setattr(tr, "find_xplane", missing)
    assert ps.read({**PARAMS, "kind": "host_ms_per_step"}, {"trace": {"chips": 1}}) is None


def snapshot(**values):
    return {name: {"samples": [{"labels": {}, "value": v}]} for name, v in values.items()}


def test_counter_ratio():
    params = {"numerator": ["pad"], "denominator": ["pad", "real"], "scale": 100}
    ctx = {"counters": {"before": snapshot(pad=100.0, real=50.0),
                        "after": snapshot(pad=940.0, real=210.0)}}
    assert counter_ratio.read(params, ctx) == pytest.approx(100 * 840 / 1000)
    assert counter_ratio.read(params, {"counters": None}) is None
    # a program without the counters (an older commit); a phase in which nothing was counted
    assert counter_ratio.read(params, {"counters": {"before": {}, "after": snapshot(pad=1.0)}}) is None
    still = {"counters": {"before": snapshot(pad=5.0, real=5.0), "after": snapshot(pad=5.0, real=5.0)}}
    assert counter_ratio.read(params, still) is None
    fresh = {"counters": {"before": {}, "after": snapshot(pad=30.0, real=10.0)}}
    assert counter_ratio.read(params, fresh) == pytest.approx(75.0)


def test_recorded_serving_trace_from_the_chip(tmp_path):
    RECORDED = str(tmp_path / "serving_small.xplane.pb")
    with gzip.open(RECORDED_GZ, "rb") as src, open(RECORDED, "wb") as dst:
        dst.write(src.read())
    spans = ps.program_spans(RECORDED, "serving.")
    names = [e.name for e in spans]
    assert names.count("serving.step") == 8 and names.count("serving.admit") == 1
    assert names.count("serving.prefill_chunk") >= 2 and names.count("serving.decode") == 8
    t = tr.read(RECORDED)
    assert len(t.ops) == 1 and [e.name for e in t.spans].count("step") == 8
    # the step programs run under their two names, and nothing is jit_wrapped
    modules = {e.name.split("(")[0] for e in next(iter(t.modules.values()))}
    assert {"jit_token_generation_model_decode", "jit_token_generation_model_chunk"} <= modules
    assert "jit_wrapped" not in modules
    # host spans on the device's clock, to within a millisecond (in this trace
    # the chip's clock runs 0.5-0.7 ms ahead of the host's): every step program
    # starts where its dispatch span does
    table = ps.idle_by_program_span(RECORDED, PARAMS)
    assert table["steps"] == 8
    dispatched, ran = ps.check_dispatches(t, spans, PARAMS)
    assert dispatched == ran == names.count("serving.decode") + names.count("serving.prefill_chunk")
    starts = sorted(e.start for e in spans if e.name.endswith(".dispatch"))
    ran_at = sorted(e.start for e in next(iter(t.modules.values()))
                    if e.name.startswith("jit_token_generation_model_"))
    assert all(d - 1e-3 <= m <= d + 2e-3 for d, m in zip(starts, ran_at))
    assert 0 < table["host_ms_per_step"] < table["window_s"] / 8 * 1e3
    assert sum(table["idle_s"].values()) == pytest.approx(table["idle_total_s"])
    assert 0 <= table["host_idle_s"] <= table["idle_total_s"]
    with pytest.raises(ValueError, match="XLA Modules"):
        ps.idle_by_program_span(RECORDED, {**PARAMS, "modules": r"^jit_token_generation_model_decode\("})
