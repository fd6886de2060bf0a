"""The host's share of a scheduler step PHASE BY PHASE, and the device's
idle time by the program span it falls under: metrics from the ``serving.*``
``TraceAnnotation``s of the traced slice (``runtime/serving.py``), one level
below ``program_span``'s two numbers.

    {"reader": "span_phase", "kind": "self_ms_per_step", "spans": "<regex>"}
    {"reader": "span_phase", "kind": "idle_ms_per_step", "spans": "<regex>"}
    {"reader": "span_phase", "kind": "unnamed_share", "spans": "<regex>"}

All take ``program_span``'s parameters besides (``step``, ``prefix``,
``wait``, and ``dispatch`` + ``modules``, whose counts over the whole trace
must agree). A span's SELF time is the part of it that no span nested in it
covers, so the self times of all the spans inside a step add up to the step.

- ``self_ms_per_step``: the self time of the spans whose name ``spans``
  finds, inside the ``step`` spans of the slice, per step. None when the
  trace holds no such span (a commit older than the span).
- ``idle_ms_per_step``: the idle time of chip 0 in the slice that falls
  under the self time of those spans, per step; ``spans`` may find
  ``(none)``, the idle time under no program span at all (between two
  steps). Idle under the waits, under the other program spans
  (``program_span``'s ``host_idle_ms_per_step``) and under ``(none)`` add up
  to the slice's idle time.
- ``unnamed_share``: the self time of the spans ``spans`` finds (the
  containers: the step and its passes) as a share of the steps' host time
  (their duration minus the waits inside them, ``program_span``'s
  ``host_ms_per_step``): what of a step's host time no leaf span names.

``program_span.self_intervals`` compares every span with every other; a
slice holds some ten thousand. Here the spans are nested with a stack in one
pass over them in start order; ``selftest/test_span_phase.py`` holds the two
equal on a recorded trace.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

from .. import trace_reduce
from ..trace_reduce import Event, Interval
from .program_span import TRACE_DIR, _intersect, check_dispatches, program_spans

NONE = "(none)"


def self_intervals(spans: List[Event]) -> Dict[str, List[Interval]]:
    """``program_span.self_intervals`` in one pass: ``spans`` in
    ``program_spans``' order (by start, the longer first). Only a span
    that is still open can hold the next one, so each span is laid under
    the open spans that hold it whole (a handful: the depth of the tree);
    a span's self time is what the spans under it leave of it."""
    open_: List[tuple] = []  # (span, the intervals nested in it), outermost first
    done: List[tuple] = []
    for e in spans:
        while open_ and open_[-1][0].end <= e.start:
            done.append(open_.pop())
        for holder, nested in open_:
            if holder.start <= e.start and e.end <= holder.end:
                nested.append((e.start, e.end))
        open_.append((e, []))
    out: Dict[str, List[Interval]] = {}
    for e, nested in done + open_:
        own = trace_reduce.subtract([(e.start, e.end)], trace_reduce.union(nested))
        out.setdefault(e.name, []).extend(own)
    return {name: trace_reduce.union(iv) for name, iv in out.items()}


def phases(path: str, params: Optional[dict] = None) -> Optional[dict]:
    """Per span name the self time inside the slice's steps and the idle
    time of chip 0 under it (``(none)``: under no program span), with the
    steps, their host time and the slice's idle time; None when the trace
    holds no step span of the program."""
    params = params or {}
    step = params.get("step", "serving.step")
    wait = re.compile(params.get("wait", r"fetch_wait$"))
    spans = program_spans(path, params.get("prefix", "serving."))
    tr = trace_reduce.read(path)
    window = trace_reduce.window_of(tr.spans) or trace_reduce.window_of(spans)
    if window is None:
        return None
    steps = [e for e in spans if e.name == step and window[0] <= e.start and e.end <= window[1]]
    if not steps:
        return None
    check_dispatches(tr, spans, params)
    own = self_intervals(spans)
    inside = trace_reduce.union((e.start, e.end) for e in steps)
    gaps = trace_reduce.idle_gaps(tr, window)
    idle = {name: trace_reduce.total(_intersect(gaps, iv)) for name, iv in own.items()}
    covered = trace_reduce.union((e.start, e.end) for e in spans)
    idle[NONE] = trace_reduce.total(trace_reduce.subtract(gaps, covered))
    self_s = {name: trace_reduce.total(_intersect(iv, inside)) for name, iv in own.items()}
    waits = trace_reduce.union((e.start, e.end) for e in spans if wait.search(e.name))
    return {
        "steps": len(steps),
        # as program_span's host_ms_per_step has it: the steps less their waits
        "host_s": sum(e.dur - trace_reduce.total(trace_reduce.clip(waits, e.start, e.end))
                      for e in steps),
        "self_s": self_s,
        "idle_s": idle,
        "idle_total_s": trace_reduce.total(gaps),
    }


def read(params: dict, ctx: dict) -> Optional[float]:
    if ctx.get("trace") is None:
        return None
    try:
        path = trace_reduce.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    # the metrics of one run that read the same spans share one reduction
    key = tuple(params.get(k) for k in ("step", "prefix", "wait", "dispatch", "modules"))
    tables = ctx.setdefault("span_phase_tables", {})
    if key not in tables:
        tables[key] = phases(path, params)
    table = tables[key]
    if table is None:
        return None
    rx = re.compile(params["spans"])
    kind = params["kind"]
    if kind == "idle_ms_per_step":
        found = [v for name, v in table["idle_s"].items() if rx.search(name)]
        return sum(found) / table["steps"] * 1e3 if found else None
    found = [v for name, v in table["self_s"].items() if rx.search(name)]
    if not found:
        return None
    if kind == "self_ms_per_step":
        return sum(found) / table["steps"] * 1e3
    if kind == "unnamed_share":
        return 100.0 * sum(found) / table["host_s"] if table["host_s"] > 0 else None
    raise ValueError(f"unknown span_phase reader kind {kind!r}")
