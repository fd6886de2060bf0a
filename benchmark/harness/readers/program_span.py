"""Metrics from the PROGRAM's own spans in the profiler trace of the traced
slice: the ``serving.*`` ``TraceAnnotation``s that ``runtime/serving.py``
records around a scheduler step and its parts, on the device trace's clock.

    {"reader": "program_span", "kind": "host_ms_per_step"}
    {"reader": "program_span", "kind": "host_idle_ms_per_step"}

Both take ``step`` (the span that is one scheduler step), ``prefix`` (what
the program's spans start with), ``wait`` (a regular expression for the
spans in which the host only waits for the device) and, optionally,
``dispatch`` + ``modules``: regular expressions for the spans around a
dispatch of a step program and for the names of those programs under
``XLA Modules``. Where they are given, their counts over the whole trace
must agree, or host spans and device events do not cover the same steps and
the reader raises.

- ``host_ms_per_step``: mean over the step spans of the window of their
  duration minus the waits inside them — the host's own work on a step's
  critical path.
- ``host_idle_ms_per_step``: the idle time of chip 0 that falls under a
  program span other than a wait, per step — what hiding the host behind
  the device could still win.

The reader finds the trace itself, in the directory ``run.py`` writes
(``<checkout>/.bench_cache/trace``), and reads the device side through
``trace_reduce``; a trace without the program's spans (an older commit, a
session whose telemetry was off) gives None.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

from .. import catalog, trace_reduce
from ..trace_reduce import Event, Interval

TRACE_DIR = os.path.join(catalog.REPO_DIR, ".bench_cache", "trace")


def program_spans(path: str, prefix: str) -> List[Event]:
    """Every host event whose name starts with ``prefix``, by start time."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events if e.name.startswith(prefix)]
    out.sort(key=lambda e: (e.start, -e.dur))
    return out


def _ivals(events) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two merged interval lists."""
    return trace_reduce.subtract(a, trace_reduce.subtract(a, b))


def self_intervals(spans: List[Event]) -> Dict[str, List[Interval]]:
    """{span name: the parts of its spans that no span nested in them
    covers}, merged. Spans of one thread nest; a span is nested in another
    when it lies inside it and is not the same event."""
    out: Dict[str, List[Interval]] = {}
    for i, e in enumerate(spans):
        inner = [(c.start, c.end) for j, c in enumerate(spans)
                 if j != i and e.start <= c.start and c.end <= e.end
                 and (c.dur < e.dur or j > i)]
        own = trace_reduce.subtract([(e.start, e.end)], trace_reduce.union(inner))
        out.setdefault(e.name, []).extend(own)
    return {name: trace_reduce.union(iv) for name, iv in out.items()}


def check_dispatches(tr, spans: List[Event], params: dict) -> Optional[Tuple[int, int]]:
    """(dispatch spans, step-program executions on the first chip) over the
    whole trace; raises when they differ."""
    if "dispatch" not in params or "modules" not in params or not tr.modules:
        return None
    d_rx, m_rx = re.compile(params["dispatch"]), re.compile(params["modules"])
    dispatched = sum(1 for e in spans if d_rx.search(e.name))
    ran = sum(1 for e in tr.modules[sorted(tr.modules)[0]] if m_rx.search(e.name))
    if dispatched != ran:
        raise ValueError(
            f"program_span: {dispatched} spans match {params['dispatch']!r} but {ran} "
            f"XLA Modules events match {params['modules']!r}: the host spans and the "
            f"device events of this trace do not cover the same steps"
        )
    return dispatched, ran


def idle_by_program_span(path: str, params: Optional[dict] = None) -> Optional[dict]:
    """The idle time of chip 0 inside the trace's window by the innermost
    program span it falls under (``(none)``: under no program span), the
    steps of the window and the host's own time on them; None when the
    trace holds no step span of the program."""
    params = params or {}
    prefix = params.get("prefix", "serving.")
    step = params.get("step", "serving.step")
    wait = re.compile(params.get("wait", r"fetch_wait$"))
    spans = program_spans(path, prefix)
    tr = trace_reduce.read(path)
    window = trace_reduce.window_of(tr.spans) or trace_reduce.window_of(spans)
    if window is None:
        return None
    steps = [e for e in spans if e.name == step and window[0] <= e.start and e.end <= window[1]]
    if not steps:
        return None
    check_dispatches(tr, spans, params)
    waits = trace_reduce.union(_ivals(e for e in spans if wait.search(e.name)))
    host_s = sum(e.dur - trace_reduce.total(trace_reduce.clip(waits, e.start, e.end))
                 for e in steps)
    gaps = trace_reduce.idle_gaps(tr, window)
    own = self_intervals(spans)
    idle = {name: trace_reduce.total(_intersect(gaps, iv)) for name, iv in own.items()}
    covered = trace_reduce.union(_ivals(spans))
    idle["(none)"] = trace_reduce.total(trace_reduce.subtract(gaps, covered))
    return {
        "window_s": window[1] - window[0],
        "steps": len(steps),
        "host_ms_per_step": host_s / len(steps) * 1e3,
        "idle_total_s": trace_reduce.total(gaps),
        "idle_s": {k: v for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "host_idle_s": sum(v for k, v in idle.items() if k != "(none)" and not wait.search(k)),
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
    tables = ctx.setdefault("program_span_tables", {})
    if key not in tables:
        tables[key] = idle_by_program_span(path, params)
    table = tables[key]
    if table is None:
        return None
    kind = params["kind"]
    if kind == "host_ms_per_step":
        return table["host_ms_per_step"]
    if kind == "host_idle_ms_per_step":
        return table["host_idle_s"] / table["steps"] * 1e3
    raise ValueError(f"unknown program_span reader kind {kind!r}")
