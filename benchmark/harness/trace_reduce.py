"""From a profiler trace (``*.xplane.pb``) to numbers, with nothing but JAX.

``jax.profiler.ProfileData.from_file`` gives planes -> lines -> events with
a start and a duration in nanoseconds. What this file reads from a TPU trace
(looked at by hand, PR 22):

- one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
  event per executed HLO op or fusion, named by its whole instruction text
  (``short_name`` keeps the instruction's own name; a Pallas kernel's is
  that of its jitted wrapper, e.g. ``paged_tkg_decode_attention.11``; a
  ``while`` op and the ops of its body are all there, nested, so per-name
  sums may overlap while the busy UNION does not); its line ``XLA Modules``
  has one event per executed program, ``<jit name>(<fingerprint>)`` — the
  serving step's prefill and decode programs are both ``jit_wrapped(...)``;
- the plane ``/host:CPU`` holds one line per host thread; the driver's
  ``jax.profiler.TraceAnnotation("bench.<span>")`` events are on the line of
  the thread that ran the loop, on the same clock.

Reductions (all inside one window [t0, t1], which is the stretch the
driver's own spans cover, so start-up and shut-down of the profiler are
outside it):

- busy: union of the op intervals of a chip; idle share = 1 - busy/window
- per-name sums and counts of ops and of modules
- idle gaps of chip 0, each attributed to the driver span that covers most
  of it, summed by span name
- collectives: time in collective ops, and the part of it during which no
  other op runs on that chip ("exposed")
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all",
    re.I,
)


CONTAINER = re.compile(r"(while|call|conditional)([.\d]|$)")


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%copy.71 = bf16[28,1057,...] copy(...)``, kilobytes for a loop); the
    instruction's own name, before `` = ``, says which op it is."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceEvents:
    """A trace, read once into plain lists."""

    ops: Dict[str, List[Event]] = field(default_factory=dict)  # device plane -> op events
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)  # driver spans, prefix stripped


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def read(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = TraceEvents()
    for plane in data.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [Event(short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                    (out.ops if line.name == OPS_LINE else out.modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.spans.append(
                            Event(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        )
    out.spans.sort(key=lambda e: e.start)
    return out


def describe(path: str, top: int = 25) -> dict:
    """Planes, lines, event counts and the commonest names: what one reads
    by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total: Dict[str, List[float]] = {}
            n = 0
            for e in line.events:
                n += 1
                acc = total.setdefault(short_name(e.name), [0, 0.0])
                acc[0] += 1
                acc[1] += e.duration_ns * 1e-9
            names = sorted(total.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({"line": line.name, "events": n,
                          "top": [[k, v[0], v[1]] for k, v in names]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"file": path, "planes": planes}


# ---- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` not covered by (merged) ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# ---- reductions -----------------------------------------------------------------


def window_of(spans: Sequence[Event]) -> Optional[Interval]:
    if not spans:
        return None
    return (min(e.start for e in spans), max(e.end for e in spans))


def _ivals(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def busy(tr: TraceEvents, window: Interval) -> Dict[str, float]:
    """{device plane: seconds in which an op ran, inside the window}."""
    return {
        plane: total(clip(union(_ivals(evs)), *window)) for plane, evs in tr.ops.items()
    }


def name_sums(events_by_plane: Dict[str, List[Event]], window: Interval) -> Dict[str, Tuple[int, float]]:
    """{name: (count, seconds)} over all chips, events that START in the
    window. Seconds are summed over chips; divide by the number of chips
    for a per-chip figure."""
    acc: Dict[str, List[float]] = {}
    for evs in events_by_plane.values():
        for e in evs:
            if window[0] <= e.start < window[1]:
                a = acc.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.dur
    return {k: (int(v[0]), v[1]) for k, v in acc.items()}


def matching(sums: Dict[str, Tuple[int, float]], pattern: str) -> Tuple[int, float]:
    """(count, seconds) of the names a regular expression finds."""
    rx = re.compile(pattern)
    n, s = 0, 0.0
    for name, (c, d) in sums.items():
        if rx.search(name):
            n += c
            s += d
    return n, s


def idle_gaps(tr: TraceEvents, window: Interval) -> List[Interval]:
    """The idle stretches of the first chip inside the window."""
    if not tr.ops:
        return [window]
    first = sorted(tr.ops)[0]
    return subtract([window], clip(union(_ivals(tr.ops[first])), *window))


def attribute_gaps(gaps: Sequence[Interval], spans: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds by what the driver was doing: each gap goes, whole, to
    the span that covers most of it (``(none)`` if no span touches it)."""
    acc: Dict[str, float] = {}
    ordered = sorted(spans, key=lambda e: e.start)
    starts = [e.start for e in ordered]
    for gap in gaps:
        best, best_cover = "(none)", 0.0
        i = max(0, bisect.bisect_right(starts, gap[0]) - 1)
        while i < len(ordered) and ordered[i].start < gap[1]:
            cover = overlap(gap, (ordered[i].start, ordered[i].end))
            if cover > best_cover:
                best, best_cover = ordered[i].name, cover
            i += 1
        acc[best] = acc.get(best, 0.0) + (gap[1] - gap[0])
    return acc


def collectives(tr: TraceEvents, window: Interval) -> Dict[str, float]:
    """Per chip, averaged: seconds in collective ops and the part of them
    with no other op running on that chip."""
    if not tr.ops:
        return {"collective_s": 0.0, "exposed_s": 0.0}
    coll_s, exposed_s = 0.0, 0.0
    for evs in tr.ops.values():
        coll = clip(union(_ivals(e for e in evs if COLLECTIVE.search(e.name))), *window)
        rest = clip(union(_ivals(e for e in evs if not COLLECTIVE.search(e.name))), *window)
        coll_s += total(coll)
        exposed_s += total(subtract(coll, rest))
    n = len(tr.ops)
    return {"collective_s": coll_s / n, "exposed_s": exposed_s / n}


def reduce_trace(path: str) -> dict:
    """Everything the trace readers and the result line's ``device`` and
    ``breakdown`` need, in one pass."""
    tr = read(path)
    window = window_of(tr.spans)
    if window is None:
        everything = [e for evs in tr.ops.values() for e in evs]
        if not everything:
            raise ValueError(f"{path}: no device op and no driver span in the trace")
        window = (min(e.start for e in everything), max(e.end for e in everything))
    per_chip = busy(tr, window)
    chips = max(1, len(per_chip))
    op_sums = name_sums(tr.ops, window)
    mod_sums = name_sums(tr.modules, window)
    gaps = idle_gaps(tr, window)
    by_span = attribute_gaps(gaps, tr.spans)
    # a while/call/conditional op is there WITH the ops of its body: leave
    # the containers out of the list of what took the time
    leaves = [kv for kv in op_sums.items() if not CONTAINER.match(kv[0])]
    top_ops = sorted(leaves, key=lambda kv: -kv[1][1])[:10]
    return {
        "window_s": window[1] - window[0],
        "busy_s": sum(per_chip.values()) / chips,
        "chips": len(per_chip),
        "op_sums": op_sums,
        "module_sums": mod_sums,
        "collectives": collectives(tr, window),
        "idle_by_span": by_span,
        "span_counts": {n: sum(1 for e in tr.spans if e.name == n) for n in {e.name for e in tr.spans}},
        "breakdown": {
            "device_ops": [[name, s / chips] for name, (_, s) in top_ops],
            "idle_gaps": [[name, s] for name, s in sorted(by_span.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
