"""From the driver's log of requests, steps and spans to numbers.

Everything is arithmetic on times the driver took itself with the host's
clock, relative to the opening of the window (t = 0).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """Linear-interpolated percentile (numpy's default), p in [0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def mean(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("mean of no values")
    return sum(values) / len(values)


@dataclass
class RequestRecord:
    """One request as the driver saw it. Times are seconds from the opening
    of the window; a request started before it (a closed loop's first round)
    has ``due_s`` None and is in no latency sample."""

    index: int
    req_id: str
    prompt_len: int
    budget: int
    due_s: Optional[float]
    sent_s: Optional[float] = None  # first add_request attempt
    admitted_s: Optional[float] = None  # add_request said yes
    commits: List[Tuple[float, int]] = field(default_factory=list)  # (t, new tokens)
    finished: bool = False
    failed: Optional[str] = None  # reason, if the request was lost

    @property
    def first_token_s(self) -> Optional[float]:
        return self.commits[0][0] if self.commits else None

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.commits)


#: a request's time per output token is taken only over this many tokens or
#: more inside the window: fewer, and the two ends' half-millisecond of host
#: clock and the step granularity are a visible share of the quotient
TPOT_MIN_TOKENS = 16


def tpot_ms(rec: RequestRecord, window_s: float) -> Optional[float]:
    """(last token - first token) / (tokens after the first), over the
    tokens this request got inside the window; None under TPOT_MIN_TOKENS."""
    inside = [(t, n) for t, n in rec.commits if 0.0 < t <= window_s]
    total = sum(n for _, n in inside)
    if total < TPOT_MIN_TOKENS or len(inside) < 2:
        return None
    gaps = total - inside[0][1]
    return (inside[-1][0] - inside[0][0]) / gaps * 1e3


def summarize(records: Sequence[RequestRecord], window_s: float,
              due_before: float = math.inf) -> Dict[str, float]:
    """Every number the end-to-end metrics and the driver-side layer metrics
    are taken from, by name, over the window [0, window_s] and the requests
    due before ``due_before``. A quantity with no sample is left out."""
    due = [r for r in records if r.due_s is not None and r.due_s < due_before]
    out: Dict[str, float] = {"attempted": len(due)}
    failed = [r for r in due if r.failed or r.first_token_s is None]
    out["failed"] = len(failed)
    ttft = [(r.first_token_s - r.due_s) * 1e3 for r in due if r.first_token_s is not None]
    if ttft:
        out["ttft_n"] = len(ttft)
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p95_ms"] = percentile(ttft, 95)
        out["ttft_mean_ms"] = mean(ttft)
    tpots = [x for x in (tpot_ms(r, window_s) for r in records) if x is not None]
    if tpots:
        out["tpot_n"] = len(tpots)
        out["tpot_p50_ms"] = percentile(tpots, 50)
        out["tpot_p95_ms"] = percentile(tpots, 95)
    # the window opens when a step has returned: what that step committed (t = 0
    # on a clock that stood still meanwhile) is not the window's
    tokens_in = sum(n for r in records for t, n in r.commits if 0.0 < t <= window_s)
    out["out_tokens"] = tokens_in
    out["out_tok_s"] = tokens_in / window_s
    late = [(r.sent_s - r.due_s) * 1e3 for r in due if r.sent_s is not None]
    if late:
        out["late_p95_ms"] = percentile(late, 95)
        out["late_mean_ms"] = mean(late)
    wait = [(r.admitted_s - r.due_s) * 1e3 for r in due if r.admitted_s is not None]
    if wait:
        out["queue_wait_mean_ms"] = mean(wait)
    out["finished"] = sum(1 for r in records if r.finished)
    return out


def span_stats(spans: Sequence[Tuple[str, float, float]], window_s: float) -> Dict[str, Dict[str, float]]:
    """{span name: {count, total_s, mean_ms}} over spans that START inside
    the window."""
    acc: Dict[str, List[float]] = {}
    for name, t0, t1 in spans:
        if 0.0 <= t0 <= window_s:
            acc.setdefault(name, []).append(t1 - t0)
    return {
        name: {"count": len(d), "total_s": sum(d), "mean_ms": mean(d) * 1e3}
        for name, d in acc.items()
    }
