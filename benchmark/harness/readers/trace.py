"""Metrics reduced from the profiler's trace of the traced slice.

    {"reader": "trace", "kind": "idle_share"}
    {"reader": "trace", "kind": "exposed_collective_share"}
    {"reader": "trace", "kind": "module_ms_per_dispatch", "pattern": "<regex>"}
    {"reader": "trace", "kind": "module_ms_per_ktok", "pattern": "<regex>",
     "sample": "prefill_tokens"}
    {"reader": "trace", "kind": "kernel_roofline", "pattern": "<regex>",
     "work": "<function in roofline.WORK>", "bound": "hbm" | "bf16",
     "program": "decode" | "prefill"}

``pattern`` is searched in the names of the ``XLA Ops`` events (kernels) or
the ``XLA Modules`` events (programs). A pattern that finds nothing returns
None: the metric waits until the trace can tell the thing apart by name.
"""

from typing import Optional

from .. import roofline, trace_reduce


def _slice_sum(ctx: dict, sample: str) -> Optional[float]:
    """Sum of a per-step sample over the steps of the traced slice: the
    steps the driver started from the turn in which the profiler started
    to the turn before the one in which it stopped. The device was drained
    at both ends (run.Profiler), so the trace holds the device work of
    exactly these steps; their number must be the number of the driver's
    ``step`` spans in the trace, or numerator and denominator of a share
    would cover different work."""
    if ctx.get("slice") is None:
        return None
    t0, t1 = ctx["slice"]
    values = [v for t, v in ctx["samples"].get(sample, []) if t0 <= t < t1]
    traced = ctx["trace"]["span_counts"].get("step", 0)
    if len(values) != traced:
        raise ValueError(
            f"{sample}: {len(values)} steps sampled in the slice, {traced} step spans in the trace"
        )
    return sum(values) if values else None


def read(params: dict, ctx: dict) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None:
        return None
    kind = params["kind"]
    chips = max(1, tr["chips"])
    if kind == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if kind == "exposed_collective_share":
        if tr["collectives"]["collective_s"] <= 0:
            return None
        return 100.0 * tr["collectives"]["exposed_s"] / tr["window_s"]
    if kind in ("module_ms_per_dispatch", "module_ms_per_ktok"):
        n, s = trace_reduce.matching(tr["module_sums"], params["pattern"])
        if n == 0:
            return None
        if kind == "module_ms_per_dispatch":
            return s / n * 1e3
        tokens = _slice_sum(ctx, params["sample"])
        return None if not tokens else (s / chips) * 1e3 / (tokens / 1e3)
    if kind == "kernel_roofline":
        if ctx.get("peaks") is None:
            return None
        if not ctx.get("kernels", {}).get(params["program"]):
            return None  # the kernel is not in the cell's compiled program
        n, s = trace_reduce.matching(tr["op_sums"], params["pattern"])
        if n == 0 or s <= 0:
            return None
        samples = {}
        for name in ("live_kv_tokens", "prefill_qk_pairs"):
            samples[name] = _slice_sum(ctx, name) or 0.0
        work = roofline.WORK[params["work"]](ctx["attrs"], ctx["chips"], samples)
        if work <= 0:
            return None
        least_s = work / ctx["peaks"][roofline.PEAK_OF[params["bound"]]]
        return 100.0 * least_s / (s / chips)
    raise ValueError(f"unknown trace reader kind {kind!r}")
