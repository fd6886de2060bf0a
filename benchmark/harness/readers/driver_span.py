"""Metrics the driver takes itself, with the host's clock, from outside the
program: a statistic of a named span, of the request log, or of a per-step
sample.

    {"reader": "driver_span", "span": "step", "stat": "mean_ms"}
    {"reader": "driver_span", "summary": "late_p95_ms"}
    {"reader": "driver_span", "sample": "decoding_rows", "stat": "mean"}
"""

from typing import Optional


def read(params: dict, ctx: dict) -> Optional[float]:
    if "span" in params:
        entry = ctx["spans"].get(params["span"])
        return None if entry is None else entry.get(params.get("stat", "mean_ms"))
    if "summary" in params:
        return ctx["summary"].get(params["summary"])
    if "sample" in params:
        values = [v for _, v in ctx["samples"].get(params["sample"], [])]
        if not values:
            return None
        if params.get("stat", "mean") == "max":
            return max(values)
        return sum(values) / len(values)
    raise ValueError(f"driver_span reader needs span, summary or sample: {params}")
