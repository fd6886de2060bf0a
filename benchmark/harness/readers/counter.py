"""A counter of the program's own telemetry registry, as its increase over
the window (the registry's snapshot before and after).

    {"reader": "counter", "counter": "nxdi_requests_preempted_total",
     "labels": {"kind": "decode"}}        # labels optional: a subset to match
"""

from typing import Optional


def total(snapshot: dict, name: str, labels: dict) -> Optional[float]:
    family = snapshot.get(name)
    if family is None:
        return None
    return sum(
        s["value"] for s in family["samples"]
        if all(s["labels"].get(k) == v for k, v in labels.items())
    )


def read(params: dict, ctx: dict) -> Optional[float]:
    counters = ctx.get("counters")
    if not counters:
        return None
    labels = params.get("labels", {})
    after = total(counters["after"], params["counter"], labels)
    if after is None:
        return None
    return after - (total(counters["before"], params["counter"], labels) or 0.0)
