"""The share of a labelled counter family that one set of labels holds, each
side taken as its increase over the traced phase (the registry's snapshot
before and after), times ``scale``:

    {"reader": "counter_share", "counter": "nxdi_chunk_rows_total",
     "labels": {"kind": "live"}, "scale": 100}

``counter_ratio`` sums a family over ALL its labels, so it cannot tell one
label from the family; this is that reader for one family whose labels
split a whole. A program without the family (an older commit), or a phase
in which the family did not move, gives None.
"""

from typing import Optional

from .counter import total


def read(params: dict, ctx: dict) -> Optional[float]:
    counters = ctx.get("counters")
    if not counters:
        return None
    name = params["counter"]
    grown = []
    for labels in (params["labels"], {}):
        after = total(counters["after"], name, labels)
        if after is None:
            return None
        grown.append(after - (total(counters["before"], name, labels) or 0.0))
    part, whole = grown
    if not whole:
        return None
    return float(params.get("scale", 1.0)) * part / whole
