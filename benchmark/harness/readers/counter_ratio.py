"""A ratio of counters of the program's own telemetry registry, each taken
as its increase over the traced phase (the registry's snapshot before and
after), times ``scale``:

    {"reader": "counter_ratio",
     "numerator": ["nxdi_prefill_padded_tokens_total"],
     "denominator": ["nxdi_prefill_padded_tokens_total", "nxdi_prefill_real_tokens_total"],
     "scale": 100}

Several names are summed. A program that has none of these counters (an
older commit), or a phase in which the denominator did not move, gives None.
"""

from typing import List, Optional

from .counter import total


def _increase(counters: dict, names: List[str]) -> Optional[float]:
    grown = 0.0
    for name in names:
        after = total(counters["after"], name, {})
        if after is None:
            return None
        grown += after - (total(counters["before"], name, {}) or 0.0)
    return grown


def read(params: dict, ctx: dict) -> Optional[float]:
    counters = ctx.get("counters")
    if not counters:
        return None
    num = _increase(counters, params["numerator"])
    den = _increase(counters, params["denominator"])
    if num is None or not den:
        return None
    return float(params.get("scale", 1.0)) * num / den
