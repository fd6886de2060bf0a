"""Two readings of an expert layer in the decode program, from the program's
own ``nxdi_moe_*`` counters (each taken as its increase over the traced
phase, as ``ssm_roofline.py`` takes its counters) and the trace.

    {"reader": "moe_roofline", "kind": "rows_per_expert",
     "rows_counter": "nxdi_moe_rows_routed_total",
     "experts_counter": "nxdi_moe_experts_hit_total", "labels": {"program": "decode"}}

token rows routed (rows x expert layers x experts per token) per expert whose
weights the dispatches streamed: how many rows share one expert's stream.

    {"reader": "moe_roofline", "kind": "expert_stream_roofline",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "experts_counter": "nxdi_moe_experts_hit_total", "labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

the least time the chip could take to stream the experts the decode
dispatches IN THE TRACE hit, over the device time of the whole decode
module:

    needed bytes = experts hit a dispatch x dispatches in the trace x 3 x hidden x expert width x 2 B

``experts hit a dispatch`` is the counter's increase over the phase per
decode dispatch of the phase (``dispatch_counter``); the dispatches in the
trace are the events of ``decode_module`` (the registry is read at the ends
of the phase, which settles before the profiled slice starts). The
denominator is the MODULE's time, not one kernel's: the PR that brought the
layer added no kernel, the experts' matrices are XLA's own products, and
what a dispatch does besides streaming them (attention, the router, the
head, sampling) lowers the share, as it should. Memory-bound: at 48 rows a
decode step does 2 x 48 operations per expert weight byte pair, far under
the chip's ~240 operations a byte.

This file keeps its own count of the needed bytes (``expert_bytes``, from the
configuration's published keys): ``harness/roofline.py`` is the accepted
benchmark's. A program that has no such counter (an older commit, a model
without routed experts) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .ssm_roofline import _increase  # a labelled counter's increase over the traced phase

WEIGHT_BYTES = 2  # the experts are served in bf16


def expert_bytes(attrs: dict) -> float:
    """Bytes of one expert's three matrices (gate, up, down) in one layer."""
    return 3.0 * attrs["hidden_size"] * attrs["moe_intermediate_size"] * WEIGHT_BYTES


def read(params: dict, ctx: dict) -> Optional[float]:
    counters = ctx.get("counters")
    if not counters:
        return None
    labels = params.get("labels", {})
    experts = _increase(counters, params["experts_counter"], labels)
    if not experts:
        return None
    if params["kind"] == "rows_per_expert":
        rows = _increase(counters, params["rows_counter"], labels)
        return None if rows is None else rows / experts
    tr, peaks, attrs = ctx.get("trace"), ctx.get("peaks"), ctx.get("attrs") or {}
    if tr is None or peaks is None or "moe_intermediate_size" not in attrs:
        return None
    n_decode, module_s = trace_reduce.matching(tr["module_sums"], params["decode_module"])
    dispatches = _increase(counters, params["dispatch_counter"], params.get("dispatch_labels", {}))
    if n_decode == 0 or module_s <= 0 or not dispatches:
        return None
    chips = max(1, tr["chips"])
    needed = (experts / dispatches) * (n_decode / chips) * expert_bytes(attrs)
    return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (module_s / chips)
