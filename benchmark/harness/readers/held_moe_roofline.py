"""The held experts' stream as a share of a decode dispatch, for an expert
block of TWO matrices an expert under a held share.

    {"reader": "held_moe_roofline",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "experts_counter": "nxdi_moe_experts_hit_total", "labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

What ``moe_roofline.py``'s ``expert_stream_roofline`` reads, by the same rule
(the least time the chip could take to stream the experts the decode
dispatches IN THE TRACE hit, over the device time of the whole decode
module), with its own count: an expert here is TWO matrices (``up`` and
``down``; no gate), and ``nxdi_moe_experts_hit_total`` counts the experts
HELD here that a dispatch streams (64 of 128 x 5 blocks), not the published
count:

    needed bytes = held experts hit a dispatch x dispatches in the trace
                   x 2 x hidden_size x moe_intermediate_size x 2 B

The denominator holds everything a decode dispatch does, so the share cannot
pass 100% while the count is of bytes that must move. A configuration whose
experts have a gate (no ``mlp_hidden_act: relu2``), a program without the
counter (an older commit) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .ssm_roofline import _increase

WEIGHT_BYTES = 2  # the experts are served in bf16


def expert_bytes(attrs: dict) -> float:
    """Bytes of one expert's two matrices (up, down) in one block."""
    return 2.0 * attrs["hidden_size"] * attrs["moe_intermediate_size"] * WEIGHT_BYTES


def read(params: dict, ctx: dict) -> Optional[float]:
    tr, counters, peaks = ctx.get("trace"), ctx.get("counters"), ctx.get("peaks")
    attrs = ctx.get("attrs") or {}
    if (tr is None or not counters or peaks is None or attrs.get("mlp_hidden_act") != "relu2"
            or "moe_intermediate_size" not in attrs):
        return None
    labels = params.get("labels", {})
    experts = _increase(counters, params["experts_counter"], labels)
    dispatches = _increase(counters, params["dispatch_counter"], params.get("dispatch_labels", {}))
    n_decode, module_s = trace_reduce.matching(tr["module_sums"], params["decode_module"])
    if not experts or not dispatches or n_decode == 0 or module_s <= 0:
        return None
    chips = max(1, tr["chips"])
    needed = (experts / dispatches) * (n_decode / chips) * expert_bytes(attrs)
    return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (module_s / chips)
