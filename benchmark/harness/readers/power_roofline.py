"""The decode-step power-retention state update's share of its memory
roofline, under the ``brumby`` keys.

    {"reader": "power_roofline", "pattern": "^power_state_update",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "rows_counter": "nxdi_power_rows_advanced_total", "rows_labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

What ``kda_roofline.py`` reads, by the same rule (needed bytes of the decode
dispatches the trace holds / peak HBM bandwidth over the time of the ops
``pattern`` names; rows a dispatch from the program's counter over the traced
phase), with its own count from THIS family's published keys:

    needed bytes = rows advanced x layers x 2 x (G x D x d + G x D) x 4
    G = num_key_value_heads;  d = head_dim;  D = d (d + 1) / 2, the EXACT symmetric square

The count is of the work, whatever implements it: the float32 state a KV head
and its normaliser read once and written once a row a layer, at the size the
mathematics needs (8256 x 128 at d = 128). A layout that holds more (the
program's tiled one: 8704) moves more and lowers its own share; what a kernel
moves besides (the row's vectors, its output) is not needed work either. A
configuration of another family, a program without the counter or without
the kernel in its decode program (an older commit) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .ssm_roofline import STATE_BYTES, _increase


def state_bytes_per_row(attrs: dict) -> float:
    """Bytes one row's state takes over all layers, once."""
    heads = attrs["num_attention_heads"]
    d = attrs.get("head_dim") or attrs["hidden_size"] // heads
    G = attrs.get("num_key_value_heads", heads)
    D = d * (d + 1) // 2
    return float(attrs["num_hidden_layers"] * (G * D * d + G * D) * STATE_BYTES)


def read(params: dict, ctx: dict) -> Optional[float]:
    tr, counters, peaks = ctx.get("trace"), ctx.get("counters"), ctx.get("peaks")
    attrs = ctx.get("attrs") or {}
    if tr is None or not counters or peaks is None or attrs.get("model_type") != "brumby":
        return None
    n_ops, kernel_s = trace_reduce.matching(tr["op_sums"], params["pattern"])
    n_decode, _ = trace_reduce.matching(tr["module_sums"], params["decode_module"])
    if n_ops == 0 or kernel_s <= 0 or n_decode == 0:
        return None  # the kernel is not in the compiled decode program
    rows = _increase(counters, params["rows_counter"], params.get("rows_labels", {}))
    dispatches = _increase(counters, params["dispatch_counter"], params.get("dispatch_labels", {}))
    if not rows or not dispatches:
        return None
    chips = max(1, tr["chips"])
    needed = (rows / dispatches) * (n_decode / chips) * 2.0 * state_bytes_per_row(attrs)
    return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (kernel_s / chips)
