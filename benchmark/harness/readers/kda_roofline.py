"""The decode-step delta-rule (KDA) state update's share of its memory
roofline, under the ``kimi_linear`` keys.

    {"reader": "kda_roofline", "pattern": "^kda_state_update",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "rows_counter": "nxdi_kda_rows_advanced_total", "rows_labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

What ``ssm_roofline.py`` reads, by the same rule (needed bytes of the decode
dispatches the trace holds / peak HBM bandwidth over the time of the ops
``pattern`` names; rows a dispatch from the program's counter over the traced
phase), with its own count from THIS family's published keys:

    needed bytes = rows advanced x KDA layers x 2 x (num_heads x head_dim x head_dim x 4
                   + (short_conv_kernel_size - 1) x 3 x num_heads x head_dim x 2)
    KDA layers = len(linear_attn_config.kda_layers);  the sizes linear_attn_config's

The count is of the work, whatever implements it: the float32 matrix state a
head read and written once and the conv tail over [q | k | v] read and
written once a row a layer. What a kernel moves besides (its packed key-side
operand, v, its output) is not needed work and lowers the share. A
configuration without the key (any other family), a program without the
counter or without the kernel in its decode program (an older commit) gives
None.
"""

from typing import Optional

from .. import trace_reduce
from .ssm_roofline import CONV_BYTES, STATE_BYTES, _increase


def state_bytes_per_row(attrs: dict) -> float:
    """Bytes one row's state takes over all KDA layers, once."""
    lin = attrs["linear_attn_config"]
    heads, dim = lin["num_heads"], lin["head_dim"]
    state = heads * dim * dim * STATE_BYTES
    conv = (lin.get("short_conv_kernel_size", 4) - 1) * 3 * heads * dim * CONV_BYTES
    return float(len(lin["kda_layers"]) * (state + conv))


def read(params: dict, ctx: dict) -> Optional[float]:
    tr, counters, peaks = ctx.get("trace"), ctx.get("counters"), ctx.get("peaks")
    attrs = ctx.get("attrs") or {}
    if tr is None or not counters or peaks is None or "linear_attn_config" not in attrs:
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
