"""The decode-step state update's share of its memory roofline, for a
state-space block with GROUPS of B/C under the ``nemotron_h`` keys.

    {"reader": "ssm_group_roofline", "pattern": "^ssm_state_update",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "rows_counter": "nxdi_ssm_rows_advanced_total", "rows_labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

What ``ssm_roofline.py`` reads, by the same rule (needed bytes of the decode
dispatches the trace holds / peak HBM bandwidth over the time of the ops
``pattern`` names; rows a dispatch from the program's counter over the traced
phase), with its own count from THIS family's published keys:

    needed bytes = rows advanced x state-space blocks x 2 x (mamba_num_heads x mamba_head_dim
                   x ssm_state_size x 4 + (conv_kernel - 1) x conv_dim x 2)
    conv_dim = mamba_num_heads x mamba_head_dim + 2 x n_groups x ssm_state_size
    state-space blocks = the 'M's of hybrid_override_pattern

The count is of the work, whatever implements it: the state read and written
once and the conv tail read and written once a row a block. What a kernel
moves besides (packed coefficients, B and C, its outputs) is not needed work
and lowers the share. A configuration without these keys (any other family),
a program without the counter or without the kernel in its decode program
(an older commit) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .ssm_roofline import CONV_BYTES, STATE_BYTES, _increase

KEYS = ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "conv_kernel", "n_groups",
        "hybrid_override_pattern")


def state_bytes_per_row(attrs: dict) -> float:
    """Bytes one row's state takes over all state-space blocks, once."""
    blocks = attrs["hybrid_override_pattern"].count("M")
    d_inner = attrs["mamba_num_heads"] * attrs["mamba_head_dim"]
    conv_dim = d_inner + 2 * attrs["n_groups"] * attrs["ssm_state_size"]
    ssm = d_inner * attrs["ssm_state_size"] * STATE_BYTES
    conv = (attrs["conv_kernel"] - 1) * conv_dim * CONV_BYTES
    return float(blocks * (ssm + conv))


def read(params: dict, ctx: dict) -> Optional[float]:
    tr, counters, peaks = ctx.get("trace"), ctx.get("counters"), ctx.get("peaks")
    attrs = ctx.get("attrs") or {}
    if tr is None or not counters or peaks is None or any(k not in attrs for k in KEYS):
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
