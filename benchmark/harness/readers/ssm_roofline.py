"""The decode-step state update's share of its memory roofline.

    {"reader": "ssm_roofline", "pattern": "^ssm_state_update",
     "decode_module": "^jit_token_generation_model_decode\\(",
     "rows_counter": "nxdi_ssm_rows_advanced_total", "rows_labels": {"program": "decode"},
     "dispatch_counter": "nxdi_steps_total", "dispatch_labels": {"kind": "decode"}}

A state-space layer's decode step must read and write the float32 state
(and the conv tail) of every row it advances, whatever the context length:

    needed bytes = rows advanced x state-space layers x 2 x (heads x head_dim x state_size x 4
                                                            + (d_conv - 1) x conv_dim x 2)

``rows advanced`` by the decode dispatches THE TRACE HOLDS is the program's
own count over the traced phase (``rows_counter``), per decode dispatch of
that phase (``dispatch_counter``), times the decode dispatches in the trace
(events of ``decode_module``): the registry is read at the ends of the
phase, which settles before the profiled slice starts, so the mean rows per
dispatch is taken from the phase and the number of dispatches from the trace.
The share is needed bytes / peak HBM bandwidth over the time of the ops
``pattern`` names. What the kernel moves besides (its packed coefficients,
its outputs) is not needed work and lowers the share, as it should.

This file keeps its own count of the needed bytes (``state_bytes_per_row``,
from the configuration's published ``mamba_*`` keys): ``harness/roofline.py``
is the accepted benchmark's. A program that has no such counter or kernel
(an older commit; a build in which the kernel is not in the decode program)
gives None.
"""

from typing import Optional

from .. import trace_reduce
from .counter import total

STATE_BYTES = 4  # the recurrent state is float32 (configuration file, assumed._note)
CONV_BYTES = 2  # the conv tail is bf16


def state_bytes_per_row(attrs: dict) -> float:
    """Bytes one row's state takes over all state-space layers, once."""
    layers = sum(1 for kind in attrs.get("layer_types", ()) if kind == "mamba")
    heads, head_dim = attrs["mamba_n_heads"], attrs["mamba_d_head"]
    state, groups = attrs["mamba_d_state"], attrs.get("mamba_n_groups", 1)
    conv_dim = heads * head_dim + 2 * groups * state
    ssm = heads * head_dim * state * STATE_BYTES
    conv = (attrs["mamba_d_conv"] - 1) * conv_dim * CONV_BYTES
    return float(layers * (ssm + conv))


def _increase(counters: dict, name: str, labels: dict) -> Optional[float]:
    after = total(counters["after"], name, labels)
    if after is None:
        return None
    return after - (total(counters["before"], name, labels) or 0.0)


def read(params: dict, ctx: dict) -> Optional[float]:
    tr, counters, peaks = ctx.get("trace"), ctx.get("counters"), ctx.get("peaks")
    attrs = ctx.get("attrs") or {}
    if tr is None or not counters or peaks is None or "mamba_n_heads" not in attrs:
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
