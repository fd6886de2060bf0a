"""Readings of a model whose stack mixes WINDOW and FULL attention layers
(``model_type: "mellum"``: a paged cache of two lifetimes), each with its own
count from the configuration's keys: ``harness/roofline.py``'s
``kernel.paged_attn_roofline`` counts every live token in every layer, which
a window layer does not attend.

The work is counted from the program's ``nxdi_attn_keys_*`` counters (the
live keys and the keys attended, over rows, layers and queries, by the
layers' kind; each taken as its increase over the traced phase per dispatch
of the phase, as ``sparse_latent_roofline.py`` takes its counters) and
divided by the device time of SCOPES of the program (``device_scope.py``'s
join), so that the work counted is the same whatever implements the
mechanism later.

    {"reader": "window_roofline", "kind": "kind_ms_per_dispatch",
     "program": "decode" | "chunk", "attn_kind": "window" | "full"}

device ms, per dispatch of ``program`` in the traced slice, of the ops under
the scope ``layer.attn.<attn_kind>``: a scope INSIDE ``layer.attn``, which
the program's table names beside an op's scope (``device_scopes.json``:
``kinds``; an op under it is still ``layer.attn`` in ``ops``, so
``decode.attn_dev_ms`` reads the sum).

    {"reader": "window_roofline", "kind": "decode_roofline"}

the bytes the decode rows MUST move: the keys they attend (``min(n, window)``
a window layer, ``n`` a full layer) x K and V x kv heads x head_dim x 2 B, at
the chip's peak HBM bandwidth, over the scope ``layer.attn`` of the decode
program.

    {"reader": "window_roofline", "kind": "prefill_roofline"}

the operations a chunk pass MUST do: the keys inside each query's frontier x
heads x head_dim x 4 (q k^T and p v), at the chip's bf16 peak, over
``layer.attn`` of the chunk program.

    {"reader": "window_roofline", "kind": "stream_roofline",
     "decode_module": "^jit_token_generation_model_decode\\("}

the share of the WHOLE decode module's time that the bytes a decode dispatch
must stream would take at the chip's peak bandwidth: every layer's weights
(attention, router, every expert: the decode strategy computes them all),
the final norm and the head, and the rows' attended K and V: what bounds any
later claim in such a cell.

    {"reader": "window_roofline", "kind": "pool_used_share"}

of the window layers' ring blocks, the share (%) live slots hold when the
traced phase ends (``nxdi_kv_window_blocks_held`` over ``..._total``, the
gauges as the session last set them).

A configuration without window layers, a program without the counters, the
gauges or the scopes (an older commit), a trace without the module gives
None.
"""

import json
import os
import tempfile
from typing import Optional

from .. import trace_reduce
from . import device_scope
from .counter import total
from .sparse_latent_roofline import DISPATCHES
from .ssm_roofline import _increase  # a labelled counter's increase over the traced phase

BYTES = 2  # weights and cache are bf16
ATTN = r"^layer\.attn$"


def head_dim(attrs: dict) -> int:
    return attrs.get("head_dim") or attrs["hidden_size"] // attrs["num_attention_heads"]


def key_bytes(attrs: dict) -> float:
    """Bytes one attended key moves in one layer: its K and its V."""
    return 2.0 * attrs["num_key_value_heads"] * head_dim(attrs) * BYTES


def pair_flops(attrs: dict) -> float:
    """Operations of one (query, key) pair in one layer: q k^T and p v, every head."""
    return 4.0 * attrs["num_attention_heads"] * head_dim(attrs)


def weight_bytes(attrs: dict) -> float:
    """Weight bytes every decode dispatch streams: each layer's attention
    with its norms, its router and EVERY expert, the final norm and the head."""
    H, d = attrs["hidden_size"], head_dim(attrs)
    heads, kv = attrs["num_attention_heads"], attrs["num_key_value_heads"]
    attn = H * (heads + 2 * kv) * d + heads * d * H + 2 * d + 2 * H
    experts = attrs["num_experts"] * (H + 3.0 * H * attrs["moe_intermediate_size"])
    return (attrs["num_hidden_layers"] * (attn + experts) + H + attrs["vocab_size"] * H) * BYTES


def _attended(counters: dict, program: str) -> Optional[float]:
    """Keys attended a dispatch of ``program`` over the phase, every layer."""
    grown = _increase(counters, "nxdi_attn_keys_attended_total", {"program": program})
    counter, labels = DISPATCHES[program]
    dispatches = _increase(counters, counter, labels)
    return None if grown is None or not dispatches else grown / dispatches


def _kind_table(ctx: dict):
    """``device_scope.reduce`` over the trace with each op named by its KIND
    of attention layer where the program's table gives one (one reduction a
    run); None where the table names no kind."""
    if "window_kind_table" not in ctx:
        ctx["window_kind_table"] = None
        try:
            path = trace_reduce.find_xplane(device_scope.TRACE_DIR)
            with open(os.path.join(device_scope.TRACE_DIR, device_scope.TABLE_FILE)) as f:
                tables = json.load(f)
        except (FileNotFoundError, OSError):
            return None
        if not any(t.get("kinds") for t in tables.values()):
            return None
        by_kind = {key: dict(t, ops=dict(t["ops"], **t.get("kinds", {}))) for key, t in tables.items()}
        with tempfile.TemporaryDirectory() as tmp:
            renamed = os.path.join(tmp, device_scope.TABLE_FILE)
            with open(renamed, "w") as f:
                json.dump(by_kind, f)
            ctx["window_kind_table"] = device_scope.reduce(path, renamed)
    return ctx["window_kind_table"]


def read(params: dict, ctx: dict) -> Optional[float]:
    attrs, counters = ctx.get("attrs") or {}, ctx.get("counters")
    if "sliding_attention" not in (attrs.get("layer_types") or ()) or not counters:
        return None
    kind = params["kind"]
    if kind == "pool_used_share":
        held = total(counters["after"], "nxdi_kv_window_blocks_held", {})
        whole = total(counters["after"], "nxdi_kv_window_blocks_total", {})
        return None if held is None or not whole else 100.0 * held / whole
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or peaks is None:
        return None
    if kind == "kind_ms_per_dispatch":
        table = _kind_table(ctx)
        acc = (table or {}).get(params["program"])
        if not acc or not acc["tabled"]:
            return None
        scope = "layer.attn." + params["attn_kind"]
        return acc["by_scope"].get(scope, 0.0) / acc["dispatches"] * 1e3
    if kind == "stream_roofline":
        attended = _attended(counters, "decode")
        n, seconds = trace_reduce.matching(tr["module_sums"], params["decode_module"])
        if attended is None or n == 0 or seconds <= 0:
            return None
        needed = weight_bytes(attrs) + attended * key_bytes(attrs)
        return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (seconds / n)
    program = {"decode_roofline": "decode", "prefill_roofline": "chunk"}.get(kind)
    if program is None:
        raise ValueError(f"unknown window_roofline kind {kind!r}")
    attended = _attended(counters, program)
    scope_ms = device_scope.read(
        {"kind": "scope_ms_per_dispatch", "program": program, "scope": ATTN}, ctx)
    if attended is None or not scope_ms:
        return None
    if program == "decode":
        least_s = attended * key_bytes(attrs) / peaks["hbm_bytes_per_s"]
    else:
        least_s = attended * pair_flops(attrs) / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (scope_ms * 1e-3) if least_s > 0 else None
