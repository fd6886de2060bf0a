"""Three readings of a looped stack (a model whose whole layer stack runs
``total_ut_steps`` = T times over one set of weights, each (loop, layer)
pass with a K/V stream of its own: ``model_type: "ouro"``), each with its own
count of the work: ``harness/roofline.py`` is the accepted benchmark's, and
its ``kv_bytes_per_token_per_chip`` counts ``num_hidden_layers`` streams a
token where this model holds T times that.

    {"reader": "loop_roofline", "kind": "paged_attn_roofline",
     "pattern": "^paged_tkg_decode_attention"}

a decode step must read every live K and V once a layer PASS: the driver's
``live_kv_tokens`` over the steps of the traced slice x T x L x 2 x KV heads x
head size x 2 B, at the chip's peak HBM bandwidth, over the device time of
the ops ``pattern`` names.

    {"reader": "loop_roofline", "kind": "stream_roofline",
     "decode_module": "^jit_token_generation_model_decode\\("}

the share of the WHOLE decode step that its streams alone would take at the
chip's peak bandwidth. A decode dispatch must stream a layer's weights once a
pass (T x L x (q, k, v, o, the gated MLP's three matrices and four norms)),
the head once, and the live K/V of its rows:

    needed bytes = dispatches in the trace x (T x L x layer bytes + vocab x hidden x 2 B)
                   + live_kv_tokens over the slice x T x L x 2 x KV heads x head size x 2 B

over the device time of the decode module's events in the trace (the
dispatches are those events: the registry is read at the ends of the traced
phase, which settles before the profiled slice starts, as ``moe_roofline.py``
says). The denominator holds everything a dispatch does, so the share cannot
pass 100% while the count is of bytes that must move; it bounds what any
later change of this program can gain in a cell whose step is a decode
dispatch.

    {"reader": "loop_roofline", "kind": "pool_used_share",
     "pool": "nxdi_kv_pool_bytes", "free": "nxdi_kv_free_bytes"}

of the pool's bytes, the share (%) live requests hold when the traced phase
ends: the two gauges as the session last set them.

A configuration without ``total_ut_steps``, a trace without the kernel or the
module, a program without the gauges (an older commit) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .counter import total
from .trace import _slice_sum

BYTES = 2  # weights and cache are bf16


def _sizes(attrs: dict):
    heads = attrs["num_attention_heads"]
    kv_heads = attrs.get("num_key_value_heads", heads)
    head_dim = attrs.get("head_dim") or attrs["hidden_size"] // heads
    return heads, kv_heads, head_dim


def layer_passes(attrs: dict) -> int:
    """Layer passes of one step = K/V streams a token holds: T x L."""
    return int(attrs["total_ut_steps"]) * int(attrs["num_hidden_layers"])


def kv_bytes_per_token(attrs: dict) -> float:
    """Bytes of K and V a token holds over all T x L streams."""
    _, kv_heads, head_dim = _sizes(attrs)
    return 2.0 * layer_passes(attrs) * kv_heads * head_dim * BYTES


def layer_weight_bytes(attrs: dict) -> float:
    """Bytes of one layer's weights: q, k, v, o, the gated MLP, four norms."""
    hidden, inter = attrs["hidden_size"], attrs["intermediate_size"]
    heads, kv_heads, head_dim = _sizes(attrs)
    attn = hidden * (heads + 2 * kv_heads) * head_dim + heads * head_dim * hidden
    return (attn + 3.0 * hidden * inter + 4 * hidden) * BYTES


def dispatch_weight_bytes(attrs: dict) -> float:
    """Weight bytes one decode dispatch must stream: every layer once a
    loop, the final norm once a loop, the head once."""
    hidden = attrs["hidden_size"]
    loops = int(attrs["total_ut_steps"])
    return (layer_passes(attrs) * layer_weight_bytes(attrs)
            + (loops * hidden + attrs["vocab_size"] * hidden) * BYTES)


def read(params: dict, ctx: dict) -> Optional[float]:
    attrs = ctx.get("attrs") or {}
    if "total_ut_steps" not in attrs:
        return None
    kind = params["kind"]
    if kind == "pool_used_share":
        counters = ctx.get("counters")
        if not counters:
            return None
        pool = total(counters["after"], params["pool"], {})
        free = total(counters["after"], params["free"], {})
        return None if not pool or free is None else 100.0 * (1.0 - free / pool)
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or peaks is None:
        return None
    chips = max(1, tr["chips"])
    kv_bytes = (_slice_sum(ctx, "live_kv_tokens") or 0.0) * kv_bytes_per_token(attrs)
    if kind == "paged_attn_roofline":
        n, seconds = trace_reduce.matching(tr["op_sums"], params["pattern"])
        work = kv_bytes
    elif kind == "stream_roofline":
        n, seconds = trace_reduce.matching(tr["module_sums"], params["decode_module"])
        work = (n / chips) * dispatch_weight_bytes(attrs) + kv_bytes
    else:
        raise ValueError(f"unknown loop_roofline kind {kind!r}")
    if n == 0 or seconds <= 0 or work <= 0:
        return None
    return 100.0 * (work / peaks["hbm_bytes_per_s"]) / (seconds / chips)
