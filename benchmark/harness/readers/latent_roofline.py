"""Three readings of a latent-attention (MLA) model's paged pool and of the
kernels that attend it, each with its own count: ``harness/roofline.py`` is
the accepted benchmark's, and its decode count is ``(H_kv, D)`` keys and
values.

    {"reader": "latent_roofline", "kind": "decode_roofline",
     "pattern": "^paged_latent_decode_attention"}

a decode step must read every live latent once a layer: the driver's
``live_kv_tokens`` over the steps of the traced slice x layers x
(``kv_lora_rank`` + ``qk_rope_head_dim``) x 2 B, at the chip's peak HBM
bandwidth, over the device time of the ops ``pattern`` names.

    {"reader": "latent_roofline", "kind": "prefill_roofline",
     "pattern": "^paged_latent_flash_attention"}

causal attention of the chunk passes in the slice: the driver's
``prefill_qk_pairs`` (one head, one layer) x heads x layers x 2 x
((``qk_nope_head_dim`` + ``qk_rope_head_dim``) + ``v_head_dim``) operations,
the EXPANDED form's count whatever form the kernel runs (an absorbed kernel
does 2 x (``kv_lora_rank`` + ``qk_rope_head_dim`` + ``kv_lora_rank``) a pair a
head and so reads low, as it should), at the chip's bf16 peak, over the
device time of the ops ``pattern`` names.

    {"reader": "latent_roofline", "kind": "pool_used_share",
     "pool": "nxdi_kv_pool_bytes", "free": "nxdi_kv_free_bytes"}

of the pool's bytes, the share (%) live requests hold when the traced phase
ends: the two gauges as the session last set them.

A program without the kernel, the gauges or the model keys (an older commit;
another model) gives None.
"""

from typing import Optional

from .. import trace_reduce
from .counter import total
from .trace import _slice_sum

CACHE_BYTES = 2  # the latents are kept in bf16


def latent_bytes_per_token(attrs: dict) -> float:
    """Bytes a token leaves in the pool over all layers: one compressed
    latent and one rotary key a layer, whatever the head count."""
    return (float(attrs["num_hidden_layers"])
            * (attrs["kv_lora_rank"] + attrs["qk_rope_head_dim"]) * CACHE_BYTES)


def expanded_pair_flops(attrs: dict) -> float:
    """Operations of one causal query-key pair over all heads and layers in
    the expanded form: 2 x d_q for the score, 2 x d_v for the value."""
    per_head = 2.0 * (attrs["qk_nope_head_dim"] + attrs["qk_rope_head_dim"] + attrs["v_head_dim"])
    return per_head * attrs["num_attention_heads"] * attrs["num_hidden_layers"]


def read(params: dict, ctx: dict) -> Optional[float]:
    attrs = ctx.get("attrs") or {}
    if "kv_lora_rank" not in attrs:
        return None
    if params["kind"] == "pool_used_share":
        counters = ctx.get("counters")
        if not counters:
            return None
        pool = total(counters["after"], params["pool"], {})
        free = total(counters["after"], params["free"], {})
        return None if not pool or free is None else 100.0 * (1.0 - free / pool)
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or peaks is None:
        return None
    n, s = trace_reduce.matching(tr["op_sums"], params["pattern"])
    if n == 0 or s <= 0:
        return None
    chips = max(1, tr["chips"])
    if params["kind"] == "decode_roofline":
        work = (_slice_sum(ctx, "live_kv_tokens") or 0.0) * latent_bytes_per_token(attrs)
        peak = peaks["hbm_bytes_per_s"]
    elif params["kind"] == "prefill_roofline":
        work = (_slice_sum(ctx, "prefill_qk_pairs") or 0.0) * expanded_pair_flops(attrs)
        peak = peaks["bf16_flops_per_s"]
    else:
        raise ValueError(f"unknown latent_roofline kind {params['kind']!r}")
    if work <= 0:
        return None
    return 100.0 * (work / peak) / (s / chips)
