"""The operations and bytes a kernel's work NEEDS, computed from shapes the
driver saw — the numerators of the roofline shares. Kept with the
benchmark so that no PR that claims a gain can change them.

A share is  (least time the chip could take) / (kernel time from the trace),
where the least time is bytes / peak HBM bandwidth for a memory-bound kernel
and operations / peak bf16 rate for a compute-bound one. Work the kernel
does beyond what is needed (padding to a bucket, masked tiles, re-reads)
is not counted, so it lowers the share, as it should.
"""

from __future__ import annotations

from typing import Callable, Dict


def kv_bytes_per_token_per_chip(attrs: dict, chips_sharing: int, dtype_bytes: int = 2) -> float:
    """Bytes of K and V one token holds over all layers on one chip (KV
    heads are sharded over the model-parallel degree)."""
    heads = attrs["num_attention_heads"]
    kv_heads = attrs.get("num_key_value_heads", heads)
    head_dim = attrs.get("head_dim") or attrs["hidden_size"] // heads
    per_chip_heads = max(1, kv_heads // chips_sharing)
    return 2.0 * attrs["num_hidden_layers"] * per_chip_heads * head_dim * dtype_bytes


def paged_decode_kv_bytes(attrs: dict, chips: int, samples: Dict[str, float]) -> float:
    """Decode attention must read every live K and V once per step:
    sum over decode steps of (live context tokens of the rows decoding)
    x bytes per token per chip. ``samples['live_kv_tokens']`` is that sum
    over the steps of the traced slice, counted by the driver."""
    return samples["live_kv_tokens"] * kv_bytes_per_token_per_chip(attrs, chips)


def causal_prefill_attn_flops(attrs: dict, chips: int, samples: Dict[str, float]) -> float:
    """Causal attention of a chunk of n queries that starts at position s
    scores n*s + n(n+1)/2 query-key pairs per head; each pair costs 2*D
    multiply-adds for QK^T and 2*D for PV, i.e. 4*D operations... counted
    as FLOPs that is 4*D per pair. ``samples['prefill_qk_pairs']`` is the
    sum of pairs over the chunks of the traced slice (one head, one layer)."""
    heads = attrs["num_attention_heads"]
    head_dim = attrs.get("head_dim") or attrs["hidden_size"] // heads
    per_chip_heads = max(1, heads // chips)
    return samples["prefill_qk_pairs"] * 4.0 * head_dim * per_chip_heads * attrs["num_hidden_layers"]


WORK: Dict[str, Callable[[dict, int, Dict[str, float]], float]] = {
    "paged_decode_kv_bytes": paged_decode_kv_bytes,
    "causal_prefill_attn_flops": causal_prefill_attn_flops,
}

#: which peak bounds which kind of work
PEAK_OF = {"hbm": "hbm_bytes_per_s", "bf16": "bf16_flops_per_s"}


def qk_pairs(start: int, n: int) -> float:
    return float(n) * start + n * (n + 1) / 2.0
