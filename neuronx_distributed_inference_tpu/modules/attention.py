"""Functional attention core — the single attention implementation all models use.

TPU-native re-design of the reference attention stack
(reference: modules/attention/attention_base.py — NeuronAttentionBase).

Structure:
- :func:`qkv_project` / :func:`o_project` — projections (+ optional bias,
  QK-norm pre/post RoPE). The head dims are GLOBAL (padded/replicated by
  :class:`~..parallel.sharding.GQASharding` at load time) and sharded over the
  model mesh axes by GSPMD — replacing GroupQueryAttention_QKV/O (gqa.py:344,1151).
- :func:`attention_prefill` — context-encoding attention. Dispatches to the
  Pallas flash kernel on TPU or a native masked-softmax path elsewhere
  (reference get_flash_attention_strategy / perform_prefill,
  attention_base.py:1314,720).
- :func:`attention_decode` — token-gen attention over the populated cache
  (reference compute_for_token_gen, attention_base.py:1909). The cache is
  updated first, then attended with a position mask — numerically identical
  to the reference's prior/active decomposition but a single fused softmax.
- Learned attention sinks (GPT-OSS) supported in both phases
  (reference attention_base.py:879-889,1964-1980).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.norm import rms_norm
from neuronx_distributed_inference_tpu.modules.rope import apply_rope
from neuronx_distributed_inference_tpu.ops.quant import linear


@dataclass(frozen=True)
class AttnSpec:
    """Static attention hyperparams (global, post-GQA-padding counts)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: Optional[float] = None
    qk_norm: bool = False  # rmsnorm on per-head q/k before rope (qwen3)
    qkv_bias: bool = False
    o_bias: bool = False
    softmax_fp32: bool = True
    has_sink: bool = False
    rms_norm_eps: float = 1e-6
    use_flash_kernel: Optional[bool] = None  # None = auto by platform
    # head-pair packed flash prefill (config attn_packed_kernel_enabled):
    # D<=64 heads ride 128-lane tiles in pairs — None = auto-on for causal
    # D<=64 shapes on the flash path, True = force, False = unpacked kernel
    use_packed_heads: Optional[bool] = None
    # decode (TKG) attention kernel (config attn_block_tkg_kernel_enabled):
    # None = auto on TPU, True = force, False = native path
    use_tkg_kernel: Optional[bool] = None
    # model-parallel degree of the rank-interleaved fused-qkv layout
    # (builder._fuse_qkv); 1 when fused_qkv is off
    qkv_shards: int = 1
    # full model-parallel degree (tp*ep). pallas_call carries no GSPMD
    # partitioning rule, so the kernels that read a cache (paged flash, TKG,
    # ragged) are launched once per head shard of the mesh
    # (parallel/sharding.shard_over_heads) and their gates ask only that both
    # head counts divide this degree; the contiguous flash prefill has no
    # such launch and its AUTO path still requires degree 1
    # (ops/kernel_mode.heads_divide, single_shard).
    model_parallel: int = 1
    # clamp qkv projection outputs to [-clip, clip] (DBRX clip_qkv)
    qkv_clip: Optional[float] = None
    # False: no positional embedding at all (Granite-4.0-H
    # position_embedding_type "nope"): q and k are not rotated
    use_rope: bool = True

    @property
    def softmax_scale(self) -> float:
        return self.scale if self.scale is not None else self.head_dim**-0.5


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, H_kv, D) -> (B, S, H_kv*n_rep, D) (reference utils.py:210)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def qkv_project(
    params: dict,
    hidden: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    spec: AttnSpec,
    adapter_ids=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """hidden (B,S,H) -> q (B,S,Hq,D), k,v (B,S,Hkv,D), with RoPE applied.

    Reference: prep_qkv_tensors (attention_base.py:555-629).
    """
    from neuronx_distributed_inference_tpu.modules.lora import apply_lora

    B, S, _ = hidden.shape
    if "qkv_proj" in params:
        # fused_qkv: one column-parallel matmul, split after (LoRA serving is
        # rejected with fused_qkv at config validation). The fused axis is
        # rank-interleaved [q_i|k_i|v_i] per model-parallel rank (see
        # builder._fuse_qkv) so this split is shard-local under GSPMD.
        fused = linear(params["qkv_proj"], hidden)
        if spec.qkv_bias:
            fused = fused + params["qkv_proj"]["bias"]
        g = spec.qkv_shards
        q_sz = spec.num_heads * spec.head_dim
        kv_sz = spec.num_kv_heads * spec.head_dim
        pq, pkv = q_sz // g, kv_sz // g
        grouped = fused.reshape(B, S, g, pq + 2 * pkv)
        q = grouped[..., :pq].reshape(B, S, q_sz)
        k = grouped[..., pq : pq + pkv].reshape(B, S, kv_sz)
        v = grouped[..., pq + pkv :].reshape(B, S, kv_sz)
    else:
        q = apply_lora(params["q_proj"], hidden, linear(params["q_proj"], hidden), adapter_ids)
        k = apply_lora(params["k_proj"], hidden, linear(params["k_proj"], hidden), adapter_ids)
        v = apply_lora(params["v_proj"], hidden, linear(params["v_proj"], hidden), adapter_ids)
        if spec.qkv_bias:
            q = q + params["q_proj"]["bias"]
            k = k + params["k_proj"]["bias"]
            v = v + params["v_proj"]["bias"]
    if spec.qkv_clip is not None:
        q = jnp.clip(q, -spec.qkv_clip, spec.qkv_clip)
        k = jnp.clip(k, -spec.qkv_clip, spec.qkv_clip)
        v = jnp.clip(v, -spec.qkv_clip, spec.qkv_clip)
    q = q.reshape(B, S, spec.num_heads, spec.head_dim)
    k = k.reshape(B, S, spec.num_kv_heads, spec.head_dim)
    v = v.reshape(B, S, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm:  # per-head rmsnorm before rope (reference qwen3, qk norm)
        q = rms_norm(q, params["q_norm"]["weight"], spec.rms_norm_eps)
        k = rms_norm(k, params["k_norm"]["weight"], spec.rms_norm_eps)
    if spec.use_rope:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def o_project(
    params: dict, attn_out: jnp.ndarray, spec: AttnSpec, adapter_ids=None
) -> jnp.ndarray:
    """(B,S,Hq,D) -> (B,S,H). Reference: GroupQueryAttention_O (gqa.py:1151)."""
    from neuronx_distributed_inference_tpu.modules.lora import apply_lora

    B, S, Hq, D = attn_out.shape
    flat = attn_out.reshape(B, S, Hq * D)
    out = apply_lora(params["o_proj"], flat, linear(params["o_proj"], flat), adapter_ids)
    if spec.o_bias:
        out = out + params["o_proj"]["bias"]
    return out


def _masked_softmax_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    spec: AttnSpec,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Native attention: q (B,Sq,Hq,D), k/v (B,Sk,Hq,D), mask (B,1,Sq,Sk)."""
    # int8/fp8-quantized caches are dequantized at the read (kvcache.read_*
    # return fp32 — the reference's post-gather fp8 dequant,
    # kv_cache_manager.py:137-160); align to q's compute dtype here
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    dtype = jnp.float32 if spec.softmax_fp32 else q.dtype
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * spec.softmax_scale
    scores = jnp.where(mask, scores.astype(dtype), jnp.finfo(dtype).min)
    if sink is not None:
        # learned per-head sink logit participates in the softmax denominator
        # (reference attention_base.py:879-889)
        B, H, Sq, Sk = scores.shape
        sink_col = jnp.broadcast_to(sink.astype(dtype)[None, :, None, None], (B, H, Sq, 1))
        full = jnp.concatenate([scores, sink_col], axis=-1)
        probs = jax.nn.softmax(full, axis=-1)[..., :Sk]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32).astype(
        q.dtype
    )


# kernel/native dispatch gates: consolidated in ops/kernel_mode.py (one
# tested predicate per kernel); the historical names stay importable here
from neuronx_distributed_inference_tpu.ops.kernel_mode import (  # noqa: E402
    flash_shape_ok as _flash_shape_ok,
    use_flash as _use_flash,
    use_packed as _use_packed,
)


def attention_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    spec: AttnSpec,
    sink: Optional[jnp.ndarray] = None,
    causal: bool = True,
    key_valid: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Context-encoding attention (reference perform_prefill, attention_base.py:720).

    ``key_valid`` (B, S) marks valid key positions; when provided the Pallas
    flash kernel is eligible — including the sliding-window / chunked-
    attention flavors (fused masks + dead-tile skip; reference
    sliding_window/attention.py:61-233) and learned sinks (folded via the
    kernel's emitted softmax stats).
    """
    n_rep = spec.num_heads // spec.num_kv_heads
    if key_valid is not None and causal and _use_flash(spec, q.shape[1]):
        from neuronx_distributed_inference_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), key_valid, spec,
            window=window, chunk=chunk, sink=sink,
            packed=_use_packed(spec),
        )
    return _masked_softmax_attention(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), mask, spec, sink)


def attention_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    mask: jnp.ndarray,
    spec: AttnSpec,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Token-gen attention over the (already updated) cache.

    q: (B, K, Hq, D); k_cache/v_cache: (B, S_bucket, Hkv, D); mask
    (B, 1, K, S_bucket). Reference: compute_for_token_gen
    (attention_base.py:1909-1987) — decomposed prior/active softmax; here a
    single masked softmax over the cache, same math.
    """
    n_rep = spec.num_heads // spec.num_kv_heads
    return _masked_softmax_attention(
        q, repeat_kv(k_cache, n_rep), repeat_kv(v_cache, n_rep), mask, spec, sink
    )
