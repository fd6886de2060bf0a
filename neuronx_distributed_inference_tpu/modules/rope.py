"""Rotary position embeddings.

Functional RoPE with the rope-scaling variants the reference model hub needs
(reference: modules/attention/utils.py:231 ``apply_rotary_pos_emb``;
llama3 scaled rope modeling_llama.py:1037; deepseek yarn rope_util.py).
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def default_inv_freq(head_dim: int, rope_theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (rope_theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def llama3_scaled_inv_freq(
    head_dim: int,
    rope_theta: float,
    factor: float = 8.0,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_position_embeddings: int = 8192,
) -> jnp.ndarray:
    """Llama-3.x rope scaling (reference modeling_llama.py:1037-1075)."""
    inv_freq = default_inv_freq(head_dim, rope_theta)
    old_context_len = original_max_position_embeddings
    low_freq_wavelen = old_context_len / low_freq_factor
    high_freq_wavelen = old_context_len / high_freq_factor
    wavelen = 2 * math.pi / inv_freq
    # wavelen < high_freq_wavelen: keep; > low_freq_wavelen: /factor; else smooth
    smooth = (old_context_len / wavelen - low_freq_factor) / (high_freq_factor - low_freq_factor)
    scaled = jnp.where(
        wavelen > low_freq_wavelen,
        inv_freq / factor,
        jnp.where(
            wavelen < high_freq_wavelen,
            inv_freq,
            (1 - smooth) * inv_freq / factor + smooth * inv_freq,
        ),
    )
    return scaled


def yarn_inv_freq(
    head_dim: int,
    rope_theta: float,
    factor: float,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    original_max_position_embeddings: int = 4096,
) -> jnp.ndarray:
    """YaRN rope scaling (reference deepseek/rope_util.py)."""
    dim = head_dim
    freq_extra = default_inv_freq(dim, rope_theta)
    freq_inter = freq_extra / factor

    def find_dim(num_rot):
        return (dim * math.log(original_max_position_embeddings / (num_rot * 2 * math.pi))) / (
            2 * math.log(rope_theta)
        )

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_attention_scaling(config) -> float:
    """cos/sin magnitude scaling factor from rope_scaling.

    HF semantics: explicit ``attention_factor`` wins; otherwise YaRN defaults
    to ``0.1 * ln(factor) + 1`` (:func:`yarn_mscale`); other rope types use 1.0.
    """
    scaling = getattr(config, "rope_scaling", None)
    if not scaling:
        return 1.0
    if scaling.get("attention_factor") is not None:
        return float(scaling["attention_factor"])
    rope_type = scaling.get("rope_type", scaling.get("type", "default"))
    if rope_type == "yarn":
        return yarn_mscale(scaling.get("factor", 1.0), scaling.get("mscale", 1.0))
    return 1.0


def compute_inv_freq(config) -> jnp.ndarray:
    """Pick the rope variant from an InferenceConfig's HF attrs."""
    head_dim = getattr(config, "head_dim", None) or (
        config.hidden_size // config.num_attention_heads
    )
    rope_dim = getattr(config, "rope_dim", None) or head_dim
    theta = getattr(config, "rope_theta", 10000.0)
    scaling = getattr(config, "rope_scaling", None)
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
        if rope_type == "llama3":
            return llama3_scaled_inv_freq(
                rope_dim,
                theta,
                factor=scaling.get("factor", 8.0),
                low_freq_factor=scaling.get("low_freq_factor", 1.0),
                high_freq_factor=scaling.get("high_freq_factor", 4.0),
                original_max_position_embeddings=scaling.get(
                    "original_max_position_embeddings", 8192
                ),
            )
        if rope_type == "yarn":
            return yarn_inv_freq(
                rope_dim,
                theta,
                factor=scaling.get("factor", 1.0),
                beta_fast=scaling.get("beta_fast", 32.0),
                beta_slow=scaling.get("beta_slow", 1.0),
                original_max_position_embeddings=scaling.get(
                    "original_max_position_embeddings", 4096
                ),
            )
        if rope_type in ("default", "linear", "dynamic"):
            inv = default_inv_freq(rope_dim, theta)
            if rope_type == "linear":
                inv = inv / scaling.get("factor", 1.0)
            return inv
    return default_inv_freq(rope_dim, theta)


def rope_cos_sin(position_ids: jnp.ndarray, inv_freq: jnp.ndarray, attention_scaling: float = 1.0):
    """cos/sin tables for positions. position_ids (B, S) -> (B, S, rope_dim/2)."""
    freqs = position_ids[..., None].astype(jnp.float32) * inv_freq[None, None, :]
    return jnp.cos(freqs) * attention_scaling, jnp.sin(freqs) * attention_scaling


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding, HF "half-rotation" convention.

    x: (B, S, H, D); cos/sin: (B, S, R/2), R <= D the rotary dimension.
    R = D matches the reference/HF ``rotate_half`` formulation
    (modules/attention/utils.py:220-240) so logits match HF checkpoints
    bit-for-bit in fp32. R < D is a PARTIAL rotary (``partial_rotary_factor``;
    the tables say how wide): the first R dimensions of a head rotate, as
    pairs (i, i + R/2), and the other D - R pass through unchanged.
    """
    d2 = cos.shape[-1]
    x1 = x[..., :d2]
    x2 = x[..., d2 : 2 * d2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    rotated = jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
    if 2 * d2 == x.shape[-1]:
        return rotated
    return jnp.concatenate([rotated, x[..., 2 * d2 :]], axis=-1)


def apply_rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Complex-pair rotary convention: adjacent pairs (x[2i], x[2i+1]) rotate
    by angle i (Llama4 apply_rotary_emb / torch.view_as_complex; reference
    models/llama4/modeling_llama4_text.py rope path).

    x: (B, S, H, D); cos/sin: (B, S, D/2).
    """
    x0 = x[..., 0::2].astype(jnp.float32)
    x1 = x[..., 1::2].astype(jnp.float32)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    out0 = x0 * c - x1 * s
    out1 = x0 * s + x1 * c
    out = jnp.stack([out0, out1], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
