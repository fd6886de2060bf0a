"""Attention mask construction for every attention flavor.

Reference: models/model_base.py:211-449 (_create_context_attn_mask,
_create_chunked_attn_mask, _create_windowed_attn_mask, _create_spec_attn_mask,
token-gen masks). Masks are boolean, True = attend.
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_mask(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """Context-encoding causal mask (reference model_base.py:211-229).

    attention_mask: (B, S) 1 for valid tokens. Returns (B, 1, S, S).
    """
    B, S = attention_mask.shape
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    valid = attention_mask.astype(bool)[:, None, None, :]  # keys valid
    return causal[None, None, :, :] & valid


def token_gen_mask(attention_mask: jnp.ndarray, n_active: int = 1) -> jnp.ndarray:
    """Decode mask over cache positions (reference model_base.py:304-318).

    attention_mask: (B, S_cache) marking populated cache positions (including
    the token(s) being written this step). Returns (B, 1, n_active, S_cache).
    """
    return jnp.broadcast_to(
        attention_mask.astype(bool)[:, None, None, :],
        (attention_mask.shape[0], 1, n_active, attention_mask.shape[1]),
    )


def spec_token_gen_mask(attention_mask: jnp.ndarray, position_ids: jnp.ndarray) -> jnp.ndarray:
    """Mask for multi-token (speculative) decode (reference model_base.py:290-302).

    attention_mask: (B, S_cache) cache-valid mask; position_ids: (B, K) the
    positions of the K active tokens. Token i may attend cache positions
    < position_ids[:, i] + 1 (its own slot included) — causal among the
    speculative tokens because they are written in order.
    """
    B, S_cache = attention_mask.shape
    cols = jnp.arange(S_cache)[None, None, :]
    per_tok = cols <= position_ids[:, :, None]  # (B, K, S_cache)
    return (per_tok & attention_mask.astype(bool)[:, None, :])[:, None, :, :]


def block_frontier(position_ids: jnp.ndarray, block: int) -> jnp.ndarray:
    """The last position a query sees under the block-causal rule (position
    ``i`` sees ``j`` iff ``j // block <= i // block``): its block's end."""
    return position_ids // block * block + (block - 1)


def block_causal_token_gen_mask(
    attention_mask: jnp.ndarray, position_ids: jnp.ndarray, block: int
) -> jnp.ndarray:
    """Mask of a multi-token pass of a model that attends causally between
    blocks of ``block`` positions and both ways inside one:
    :func:`spec_token_gen_mask` with each token's frontier at its block's
    end, clipped by the cache-valid mask. Returns (B, 1, K, S_cache)."""
    return spec_token_gen_mask(attention_mask, block_frontier(position_ids, block))


def windowed_mask(attention_mask: jnp.ndarray, position_ids: jnp.ndarray, window: int) -> jnp.ndarray:
    """Sliding-window causal mask for prefill (reference model_base.py:247-258).

    Query at position p attends keys in (p - window, p].
    """
    B, S = attention_mask.shape
    q_pos = position_ids[:, :, None]  # (B, S, 1)
    k_pos = position_ids[:, None, :]  # (B, 1, S)
    in_window = (k_pos <= q_pos) & (k_pos > q_pos - window)
    valid = attention_mask.astype(bool)[:, None, :]
    return (in_window & valid)[:, None, :, :]


def windowed_token_gen_mask(
    cache_positions: jnp.ndarray, position_ids: jnp.ndarray, valid: jnp.ndarray, window: int
) -> jnp.ndarray:
    """Decode mask for a sliding-window (ring-buffer) cache
    (reference model_base.py:319-340).

    cache_positions: (B, W) absolute position stored in each cache slot;
    position_ids: (B, 1) current position; valid: (B, W) slot-populated mask.
    """
    q = position_ids[:, :, None]
    k = cache_positions[:, None, :]
    ok = (k <= q) & (k > q - window) & valid[:, None, :]
    return ok[:, None, :, :]


def chunked_mask(attention_mask: jnp.ndarray, position_ids: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Chunked-attention prefill mask (llama4; reference model_base.py:231-245).

    Query attends causally only within its own chunk of size ``chunk``.
    """
    q_pos = position_ids[:, :, None]
    k_pos = position_ids[:, None, :]
    same_chunk = (q_pos // chunk) == (k_pos // chunk)
    causal = k_pos <= q_pos
    valid = attention_mask.astype(bool)[:, None, :]
    return (same_chunk & causal & valid)[:, None, :, :]


def block_diagonal_mask(seq_lens: jnp.ndarray, total_len: int) -> jnp.ndarray:
    """Block-diagonal causal mask for concatenated requests (chunked prefill;
    reference modules/attention/utils.py:331)."""
    ends = jnp.cumsum(seq_lens)
    starts = ends - seq_lens
    pos = jnp.arange(total_len)
    seg = jnp.sum(pos[:, None] >= ends[None, :], axis=1)  # segment id per pos
    same = seg[:, None] == seg[None, :]
    causal = pos[:, None] >= pos[None, :]
    in_range = pos < ends[-1]
    return same & causal & in_range[None, :] & in_range[:, None]
