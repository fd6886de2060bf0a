"""Learned sparse attention: an indexer that scores a row's cached keys and
the selection of the ``index_topk`` a query attends (DeepSeek-V3.2's
"lightning indexer" in front of latent attention; ``model_type:
"glm_moe_dsa"``, models/glm_moe_dsa.py).

A layer with an indexer keeps one more stream a token beside what its
attention keeps: the indexer's key ``k_I`` (``index_head_dim`` wide, ONE a
token, shared by the ``index_n_heads`` index heads). Per query ``t`` and
live key ``s <= t``::

    q_I[t]   = cq[t] W_Iq      index_n_heads heads of index_head_dim, the first
                               rope_dim of each rotated (cq: the q latent, normed)
    k_I[s]   = LayerNorm(a[s] W_Ik)   the first rope_dim rotated          (cached)
    w[t]     = (a[t] W_Iw) * index_n_heads^-1/2 * index_head_dim^-1/2     float32
    I[t, s]  = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])                   float32
    S_t      = the index_topk live keys of largest I[t, .]; every live key
               while a row has no more than index_topk

and the attention attends ``S_t`` alone. Three parts, each under its own
named scope in the layer (``layer.indexer``: projections and scores,
``layer.select``, ``layer.attn``):

* :func:`index_projections`, then the scores: on the serving path
  ``ops/index_scores.paged_index_scores``, which reads the index keys where
  they lie in the pool's third stream and walks a row's LIVE block groups
  (no gathered copy of the kv bucket, nothing scored past a row's frontier,
  an empty row free; what it leaves unwritten there nobody reads);
  :func:`index_scores` over keys gathered by the block table for what the
  kernel's gate refuses (off the chip, a stream off the lanes, a width
  under a group), and over a whole prompt's own keys;
* :func:`select`: the chosen keys as a predicate ``(B, S, W)`` over the
  row's kv width (it still reads the BUCKET's width, the live keys alone
  by the mask), found WITHOUT a sort: the ``index_topk``-th largest score
  of a query is bisected on the scores' bit patterns (32 counting passes
  over the scores; a top-k of 2048 out of 16k is a full sort on the chip),
  ties at the threshold broken towards the lower position as
  ``lax.top_k`` breaks them; :func:`chosen_positions` turns the predicate
  into positions in the row (``-1`` padded), for the step's
  ``output_choices``;
* the attention over the picked keys: on the serving path the latent
  kernels with the predicate (``ops/latent_attention.latent_attend``: the
  decode program's through the decode kernel with the selection as its
  mask, a chunk pass's inside the chunk kernel over the row's live block
  groups); :func:`attend_selected`, the dense walk of every gathered latent
  under the predicate, for what no kernel serves (a whole prompt, the CPU).
  Either way the same sum over the picked keys, nothing left out.

Invalid rows and padded positions have no live key: they choose nothing
(their attention output is finite and unread) and write nothing (their slot
is the garbage slot), the contract ``modules/ssm.causal_conv`` states for
carries.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.norm import layer_norm
from neuronx_distributed_inference_tpu.modules.rope import apply_rope
from neuronx_distributed_inference_tpu.ops.latent_attention import native_latent_attention
from neuronx_distributed_inference_tpu.ops.quant import linear

#: the name, in ``StepOutput.aux``, of the keys each (layer, position)
#: attended: int32 ``(B, S, layers, index_topk)`` positions in the row,
#: ascending, ``-1`` padded (``ModelSpec.output_choices``)
SELECTION_CHOICES = "selection"

#: float32 bytes of one pass's index scores / attention scores above which
#: the rows of the pass are taken one after another (``lax.map``)
ROWS_AT_ONCE_BYTES = 512 * 2**20


@dataclass(frozen=True)
class IndexerSpec:
    """Static dims of a layer's indexer (the config's ``index_*`` keys)."""

    n_heads: int
    head_dim: int
    topk: int
    ln_eps: float = 1e-6

    @property
    def weight_scale(self) -> float:
        return self.n_heads ** -0.5 * self.head_dim ** -0.5


def index_projections(p: dict, x: jax.Array, cq: jax.Array, cos, sin, spec: IndexerSpec):
    """``(q_I (B, S, heads, D), k_I (B, S, D), w (B, S, heads) float32)`` of
    a pass: ``x`` the layer's normed input, ``cq`` its normed q latent."""
    B, S, _ = x.shape
    # the rotary tables are rope_dim wide: a head's first rope_dim dimensions
    # rotate, the others pass (modules/rope.apply_rope, partial rotary)
    q_i = apply_rope(linear(p["wq_b"], cq).reshape(B, S, spec.n_heads, spec.head_dim), cos, sin)
    k_i = layer_norm(linear(p["wk"], x), p["k_norm"]["weight"], p["k_norm"]["bias"], spec.ln_eps)
    k_i = apply_rope(k_i[:, :, None, :], cos, sin)[:, :, 0]
    # float32 from the rounded input: a product of bf16 operands accumulated
    # in float32 and not rounded, as a router's logits are
    w = jnp.einsum(
        "bsh,hj->bsj", x, p["weights_proj"]["weight"], preferred_element_type=jnp.float32
    ) * spec.weight_scale
    return q_i, k_i, w


def _rows(fn, args, bytes_at_once: int, row_live=None):
    """``fn`` over the rows (leading axis) of ``args``: at once where the
    pass's float32 temporaries are small, else a row after another, and then
    a row that ``row_live (B,)`` says has no live key (a padded row of a
    chunk pass) is not computed: its result is zeros nobody reads. (A loop
    over the live rows, not a conditional a row: a trace names a loop's ops
    once, under the loop.)"""
    if bytes_at_once <= ROWS_AT_ONCE_BYTES:
        return jax.vmap(fn)(*args)
    if row_live is None:
        return jax.lax.map(lambda a: fn(*a), args)
    take = lambda r: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False), args
    )
    one = jax.eval_shape(fn, *take(0))
    order = jnp.argsort(jnp.logical_not(row_live))  # the live rows first

    def body(i, out):
        r = order[i]
        return jax.lax.dynamic_update_index_in_dim(out, fn(*take(r)), r, 0)

    return jax.lax.fori_loop(
        0, jnp.sum(row_live, dtype=jnp.int32), body,
        jnp.zeros((row_live.shape[0],) + one.shape, one.dtype),
    )


def index_scores(q_i: jax.Array, w: jax.Array, k_all: jax.Array, row_live=None) -> jax.Array:
    """``I (B, S, W)`` float32 of a pass's queries against the row's keys
    ``k_all (B, W, D)`` (dead keys score something too: :func:`select` reads
    the live ones alone)."""
    B, S, Hn, _ = q_i.shape

    def row(q, wt, k):
        s = jnp.einsum("shd,wd->shw", q, k, preferred_element_type=jnp.float32)
        return jnp.einsum("shw,sh->sw", jax.nn.relu(s), wt)

    return _rows(row, (q_i, w, k_all), B * S * Hn * k_all.shape[1] * 4, row_live)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def select(scores: jax.Array, live: jax.Array, k: int) -> jax.Array:
    """The ``k`` live keys of largest score for each query, all of them where
    no more than ``k`` are live: ``(B, S, W)`` bool. ``scores`` float32,
    ``live`` bool, both ``(B, S, W)``. Exact: the ``k``-th largest score is
    found bit by bit (a key is kept iff its pattern is >= the threshold's),
    and of the keys AT the threshold the lowest positions are kept."""
    key = jnp.where(live, _ordered_bits(scores), jnp.uint32(0))  # a dead key: below every score

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = live & (key > thr)
    taken = live & (key >= thr)

    def with_ties(_, __):
        tied = live & (key == thr)
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room))

    # ties at a threshold are rare among float32 scores: their pass (a prefix
    # sum over the row) runs only where some query has one (a loop of 0 or 1
    # turns: a trace names a loop's ops once, under the loop)
    over = jnp.any(jnp.sum(taken, axis=-1, dtype=jnp.int32) > k)
    return jax.lax.fori_loop(0, over.astype(jnp.int32), with_ties, taken)


def chosen_positions(chosen: jax.Array, k: int) -> jax.Array:
    """``(B, S, W)`` bool -> the chosen positions ``(B, S, k)`` int32,
    ascending, ``-1`` where a query chose fewer than ``k``."""
    W = chosen.shape[-1]
    pos = jnp.where(chosen, jnp.arange(W, dtype=jnp.int32), W)
    first = -jax.lax.top_k(-pos, k)[0]
    return jnp.where(first < W, first, -1)


def attend_selected(q_c, q_pe, c_all, kr_all, chosen, scale, row_live=None):
    """The dense walk under the predicate: absorbed attention of ``q_c (B,
    S, H, r)`` / ``q_pe`` over every latent of the row ``c_all (B, W, r)`` /
    ``kr_all (B, W, d_rope)`` with ``chosen (B, S, W)`` as the mask, float32
    scores of the whole width. What attends where no kernel does: a
    whole-prompt pass (its keys are the pass's own) and a call
    ``use_latent_kernel`` refuses (off the chip, a pool off the lanes)."""
    B, S, H, _ = q_c.shape

    def row(qc, qp, c, kr, m):
        return native_latent_attention(qc[None], qp[None], c[None], kr[None], m[None, None], scale)[0]

    return _rows(
        row, (q_c, q_pe, c_all, kr_all, chosen), 2 * B * S * H * c_all.shape[1] * 4, row_live
    )


def sparse_latent_attention(
    spec: IndexerSpec, index, q_c, q_pe, c, k_r, caches, layer_idx, mask, block_table,
    kv_limit, positions, *, whole_prompt: bool, scale: float, want_positions: bool,
):
    """What a layer with an indexer attends, this pass's three streams already
    written to ``caches`` (latent, rotary key, index key): ``(attended
    latents (B, S, H, r), chosen)``. ``mask (B, 1, S, W)`` says which keys are
    live for a query. At a kv width of no more than ``index_topk`` the
    selection is every live key and the layer is dense latent attention (the
    existing kernels, unchanged); past it the indexer scores the row's live
    keys (scope ``layer.indexer``: in both step programs
    ``paged_index_scores`` over the row's live block groups in the pool where
    ``use_index_kernel`` admits the call, by what it shows: the chip, the
    stream on whole lanes, a kv width of whole lane rows; else, and for a
    whole prompt, :func:`index_scores` over the gathered bucket),
    :func:`select` picks over the bucket's width, reading the live keys alone
    (``layer.select``: what the kernel left unwritten past a row's frontier
    is never read), and the attention (``layer.attn``) runs over the picked
    alone, through
    ``latent_attend`` with the selection as a predicate, by the rule the
    dense call follows: the decode program's through the latent decode kernel
    with the selection as its mask, a chunk pass's inside the latent chunk
    kernel, which walks the row's LIVE block groups in the pool (every live
    block copied once a row, a key not picked has probability 0; no gathered
    copy of the bucket and no float32 score array reach HBM: PERF.md, PR 55);
    a whole prompt's, and a call the kernels' gate refuses, as
    :func:`attend_selected`. (The picked latents GATHERED through the block
    table, then attended, read 3 x slower in both programs on the chip, a
    sort for the positions and XLA's gather of 1 KB rows: PERF.md, PR 54; a
    kernel that copies only the blocks that hold a picked key is what both
    lack.) ``chosen``: under ``want_positions`` the positions each
    query attended ``(B, S, index_topk)``, else None. ``whole_prompt``: a
    context-encoding pass, whose own keys are its whole context."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        read_latent_cache_at_layer,
        read_stream_at_layer,
    )
    from neuronx_distributed_inference_tpu.ops.index_scores import (
        paged_index_scores,
        use_index_kernel,
    )
    from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret
    from neuronx_distributed_inference_tpu.ops.latent_attention import (
        latent_attend,
        use_latent_kernel,
    )

    q_i, k_i, w_i = index
    c_cache, kr_cache, index_cache = caches
    S, W = q_c.shape[1], mask.shape[-1]
    live = mask[:, 0]
    k = spec.topk
    if W <= k:
        with jax.named_scope("layer.attn"):
            if whole_prompt:
                latent = native_latent_attention(q_c, q_pe, c, k_r, mask, scale)
            else:
                latent = latent_attend(
                    q_c, q_pe, c_cache, kr_cache, layer_idx, mask, block_table, kv_limit,
                    positions, scale=scale, interpret=kernel_interpret(),
                )
        chosen = None
        if want_positions:
            with jax.named_scope("layer.select"):
                chosen = jnp.pad(
                    chosen_positions(live, W), ((0, 0), (0, 0), (0, k - W)), constant_values=-1
                )
        return latent, chosen

    row_live = jnp.any(live, axis=(1, 2))
    with jax.named_scope("layer.indexer"):
        if not whole_prompt and use_index_kernel(index_cache, W):
            # one past a row's last live key: what the mask shows, in both programs
            frontier = jnp.max(jnp.where(live, jnp.arange(1, W + 1, dtype=jnp.int32), 0), axis=(1, 2))
            scores = paged_index_scores(
                q_i, w_i, index_cache, layer_idx, block_table, frontier,
                interpret=kernel_interpret(),
            )
        else:
            k_all = k_i if whole_prompt else read_stream_at_layer(index_cache, layer_idx, block_table)
            scores = index_scores(q_i, w_i, k_all, row_live)
    with jax.named_scope("layer.select"):
        picked = select(scores, live, k)
        chosen = chosen_positions(picked, k) if want_positions else None
    with jax.named_scope("layer.attn"):
        if whole_prompt:
            latent = attend_selected(q_c, q_pe, c, k_r, picked, scale, row_live)
        elif use_latent_kernel(c_cache, kr_cache, S, W):
            latent = latent_attend(
                q_c, q_pe, c_cache, kr_cache, layer_idx, mask, block_table, kv_limit,
                positions, picked, scale=scale, interpret=kernel_interpret(),
            )
        else:
            c_all, kr_all = read_latent_cache_at_layer(c_cache, kr_cache, layer_idx, block_table)
            latent = attend_selected(q_c, q_pe, c_all, kr_all, picked, scale, row_live)
    return latent, chosen
