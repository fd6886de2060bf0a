"""Pallas ragged paged attention: one kernel for mixed prefill+decode batches.

The serving path historically dispatched separate context-encoding and
token-generation programs per step and interleaved them on the host.
Following *Ragged Paged Attention: A High-Performance and Flexible LLM
Inference Kernel for TPU* (PAPERS.md), this kernel processes a RAGGED batch
against the paged KV cache in a single launch: each row is described by
``(query_start, query_len, context_len)`` — prefill chunks carry
``query_len > 1``, decode rows ``query_len == 1`` — and all rows' query
tokens are packed along one axis.

Packing contract (enforced by the host packer, ``MixedStepRunner.prepare``):

- every row's ``query_start`` is a multiple of :data:`RAGGED_Q_TILE`, so one
  q tile never spans two rows (the grid maps tile -> row via a scalar-
  prefetched ``tile_row`` table instead of the full per-token search the
  reference kernel does in its DMA schedule);
- padded slots between segments carry position ``-1`` (masked out of the
  softmax, their cache writes dropped via slot ``-1``).

Grid: ``(Hq, q_tiles, kv_blocks)`` — the KV BlockSpec index map reads the
per-row ``block_table`` through ``tile_row`` to DMA the right cache block
per step (no gather materialization); tiles above the causal frontier or
beyond a row's populated length are skipped via ``pl.when`` on scalar-
prefetched per-tile maxima, as ``ops/paged_flash_attention.py`` still does
at a head_dim off the 128 lanes (at head_dim 128 that kernel walks a row's
live groups of blocks in a loop and takes no step past its frontier; this
one has not been moved to that plan: no benchmark cell serves it).

Quantized caches reuse the int8/fp8 code/scale convention of the paged
flash kernel: the K dequant factor folds into q before the launch (scaling
QK^T exactly), the V factor multiplies the per-head output after the online
softmax — narrow code tiles are DMA'd and converted in-register; no
dequantized cache is ever materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30

#: q-tile granularity of the packed layout: row starts must align to it (a
#: tile belongs to exactly ONE row). 16 keeps bf16 q tiles Mosaic-friendly
#: ((16, 128) native tiling); a decode row therefore occupies one mostly-
#: padded 16-slot tile — masked VPU work, not extra KV DMA, and far less
#: waste than the per-phase full-batch padding the split dispatch paid.
RAGGED_Q_TILE = 16


def _use_ragged_kernel(spec, total_q: int) -> bool:
    """Kernel/native gate for the ragged mixed-step attention — consolidated
    in ops/kernel_mode.py. NO single-shard condition: tp>1 meshes dispatch
    the kernel per-shard via shard_map (see :func:`ragged_attention`)."""
    from neuronx_distributed_inference_tpu.ops.kernel_mode import use_ragged

    return use_ragged(spec, total_q, RAGGED_Q_TILE)


def _ragged_kernel(
    # scalar prefetch
    tile_row_ref,  # (NT,) int32 owning row per q tile
    tile_max_ref,  # (NT,) int32 max absolute q position per tile (-1 = pad)
    row_start_ref,  # (R,) int32 packed offset per row
    row_len_ref,  # (R,) int32 query tokens per row
    ctx_len_ref,  # (R,) int32 total kv length per row (incl. new tokens)
    block_table_ref,  # (R, MB) int32
    # blocked operands
    q_ref,  # (1, tq, D) one head's q tile
    k_ref,  # (1, 1, bs, D) one head's cache block
    v_ref,  # (1, 1, bs, D)
    o_ref,  # (1, tq, D)
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    tq: int,
    bs: int,
    nkv: int,
):
    t = pl.program_id(1)
    j = pl.program_id(2)
    r = tile_row_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_start = j * bs
    # skip blocks above the tile's causal frontier or beyond the row's
    # populated cache (padded tiles carry tile_max == -1: nothing runs)
    run = (kv_start <= tile_max_ref[t]) & (kv_start < ctx_len_ref[r])

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (tq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (tq, bs)

        # per-token absolute position from the scalar descriptors alone (the
        # packed-positions array would need a Mosaic-hostile (1, tq) block):
        # in-row offset of packed slot t*tq+i, then position = the row's
        # first new-token position + offset; offsets past row_len are pad
        offs = (
            t * tq
            + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 0)
            - row_start_ref[r]
        )
        q_pos = (ctx_len_ref[r] - row_len_ref[r]) + offs
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        mask = (
            (kv_pos <= q_pos)
            & (kv_pos < ctx_len_ref[r])
            & (offs < row_len_ref[r])
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # fully-masked rows: m_new = NEG_INF -> exp(0) = 1; zero via the mask
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # (bs, D)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, :, :] = (acc_scr[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "n_rep", "tq", "interpret")
)
def ragged_paged_attention(
    q: jax.Array,  # (T, Hq, D) packed queries, row starts tq-aligned
    k_cache: jax.Array,  # (NB+1, Hkv, bs, D) one layer's head-major paged cache
    v_cache: jax.Array,
    block_table: jax.Array,  # (R, MB) int32
    row_start: jax.Array,  # (R,) int32 packed offset of each row's segment
    row_len: jax.Array,  # (R,) int32 query tokens per row (0 = inactive)
    ctx_len: jax.Array,  # (R,) int32 total kv length per row (incl. new)
    *,
    scale: float,
    n_rep: int,
    tq: int = None,
    k_scale: jax.Array = None,  # (Hkv,) per-head dequant factor (scale/qmax)
    v_scale: jax.Array = None,  # for int8/fp8 caches; None = plain cache
    interpret: bool = False,
) -> jax.Array:
    """One launch of mixed prefill-chunk + decode attention off the paged
    cache. Returns (T, Hq, D): the i-th query token of row r sits at
    absolute position ``ctx_len[r] - row_len[r] + i`` and attends cache
    positions p <= its own with p < ctx_len[r] — prior context plus causal
    among the new tokens (write-then-attend as everywhere else). Everything
    the kernel needs rides the scalar-prefetched descriptors; there is no
    per-token operand besides q itself.
    """
    T, Hq, D = q.shape
    _, Hkv, bs, width = k_cache.shape
    # a pool of several heads a row comes through _dispatch_ragged_kernel,
    # which lays the queries in their head's lanes
    assert width == D, f"q of {D} lanes against pool rows of {width}"
    R, MB = block_table.shape
    if tq is None:
        # default through the tuning table (KERN704). The packing contract
        # pins tq to a divisor of RAGGED_Q_TILE (row starts are
        # RAGGED_Q_TILE-aligned, so any divisor tile never spans rows);
        # KERN702 checks the committed entry against that arithmetic.
        tq = tile_default(
            "ragged_paged_attention", "mixed", k_cache.dtype, "tq", RAGGED_Q_TILE
        )
    if T % tq:
        raise ValueError(f"packed q length {T} not a multiple of tq={tq}")
    NT = T // tq

    out_dtype = q.dtype
    if k_scale is not None:
        q = q.astype(jnp.float32) * jnp.repeat(k_scale, n_rep)[None, :, None]
    qt = jnp.swapaxes(q, 0, 1)  # (Hq, T, D)

    row_start = row_start.astype(jnp.int32)
    row_len = row_len.astype(jnp.int32)
    ctx_len = ctx_len.astype(jnp.int32)
    # tile -> owning row (starts are tq-aligned so each tile has exactly one;
    # tiles past every row keep 0 and are skipped via tile_max == -1)
    t0 = jnp.arange(NT, dtype=jnp.int32) * tq
    hits = (t0[:, None] >= row_start[None, :]) & (
        t0[:, None] < (row_start + row_len)[None, :]
    )
    tile_row = jnp.argmax(hits, axis=1).astype(jnp.int32)
    # per-tile causal frontier: the highest absolute position among the
    # tile's valid tokens; -1 marks a fully-padded tile (nothing runs)
    last_off = jnp.minimum(
        jnp.take(row_len, tile_row) - 1,
        t0 + tq - 1 - jnp.take(row_start, tile_row),
    )
    ctx_first = jnp.take(ctx_len, tile_row) - jnp.take(row_len, tile_row)
    tile_max = jnp.where(
        jnp.any(hits, axis=1), ctx_first + last_off, -1
    ).astype(jnp.int32)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, tq=tq, bs=bs, nkv=MB
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(Hq, NT, MB),
        in_specs=[
            pl.BlockSpec(
                (1, tq, D), lambda h, t, j, tr, tm, rs, rl, cl, bt: (h, t, 0)
            ),
            # head-major cache: one head's block is a (bs, D) tile addressed
            # through the OWNING ROW's block table
            pl.BlockSpec(
                (1, 1, bs, D),
                lambda h, t, j, tr, tm, rs, rl, cl, bt: (
                    bt[tr[t], j], h // n_rep, 0, 0,
                ),
            ),
            pl.BlockSpec(
                (1, 1, bs, D),
                lambda h, t, j, tr, tm, rs, rl, cl, bt: (
                    bt[tr[t], j], h // n_rep, 0, 0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, tq, D), lambda h, t, j, tr, tm, rs, rl, cl, bt: (h, t, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hq, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        tile_row,
        tile_max,
        row_start,
        row_len,
        ctx_len,
        block_table.astype(jnp.int32),
        qt,
        k_cache,
        v_cache,
    )
    out = jnp.swapaxes(out, 0, 1)  # (T, Hq, D)
    if v_scale is not None:
        out = (out * jnp.repeat(v_scale, n_rep)[None, :, None]).astype(out_dtype)
    return out


def _dispatch_ragged_kernel(
    q3, k_l, v_l, block_table, row_start, row_len, ctx_len,
    *, scale, n_rep, k_scale, v_scale, interpret,
):
    """Launch the ragged kernel, per-shard over the model-parallel axes when
    the ambient mesh has any (ISSUE 17 tentpole a).

    The kernel grid is already head-parallel: q's head axis and the paged
    cache's kv-head axis are sharded over the model group (the same
    ``sharding.TENSOR`` axes the projection weights use), the descriptors
    (block table, row start/len, context lengths) are replicated host
    metadata, and GQA replication (parallel/sharding.GQASharding) guarantees
    both head counts divide the degree — so ``shard_over_heads`` runs the
    identical per-head math on each shard with NO cross-shard collectives
    inside, and the tp>1 stream stays byte-identical to tp=1 and to the
    native fallback (pinned in tests/test_ragged_tp.py)."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        fold_queries,
        pool_fold,
        unfold_outputs,
    )
    from neuronx_distributed_inference_tpu.parallel.sharding import (
        shard_over_heads,
    )

    def per_shard(q_s, k_s, v_s, bt, rs, rl, cl, ks_s, vs_s):
        # a pool of g KV heads a 128-lane row (block_kvcache.kv_streams): the
        # queries laid in their own head's lanes, the output cut back
        g = pool_fold(k_s.shape[-1], q_s.shape[-1])
        out = ragged_paged_attention(
            fold_queries(q_s, g, n_rep), k_s, v_s, bt, rs, rl, cl,
            scale=scale, n_rep=g * n_rep, k_scale=ks_s, v_scale=vs_s,
            interpret=interpret,
        )
        return unfold_outputs(out, g, n_rep)

    return shard_over_heads(
        per_shard,
        (q3, k_l, v_l, block_table, row_start, row_len, ctx_len, k_scale, v_scale),
        in_heads=(1, 1, 1, None, None, None, None, 0, 0), out_heads=1,
    )


def ragged_attention_native(
    q: jax.Array,  # (T, Hq, D)
    k_cache,  # full stacked paged cache (L, NB+1, Hkv, bs, D) or QuantizedKV
    v_cache,
    layer_idx: jax.Array,
    block_table: jax.Array,  # (R, MB)
    positions: jax.Array,  # (T,)
    row_start: jax.Array,  # (R,)
    row_len: jax.Array,  # (R,)
    ctx_len: jax.Array,  # (R,)
    aspec,
) -> jax.Array:
    """Native reference/fallback: gather each row's blocks into a contiguous
    view (dequantizing quantized codes after the gather, like every native
    paged path), route each packed token to its row, and run the standard
    masked-softmax attention with the token axis as the batch — the exact
    math the legacy split dispatch runs, so greedy serving outputs are
    byte-identical across the dispatch modes."""
    from neuronx_distributed_inference_tpu.modules.attention import (
        attention_decode,
    )
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        read_block_cache_at_layer,
    )

    T = q.shape[0]
    k_r, v_r = read_block_cache_at_layer(
        k_cache, v_cache, layer_idx, block_table, head_dim=q.shape[-1]
    )
    W = k_r.shape[1]
    tok = jnp.arange(T, dtype=jnp.int32)
    hits = (tok[:, None] >= row_start[None, :]) & (
        tok[:, None] < (row_start + row_len)[None, :]
    )
    row_id = jnp.argmax(hits, axis=1)  # (T,) 0 for padded slots (masked below)
    k_tok = jnp.take(k_r, row_id, axis=0)  # (T, W, Hkv, D)
    v_tok = jnp.take(v_r, row_id, axis=0)
    cols = jnp.arange(W, dtype=jnp.int32)[None, None, None, :]
    qpos = positions[:, None, None, None]
    mask = (
        (cols <= qpos)
        & (cols < jnp.take(ctx_len, row_id)[:, None, None, None])
        & (qpos >= 0)
    )  # (T, 1, 1, W)
    out = attention_decode(q[:, None], k_tok, v_tok, mask, aspec)
    return out[:, 0]


def ragged_attention(
    q: jax.Array,  # (1, T, Hq, D) — the mixed step's batch-1 packed layout
    k_cache,  # full stacked paged cache (or QuantizedKV stream)
    v_cache,
    layer_idx: jax.Array,
    block_table: jax.Array,
    positions: jax.Array,  # (1, T)
    row_start: jax.Array,
    row_len: jax.Array,
    ctx_len: jax.Array,
    aspec,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Layer-level dispatch for the mixed-step program: the Pallas ragged
    kernel when eligible (DMA'ing this layer's raw code blocks with fused
    dequant for quantized caches), else the native gather fallback so every
    config runs on CPU. Returns (1, T, Hq, D)."""
    from neuronx_distributed_inference_tpu.modules.kvcache import (
        QuantizedKV,
        layer_dequant_factors,
    )

    q3 = q[0]
    T = q3.shape[0]
    if _use_ragged_kernel(aspec, T):
        ks = vs = None
        if isinstance(k_cache, QuantizedKV):
            ks = layer_dequant_factors(k_cache, layer_idx)
            vs = layer_dequant_factors(v_cache, layer_idx)
            k_arr, v_arr = k_cache.data, v_cache.data
        else:
            k_arr, v_arr = k_cache, v_cache
        k_l = jax.lax.dynamic_index_in_dim(k_arr, layer_idx, axis=0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(v_arr, layer_idx, axis=0, keepdims=False)
        out = _dispatch_ragged_kernel(
            q3, k_l, v_l, block_table, row_start, row_len, ctx_len,
            scale=aspec.softmax_scale,
            n_rep=aspec.num_heads // aspec.num_kv_heads,
            k_scale=ks, v_scale=vs,
            interpret=interpret,
        )
    else:
        out = ragged_attention_native(
            q3, k_cache, v_cache, layer_idx, block_table, positions[0],
            row_start, row_len, ctx_len, aspec,
        )
    return out[None]
