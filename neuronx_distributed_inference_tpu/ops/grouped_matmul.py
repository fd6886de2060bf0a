"""Grouped matmul over expert-sorted rows: each expert's weights read once,
where the layer scan holds them.

The prefill-sized expert layer (modules/moe.expert_mlps_grouped) sorts its
``T * top_k`` token-replica rows by expert and multiplies each group by its
expert's matrix. ``jax.lax.ragged_dot`` lowers that at 2.9-5.6 x the weight
stream on a v5e, and under ``lax.scan`` it is fed a COPY of the layer's
``(E, in, out)`` stack, because a custom call cannot have the scan's
``dynamic-slice`` fused into it. This kernel takes the stacked
``(L, E, in, out)`` weights and the layer's index as scalar prefetch and its
block index map picks ``(layer, expert, tile)``: the stack is a loop-invariant
operand of the scan and never copied (the precedent is the paged attention
kernels, which take the stacked pool and a layer index).

The form is ``jax.experimental.pallas.ops.tpu.megablox.gmm``'s, cut to what
serving needs: rows are cut into tiles of ``tm``; a VISIT is one (row tile,
group) pair whose rows intersect, so a tile that straddles two groups is
visited once a group with the other's rows masked at the store, an empty
group is never visited, and every row of every group is computed (dropless).
The grid is (output tiles, visits, contraction tiles) with the visits' group
and row tile as scalar prefetch. With the whole contraction in one tile (every
benchmarked shape) a group's consecutive visits keep the weight block's index,
so the pipeline fetches each expert's ``(in, tn)`` tile ONCE however many row
tiles the group spans. bf16 (or float32) operands, a float32 accumulator, the
result in the rows' dtype: ``ragged_dot``'s precision.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops.kernel_mode import GROUPED_ROW_TILE
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

#: most bytes one weight block may hold: it is double-buffered beside the
#: row tile and the output tile, under the kernel's VMEM limit (a v5e has
#: 128 MiB; 2048 x 1408 bf16, kimi's gate tile, is 5.5 MiB)
_WEIGHT_BLOCK_BYTES = 6 << 20
_VMEM_LIMIT = 40 << 20


def visit_plan(group_sizes: jax.Array, m: int, tm: int):
    """The visits of a grouped product over ``m`` sorted rows in tiles of
    ``tm`` (``m % tm == 0``): ``(group_offsets (E + 1,), group_ids (V,),
    tile_ids (V,), num_visits (1,))`` with ``V = m // tm + E - 1``, the most
    there can be. Visit ``v < num_visits`` multiplies row tile ``tile_ids[v]``
    by expert ``group_ids[v]``; visits come in row order, so a tile's visits
    and a group's are consecutive. The tail repeats the last visit."""
    E = group_sizes.shape[0]
    tiles_m = m // tm
    V = tiles_m + E - 1
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # tiles a group touches: from its first row's tile to its last row's
    first = starts // tm
    touched = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    num_visits = jnp.sum(touched)
    group_ids = jnp.repeat(
        jnp.arange(E, dtype=jnp.int32), touched, total_repeat_length=V
    )
    # a visit's tile: its group's first tile + its ordinal among the group's
    before = jnp.cumsum(touched) - touched  # visits before each group's
    v = jnp.arange(V, dtype=jnp.int32)
    tile_ids = first[group_ids] + v - before[group_ids]
    last = jnp.maximum(num_visits - 1, 0)
    live = v < num_visits
    group_ids = jnp.where(live, group_ids, group_ids[last])
    tile_ids = jnp.where(live, tile_ids, tile_ids[last])
    return offsets, group_ids, tile_ids.astype(jnp.int32), num_visits.reshape(1)


def _gmm_kernel(
    # scalar prefetch
    layer_ref,  # (1,)
    offsets_ref,  # (E + 1,) first row of each group, then m
    group_ref,  # (V,) a visit's expert
    tile_ref,  # (V,) a visit's row tile
    visits_ref,  # (1,)
    # blocks
    x_ref,  # (tm, tk)
    w_ref,  # (tk, tn): the visit's expert, in the layer's stack
    o_ref,  # (tm, tn)
    *scratch,  # (tm, tn) float32 where the contraction is tiled
    tm: int,
    tiles_k: int,
    out_in: bool = False,  # the weight block is (tn, tk)
):
    del layer_ref
    v = pl.program_id(1)
    k = pl.program_id(2)

    def product():
        return jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1 if out_in else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def store(acc):
        g = group_ref[v]
        rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        # the tile's other rows are another visit's: kept as they stand
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(v < visits_ref[0])
    def _visit():
        if tiles_k == 1:
            store(product())
            return
        (acc_ref,) = scratch

        @pl.when(k == 0)
        def _first():
            acc_ref[...] = product()

        @pl.when(k > 0)
        def _rest():
            acc_ref[...] += product()

        @pl.when(k == tiles_k - 1)
        def _last():
            store(acc_ref[...])


def _tiles(m: int, K: int, N: int, itemsize: int):
    """(tm, tk, tn) from the shapes. ``tm`` 128: a visit latches every weight
    tile into the MXU once and streams ``tm`` rows through it, so up to the
    MXU's 128 rows a visit costs what latching costs (about what the tile's
    fetch costs) and above it more, while a smaller tile only adds visits
    (PERF.md section 6, PR 45: 256 reads the same, nothing reads better).
    ``tn``: the widest multiple of 128 lanes that divides ``N`` and keeps a
    weight block of the WHOLE contraction under the budget, one fetch a group
    (the widest read fastest at every benchmarked shape: fewer grid steps,
    longer rows a DMA); where even 128 lanes do not fit, the contraction is
    tiled and a group's every visit fetches."""
    tm = min(tile_default("grouped_matmul", f"k{K}_n{N}", "bfloat16", "tm", GROUPED_ROW_TILE), m)
    lanes = [t for t in range(128, N + 1, 128) if N % t == 0] or [N]
    fits = [t for t in lanes if K * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    if fits:
        return tm, K, fits[-1]
    tn = lanes[0]
    tk = K
    while tk % 2 == 0 and (tk // 2) % 128 == 0 and tk * tn * itemsize > _WEIGHT_BLOCK_BYTES:
        tk //= 2
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("out_in", "interpret"))
def grouped_matmul(
    x_rows: jax.Array,  # (R, in) rows sorted by group
    weight: jax.Array,  # (E, in, out), or (L, E, in, out) with ``layer``
    group_sizes: jax.Array,  # (E,) int32, summing to R or less
    layer: Optional[jax.Array] = None,  # () int32 index into the stack
    *,
    out_in: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``out[r] = x_rows[r] @ weight[(layer,) group of r]`` -> (R, out) in the
    rows' dtype. The weights are read where they lie: one ``(in, tn)`` tile of
    one expert a fetch, no expert of an empty group, nothing copied. Rows past
    the last group (``group_sizes`` may sum to less than R: a held share of
    the experts) are in no group and are never written. ``out_in``: the
    weight is held ``(..., out, in)`` and multiplied transposed, one block of
    a group's whole ``(out, in)`` (an ``out`` off the lanes, 1856, cannot be
    tiled) or, over the budget, of ``(out, tk)``."""
    if weight.ndim == 3:
        weight, layer = weight[None], jnp.zeros((), jnp.int32)
    R, K = x_rows.shape
    if out_in:
        _, E, N, Kw = weight.shape
    else:
        _, E, Kw, N = weight.shape
    if Kw != K or weight.dtype != x_rows.dtype:
        raise ValueError(
            f"grouped_matmul: rows {x_rows.shape} {x_rows.dtype} against weights "
            f"{weight.shape} {weight.dtype}"
        )
    if out_in:
        tm, tn, tk = _tiles(R, K, N, weight.dtype.itemsize)[0], N, K
        while tk % 256 == 0 and tk * tn * weight.dtype.itemsize > 2 * _WEIGHT_BLOCK_BYTES:
            tk //= 2
    else:
        tm, tk, tn = _tiles(R, K, N, weight.dtype.itemsize)
    tm = -(-tm // 16) * 16
    m = -(-R // tm) * tm
    if m != R:  # the padding belongs to no group: never stored, cut off below
        x_rows = jnp.pad(x_rows, ((0, m - R), (0, 0)))
    offsets, group_ids, tile_ids, num_visits = visit_plan(group_sizes, m, tm)
    tiles_k = K // tk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, group_ids.shape[0], tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda n, v, k, li, off, g, t, nv: (t[v], k)),
            pl.BlockSpec(
                (None, None, tn, tk), lambda n, v, k, li, off, g, t, nv: (li[0], g[v], n, k)
            ) if out_in else pl.BlockSpec(
                (None, None, tk, tn), lambda n, v, k, li, off, g, t, nv: (li[0], g[v], k, n)
            ),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda n, v, k, li, off, g, t, nv: (t[v], n)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] if tiles_k > 1 else [],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k, out_in=out_in),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, N), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * K * N,
            bytes_accessed=(E * K * N + m * K * (N // tn) + m * N) * weight.dtype.itemsize,
            transcendentals=0,
        ),
        name="grouped_matmul",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, group_ids, tile_ids, num_visits,
      x_rows, weight)
    return out[:R] if m != R else out
