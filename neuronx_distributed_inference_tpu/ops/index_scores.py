"""The indexer's scores, read off the pool where the index keys lie.

A layer with an indexer (modules/sparse_index.py) keeps one index key a
token, ``index_head_dim`` wide, in the pool's third stream
(``BlockKVCache.extra``: ``(L, NB+1, 1, bs, D)``, one ``(bs, D)`` block a
(layer, pool block)). Per query ``t`` of a row and key ``s``::

    I[b, t, s] = sum_j w[b, t, j] * relu(q_I[b, t, j] . k_I[s])        float32

:func:`paged_index_scores` computes it on the plan of the latent kernels
beside it (``ops/latent_attention.py``: grid = one ROW a step, an in-kernel
loop over the row's live groups of ``P`` pool blocks copied by hand into two
VMEM slots through the block table, a row's last group starting the next
live row's first): no block past a row's frontier is copied or multiplied,
a row with no live key costs nothing, and no gathered copy of the kv bucket
reaches HBM. A group's keys ``(P * bs, D)`` meet the row's queries a TILE of
the index heads at a time (``rows``: the most score rows, heads of a tile x
the pass's positions, of one product; the float32 scores of every head over
a group would not stand in VMEM at 128 positions): the products on the
matrix unit as the keys are stored, accumulated in float32; the rectifier,
the head weights and the sum over the heads in float32 on the vector unit.
A group's scores leave through their own two slots, copied to ``scores[b, :,
group]`` while the next group is multiplied.

WHAT IS LEFT UNWRITTEN: ``scores[b, :, s]`` for every ``s`` past the last
group that holds a live key of row ``b`` (all of an empty row) is whatever
the buffer held, and inside that last group the columns past the row's
frontier are scores against what an earlier group left in the slot. A caller
reads the live keys alone, as ``sparse_index.select`` does.

A kv width that no group divides (16896 = 16.5 x 1024) is walked with its
LAST group drawn back to end at the table's end: it overlaps the one before
it, and the keys both hold are scored twice, to the same numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops import decode_attention as _da
from neuronx_distributed_inference_tpu.ops.kernel_mode import on_tpu
from neuronx_distributed_inference_tpu.ops.latent_attention import _copies, _live_from, _row_spec
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

#: the kernel's name: the pallas call's (a trace names the device op by it),
#: the tuning table's and the kernel registry's
KERNEL = "paged_index_scores"

#: the kernel's tiles where the tuning table has no entry under its name for
#: the pool block: the most score rows of one product (as many of the index
#: heads as divide their count and fit, x the pass's positions) and the tokens
#: a group of pool blocks holds. PERF.md, PR 67, has the sweep (256 / 512 /
#: 1024 / 2048 rows x 512 / 1024 / 2048 tokens at both programs' shapes)
Q_ROWS = 1024
GROUP_TOKENS = 1024


def _tile(param: str, fallback: int, bs: int, head_dim: int, cache_dtype) -> int:
    """A tile under the kernel's name in the tuning table, by the pool
    block's shape (``blk1x32x128``: one index key of 128 a token)."""
    return tile_default(
        KERNEL, f"blk1x{bs}x{head_dim}", jnp.dtype(cache_dtype).name, param, fallback
    )


def blocks_per_group(bs: int, head_dim: int, cache_dtype, max_blocks: int) -> int:
    """Pool blocks the kernel copies and scores per pass of its loop (its
    ``P``): a power of two (a group's live count is taken apart by bits),
    never more than the block table is wide."""
    p = _tile("pages", GROUP_TOKENS // bs, bs, head_dim, cache_dtype)
    p = max(1, min(p, max_blocks))
    return 1 << (p.bit_length() - 1)


def use_index_kernel(index_cache, kv_width: int) -> bool:
    """Gate of the kernel, on what the call shows: the chip, a stream whose
    block lies on whole lanes and whole sublane tiles, and a kv width of whole
    blocks and whole lane rows that holds a group (a group's scores leave as
    whole lane rows of the bucket's width)."""
    bs, d = index_cache.shape[3:]
    sublanes = 32 // jnp.dtype(index_cache.dtype).itemsize
    if not (on_tpu() and d % 128 == 0 and bs % sublanes == 0 and kv_width % bs == 0):
        return False
    group = blocks_per_group(bs, d, index_cache.dtype, kv_width // bs) * bs
    return kv_width % 128 == 0 and group % 128 == 0


def index_blocks_walked(live_blocks, max_blocks: int, index_cache) -> int:
    """Block-table entries of index keys a pass scores for rows whose live
    keys hold ``live_blocks`` (one count a row) blocks of a table
    ``max_blocks`` wide over the stream ``index_cache`` (its shape and dtype
    are read, nothing else): where the gate admits the width, whole groups up
    to each row's frontier and the table's width at most; every entry of the
    table where the bucket is gathered instead. Host code calls this
    (``ServingSession`` counts it a layer with an indexer)."""
    bs, d = index_cache.shape[3:]
    if not use_index_kernel(index_cache, max_blocks * bs):
        return max_blocks * len(live_blocks)
    P = blocks_per_group(bs, d, index_cache.dtype, max_blocks)
    return sum(min(-(-n // P) * P, max_blocks) for n in live_blocks)


def _key_copies(bt_ref, end_ref, k_hbm, k_buf, sems, *, layer, P):
    """``(start, start_whole, wait)``: ``start`` and ``wait`` are
    ``latent_attention._copies``' for the one stream of index keys;
    ``start_whole`` issues a WHOLE group's ``P`` descriptors from
    straight-line code and falls to ``start`` for a row's last, partial one.
    A block here is 8 KB, and what a copy costs is its descriptor on the
    scalar core: 36 ns from the loop, 23 ns unrolled (PERF.md, PR 67: the
    decode program walks ~12.5k blocks a layer). The kernel takes it in ONE
    place, the prefetch inside its group loop (a row's very first group is
    one of hundreds): every descriptor traced is ~2 ms of lowering in each of
    the 36 places the cell's programs hold the kernel, which is set-up."""
    start, wait = _copies(bt_ref, end_ref, ((k_hbm, k_buf),), sems, layer=layer, P=P)

    def start_whole(row, group, slot):
        whole = end_ref[row] - group * P >= P

        @pl.when(whole)
        def _():
            for p in range(P):
                page = bt_ref[row, group * P + p]
                pltpu.make_async_copy(
                    k_hbm.at[layer, page, 0], k_buf.at[slot, p], sems.at[0, slot]
                ).start()

        @pl.when(jnp.logical_not(whole))
        def _():
            start(row, group, slot)

    return start, start_whole, wait


def _kernel(
    li_ref, bt_ref, end_ref, live_from_ref,
    q_ref,  # (1, NT, R, D): R = ht heads x Sp positions, head-major
    w_ref,  # (1, NT, R, 1) float32
    k_hbm,  # (L, NB+1, 1, bs, D)
    o_hbm,  # (B, Sp, W) float32
    k_buf, o_buf, sems, o_sems, state_ref,
    *, P, q_dtype,
):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    NT, R, _ = q_ref.shape[1:]
    D = k_buf.shape[3]
    Sp, G = o_buf.shape[1:]
    W = o_hbm.shape[2]
    start, start_whole, wait = _key_copies(
        bt_ref, end_ref, k_hbm, k_buf, sems, layer=li_ref[0], P=P
    )

    def out_copy(slot, col0):
        return pltpu.make_async_copy(
            o_buf.at[slot], o_hbm.at[b, :, pl.ds(col0, G)], o_sems.at[slot]
        )

    @pl.when(b == 0)
    def _first():
        state_ref[0] = 0  # the slot the next group's keys land in
        state_ref[1] = 0  # groups whose scores have left, over all the rows
        row = live_from_ref[0]

        @pl.when(row < B)
        def _():
            start(row, 0, 0)

    hi = (end_ref[b] + P - 1) // P

    def head_tile(n, k_t):
        s = _da._dot_tile(q_ref[0, n].astype(q_dtype), k_t, 1)  # (R, G)
        s = jnp.maximum(s, 0.0) * w_ref[0, n]
        if Sp == 1:
            return jnp.sum(s, axis=0, keepdims=True)
        return jnp.sum(s.reshape(R // Sp, Sp, G), axis=0)

    def group(g, _):
        slot = state_ref[0]
        last = g == hi - 1
        nrow = jnp.where(last, live_from_ref[b + 1], b)

        @pl.when(nrow < B)
        def _prefetch():
            start_whole(nrow, jnp.where(last, 0, g + 1), 1 - slot)

        wait(b, g, slot)
        state_ref[0] = 1 - slot
        left = state_ref[1]
        oslot = left % 2

        @pl.when(left >= 2)
        def _():  # the scores that left this slot two groups ago have landed
            out_copy(oslot, 0).wait()

        k_t = k_buf[slot].reshape(G, D)
        o_buf[oslot] = head_tile(0, k_t)

        def more(n, _):
            o_buf[oslot] += head_tile(n, k_t)

        jax.lax.fori_loop(1, NT, more, None)
        # the table's last group is drawn back to end at its end
        out_copy(oslot, pl.multiple_of(jnp.minimum(g * G, W - G), 128)).start()
        state_ref[1] = left + 1

    jax.lax.fori_loop(0, hi, group, None)

    @pl.when(b == B - 1)
    def _drain():
        left = state_ref[1]
        for back in (1, 2):
            @pl.when(left >= back)
            def _(back=back):
                out_copy((left - back) % 2, 0).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_index_scores(
    q_i: jax.Array,  # (B, Sq, Hn, D) the index heads' queries
    w: jax.Array,  # (B, Sq, Hn) float32 head weights
    index_cache: jax.Array,  # (L, NB+1, 1, bs, D) the pool's index-key stream
    layer_idx: jax.Array,
    block_table: jax.Array,  # (B, MB)
    frontier: jax.Array,  # (B,) one past a row's last live key
    *,
    interpret: bool = False,
) -> jax.Array:
    """``I (B, Sq, MB * bs)`` float32 of a pass's queries against the index
    keys as they lie in the pool, a row's live block groups alone. Written:
    every column of a group that holds a key under ``frontier[b]``, and of
    those the ones under the frontier are the scores. NOT written: the
    columns of the groups past it, all of a row whose frontier is 0 (the
    module docstring); the caller reads the live keys alone."""
    B, Sq, Hn, D = q_i.shape
    bs = index_cache.shape[3]
    MB = block_table.shape[1]
    W = MB * bs
    P = blocks_per_group(bs, D, index_cache.dtype, MB)
    G = P * bs
    NG = -(-MB // P)
    Sp = 1 if Sq == 1 else -(-Sq // 8) * 8  # whole sublane tiles
    rows = _tile("rows", Q_ROWS, bs, D, index_cache.dtype)
    ht = max(d for d in range(1, Hn + 1) if Hn % d == 0 and (d == 1 or d * Sp <= rows))
    NT, R = Hn // ht, ht * Sp

    def tiles(x):  # (B, Sq, Hn, n) -> (B, NT, R, n): row (head of the tile, position)
        x = jnp.pad(x, ((0, 0), (0, Sp - Sq), (0, 0), (0, 0)))
        x = x.reshape(B, Sp, NT, ht, x.shape[-1])
        return x.transpose(0, 2, 3, 1, 4).reshape(B, NT, R, x.shape[-1])

    end = jnp.clip(-(-frontier.astype(jnp.int32) // bs), 0, MB)
    bt = block_table.astype(jnp.int32)
    back = NG * P - MB  # how far the last group is drawn back: the table as the walk meets it
    if back:
        bt = jnp.concatenate([bt[:, : (NG - 1) * P], bt[:, MB - P :]], axis=1)
        end = jnp.where(end > (NG - 1) * P, end + back, end)
    q, wt = tiles(q_i), tiles(w.astype(jnp.float32)[..., None])
    item = jnp.dtype(index_cache.dtype).itemsize
    vmem = (
        2 * G * D * item  # the keys, two slots
        + 2 * NT * R * (D * q_i.dtype.itemsize + 128 * 4)  # q and the lane-padded weights, pipelined
        + 2 * Sp * G * 4  # a group's scores, two slots
        + 6 * R * G * 4  # a head tile's products and what is made from them
    )
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    out = _da._common_call(
        functools.partial(
            _kernel, P=P, q_dtype=jnp.bfloat16 if q_i.dtype == jnp.bfloat16 else jnp.float32
        ),
        grid=(B,),
        in_specs=[
            _row_spec(q.shape[1:]), _row_spec(wt.shape[1:]), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        operands=([li, bt, end, _live_from(end)], [q, wt, index_cache]),
        out_shape=jax.ShapeDtypeStruct((B, Sp, W), jnp.float32),
        scratch=[
            pltpu.VMEM((2, P, bs, D), index_cache.dtype),
            pltpu.VMEM((2, Sp, G), jnp.float32),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
        interpret=interpret,
        name=KERNEL,
        # rows in order: a row's last group starts the next live row's copies
        semantics=("arbitrary",),
        vmem_limit_bytes=max(32 * 2**20, min(100 * 2**20, 2 * vmem)),
    )
    return out[:, :Sq]
