"""Pallas paged (block-KV) flash attention for chunked / prefix prefill.

TPU-native re-design of the reference's schedule-driven paged flash kernel
(reference: modules/chunked_prefill/flash_pa_with_schedule.py:157 +
flash_attn_core.py:70, driven by the host GridTileScheduler,
scheduler.py:274-420).

Design: the reference builds an explicit host-side tile schedule because NKI
kernels address SBUF manually. Here the schedule is a loop inside the kernel,
on the plan of ``ops/decode_attention.py``'s paged decode kernel. Grid =
``(B,)``: one step a ROW of the chunk program. K and V stay in HBM
(``pl.ANY``), the WHOLE stacked pool with the layer index a scalar prefetch;
an in-kernel loop runs over the row's live GROUPS of ``P`` pool blocks only
(16 x 32 = 512 tokens at the served shapes: ``pages_per_step``'s rule under
this kernel's name in the tuning table), up to the populated cache AND the
row's causal frontier. A group's blocks are copied by hand into one of two
VMEM slots while the group before it is attended (a row's last group starts
the next LIVE row's first copies), and no block past a row's last live one
is copied: a padded row (``kv_limit`` 0) is one empty grid step, and the cost
of a dispatch follows the live context, not rows x bucket.

Inside a group a loop over the PARTS of a query group, and for each over the
KV heads: the ``n_rep`` q heads that share a KV head are stacked on the
query axis, ``hp`` of them x one q tile a part (``Q_ROWS`` = 256 rows at
most), so one product is ``(hp x tq, D) x (D, P x bs)`` and a block is
fetched once per KV head. The online-softmax statistics of every (KV head,
part) live in VMEM scratch from group to group. A part whose q tile's
frontier lies under the group skips it; a group wholly under the tile's
lowest position and ``kv_limit`` is attended with no mask at all (no iota,
compare or select: the vector unit is what binds this kernel), and only the
groups that cross the diagonal or the limit take the mask.

Numerics: online-softmax flash attention over the query's full prior context
(prefix blocks + causal among the new tokens) — the mask the native path
builds from masks.spec_token_gen_mask, fused into the kernel. Both products
take the cache tile as it is stored (``decode_attention._dot_tile``): a
bfloat16 q against a bfloat16 K is one pass of the matrix unit, exact in its
float32 accumulator; the float32 probabilities (and a float32 q: quantized
caches fold the K scale into it) go as three bfloat16 parts that sum to the
value exactly, so nothing is rounded that the float32 form would keep.

A layer that attends a WINDOW (``window``, static: query ``i`` sees key ``j``
iff ``i - window < j <= i``) gives a row a LOWER frontier beside the upper
one: the loop starts at the group that holds the first key the row's lowest
query sees, a part skips a group that lies wholly behind its tile's window,
and the mask of a group that crosses a window's edge takes one more compare.
Without a window none of this is traced: no operand, no branch, the kernel
it always was.

At a pool row that is no multiple of the 128 lanes the chip's compiler
refuses a hand copy of a block (a 64-lane slice of an HBM ref), and the
launch keeps one block a grid step through a ``BlockSpec`` on the block
table: grid = (B, Hq, q_tiles, kv_blocks), tiles past the frontier or the
populated length skipped via ``pl.when`` (``_paged_by_block``). A head_dim
that divides the lanes does not come here: its pool holds ``128 // head_dim``
heads a row (modules/block_kvcache.kv_streams) and ``dispatch_paged_flash``
hands this kernel the head_dim-128 problem it then is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops import decode_attention as _da
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30

#: the most query rows of one product: the q heads of a KV head are stacked
#: on the query axis up to this many rows (a q tile x as many of the heads as
#: divide ``n_rep`` and fit), so the score tile ``(Q_ROWS, P x bs)`` float32
#: and a part's accumulators stay a small share of VMEM
Q_ROWS = 256

#: the kernel's name: the pallas call's (the roofline reads the device op by
#: it), the tuning table's and the kernel registry's
KERNEL = "paged_flash_attention"


# kernel/native dispatch gate: consolidated in ops/kernel_mode.py (one
# tested predicate per kernel); the historical name stays importable here
from neuronx_distributed_inference_tpu.ops.kernel_mode import (  # noqa: E402
    use_paged_flash as _use_paged_flash,
)


#: pool blocks the kernel copies and attends per pass of its loop (its ``P``):
#: ``decode_attention.pages_per_step``'s rule under this kernel's name in the
#: tuning table; 1 where blocks come one a grid step.
#: ``(n_kv, bs, head_dim, cache_dtype, max_blocks) -> int``
blocks_per_group = functools.partial(_da.pages_per_step, kernel=KERNEL)

#: block-table entries the kernel attends for the rows of a chunk pass whose
#: causal contexts hold ``live_blocks`` (one count a row) blocks of a table
#: ``max_blocks`` wide: whole groups up to each row's frontier; every entry of
#: the table where blocks come one a grid step. Host code calls this
#: (``ServingSession`` counts it beside the decode kernel's).
#: ``(live_blocks, max_blocks, *, n_kv, bs, head_dim, cache_dtype) -> int``
kv_blocks_walked = functools.partial(_da.kv_blocks_walked, kernel=KERNEL)


def _paged_group_kernel(
    # scalar prefetch
    li_ref,  # (1,) the layer of the stacked pool
    bt_ref,  # (B, NG * P) block table, padded to whole groups
    end_ref,  # (B,) one past the row's last live block: cache AND frontier
    live_from_ref,  # (B + 1,) from each row the next row that has any
    lim_ref,  # (B,) valid cache length per row
    tmax_ref,  # (B, nq) highest / lowest query position of a q tile
    tmin_ref,
    *rest,  # under a window: lo_ref (B,), the row's first live group; then
    # operands
    #   q_ref (1, Hkv, NP, R, D): R = hp heads x tq positions, head-major
    #   pos_ref (1, nq, tq) query positions
    #   k_hbm, v_hbm (L, NB+1, Hkv, bs, D), left in HBM
    #   o_ref (1, Hkv, NP, R, D)
    #   k_buf, v_buf (2, Hkv, G, D): a group of blocks, two slots
    #   sems, slot_ref
    #   m_scr, l_scr (Hkv, NP, R, 1) running max / sum, acc_scr (Hkv, NP, R, D)
    scale: float,
    P: int,
    nq: int,
    q_dtype,
    window: int = None,
):
    """One ROW per grid step; inside, a loop over the row's live block groups
    only, and for each group over the parts of a query group and the KV
    heads. The copies are ``decode_attention._group_copies``'. ``window``:
    the groups behind the row's lower frontier (``lo_ref``) are neither
    copied nor attended (module docstring)."""
    lo_ref = None
    if window is not None:
        lo_ref, *rest = rest
    (q_ref, pos_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref,
     m_scr, l_scr, acc_scr) = rest
    first_group = (lambda row: 0) if lo_ref is None else (lambda row: lo_ref[row])
    b = pl.program_id(0)
    B = pl.num_programs(0)
    _, n_kv, NP, R, D = q_ref.shape
    G = k_buf.shape[2]
    tq = pos_ref.shape[2]
    hp = R // tq

    start, wait = _da._group_copies(
        bt_ref, end_ref, ((k_hbm, k_buf), (v_hbm, v_buf)), sems, layer=li_ref[0], P=P
    )

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        # a masked token's probability is 0, and 0 x what the slot was born
        # with need not be 0: V's slots start from zeros
        v_buf[...] = jnp.zeros_like(v_buf)
        row = live_from_ref[0]

        @pl.when(row < B)
        def _():
            start(row, first_group(row), 0)

    hi = (end_ref[b] + P - 1) // P
    lim = lim_ref[b]

    @pl.when(hi == 0)
    def _empty():  # a padded row: nothing copied, nothing computed
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hi > 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(slot, h, pi, kv0, iq, masked):
        q = q_ref[0, h, pi].astype(q_dtype)  # (R, D): bfloat16 where q came so
        s = _da._dot_tile(q, k_buf[slot, h], 1) * scale  # (R, G)
        if masked:
            q_pos = pos_ref[0, iq][:, None]  # (tq, 1)
            kv_pos = kv0 + jax.lax.broadcasted_iota(jnp.int32, (tq, G), 1)
            mask = (kv_pos <= q_pos) & (kv_pos < lim)
            if window is not None:
                mask = mask & (kv_pos > q_pos - window)
            # -inf under a running max that starts at NEG_INF: a masked
            # score's probability is exp(-inf) = 0 with no second select,
            # also for a query that has seen no valid key yet
            s = jnp.where(mask[None], s.reshape(hp, tq, G), -jnp.inf).reshape(R, G)
        m_prev = m_scr[h, pi]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h, pi] = l_scr[h, pi] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h, pi] = acc_scr[h, pi] * alpha + _da._dot_tile(p, v_buf[slot, h], 0)
        m_scr[h, pi] = m_new

    def group(g, _):
        slot = slot_ref[0]
        last = g == hi - 1
        nrow = jnp.where(last, live_from_ref[b + 1], b)

        @pl.when(nrow < B)
        def _prefetch():
            start(nrow, jnp.where(last, first_group(nrow), g + 1), 1 - slot)

        wait(b, g, slot)
        slot_ref[0] = 1 - slot
        kv0 = g * G

        def part(pi, _):
            iq = pi % nq if nq > 1 else 0
            # the group lies under this tile's frontier and the cache's end;
            # and wholly under both: no mask can bite
            run = kv0 <= jnp.minimum(tmax_ref[b, iq], lim - 1)
            clear = (kv0 + G - 1 <= tmin_ref[b, iq]) & (kv0 + G <= lim)
            if window is not None:
                # some key of the group lies inside the window of the tile's
                # lowest query; and every key inside that of its highest
                run = run & (kv0 + G - 1 > tmin_ref[b, iq] - window)
                clear = clear & (kv0 > tmax_ref[b, iq] - window)

            def head(h, _):
                @pl.when(clear)
                def _():
                    attend(slot, h, pi, kv0, iq, masked=False)

                @pl.when(jnp.logical_not(clear))
                def _():
                    attend(slot, h, pi, kv0, iq, masked=True)

            @pl.when(run)
            def _():
                jax.lax.fori_loop(0, n_kv, head, None)

        jax.lax.fori_loop(0, NP, part, None)

    jax.lax.fori_loop(first_group(b), hi, group, None)

    @pl.when(hi > 0)
    def _finalize():
        def head(h, _):
            def part(pi, _):
                denom = jnp.maximum(l_scr[h, pi], 1e-30)
                o_ref[0, h, pi] = (acc_scr[h, pi] / denom).astype(o_ref.dtype)

            jax.lax.fori_loop(0, NP, part, None)

        jax.lax.fori_loop(0, n_kv, head, None)


def _paged_by_group(q, k_cache, v_cache, li, block_table, positions, kv_limit,
                    *, scale, n_rep, tq, P, interpret, window=None):
    """The launch of :func:`_paged_group_kernel`. (B, Sq, Hq, D) -> same."""
    B, Sq, Hq, D = q.shape
    _, _, n_kv, bs, _ = k_cache.shape
    MB = block_table.shape[1]
    tq = -(-min(tq, Sq) // 8) * 8  # whole sublane tiles
    nq = -(-Sq // tq)
    # the q heads of a KV head a part stacks: the most that divide n_rep
    # and keep the part within Q_ROWS
    hp = max(d for d in range(1, n_rep + 1) if n_rep % d == 0 and (d == 1 or d * tq <= Q_ROWS))
    R, NP = hp * tq, n_rep // hp * nq
    NG = -(-MB // P)
    G = P * bs

    pad_q = nq * tq - Sq
    pos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, pad_q)), mode="edge")
    pos = pos.reshape(B, nq, tq)
    tile_max, tile_min = jnp.max(pos, axis=-1), jnp.min(pos, axis=-1)
    lim = kv_limit.astype(jnp.int32)
    # per row: one past the last block any of its queries attends, and from
    # each row the next row that has any
    frontier = jnp.minimum(lim, jnp.max(tile_max, axis=-1) + 1)
    end = jnp.clip(-(-frontier // bs), 0, MB)
    live_from = jax.lax.cummin(
        jnp.where(end > 0, jnp.arange(B, dtype=jnp.int32), B), reverse=True
    )
    live_from = jnp.concatenate([live_from, jnp.full((1,), B, jnp.int32)])
    # a table no multiple of P wide: dead entries, never copied
    bt = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, NG * P - MB)))
    prefetch = [li, bt, end, live_from, lim, tile_max, tile_min]
    if window is not None:
        # the group of the first key the row's lowest query sees; a row with
        # no live block keeps its loop empty (lo <= hi = 0)
        first_key = jnp.maximum(jnp.min(tile_min, axis=-1) - window + 1, 0)
        prefetch.append(jnp.minimum(first_key // G, jnp.maximum(-(-end // P) - 1, 0)))

    def parts(x):  # (B, Sq, Hq, D) -> (B, Hkv, NP, R, D)
        x = jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        x = x.reshape(B, nq, tq, n_kv, n_rep // hp, hp, D)
        return x.transpose(0, 3, 4, 1, 5, 2, 6).reshape(B, n_kv, NP, R, D)

    def row_spec(shape):
        return pl.BlockSpec((1,) + shape, lambda b, *_: (b,) + (0,) * len(shape))

    item = jnp.dtype(k_cache.dtype).itemsize
    vmem = (
        2 * 2 * n_kv * G * D * item  # K and V, two slots
        + 2 * 2 * n_kv * NP * R * D * q.dtype.itemsize  # q and out, pipelined
        + n_kv * NP * R * (D + 2 * 128) * 4  # accumulators, lane-padded stats
        + 12 * R * G * 4  # a part's score tile and what is made from it
    )
    out = _da._common_call(
        functools.partial(
            _paged_group_kernel, scale=scale, P=P, nq=nq,
            q_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32, window=window,
        ),
        grid=(B,),
        in_specs=[
            row_spec((n_kv, NP, R, D)),
            row_spec((nq, tq)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row_spec((n_kv, NP, R, D)),
        operands=(prefetch, [parts(q), pos, k_cache, v_cache]),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, NP, R, D), q.dtype),
        scratch=[
            pltpu.VMEM((2, n_kv, G, D), k_cache.dtype),
            pltpu.VMEM((2, n_kv, G, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_kv, NP, R, 1), jnp.float32),
            pltpu.VMEM((n_kv, NP, R, 1), jnp.float32),
            pltpu.VMEM((n_kv, NP, R, D), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL,
        # rows in order: a row's last group starts the next live row's copies
        semantics=("arbitrary",),
        vmem_limit_bytes=max(32 * 2**20, min(100 * 2**20, 2 * vmem)),
    )
    out = out.reshape(B, n_kv, n_rep // hp, nq, hp, tq, D)
    return out.transpose(0, 3, 5, 1, 2, 4, 6).reshape(B, nq * tq, Hq, D)[:, :Sq]


def _by_block_kernel(
    # scalar prefetch
    li_ref,  # (1,) the layer of the stacked pool
    block_table_ref,  # (B, MB) int32
    kv_limit_ref,  # (B,) int32 valid cache length per row
    tile_max_ref,  # (B, nq) int32 max q position per q tile
    # blocked operands
    q_ref,  # (1, 1, tq, D)
    pos_ref,  # (1, 1, tq) int32 q positions (dummy middle axis for Mosaic)
    k_ref,  # (1, 1, 1, bs, D) one head's cache block
    v_ref,
    o_ref,  # (1, 1, tq, D)
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    tq: int,
    bs: int,
    nkv: int,
    window: int = None,
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_start = j * bs
    # skip tiles above the causal frontier or beyond the populated cache
    run = (kv_start <= tile_max_ref[b, iq]) & (kv_start < kv_limit_ref[b])

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (tq, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)  # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (tq, bs)

        q_pos = pos_ref[0, 0]  # (tq,)
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        mask = (kv_pos <= q_pos[:, None]) & (kv_pos < kv_limit_ref[b])
        if window is not None:
            mask = mask & (kv_pos > q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # rows with no valid kv yet: m_new = NEG_INF -> p = exp(0) = 1;
        # zero them via the mask instead
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0, 0].astype(jnp.float32)  # (bs, D)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[:] / denom).astype(o_ref.dtype)


def _paged_by_block(q, k_cache, v_cache, li, block_table, positions, kv_limit,
                    *, scale, n_rep, tq, interpret, window=None):
    """The launch at a pool row that is no multiple of the 128 lanes: the
    chip's compiler refuses such a slice of an HBM ref, so the blocks cannot
    be copied by hand; they come one a grid step through a ``BlockSpec`` on
    the block table, ``(B, Hq, nq, MB)`` steps, one q head's tile against one
    block, in float32 (as ``decode_attention._paged_by_block``, and reached
    by the same shapes: a head_dim of 72, 80 or 96, an odd KV head count a
    device, a quantised pool at head_dim 64; no benchmark cell since PR 65).
    (B, Sq, Hq, D) -> same."""
    B, Sq, Hq, D = q.shape
    bs = k_cache.shape[3]
    MB = block_table.shape[1]
    tq = min(tq, Sq)
    nq = pl.cdiv(Sq, tq)
    qt = jnp.swapaxes(q, 1, 2)  # (B, Hq, Sq, D)
    # per-(row, q-tile) causal frontier for tile skipping
    pos_pad = jnp.pad(positions, ((0, 0), (0, nq * tq - Sq)))
    tile_max = jnp.max(pos_pad.reshape(B, nq, tq), axis=-1).astype(jnp.int32)

    # head-major cache: one head's block is a (bs, D) tile whose last-two
    # block dims equal the array dims
    block = pl.BlockSpec(
        (1, 1, 1, bs, D),
        lambda b, h, iq, j, li, bt, lim, tm: (li[0], bt[b, j], h // n_rep, 0, 0),
    )
    out = _da._common_call(
        functools.partial(_by_block_kernel, scale=scale, tq=tq, bs=bs, nkv=MB, window=window),
        grid=(B, Hq, nq, MB),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, iq, j, *_: (b, h, iq, 0)),
            # dummy middle axis: block (1, tq) over a (B, Sq) array violates
            # Mosaic's (8, 128) last-two-dims rule for B > 1
            pl.BlockSpec((1, 1, tq), lambda b, h, iq, j, *_: (b, 0, iq)),
            block,
            block,
        ],
        out_specs=pl.BlockSpec((1, 1, tq, D), lambda b, h, iq, j, *_: (b, h, iq, 0)),
        operands=(
            [li, block_table.astype(jnp.int32), kv_limit.astype(jnp.int32), tile_max],
            [qt, positions.astype(jnp.int32)[:, None, :], k_cache, v_cache],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * tq, D), q.dtype),
        scratch=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, D), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL,
        semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )
    return jnp.swapaxes(out, 1, 2)[:, :Sq]


@functools.partial(
    jax.jit, static_argnames=("scale", "n_rep", "tq", "interpret", "window")
)
def paged_flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k_cache: jax.Array,  # (L, NB+1, Hkv, bs, D) the stacked head-major paged cache
    v_cache: jax.Array,  # (or one layer's (NB+1, Hkv, bs, D), layer_idx None)
    block_table: jax.Array,  # (B, MB) int32
    positions: jax.Array,  # (B, Sq) int32 query positions
    kv_limit: jax.Array,  # (B,) int32 valid cache length per row
    *,
    scale: float,
    n_rep: int,
    layer_idx: jax.Array = None,  # int32 scalar: the layer of a stacked pool
    tq: int = None,
    k_scale: jax.Array = None,  # (Hkv,) per-head dequant factor (scale/qmax)
    v_scale: jax.Array = None,  # for int8/fp8 caches; None = plain cache
    interpret: bool = False,
    window: int = None,  # static: the layer attends (position - window, position] only
) -> jax.Array:
    """Prefix/chunked-prefill attention straight off the paged cache.

    Returns (B, Sq, Hq, D). Query token t of row b attends cache positions
    p <= positions[b, t] with p < kv_limit[b] — prior context plus causal
    among the new tokens (KV for the new tokens must already be written;
    write-then-attend as everywhere else) — and, under ``window``, with
    p > positions[b, t] - window: the block groups behind the row's lowest
    query's window are neither copied nor attended. ``window`` None is a
    static absence: the call lowers to the kernel it lowered to before the
    argument existed.

    Quantized caches pass the raw int8/fp8 code blocks plus this layer's
    per-head dequant factors: the K factor folds into q (scaling the QKᵀ
    product), the V factor scales the per-head output after the online
    softmax — the kernel DMAs narrow code tiles, converts in-register, and
    never materializes a dequantized cache.
    """
    B, Sq, Hq, D = q.shape
    if k_cache.ndim == 4:  # one layer's pool: a stack of one
        k_cache, v_cache, layer_idx = k_cache[None], v_cache[None], 0
    _, _, Hkv, bs, width = k_cache.shape
    # a pool of several heads a row comes through dispatch_paged_flash, which
    # lays the queries in their head's lanes
    assert width == D, f"q of {D} lanes against pool rows of {width}"
    if tq is None:
        # q-tile default through the tuning table (KERN704), keyed as the
        # group of blocks is: by the block's shape a chip and the cache dtype
        tq = tile_default(KERNEL, f"blk{Hkv}x{bs}x{D}", k_cache.dtype, "tq", 128)

    out_dtype = q.dtype
    if k_scale is not None:
        q = q.astype(jnp.float32) * jnp.repeat(k_scale, n_rep)[None, None, :, None]
    if D % 128:
        # blocks come through a BlockSpec, which takes a layer's slice as
        # well as the stack; at these widths the chip's own layout of the
        # pool is not the kernel's, and a slice is what is relaid
        k_cache, v_cache = (
            jax.lax.dynamic_index_in_dim(c, layer_idx, axis=0) for c in (k_cache, v_cache)
        )
        layer_idx, launch = 0, _paged_by_block
    else:
        P = blocks_per_group(Hkv, bs, D, k_cache.dtype, block_table.shape[1])
        launch = functools.partial(_paged_by_group, P=P)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    out = launch(
        q, k_cache, v_cache, li, block_table, positions, kv_limit,
        scale=scale, n_rep=n_rep, tq=tq, interpret=interpret, window=window,
    )
    if v_scale is not None:
        out = (out * jnp.repeat(v_scale, n_rep)[None, None, :, None]).astype(out_dtype)
    return out


def dispatch_paged_flash(
    q, k_cache, v_cache, layer_idx, block_table, positions, kv_limit,
    *, scale, n_rep, k_scale=None, v_scale=None, interpret, window=None,
):
    """:func:`paged_flash_attention` once per head shard of the ambient mesh
    (parallel/sharding.shard_over_heads): q and the output split on the q
    heads, the stacked block pool ``(L, NB+1, Hkv, bs, D)`` and the per-head
    dequant factors on the kv heads, layer index, block table, positions and
    ``kv_limit`` replicated; no collective inside. The plain call at degree 1.

    ``n_rep`` is the MODEL's. A pool of ``g`` KV heads a 128-lane row
    (block_kvcache.kv_streams) is attended as ``H_kv / g`` KV heads at
    ``g x D`` with ``g x n_rep`` query heads each, the queries laid in their
    own head's lanes and the output cut back (block_kvcache.fold_queries), as
    ``decode_attention.dispatch_paged_tkg_decode`` does."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        fold_queries,
        pool_fold,
        unfold_outputs,
    )
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_over_heads

    def per_shard(q_s, k_s, v_s, li, bt, pos, lim, ks_s, vs_s):
        g = pool_fold(k_s.shape[-1], q_s.shape[-1])
        out = paged_flash_attention(
            fold_queries(q_s, g, n_rep), k_s, v_s, bt, pos, lim,
            scale=scale, n_rep=g * n_rep, layer_idx=li, k_scale=ks_s, v_scale=vs_s,
            interpret=interpret, window=window,
        )
        return unfold_outputs(out, g, n_rep)

    return shard_over_heads(
        per_shard,
        (q, k_cache, v_cache, layer_idx, block_table, positions, kv_limit, k_scale, v_scale),
        in_heads=(2, 2, 2, None, None, None, None, 0, 0), out_heads=2,
    )
