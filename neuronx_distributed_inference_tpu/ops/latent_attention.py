"""Paged attention over an MLA layer's latent pool, in absorbed form.

What a token leaves in an MLA layer (models/deepseek.py) is one compressed
latent ``c`` (``kv_lora_rank`` wide) and one rotary key ``k_r``
(``qk_rope_head_dim``), shared by every q head. Absorbed, the layer IS
multi-query attention with one KV head: a head's score against a token is
``q_c . c + q_r . k_r`` (``q_c = W_uk^T q_nope``) and its value is ``c``
itself (``W_uv`` is applied to the attended latent outside). So a block of
latents is copied ONCE a row and serves the scores and the values of all the
heads.

The pool (modules/block_kvcache.CacheStream) keeps ``c`` as a stream
``(L, NB+1, 1, bs, r)`` and ``k_r`` PACKED, ``pack = 128 / d_rope`` tokens a
128-lane row, ``(L, NB+1, 1, bs / pack, 128)``: token ``o`` of a block lies in
row ``o % rows`` at lane group ``o // rows`` (``rows = bs / pack``). Both
minor dims are whole lanes, so the chip's compiler takes a hand copy of a
block (it refuses one of a 576- or a 64-lane slice) and the pool holds
exactly ``r + d_rope`` numbers a token. The kernels never unpack: a block's
tokens are attended one LANE GROUP at a time, group ``j`` being rows
``[j * rows, (j + 1) * rows)`` of the latent block (whole sublane tiles)
against the rope rows under a q whose rotary part sits in lanes ``[j *
d_rope, (j + 1) * d_rope)`` and is zero elsewhere. A softmax does not care
in which order it meets its keys.

Two kernels on the plan of ``ops/decode_attention.py``'s paged decode kernel
and ``ops/paged_flash_attention.py``'s (grid = one ROW a step, an in-kernel
loop over the row's live groups of ``P`` pool blocks, two VMEM slots, no
block past a row's last live one copied): :func:`paged_latent_decode_attention`
for decode widths (the mask the native path uses, float32 statistics in
registers; its ``P`` is ``decode_attention.pages_per_step``'s, as the paged
decode kernel's) and :func:`paged_latent_flash_attention` for a prefill chunk
(causal by position under ``kv_limit``, the q heads stacked on the query
axis in parts, statistics in VMEM from group to group). The chunk kernel's
tiles are its OWN (:data:`Q_ROWS`, :func:`blocks_per_group`, under its name
in the tuning table): every q head shares the one latent, so the row cap
alone sets how many heads a part stacks, where the GQA prefill kernel's is
bounded by ``n_rep``; :func:`kv_blocks_walked` is what the session counts
for a latent pool. Both products take the cache tile as it is stored
(``decode_attention._dot_tile``).
:func:`latent_attend` is what the layer calls: a kernel where its gate
admits the call, else blocks gathered by the table and attended natively.

A SELECTION (learned sparse attention, modules/sparse_index.py: the keys a
query may attend, a predicate ``(B, Sq, W)``) rides the same kernels, by the
same rule: the decode kernel takes it as its mask, the chunk kernel as one
more operand, a row's slab in VMEM in the kernel's column order, made into a
group's bias tile (0 | -inf) once for all the parts. The walk is the row's
live groups either way: every live block copied once a row, a key not picked
given probability 0. Under a selection the chunk kernel's probabilities go
to the matrix unit in the pool's dtype, as the dense walk it replaces
rounds them (``native_latent_attention``): one pass over a bf16 pool where
the float32 probabilities of the dense call take three.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops import decode_attention as _da
from neuronx_distributed_inference_tpu.ops.kernel_mode import TKG_MAX_Q_LEN, on_tpu
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

NEG_INF = _da.NEG_INF

#: the kernels' names: the pallas calls' (the benchmark reads the device ops
#: by them), the tuning table's and the kernel registry's
DECODE_KERNEL = "paged_latent_decode_attention"
CHUNK_KERNEL = "paged_latent_flash_attention"

#: the chunk kernel's tiles where the tuning table has no entry under its
#: name for the pool's block: the most query rows of one product (a part
#: stacks as many of the q heads as divide ``Hq`` and fit, x one q tile: every
#: head shares the one latent, so this cap and nothing else sets the part)
#: and the tokens a group of pool blocks holds. The matrix unit binds the
#: kernel, and a key tile it has loaded serves a part's rows: PERF.md, PR 57,
#: has the sweep (256 / 512 / 1024 rows x 512 / 1024 / 2048 tokens, at 64 and
#: at 16 heads a latent)
Q_ROWS = 512
GROUP_TOKENS = 1024


def _tile(param: str, fallback: int, n_kv: int, bs: int, head_dim: int, cache_dtype) -> int:
    """A tile of the chunk kernel under its own name in the tuning table, by
    the pool block's shape a chip (``blk1x32x512``: one latent of 512 a token)."""
    shape_class = f"blk{n_kv}x{bs}x{head_dim}"
    return tile_default(CHUNK_KERNEL, shape_class, jnp.dtype(cache_dtype).name, param, fallback)


def blocks_per_group(n_kv: int, bs: int, head_dim: int, cache_dtype, max_blocks: int) -> int:
    """Pool blocks the chunk kernel copies and attends per pass of its loop
    (its ``P``): a power of two (the kernel takes a group's live count apart
    by bits), never more than the block table is wide. The signature is
    ``paged_flash_attention.blocks_per_group``'s (``n_kv`` is 1 a chip: one
    latent a token), the rule the latent kernel's own."""
    p = _tile("pages", GROUP_TOKENS // bs, n_kv, bs, head_dim, cache_dtype)
    p = max(1, min(p, max_blocks))
    return 1 << (p.bit_length() - 1)


def kv_blocks_walked(
    live_blocks, max_blocks: int, *, n_kv: int, bs: int, head_dim: int, cache_dtype
) -> int:
    """Block-table entries the chunk kernel attends for the rows of a chunk
    pass whose causal contexts hold ``live_blocks`` (one count a row) blocks
    of a table ``max_blocks`` wide: whole groups up to each row's frontier.
    Host code calls this (``ServingSession`` counts it for a latent pool)."""
    P = blocks_per_group(n_kv, bs, head_dim, cache_dtype, max_blocks)
    return sum(-(-n // P) * P for n in live_blocks)


def use_latent_kernel(c_cache, kr_cache, q_len: int, kv_width: int) -> bool:
    """Gate of both kernels, on what the call shows: the chip, a pool whose
    two streams lie on whole lanes with a lane group of whole sublane tiles,
    and a kv width a group of blocks divides (the decode mask is re-tiled by
    groups)."""
    bs, r = c_cache.shape[3:]
    rows, lanes = kr_cache.shape[3:]
    sublanes = 32 // jnp.dtype(c_cache.dtype).itemsize
    return (
        on_tpu() and r % 128 == 0 and lanes % 128 == 0 and rows % sublanes == 0
        and kv_width % bs == 0 and (q_len > TKG_MAX_Q_LEN or kv_width >= 512)
    )


def _copies(bt_ref, end_ref, streams, sems, *, layer, P):
    """``(start, wait)`` as ``decode_attention._group_copies``', for the
    latent pool's two streams: ``streams`` is ``((hbm (L, NB+1, 1, n, w),
    buf (2, P, n, w)), ...)``, a block a leading index of its buffer."""

    def live_blocks(row, group):
        return jnp.clip(end_ref[row] - group * P, 0, P)

    def start(row, group, slot):
        def block(p, _):
            page = bt_ref[row, group * P + p]
            for i, (hbm, buf) in enumerate(streams):
                pltpu.make_async_copy(
                    hbm.at[layer, page, 0], buf.at[slot, p], sems.at[i, slot]
                ).start()

        jax.lax.fori_loop(0, live_blocks(row, group), block, None)

    def wait(row, group, slot):
        # the live count taken apart into powers of two: one wait a run
        n = live_blocks(row, group)
        width = P
        while width:
            @pl.when(n & width > 0)
            def _(width=width):
                for i, (_, buf) in enumerate(streams):
                    run = buf.at[slot, pl.ds(0, width)]
                    pltpu.make_async_copy(run, run, sems.at[i, slot]).wait()

            width //= 2

    return start, wait


def _lane_groups(c_buf, kr_buf, slot):
    """A slot's group of blocks as the kernels attend it: the rope rows
    ``(P * rows, lanes)`` and per lane group ``j`` the latent tile ``(P *
    rows, r)`` of the tokens that lie in it."""
    P, bs, r = c_buf.shape[1:]
    rows, lanes = kr_buf.shape[2:]
    kr_t = kr_buf[slot].reshape(P * rows, lanes)
    c_ts = [
        c_buf[slot, :, j * rows:(j + 1) * rows, :].reshape(P * rows, r)
        for j in range(bs // rows)
    ]
    return kr_t, c_ts


def _rope_rows(q_pe: jax.Array, pack: int) -> jax.Array:
    """``(..., R, w)`` -> ``(..., pack, R, pack * w)``: copy ``j`` holds the
    rotary part of q in lane group ``j`` and zeros elsewhere."""
    w = q_pe.shape[-1]
    return jnp.stack(
        [jnp.pad(q_pe, [(0, 0)] * (q_pe.ndim - 1) + [(j * w, (pack - 1 - j) * w)])
         for j in range(pack)],
        axis=-3,
    )


def _by_lane_group(x: jax.Array, P: int, pack: int, rows: int) -> jax.Array:
    """``(B, S, NG * P * bs)`` over a row's tokens -> ``(B, NG, pack, S, P *
    rows)``: a group's columns as the kernels meet them, lane group ``j``,
    then block, then row (column ``(p, i)`` is token ``p * bs + j * rows + i``)."""
    B, S, W = x.shape
    x = x.reshape(B, S, W // (P * pack * rows), P, pack, rows)
    return x.transpose(0, 2, 4, 1, 3, 5).reshape(B, -1, pack, S, P * rows)


def _row_spec(shape):
    return pl.BlockSpec((1,) + shape, lambda b, *_: (b,) + (0,) * len(shape))


def _live_from(end):
    """From each row the next row that has any live block (``B`` = none)."""
    B = end.shape[0]
    nxt = jax.lax.cummin(jnp.where(end > 0, jnp.arange(B, dtype=jnp.int32), B), reverse=True)
    return jnp.concatenate([nxt, jnp.full((1,), B, jnp.int32)])


# ---------------------------------------------------------------------------
# decode widths
# ---------------------------------------------------------------------------


def _decode_kernel(
    li_ref, bt_ref, lo_ref, end_ref, live_from_ref,
    qc_ref,  # (1, R, r)
    qr_ref,  # (1, pack, R, lanes)
    mask_ref,  # (1, NG, pack, 1 | R, P * rows)
    c_hbm, kr_hbm, o_ref, c_buf, kr_buf, sems, slot_ref,
    *, scale, P, q_dtype,
):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    R, r = qc_ref.shape[1:]
    pack = qr_ref.shape[1]
    start, wait = _copies(
        bt_ref, end_ref, ((c_hbm, c_buf), (kr_hbm, kr_buf)), sems, layer=li_ref[0], P=P
    )

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        # the latent is the value too: a masked token's probability is 0, and
        # 0 x what a slot was born with need not be 0
        c_buf[...] = jnp.zeros_like(c_buf)
        row = live_from_ref[0]

        @pl.when(row < B)
        def _():
            start(row, lo_ref[row], 0)

    lo, hi = lo_ref[b], (end_ref[b] + P - 1) // P

    def group(g, carry):
        slot = slot_ref[0]
        last = g == hi - 1
        nrow = jnp.where(last, live_from_ref[b + 1], b)

        @pl.when(nrow < B)
        def _prefetch():
            start(nrow, jnp.where(last, lo_ref[nrow], g + 1), 1 - slot)

        wait(b, g, slot)
        slot_ref[0] = 1 - slot
        kr_t, c_ts = _lane_groups(c_buf, kr_buf, slot)
        qc = qc_ref[0].astype(q_dtype)
        m_prev, l_prev, acc = carry
        for j in range(pack):
            live = jnp.broadcast_to(mask_ref[0, g, j] > 0, (R, kr_t.shape[0]))
            s = _da._dot_tile(qc, c_ts[j], 1) + _da._dot_tile(qr_ref[0, j].astype(q_dtype), kr_t, 1)
            s = jnp.where(live, s * scale, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_prev = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _da._dot_tile(p, c_ts[j], 0)
            m_prev = m_new
        return m_prev, l_prev, acc

    init = (
        jnp.full((R, 1), NEG_INF, jnp.float32),
        jnp.zeros((R, 1), jnp.float32),
        jnp.zeros((R, r), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(lo, hi, group, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_decode_attention(
    q_c: jax.Array,  # (B, K, Hq, r): q_nope absorbed into the latent space
    q_pe: jax.Array,  # (B, K, Hq, d_rope), rotated
    c_cache: jax.Array,  # (L, NB+1, 1, bs, r) the stacked latent pool
    kr_cache: jax.Array,  # (L, NB+1, 1, bs / pack, pack * d_rope)
    layer_idx: jax.Array,
    block_table: jax.Array,  # (B, MB)
    mask: jax.Array,  # (B, 1, K, MB * bs) bool decode mask over the block view
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Decode / speculation-width attention off the latent pool. Returns the
    attended latents ``(B, K, Hq, r)``."""
    B, K, Hq, r = q_c.shape
    bs = c_cache.shape[3]
    rows, lanes = kr_cache.shape[3:]
    pack = bs // rows
    MB = block_table.shape[1]
    P = _da.pages_per_step(1, bs, r, c_cache.dtype, MB)
    NG = -(-MB // P)
    pad = NG * P - MB  # a table no multiple of P wide: dead entries, masked
    block_table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, pad)))
    mask = jnp.pad(mask[:, 0], ((0, 0), (0, 0), (0, pad * bs)))  # (B, K, NG * P * bs)
    rk = Hq * K
    R = -(-rk // 8) * 8

    def q_rows(x):  # (B, K, Hq, w) -> (B, R, w): row h * K + t
        x = _da._prep_q(x)
        return jnp.pad(x, ((0, 0), (0, R - rk), (0, 0)))

    # per row: one past its last live block, the group of its first, and
    # from each row the next row that has any
    live = jnp.any(mask.reshape(B, K, NG * P, bs), axis=(1, 3))
    idx = jnp.arange(NG * P, dtype=jnp.int32)
    end = jnp.max(jnp.where(live, idx + 1, 0), axis=1)
    lo = jnp.min(jnp.where(live, idx, NG * P - 1), axis=1) // P
    # the mask a lane group at a time: (B, NG, pack, K, P * rows)
    m = _by_lane_group(mask.astype(jnp.int32), P, pack, rows)
    if K > 1:  # row h * K + t reads mask row t
        m = jnp.pad(jnp.tile(m, (1, 1, 1, Hq, 1)), ((0, 0),) * 3 + ((0, R - rk), (0, 0)))
    qc, qr = q_rows(q_c), _rope_rows(q_rows(q_pe), pack)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    # what a grid step holds in VMEM, as decode_attention.paged_tkg_decode_attention
    # reckons it: the row's mask slab, its queries and its output, each twice
    # (pipelined), and the two slots of latents and keys. Under the compiler's
    # own scoped limit nothing is asked; (heads x K) score rows over a long
    # bucket (32 heads x 8 positions x 8192 keys: 16 MiB of slab, refused by
    # 1.1 MiB on a described v5e) ask for what they need
    item = jnp.dtype(c_cache.dtype).itemsize
    held = (2 * math.prod(m.shape[1:]) * 4 + 2 * R * (2 * r + pack * lanes) * jnp.dtype(q_c.dtype).itemsize
            + 2 * P * (bs * r + rows * lanes) * item)
    vmem_limit = None if held <= _da.SCOPED_VMEM_BYTES else held + 8 * 2**20
    out = _da._common_call(
        functools.partial(
            _decode_kernel, scale=scale, P=P,
            q_dtype=jnp.bfloat16 if q_c.dtype == jnp.bfloat16 else jnp.float32,
        ),
        grid=(B,),
        in_specs=[
            _row_spec(qc.shape[1:]), _row_spec(qr.shape[1:]), _row_spec(m.shape[1:]),
            pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=_row_spec((R, r)),
        operands=([li, block_table, lo, end, _live_from(end)], [qc, qr, m, c_cache, kr_cache]),
        out_shape=jax.ShapeDtypeStruct((B, R, r), q_c.dtype),
        scratch=[
            pltpu.VMEM((2, P, bs, r), c_cache.dtype),
            pltpu.VMEM((2, P, rows, lanes), kr_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
        name=DECODE_KERNEL,
        # rows in order: a row's last group starts the next live row's copies
        semantics=("arbitrary",),
        vmem_limit_bytes=vmem_limit,
    )
    return _da._unprep_out(out[:, :rk], B, K, Hq, r)


# ---------------------------------------------------------------------------
# a prefill chunk
# ---------------------------------------------------------------------------


def _chunk_kernel(
    li_ref, bt_ref, end_ref, live_from_ref, lim_ref, tmax_ref, tmin_ref,
    qc_ref,  # (1, NP, R, r): R = hp heads x tq positions, head-major
    qr_ref,  # (1, NP, pack, R, lanes)
    pos_ref,  # (1, nq, tq)
    *rest,  # the pools, out, scratch; under a selection between one more operand and scratch
    scale, P, nq, q_dtype, selected,
):
    if selected:
        # chosen_ref (1, NG, pack, nq, tq, P * rows) int; bias_scr (nq, pack, tq, P * rows) float32
        chosen_ref, *rest, bias_scr = rest
    c_hbm, kr_hbm, o_ref, c_buf, kr_buf, sems, slot_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    B = pl.num_programs(0)
    _, NP, R, r = qc_ref.shape
    pack = qr_ref.shape[2]
    bs = c_buf.shape[2]
    rows = kr_buf.shape[2]
    G, Gj = P * bs, P * rows
    tq = pos_ref.shape[2]
    hp = R // tq
    start, wait = _copies(
        bt_ref, end_ref, ((c_hbm, c_buf), (kr_hbm, kr_buf)), sems, layer=li_ref[0], P=P
    )

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        c_buf[...] = jnp.zeros_like(c_buf)  # the latent is the value too
        if selected:
            # a masked score meets -inf by a SUM there, which does not hide what
            # a slot was born with (a NaN) as a select does
            kr_buf[...] = jnp.zeros_like(kr_buf)
        row = live_from_ref[0]

        @pl.when(row < B)
        def _():
            start(row, 0, 0)

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

    def live(kv0, iq, j):
        """Which of group ``kv0``'s lane group ``j`` a q tile's queries may
        attend by the kernel's own rule: ``(tq, Gj)``."""
        q_pos = pos_ref[0, iq][:, None]  # (tq, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, Gj), 1)
        # column (p, i) of lane group j is token p * bs + j * rows + i
        kv_pos = kv0 + col // rows * bs + j * rows + col % rows
        return (kv_pos <= q_pos) & (kv_pos < lim)

    def attend(slot, pi, kv0, iq, masked):
        kr_t, c_ts = _lane_groups(c_buf, kr_buf, slot)
        qc = qc_ref[0, pi].astype(q_dtype)
        for j in range(pack):
            s = _da._dot_tile(qc, c_ts[j], 1) + _da._dot_tile(
                qr_ref[0, pi, j].astype(q_dtype), kr_t, 1
            )
            s = s * scale
            # -inf under a running maximum that starts at the finite NEG_INF: a
            # masked score's probability is exp(-inf) = 0, also for a query
            # that has met no key of its own yet
            if selected:
                s = (s.reshape(hp, tq, Gj) + bias_scr[iq, j][None]).reshape(R, Gj)
            elif masked:
                s = jnp.where(live(kv0, iq, j)[None], s.reshape(hp, tq, Gj), -jnp.inf).reshape(R, Gj)
            m_prev = m_scr[pi]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[pi] = l_scr[pi] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if selected:
                # as the dense walk under a selection does (native_latent_attention):
                # the probabilities in the latents' dtype, ONE pass of the matrix
                # unit over a bf16 pool where float32 ones take three
                p = p.astype(c_ts[j].dtype)
            acc_scr[pi] = acc_scr[pi] * alpha + _da._dot_tile(p, c_ts[j], 0)
            m_scr[pi] = m_new

    def group(g, _):
        slot = slot_ref[0]
        last = g == hi - 1
        nrow = jnp.where(last, live_from_ref[b + 1], b)

        @pl.when(nrow < B)
        def _prefetch():
            start(nrow, jnp.where(last, 0, g + 1), 1 - slot)

        wait(b, g, slot)
        slot_ref[0] = 1 - slot
        kv0 = g * G
        if selected:
            # what the selection leaves of the group, once for all the parts:
            # 0 where a query attends a key, -inf where it does not
            def bias(iq, _):
                for j in range(pack):
                    keep = live(kv0, iq, j) & (chosen_ref[0, g, j, iq].astype(jnp.int32) > 0)
                    bias_scr[iq, j] = jnp.where(keep, 0.0, -jnp.inf)

            jax.lax.fori_loop(0, nq, bias, None)

        def part(pi, _):
            iq = pi % nq if nq > 1 else 0
            # the group lies under this tile's frontier and the cache's end;
            # and wholly under both: no mask can bite
            run = kv0 <= jnp.minimum(tmax_ref[b, iq], lim - 1)
            if selected:  # no group is clear of a selection

                @pl.when(run)
                def _():
                    attend(slot, pi, kv0, iq, masked=True)

                return
            clear = (kv0 + G - 1 <= tmin_ref[b, iq]) & (kv0 + G <= lim)

            @pl.when(run & clear)
            def _():
                attend(slot, pi, kv0, iq, masked=False)

            @pl.when(run & jnp.logical_not(clear))
            def _():
                attend(slot, pi, kv0, iq, masked=True)

        jax.lax.fori_loop(0, NP, part, None)

    jax.lax.fori_loop(0, hi, group, None)

    @pl.when(hi > 0)
    def _finalize():
        def part(pi, _):
            o_ref[0, pi] = (acc_scr[pi] / jnp.maximum(l_scr[pi], 1e-30)).astype(o_ref.dtype)

        jax.lax.fori_loop(0, NP, part, None)


@functools.partial(jax.jit, static_argnames=("scale", "tq", "interpret"))
def paged_latent_flash_attention(
    q_c: jax.Array,  # (B, Sq, Hq, r)
    q_pe: jax.Array,  # (B, Sq, Hq, d_rope)
    c_cache: jax.Array,
    kr_cache: jax.Array,
    layer_idx: jax.Array,
    block_table: jax.Array,  # (B, MB)
    positions: jax.Array,  # (B, Sq) query positions
    kv_limit: jax.Array,  # (B,) valid cache length per row
    chosen: jax.Array = None,  # (B, Sq, MB * bs) bool: the keys a query may attend
    *,
    scale: float,
    tq: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Attention of a prefill chunk over prior latents plus itself: query
    ``t`` of row ``b`` attends positions ``p <= positions[b, t]`` with ``p <
    kv_limit[b]`` (the chunk's own latents are already written), and under a
    selection (``chosen``: ``sparse_index.select``'s predicate) of those the
    ones with ``chosen[b, t, p]``: the same walk of the row's live groups,
    every group in the masked form, the predicate a row's slab in VMEM in the
    kernel's column order. A query that picks nothing in a group adds nothing
    there (and reads zeros if it picks nothing at all). Returns the attended
    latents ``(B, Sq, Hq, r)``."""
    B, Sq, Hq, r = q_c.shape
    bs = c_cache.shape[3]
    rows, lanes = kr_cache.shape[3:]
    pack = bs // rows
    MB = block_table.shape[1]
    P = blocks_per_group(1, bs, r, c_cache.dtype, MB)
    NG = -(-MB // P)
    tq = -(-min(tq, Sq) // 8) * 8  # whole sublane tiles
    nq = -(-Sq // tq)
    # the q heads a part stacks: the most that divide Hq and keep it within its row cap
    q_rows = _tile("rows", Q_ROWS, 1, bs, r, c_cache.dtype)
    hp = max(d for d in range(1, Hq + 1) if Hq % d == 0 and (d == 1 or d * tq <= q_rows))
    R, NP = hp * tq, Hq // hp * nq

    pad_q = nq * tq - Sq
    pos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, pad_q)), mode="edge")
    pos = pos.reshape(B, nq, tq)
    tile_max, tile_min = jnp.max(pos, axis=-1), jnp.min(pos, axis=-1)
    lim = kv_limit.astype(jnp.int32)
    frontier = jnp.minimum(lim, jnp.max(tile_max, axis=-1) + 1)
    end = jnp.clip(-(-frontier // bs), 0, MB)
    bt = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, NG * P - MB)))

    def parts(x):  # (B, Sq, Hq, w) -> (B, NP, R, w): part (head part, q tile), rows head-major
        x = jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        x = x.reshape(B, nq, tq, Hq // hp, hp, x.shape[-1])
        return x.transpose(0, 3, 1, 4, 2, 5).reshape(B, NP, R, x.shape[-1])

    qc, qr = parts(q_c), _rope_rows(parts(q_pe), pack)
    item = jnp.dtype(c_cache.dtype).itemsize
    vmem = (
        2 * P * (bs * r + rows * lanes) * item  # both streams, two slots
        + 2 * NP * R * (2 * r + pack * lanes) * q_c.dtype.itemsize  # q and out, pipelined
        + NP * R * (r + 2 * 128) * 4  # accumulators, lane-padded statistics
        + 12 * R * P * rows * 4  # a part's score tile and what is made from it
    )
    selection = []
    if chosen is not None:
        ch = jnp.pad(chosen, ((0, 0), (0, pad_q), (0, NG * P * bs - chosen.shape[-1])))
        ch = _by_lane_group(ch, P, pack, rows).reshape(B, NG, pack, nq, tq, P * rows)
        # int8 where a q tile is whole int8 sublane tiles (32 rows)
        selection = [ch.astype(jnp.int8 if tq % 32 == 0 else jnp.int32)]
        vmem += 2 * selection[0][0].nbytes + nq * tq * P * bs * 4  # a row's slab, pipelined; a group's bias
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    out = _da._common_call(
        functools.partial(
            _chunk_kernel, scale=scale, P=P, nq=nq, selected=bool(selection),
            q_dtype=jnp.bfloat16 if q_c.dtype == jnp.bfloat16 else jnp.float32,
        ),
        grid=(B,),
        in_specs=[
            _row_spec(qc.shape[1:]), _row_spec(qr.shape[1:]), _row_spec((nq, tq)),
            *[_row_spec(x.shape[1:]) for x in selection],
            pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=_row_spec((NP, R, r)),
        operands=(
            [li, bt, end, _live_from(end), lim, tile_max, tile_min],
            [qc, qr, pos, *selection, c_cache, kr_cache],
        ),
        out_shape=jax.ShapeDtypeStruct((B, NP, R, r), q_c.dtype),
        scratch=[
            pltpu.VMEM((2, P, bs, r), c_cache.dtype),
            pltpu.VMEM((2, P, rows, lanes), kr_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((NP, R, 1), jnp.float32),
            pltpu.VMEM((NP, R, 1), jnp.float32),
            pltpu.VMEM((NP, R, r), jnp.float32),
            *[pltpu.VMEM((nq, pack, tq, P * rows), jnp.float32) for _ in selection],
        ],
        interpret=interpret,
        name=CHUNK_KERNEL,
        semantics=("arbitrary",),
        vmem_limit_bytes=max(32 * 2**20, min(100 * 2**20, 2 * vmem)),
    )
    out = out.reshape(B, Hq // hp, nq, hp, tq, r)
    return out.transpose(0, 2, 4, 1, 3, 5).reshape(B, nq * tq, Hq, r)[:, :Sq]


# ---------------------------------------------------------------------------
# what the layer calls
# ---------------------------------------------------------------------------


def native_latent_attention(q_c, q_pe, c_all, kr_all, mask, scale):
    """Absorbed attention over gathered latents ``c_all (B, W, r)`` and rotary
    keys ``kr_all (B, W, d_rope)`` under ``mask (B, 1, S, W)``: float32
    scores and softmax. Returns the attended latents ``(B, S, Hq, r)``."""
    # float32 operands: a product of bf16 values is exact in the float32 it
    # accumulates in either way, and the CPU's dot takes no bf16 pair
    f32 = lambda a: a.astype(jnp.float32)
    scores = (
        jnp.einsum("bshr,bwr->bhsw", f32(q_c), f32(c_all))
        + jnp.einsum("bshd,bwd->bhsw", f32(q_pe), f32(kr_all))
    ) * scale
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhsw,bwr->bshr", f32(probs.astype(c_all.dtype)), f32(c_all)).astype(q_c.dtype)


def latent_attend(
    q_c, q_pe, c_cache, kr_cache, layer_idx, mask, block_table, kv_limit, positions,
    chosen=None, *, scale: float, interpret: bool,
):
    """Attention of the split serving step over the latent pool, this pass's
    latents already written: a prefill chunk rides the chunk kernel, a decode
    step the decode kernel, each where :func:`use_latent_kernel` admits the
    call; else blocks are gathered by the table and attended natively. Under
    a selection ``chosen (B, Sq, W)`` (live AND picked) the same rule picks
    the kernel and the predicate is what a query may attend: the chunk
    kernel's operand, the decode kernel's and the native form's mask."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        read_latent_cache_at_layer,
    )

    Sq = q_c.shape[1]
    if chosen is not None:
        mask = chosen[:, None]
    if use_latent_kernel(c_cache, kr_cache, Sq, mask.shape[-1]):
        # the decode kernel's mask is a slab a (head, position) ROW: a selection
        # over a long row at several positions (64 heads x 16 x a 16896 bucket:
        # 69 MB) stands in no VMEM, the chunk kernel's is one a position
        if Sq > TKG_MAX_Q_LEN or (chosen is not None and Sq > 1):
            return paged_latent_flash_attention(
                q_c, q_pe, c_cache, kr_cache, layer_idx, block_table, positions, kv_limit,
                chosen, scale=scale, interpret=interpret,
            )
        return paged_latent_decode_attention(
            q_c, q_pe, c_cache, kr_cache, layer_idx, block_table, mask,
            scale=scale, interpret=interpret,
        )
    c_all, kr_all = read_latent_cache_at_layer(c_cache, kr_cache, layer_idx, block_table)
    return native_latent_attention(q_c, q_pe, c_all, kr_all, mask, scale)
