"""Pallas decode-attention (TKG) kernels — contiguous and paged caches.

TPU-native re-design of the reference's token-generation attention kernels
(reference: modules/attention/attention_base.py:1467 plain TKG NKI kernel,
:1531 builtin ISA kernel, :1609 attention_block_tokengen "mega" kernel for
the block cache).

Why a kernel at all: decode q_len is tiny (1..spec_len), so the native path's
``read_*_cache_at_layer`` + ``repeat_kv`` materializes a (B, S_kv, Hq, D)
gathered/broadcast view in HBM before the softmax — for the paged cache that
is a full gather of every active block per layer per step. These kernels DMA
cache tiles straight out of the FULL stacked cache (layer index and block
table ride scalar prefetch), with the decode mask fused in — nothing is
materialized.

Grid layout, contiguous cache: (B, kv_tiles). Each step DMAs one (bs, Hkv, D)
cache tile — all KV heads at once, so the last two block dims stay full-size
for Mosaic — and an unrolled loop over the Hkv head groups runs the online
softmax for that group's n_rep*K query rows (GQA needs NO repeat_kv: queries
are pre-grouped rep-major). The cache is read exactly once, in tile-sized
DMAs; the default tile is 512 tokens.

Grid layout, paged cache: (B,) — one step a ROW. The pool's blocks are 32
tokens and a row's are scattered, so a tile is a GROUP of ``pages_per_step``
blocks (16 at the served shapes: 512 tokens) that the kernel copies by hand:
K and V stay in HBM (``pl.ANY``), an in-kernel loop runs over the row's live
groups only, each group's blocks land in one of two VMEM slots while the
group before it is attended (a row's last group starts the next LIVE row's
first copies), and no block past a row's last live one is copied. A row with
no live block costs one empty grid step; the cost of a dispatch follows the
live context, not slots x bucket. One online-softmax update per KV head runs
over a whole group, on query rows padded to the 8-sublane tile, the
statistics carried in registers; the heads' updates are written stage by
stage ACROSS the heads, so that their chains of dependent operations overlap
(``_attend_group``). Both products take the cache tile as it is
stored: a float32 operand goes to the matrix unit as three bfloat16 parts
that sum to it exactly (``_split3``), every product exact in the float32
accumulator, so nothing is rounded that the float32 form would keep. At a
pool row that is no multiple of the 128 lanes the chip's compiler refuses a
hand copy of a block (a 64-lane slice of an HBM ref), and the launch keeps
one block a grid step through a ``BlockSpec`` on the block table
(``_paged_by_block``, the contiguous kernel's body). A head_dim that divides
the lanes does not come here: its pool holds ``128 // head_dim`` heads a row
(modules/block_kvcache.kv_streams), and ``dispatch_paged_tkg_decode`` hands
this kernel the head_dim-128 problem it then is.

The paged kernel is also the paged KV WRITE of a one-token decode pass
(``new_kv``; modules/block_kvcache.write_form says when). A decode row's new
token belongs in the row's LAST live block, which the row's last group has in
VMEM for every KV head at once: the kernel lays the token's K and V rows over
that block's 16-row tile with a select against an iota (the chip's compiler
refuses a single-row store into a packed bfloat16 tile), attends the group as
ever, which is what write-then-attend attends, and sends the tile
``(Hkv, 16, D)`` back to the pool with one copy a stream from a scratch of
its own, waited for a row later, so that it runs under the next row's
arithmetic. The pools are aliased in and out (``input_output_aliases``) and
stay the layer scan's carry, in place. The step then holds no scatter, whose
``B x Hkv`` index rows a stream a layer cost more than the bytes they placed.

Masking is taken from the SAME (B, 1, K, S_kv) boolean mask the native path
uses — window/chunk/speculation decode masks all work unchanged — re-tiled to
(B, kv_tiles, K, bs), plus per-(row, tile) any() maxima as scalar prefetch so
fully-masked tiles are skipped (the causal-frontier skip of the reference
kernels).

Learned attention sinks (GPT-OSS) join the softmax denominator at finalize
(reference attention_base.py:1964-1980).

Quantized (int8/fp8) caches: both kernels take the :class:`QuantizedKV`
streams directly and DMA the NARROW code tiles — half (or a quarter of) the
bf16 bytes, which is the entire win on the bandwidth-bound decode step. The
per-(layer, head) symmetric scale is applied exactly, without materializing
a dequantized cache anywhere: the K scale folds into q before the kernel
(scaling the QKᵀ product — the online-softmax stats then run on true
scores), and the V scale multiplies the per-head output after finalize
(linear in the PV accumulation). In-kernel the codes convert to fp32
in-register (``.astype`` in ``_body``); stats/accumulators stay fp32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

from neuronx_distributed_inference_tpu.modules.kvcache import (
    QuantizedKV,
    layer_dequant_factors,
)

try:  # pallas TPU backend
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30


# kernel/native dispatch gate: consolidated in ops/kernel_mode.py (one
# tested predicate per kernel); the historical name stays importable here
from neuronx_distributed_inference_tpu.ops.kernel_mode import (  # noqa: E402
    use_tkg as use_tkg_kernel,
)


def _body(
    q_ref, mask_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
    *, scale, n_kv, rk, K, head_major=False,
):
    """One cache tile: unrolled loop over the Hkv head groups.

    ``head_major`` selects the cache tile layout: (Hkv, bs, D) for the paged
    cache (head-major blocks, see block_kvcache), (bs, Hkv, D) contiguous."""
    k_all = k_ref[0, 0].astype(jnp.float32)
    v_all = v_ref[0, 0].astype(jnp.float32)
    mt = mask_ref[0, 0] > 0  # (K, bs)
    bs = k_all.shape[1] if head_major else k_all.shape[0]
    row_mask = jnp.repeat(mt[None], rk // K, axis=0).reshape(rk, bs)
    for g in range(n_kv):
        rows = slice(g * rk, (g + 1) * rk)
        q = q_ref[0, rows, :].astype(jnp.float32)  # (rk, D)
        k = k_all[g] if head_major else k_all[:, g, :]  # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rk, bs)
        s = jnp.where(row_mask, s, NEG_INF)

        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(row_mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[rows, :] = l_scr[rows, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_all[g] if head_major else v_all[:, g, :]
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[rows, :] = m_new


def _finalize(o_ref, m_scr, l_scr, acc_scr, sink_ref, all_rows, K):
    if sink_ref is None:
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
    else:
        # sink logit joins the denominator (reference attention_base.py:1964):
        # renormalize both accumulators to m2 = max(m, sink) so rows that saw
        # no valid kv (m == -inf) stay finite and output zeros
        sink = sink_ref[0].astype(jnp.float32)  # (Hq,) row-major per head
        sink_row = jnp.repeat(sink[:, None], K, axis=1).reshape(all_rows, 1)
        m2 = jnp.maximum(m_scr[:], sink_row)
        alpha = jnp.exp(m_scr[:] - m2)
        denom = l_scr[:] * alpha + jnp.exp(sink_row - m2)
        o_ref[0] = (acc_scr[:] * alpha / denom).astype(o_ref.dtype)


def _tkg_kernel(*args, scale, n_kv, rk, K, nkv, has_sink, n_prefetch, head_major=False):
    prefetch, rest = args[:n_prefetch], args[n_prefetch:]
    tile_any_ref = prefetch[-1]
    if has_sink:
        q_ref, mask_ref, sink_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        q_ref, mask_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
        sink_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(tile_any_ref[b, j] > 0)
    def _compute():
        _body(
            q_ref, mask_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
            scale=scale, n_kv=n_kv, rk=rk, K=K, head_major=head_major,
        )

    @pl.when(j == nkv - 1)
    def _fin():
        _finalize(o_ref, m_scr, l_scr, acc_scr, sink_ref, n_kv * rk, K)


def _prep_q(q: jax.Array):
    """(B, K, Hq, D) -> (B, Hq*K, D): row h*K + t. Head h's kv group is
    h // n_rep, so group g's rows are the contiguous [g*n_rep*K, (g+1)*n_rep*K)
    slice — the repeat_kv pairing with no broadcast."""
    B, K, Hq, D = q.shape
    return q.transpose(0, 2, 1, 3).reshape(B, Hq * K, D)


def _fold_k_dequant(q: jax.Array, k_cache: QuantizedKV, layer_idx, n_rep: int):
    """Fold the K stream's per-head dequant factor into q (fp32): the QKᵀ
    product then equals q·k̂ exactly, so mask/max/exp see true scores."""
    ks = layer_dequant_factors(k_cache, layer_idx)  # (Hkv,)
    return q.astype(jnp.float32) * jnp.repeat(ks, n_rep)[None, None, :, None]


def _apply_v_dequant(out: jax.Array, v_cache: QuantizedKV, layer_idx, n_rep: int):
    """Scale the per-head output by the V dequant factor: the accumulated
    Σ p·v_codes times scale/qmax equals Σ p·v̂ (scale constant per head)."""
    vs = layer_dequant_factors(v_cache, layer_idx)  # (Hkv,)
    return out * jnp.repeat(vs, n_rep)[None, None, :, None]


def _unprep_out(out: jax.Array, B: int, K: int, Hq: int, D: int):
    return out.reshape(B, Hq, K, D).transpose(0, 2, 1, 3)


def _mask_tiles(mask: jax.Array, nkv: int, bs: int):
    """(B, 1, K, S_kv) bool -> ((B, nkv, K, bs) int32, (B, nkv) int32 any)."""
    B, _, K, S = mask.shape
    m = mask[:, 0].astype(jnp.int32).reshape(B, K, nkv, bs).transpose(0, 2, 1, 3)
    tile_any = (m.sum(axis=(2, 3)) > 0).astype(jnp.int32)
    return m, tile_any


def _common_call(
    kernel, grid, in_specs, out_specs, operands, out_shape, scratch, interpret, name,
    semantics=("parallel", "arbitrary"), vmem_limit_bytes=None, aliases=None,
):
    """The launch of every paged and decode attention kernel (this module's
    and ``ops/paged_flash_attention.py``'s): ``operands`` is (scalar
    prefetch, tensors); ``aliases`` maps an operand (counted over both) to
    the output that is the same buffer."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(operands[0]),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes
        ),
        input_output_aliases=aliases or {},
        interpret=interpret,
        name=name,
    )(*operands[0], *operands[1])


@functools.partial(jax.jit, static_argnames=("scale", "n_kv", "bs", "interpret"))
def tkg_decode_attention(
    q: jax.Array,  # (B, K, Hq, D)
    k_cache: jax.Array,  # (L, R, S_max, Hkv, D) FULL stacked contiguous cache
    v_cache: jax.Array,
    layer_idx: jax.Array,  # int32 scalar
    mask: jax.Array,  # (B, 1, K, S_kv) bool decode mask, S_kv <= S_max
    sink: jax.Array = None,  # (Hq,) learned sink logits
    *,
    scale: float,
    n_kv: int,
    bs: int = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention straight off the stacked contiguous cache (batch row b
    owns cache line b — the sorted-batch convention of read_cache_at_layer).
    Quantized caches (QuantizedKV streams) DMA the int8/fp8 code tiles and
    dequantize in-register (see module docstring). Returns (B, K, Hq, D)."""
    B, K, Hq, D = q.shape
    S_kv = mask.shape[-1]
    if bs is None:
        # default kv tile through the tuning table (KERN704): keyed by the
        # kv bucket and the CACHE dtype (a quantized cache DMAs int8 tiles)
        cache_dt = k_cache.data.dtype if isinstance(k_cache, QuantizedKV) else k_cache.dtype
        bs = tile_default("tkg_decode_attention", f"kv{S_kv}", cache_dt, "bs", 512)
    bs = min(bs, S_kv)
    nkv = S_kv // bs
    n_rep = Hq // n_kv
    rk = n_rep * K
    out_dtype = q.dtype
    quantized = isinstance(k_cache, QuantizedKV)
    if quantized:
        q = _fold_k_dequant(q, k_cache, layer_idx, n_rep)
        k_cache, v_quant = k_cache.data, v_cache
        v_cache = v_cache.data
    qr = _prep_q(q)
    m, tile_any = _mask_tiles(mask, nkv, bs)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    kernel = functools.partial(
        _tkg_kernel, scale=scale, n_kv=n_kv, rk=rk, K=K, nkv=nkv,
        has_sink=sink is not None, n_prefetch=2,
    )
    in_specs = [
        pl.BlockSpec((1, Hq * K, D), lambda b, j, li, ta: (b, 0, 0)),
        pl.BlockSpec((1, 1, K, bs), lambda b, j, li, ta: (b, j, 0, 0)),
    ]
    tensors = [qr, m]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, Hq), lambda b, j, li, ta: (0, 0)))
        tensors.append(sink.reshape(1, Hq))
    in_specs += [
        pl.BlockSpec((1, 1, bs, n_kv, D), lambda b, j, li, ta: (li[0], b, j, 0, 0)),
        pl.BlockSpec((1, 1, bs, n_kv, D), lambda b, j, li, ta: (li[0], b, j, 0, 0)),
    ]
    tensors += [k_cache, v_cache]

    out = _common_call(
        kernel,
        grid=(B, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq * K, D), lambda b, j, li, ta: (b, 0, 0)),
        operands=([li, tile_any], tensors),
        out_shape=jax.ShapeDtypeStruct((B, Hq * K, D), q.dtype),
        scratch=[
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, D), jnp.float32),
        ],
        interpret=interpret,
        name="tkg_decode_attention",
    )
    out = _unprep_out(out, B, K, Hq, D)
    if quantized:
        out = _apply_v_dequant(out, v_quant, layer_idx, n_rep).astype(out_dtype)
    return out


# ---------------------------------------------------------------------------
# paged decode: a group of pool blocks a step, no step past a row's context
# ---------------------------------------------------------------------------

#: what a group of pool blocks should hold per stream (K or V): large enough
#: that the fixed cost of a step is paid per ~MB of cache and not per block,
#: small enough that two slots of both streams sit in VMEM beside q, the
#: row's mask and the accumulators (4 x this)
GROUP_BYTES = 1024 * 1024
#: and the most tokens a group may span: the width ``pages`` was measured at
#: (analysis/tuning_table.json). Not for the arithmetic's sake: a group's
#: update costs by its stages, not by its tokens, and hides under the copies
#: (PERF.md, PR 53: attending only the live sub-tiles of a row's last group
#: moved nothing)
GROUP_TOKENS = 512


def pages_per_step(
    n_kv: int, bs: int, head_dim: int, cache_dtype, max_blocks: int,
    kernel: str = "paged_tkg_decode_attention",
) -> int:
    """Pool blocks the paged decode kernel fetches and attends per step (its
    ``P``): a power of two that follows the block's shape through the tuning
    table, never more than the block table is wide (a table no multiple of
    it wide is padded with dead entries). Host code calls this too
    (``ServingSession`` counts the blocks the kernel walks). The paged prefill
    kernel walks a row by the same rule under its own name in the table
    (``kernel``)."""
    dt = jnp.dtype(cache_dtype)
    if head_dim % 128:  # the POOL ROW's width: a pool of g heads a row is asked at g x D
        return 1  # blocks come through a BlockSpec, one a step: _paged_by_block
    block_bytes = n_kv * bs * head_dim * dt.itemsize
    p = 1
    while 2 * p * block_bytes <= GROUP_BYTES and 2 * p * bs <= GROUP_TOKENS:
        p *= 2
    p = tile_default(kernel, f"blk{n_kv}x{bs}x{head_dim}", dt.name, "pages", p)
    p = max(1, min(p, max_blocks))
    return 1 << (p.bit_length() - 1)  # the kernel takes a group's live count apart by bits


def kv_blocks_walked(
    live_blocks, max_blocks: int, *, n_kv: int, bs: int, head_dim: int, cache_dtype,
    kernel: str = "paged_tkg_decode_attention",
) -> int:
    """Block-table entries a paged kernel's kv axis attends for rows whose
    contexts hold ``live_blocks`` (one count a row) blocks of a table
    ``max_blocks`` wide: whole groups up to a row's last live block; every
    entry of the table where blocks come one a grid step (``_paged_by_block``)."""
    if head_dim % 128:
        return max_blocks * len(live_blocks)
    P = pages_per_step(n_kv, bs, head_dim, cache_dtype, max_blocks, kernel)
    return sum(-(-n // P) * P for n in live_blocks)


def _bf16_part(x):
    """The top 16 bits of a float32: a value bfloat16 holds exactly. Cut, not
    rounded, and through the bit pattern, so that no compiler may take the
    float32 -> bfloat16 -> float32 pair for the identity."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(x):
    """float32 (R, N) -> bfloat16 (3R, N) whose three row blocks sum to ``x``
    EXACTLY (8 + 8 + 8 significand bits): the matrix unit then multiplies a
    float32 operand by a bfloat16 tile in one pass, every product exact in
    its float32 accumulator, and nothing of ``x`` is rounded away."""
    hi = _bf16_part(x)
    rest = x - hi
    mid = _bf16_part(rest)
    return jnp.concatenate([hi, mid, rest - mid], axis=0).astype(jnp.bfloat16)


def _dot_tile(x, tile, contract_tile_dim: int):
    """``x`` (R, N), float32 or bfloat16, against one head's cache tile,
    contracting ``tile``'s ``contract_tile_dim``, in float32. A tile whose
    values bfloat16 holds exactly (bf16 itself, int8 / fp8 codes) goes to the
    matrix unit as it is stored, a float32 ``x`` as its three bfloat16 parts;
    any other tile (float32 caches: the parity tests) is converted and
    multiplied in float32."""
    dims = (((1,), (contract_tile_dim,)), ((), ()))
    if tile.dtype == jnp.float32 or tile.dtype == jnp.float16:
        return jax.lax.dot_general(
            x.astype(jnp.float32), tile.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32,
        )
    if tile.dtype != jnp.bfloat16:
        tile = tile.astype(jnp.float32).astype(jnp.bfloat16)  # codes: exact
    if x.dtype == jnp.bfloat16:
        return jax.lax.dot_general(x, tile, dims, preferred_element_type=jnp.float32)
    R = x.shape[0]
    y = jax.lax.dot_general(_split3(x), tile, dims, preferred_element_type=jnp.float32)
    return y[:R] + y[R : 2 * R] + y[2 * R :]


def _group_copies(bt_ref, end_ref, streams, sems, *, layer, P):
    """``(start, wait)`` of a paged kernel that copies its K and V by hand:
    ``start(row, group, slot)`` begins the copies of the ``group``-th ``P``
    blocks of ``row``'s table into ``slot`` of each stream's buffer, and
    ``wait(row, group, slot)`` returns when they have landed. ``streams`` is
    ``((hbm, buf), ...)``: the stacked pool ``(L, NB+1, Hkv, bs, D)`` left in
    HBM and its two-slot buffer ``(2, Hkv, P * bs, D)``; ``sems`` is a DMA
    semaphore per (stream, slot); ``end_ref[row]`` is one past the row's last
    live block, and nothing past it is copied."""
    bs = streams[0][1].shape[2] // P

    def live_blocks(row, group):
        """Blocks of ``group`` up to ``row``'s last live one: what is copied.
        None past it (what lies there in the slot is an earlier group's,
        masked)."""
        return jnp.clip(end_ref[row] - group * P, 0, P)

    def start(row, group, slot):
        def block(p, _):
            tokens = pl.ds(pl.multiple_of(p * bs, bs), bs)
            page = bt_ref[row, group * P + p]
            for stream, (hbm, buf) in enumerate(streams):
                pltpu.make_async_copy(
                    hbm.at[layer, page], buf.at[slot, :, tokens, :], sems.at[stream, slot]
                ).start()

        jax.lax.fori_loop(0, live_blocks(row, group), block, None)

    def wait(row, group, slot):
        # a wait needs only the bytes and the semaphore: the live count taken
        # apart into powers of two, one wait stands for a whole run of blocks
        n = live_blocks(row, group)
        width = P
        while width:
            @pl.when(n & width > 0)
            def _(width=width):
                for stream, (_, buf) in enumerate(streams):
                    run = buf.at[slot, :, pl.ds(0, width * bs), :]
                    pltpu.make_async_copy(run, run, sems.at[stream, slot]).wait()

            width //= 2

    return start, wait


#: what the chip's compiler gives a kernel's scoped allocations unasked
SCOPED_VMEM_BYTES = 16 * 2**20

#: rows of a block the in-kernel KV write reads, merges and stores for ONE
#: new token: bfloat16's sublane tile, the least the chip stores whole
WRITE_TILE_ROWS = 16


def _token_placer(layer, page, off, pools, bufs, tiles, news, sems, pending_ref):
    """``(place, settle, fetch)`` of the in-kernel KV write of a row's ONE new
    token, which belongs at row ``off`` of pool block ``page`` (< 0: nowhere).

    ``place(held, into)`` merges the token's K and V rows (``news``, a
    ``(n_kv, 1, D)`` block each) into the ``T`` rows around ``off`` that
    ``held(stream)`` gives as the pool holds them, a select against an iota
    (no single-row store into a packed tile), leaves the merged rows in the
    stream's ``tiles`` scratch and, where ``into = (slot, first_row)`` says
    so, in the group buffer the row is attended from; then starts ONE copy a
    stream of the tile ``(n_kv, T, D)`` to its place in the pool. The copy
    runs under the rows that follow: ``settle()`` waits for it, before the
    tiles are written again and before the kernel returns."""
    n_kv, T, D = tiles[0].shape
    rows = pl.ds(pl.multiple_of(off // T * T, T), T)

    def copy(stream, to_pool: bool, page):
        there = pools[stream].at[layer, page, :, rows, :]
        src, dst = (tiles[stream], there) if to_pool else (there, tiles[stream])
        return pltpu.make_async_copy(src, dst, sems.at[stream])

    def settle():
        @pl.when(pending_ref[0] > 0)
        def _():
            for stream in range(len(pools)):
                copy(stream, True, 0).wait()  # a wait takes the bytes and the semaphore
            pending_ref[0] = 0

    def place(held, into=None):
        mine = jax.lax.broadcasted_iota(jnp.int32, (n_kv, T, D), 1) == off % T
        for stream, (tile, new_ref) in enumerate(zip(tiles, news)):
            merged = jnp.where(mine, new_ref[0], held(stream))
            tile[...] = merged
            if into is not None:
                slot, first = into
                bufs[stream][slot, :, pl.ds(first, T), :] = merged
            copy(stream, True, page).start()
        pending_ref[0] = 1

    def fetch(stream):
        """The token's tile as the pool holds it, for a token whose block the
        row's attention has not brought in."""
        held = copy(stream, False, page)
        held.start()
        held.wait()
        return tiles[stream][...]

    return place, settle, fetch


#: vector registers' worth of scores the KV heads attended AT ONCE may hold
#: (:func:`_attend_group`): the chip's 64. Every served shape fits whole (8
#: heads x 8 rows x 512 tokens = 32; 4 heads x 32 rows x 512 = 64, which
#: read faster at once, spills and all, than two heads at a time: PERF.md,
#: PR 53); past it the heads go in chunks
SCORE_VREGS = 64


def _attend_group(q_ref, mask, k_ref, v_ref, carry, *, scale, q_dtype):
    """One online-softmax update a KV head over a group of ``G`` tokens:
    ``q_ref`` (Hkv, R, D), ``k_ref`` / ``v_ref`` (Hkv, G, D) the cache tiles as
    they are stored, ``mask`` (1, G) bool at one query token, (R, G) laid out
    per query row otherwise; ``carry`` and the result are ``(m, l, acc)`` a
    head, in registers.

    Written STAGE BY STAGE across the heads (every head's scores, then every
    head's maximum, ... then every head's ``p . v``), not head by head: a
    head's update is a chain of two products, two reductions over the lanes
    and an exp, each waiting for the one before, and the chip runs the chains
    of heads written one after the other one after the other (PERF.md, PR 53:
    8 heads of the 1.7B 2.0 us a group whatever the group's width, 0.5 us
    stage by stage). As many heads at once as ``SCORE_VREGS`` admits; the
    values are the same, operation for operation."""
    n_kv, R, _ = q_ref.shape
    G = k_ref.shape[1]
    row_mask = jnp.broadcast_to(mask, (R, G))
    at_once = max(1, SCORE_VREGS // (-(-R // 8) * -(-G // 128)))
    out = []
    for first in range(0, n_kv, at_once):
        heads = range(first, min(first + at_once, n_kv))
        m_prev, l_prev, acc_prev = zip(*(carry[h] for h in heads))
        # q (R, D): bfloat16 where it came so; s (R, G)
        s = [_dot_tile(q_ref[h].astype(q_dtype), k_ref[h], 1) * scale for h in heads]
        s = [jnp.where(row_mask, x, NEG_INF) for x in s]
        m_new = [jnp.maximum(m, jnp.max(x, axis=1, keepdims=True)) for m, x in zip(m_prev, s)]
        p = [jnp.where(row_mask, jnp.exp(x - m), 0.0) for x, m in zip(s, m_new)]
        alpha = [jnp.exp(m0 - m) for m0, m in zip(m_prev, m_new)]
        l_new = [l * a + jnp.sum(x, axis=1, keepdims=True) for l, a, x in zip(l_prev, alpha, p)]
        pv = [_dot_tile(x, v_ref[h], 0) for x, h in zip(p, heads)]
        out += [(m, l, acc * a + y) for m, l, acc, a, y in zip(m_new, l_new, acc_prev, alpha, pv)]
    return tuple(out)


def _paged_group_kernel(
    li_ref, bt_ref, lo_ref, end_ref, live_from_ref, *rest,
    scale, n_kv, P, has_sink, q_dtype, writes=False,
):
    """One ROW per grid step; inside, a loop over the row's live block groups
    only. K and V stay in HBM: each group's ``P`` blocks are copied into one
    of two VMEM slots while the group before it (of this row or of the last
    live row) is attended, so a row with no live block starts no copy and
    runs no arithmetic.

    ``writes``: the kernel also PLACES the row's one new token (the paged KV
    write's in-kernel form, modules/block_kvcache.write_form). The pools are
    aliased in and out and read and written through the output refs; the
    token's ``(block, offset)`` rides scalar prefetch (a negative block: no
    write). A decode row's token lies in the row's LAST live block (the mask
    admits the token's own position and nothing after it): in the row's last
    group, once its blocks have landed, the token's K and V rows are laid
    over the block's tile in the slot, the group is attended as ever (what
    write-then-attend attends), and the tile goes back to the pool under the
    next rows' arithmetic (:func:`_token_placer`). A token whose block the
    row does not end in (no live block, another page) is stored all the same,
    through a read of its tile, and is not attended."""
    rest = list(rest)
    if writes:
        page_ref, off_ref = rest[:2]
        del rest[:2]
    q_ref, mask_ref = rest[:2]
    del rest[:2]
    sink_ref = rest.pop(0) if has_sink else None
    if writes:
        # the pools' input refs are the output refs' own buffers: unused
        (k_new_ref, v_new_ref, _, _, o_ref, k_hbm, v_hbm, k_buf, v_buf, sems, slot_ref,
         k_tile, v_tile, tile_sems, pending_ref) = rest
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = rest
    b = pl.program_id(0)
    B = pl.num_programs(0)
    R = q_ref.shape[2]  # a head group's query rows, padded to the sublane tile

    start, wait = _group_copies(
        bt_ref, end_ref, ((k_hbm, k_buf), (v_hbm, v_buf)), sems, layer=li_ref[0], P=P
    )

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        if writes:
            pending_ref[0] = 0
        # a masked token's probability is 0, and 0 x what the slot was born
        # with need not be 0: V's slots start from zeros
        v_buf[...] = jnp.zeros_like(v_buf)
        row = live_from_ref[0]

        @pl.when(row < B)
        def _():
            start(row, lo_ref[row], 0)

    lo, hi = lo_ref[b], (end_ref[b] + P - 1) // P
    if writes:
        page, off = page_ref[b], off_ref[b]
        place, settle, fetch = _token_placer(
            li_ref[0], page, off, (k_hbm, v_hbm), (k_buf, v_buf), (k_tile, v_tile),
            (k_new_ref, v_new_ref), tile_sems, pending_ref,
        )
        last_block = end_ref[b] - 1
        attended = (page >= 0) & (last_block >= 0) & (bt_ref[b, jnp.maximum(last_block, 0)] == page)

    def group(g, carry):
        slot = slot_ref[0]
        last = g == hi - 1
        nrow = jnp.where(last, live_from_ref[b + 1], b)

        @pl.when(nrow < B)
        def _prefetch():
            start(nrow, jnp.where(last, lo_ref[nrow], g + 1), 1 - slot)

        wait(b, g, slot)
        slot_ref[0] = 1 - slot

        if writes:
            @pl.when(last & attended)
            def _place():
                T = k_tile.shape[1]
                bs = k_buf.shape[2] // P
                first = pl.multiple_of((last_block - g * P) * bs + off // T * T, T)
                settle()
                place(
                    lambda stream: (k_buf, v_buf)[stream][slot, :, pl.ds(first, T), :],
                    into=(slot, first),
                )

        return _attend_group(
            q_ref.at[0], mask_ref[0, g] > 0, k_buf.at[slot], v_buf.at[slot], carry,
            scale=scale, q_dtype=q_dtype,
        )

    D = q_ref.shape[3]
    init = tuple(
        (
            jnp.full((R, 1), NEG_INF, jnp.float32),
            jnp.zeros((R, 1), jnp.float32),
            jnp.zeros((R, D), jnp.float32),
        )
        for _ in range(n_kv)
    )
    stats = jax.lax.fori_loop(lo, hi, group, init)
    for h, (m, l, acc) in enumerate(stats):
        if sink_ref is None:
            o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        else:
            # as _finalize: renormalize to max(m, sink); a row that saw no
            # valid kv stays finite and writes zeros
            sink = sink_ref[h]  # (R, 1)
            m2 = jnp.maximum(m, sink)
            alpha = jnp.exp(m - m2)
            o_ref[0, h] = (acc * alpha / (l * alpha + jnp.exp(sink - m2))).astype(o_ref.dtype)

    if writes:
        @pl.when((page >= 0) & jnp.logical_not(attended))
        def _store_unattended():
            settle()
            place(fetch)

        @pl.when(b == B - 1)
        def _last():
            settle()


def _paged_by_block(q, k_cache, v_cache, li, block_table, mask, sink, *, scale, n_kv, interpret):
    """The launch at a pool row that is no multiple of the 128 lanes: the
    chip's compiler refuses such a slice of an HBM ref, so the blocks cannot
    be copied by hand; they come one a grid step through a ``BlockSpec`` on
    the block table, ``(B, MB)`` steps, as the contiguous kernel's tiles do
    (a dead step repeats the last block index and fetches nothing). Still
    reached by a head_dim that fills no 128-lane row with whole heads (72,
    80, 96), an odd KV head count a device, and a quantised pool at head_dim
    64 (block_kvcache.heads_a_row keeps those a head a row); no benchmark
    cell since PR 65. (B, K, Hq, D) -> (B, Hq*K, D)."""
    B, K, Hq, D = q.shape
    bs = k_cache.shape[3]
    MB = block_table.shape[1]
    m, tile_any = _mask_tiles(mask, MB, bs)
    kernel = functools.partial(
        _tkg_kernel, scale=scale, n_kv=n_kv, rk=Hq // n_kv * K, K=K, nkv=MB,
        has_sink=sink is not None, n_prefetch=3, head_major=True,
    )
    in_specs = [
        pl.BlockSpec((1, Hq * K, D), lambda b, j, li, bt, ta: (b, 0, 0)),
        pl.BlockSpec((1, 1, K, bs), lambda b, j, li, bt, ta: (b, j, 0, 0)),
    ]
    tensors = [_prep_q(q), m]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, Hq), lambda b, j, li, bt, ta: (0, 0)))
        tensors.append(sink.reshape(1, Hq))
    block = pl.BlockSpec(
        (1, 1, n_kv, bs, D), lambda b, j, li, bt, ta: (li[0], bt[b, j], 0, 0, 0)
    )
    in_specs += [block, block]
    tensors += [k_cache, v_cache]
    return _common_call(
        kernel,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq * K, D), lambda b, j, li, bt, ta: (b, 0, 0)),
        operands=([li, block_table, tile_any], tensors),
        out_shape=jax.ShapeDtypeStruct((B, Hq * K, D), q.dtype),
        scratch=[
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, D), jnp.float32),
        ],
        interpret=interpret,
        name="paged_tkg_decode_attention",
    )


def _paged_by_group(
    q, k_cache, v_cache, li, block_table, mask, sink, new=None, *, scale, n_kv, P, interpret
):
    """The launch of :func:`_paged_group_kernel`. (B, K, Hq, D) -> (B, Hq*K, D);
    with ``new = (k_new, v_new (B, Hkv, 1, D), page, off (B,))``, one token a
    row to place, -> that and the two pools, updated in place."""
    B, K, Hq, D = q.shape
    bs = k_cache.shape[3]
    MB = block_table.shape[1]
    n_rep = Hq // n_kv
    rk = n_rep * K
    R = -(-rk // 8) * 8
    NG = -(-MB // P)
    G = P * bs
    pad = NG * P - MB  # a table no multiple of P wide: dead entries, masked
    block_table = jnp.pad(block_table, ((0, 0), (0, pad)))
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pad * bs)))

    def rows(x):  # (N, Hq*K, ...) -> (N, Hkv, R, ...): a head group's rows, padded
        x = x.reshape(x.shape[0], n_kv, rk, *x.shape[2:])
        return jnp.pad(x, ((0, 0), (0, 0), (0, R - rk)) + ((0, 0),) * (x.ndim - 3))

    m, _ = _mask_tiles(mask, NG, G)  # (B, NG, K, G)
    if K > 1:  # row r*K + t of a head group reads mask row t
        m = jnp.pad(jnp.tile(m, (1, 1, n_rep, 1)), ((0, 0), (0, 0), (0, R - rk), (0, 0)))
    # per row: one past its last live block, the group its first live block
    # lies in, and from each row the next row that has any
    live = _mask_tiles(mask, NG * P, bs)[1] > 0
    idx = jnp.arange(NG * P, dtype=jnp.int32)
    end = jnp.max(jnp.where(live, idx + 1, 0), axis=1)
    lo = jnp.min(jnp.where(live, idx, NG * P - 1), axis=1) // P
    live_from = jax.lax.cummin(
        jnp.where(end > 0, jnp.arange(B, dtype=jnp.int32), B), reverse=True
    )
    live_from = jnp.concatenate([live_from, jnp.full((1,), B, jnp.int32)])

    def row_spec(shape):
        return pl.BlockSpec((1,) + shape, lambda b, *_: (b,) + (0,) * len(shape))

    in_specs = [row_spec((n_kv, R, D)), row_spec(m.shape[1:])]
    tensors = [rows(_prep_q(q).astype(jnp.float32)), m]
    if sink is not None:
        in_specs.append(pl.BlockSpec((n_kv, R, 1), lambda b, *_: (0, 0, 0)))
        tensors.append(rows(jnp.repeat(sink.astype(jnp.float32), K)[None, :, None])[0])
    prefetch = [li, block_table, lo, end, live_from]
    out_specs = row_spec((n_kv, R, D))
    out_shape = jax.ShapeDtypeStruct((B, n_kv, R, D), q.dtype)
    scratch = [
        pltpu.VMEM((2, n_kv, G, D), k_cache.dtype),
        pltpu.VMEM((2, n_kv, G, D), v_cache.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    aliases = None
    if new is not None:
        k_new, v_new, page, off = new
        prefetch += [page, off]
        in_specs += [row_spec((n_kv, 1, D))] * 2
        tensors += [k_new, v_new]
        # the pools are the kernel's outputs too, in place
        first_pool = len(prefetch) + len(tensors)
        aliases = {first_pool: 1, first_pool + 1: 2}
        pool = pl.BlockSpec(memory_space=pl.ANY)
        out_specs = [out_specs, pool, pool]
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in (k_cache, v_cache)]
        T = WRITE_TILE_ROWS if bs % WRITE_TILE_ROWS == 0 else bs
        scratch += [
            pltpu.VMEM((n_kv, T, D), k_cache.dtype),
            pltpu.VMEM((n_kv, T, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    tensors += [k_cache, v_cache]
    # what a grid step holds in VMEM: the row's mask slab and its q and
    # output, each twice (pipelined), and the two slots of K and V. Under the
    # compiler's own scoped limit nothing is asked (every shape served before
    # a window layer was: the call it always was); a slab of (n_rep x K) rows
    # over a long bucket (8 q heads a KV head x 16 positions x 16384 keys:
    # 18 MiB) asks for what it needs
    item = jnp.dtype(k_cache.dtype).itemsize
    held = 2 * math.prod(m.shape[1:]) * 4 + 4 * n_kv * G * D * item + 4 * n_kv * R * D * 4
    vmem_limit = None if held <= SCOPED_VMEM_BYTES else held + 8 * 2**20

    out = _common_call(
        functools.partial(
            _paged_group_kernel, scale=scale, n_kv=n_kv, P=P, has_sink=sink is not None,
            q_dtype=jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32,
            writes=new is not None,
        ),
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        operands=(prefetch, tensors),
        out_shape=out_shape,
        scratch=scratch,
        interpret=interpret,
        name="paged_tkg_decode_attention",
        # rows in order: a row's last group starts the next live row's copies
        semantics=("arbitrary",),
        aliases=aliases,
        vmem_limit_bytes=vmem_limit,
    )
    if new is None:
        return out[:, :, :rk].reshape(B, Hq * K, D)
    return out[0][:, :, :rk].reshape(B, Hq * K, D), out[1], out[2]


@functools.partial(jax.jit, static_argnames=("scale", "n_kv", "interpret"))
def paged_tkg_decode_attention(
    q: jax.Array,  # (B, K, Hq, D)
    k_cache: jax.Array,  # (L, NB+1, Hkv, bs, D) FULL stacked head-major paged cache
    v_cache: jax.Array,
    layer_idx: jax.Array,  # int32 scalar
    block_table: jax.Array,  # (B, MB) int32
    mask: jax.Array,  # (B, 1, K, MB*bs) bool decode mask over the block view
    sink: jax.Array = None,
    new_kv=None,  # (k_new (B, 1, Hkv, D), v_new, slot_mapping (B, 1)): placed by the kernel
    *,
    scale: float,
    n_kv: int,
    interpret: bool = False,
):
    """Paged decode attention: cache blocks are DMA'd straight via the block
    table (scalar prefetch), :func:`pages_per_step` of them a step and none
    past a row's last live block — kills the materializing
    read_block_cache_at_layer gather on the serving decode path
    (reference attention_block_tokengen kernel, attention_base.py:1609).
    Quantized caches DMA the code blocks and dequantize in-register.
    Returns (B, K, Hq, D).

    With ``new_kv`` the kernel is also the paged KV write of the pass
    (modules/block_kvcache.write_form says when: one token a row, ``D`` on
    the lanes, an unquantised pool): the caller has NOT written this pass's
    K and V; the kernel places a row's token at its ``slot_mapping`` entry (a
    slot < 0 or past the pool: dropped, as the scatter drops it), attends as
    write-then-attend does, and returns ``(out, k_cache, v_cache)`` with the
    pools updated in place (``input_output_aliases``)."""
    B, K, Hq, D = q.shape
    _, NB1, Hkv, bs, width = k_cache.shape
    MB = block_table.shape[1]
    assert mask.shape[-1] == MB * bs, (mask.shape, MB, bs)
    # a pool of several heads a row comes through dispatch_paged_tkg_decode,
    # which lays the queries in their head's lanes
    assert width == D, f"q of {D} lanes against pool rows of {width}"
    n_rep = Hq // n_kv
    out_dtype = q.dtype
    quantized = isinstance(k_cache, QuantizedKV)
    if quantized:
        q = _fold_k_dequant(q, k_cache, layer_idx, n_rep)
        k_cache, v_quant = k_cache.data, v_cache
        v_cache = v_cache.data
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    operands = (q, k_cache, v_cache, li, block_table.astype(jnp.int32), mask, sink)
    P = pages_per_step(n_kv, bs, D, k_cache.dtype, MB)
    if new_kv is not None:
        assert K == 1 and D % 128 == 0 and not quantized, (K, D, quantized)
        k_new, v_new, slot_mapping = new_kv
        slots = slot_mapping.reshape(B).astype(jnp.int32)
        kept = (slots >= 0) & (slots // bs < NB1)
        new = (
            k_new.astype(k_cache.dtype).transpose(0, 2, 1, 3),
            v_new.astype(v_cache.dtype).transpose(0, 2, 1, 3),
            jnp.where(kept, slots // bs, -1),
            jnp.where(kept, slots % bs, 0),
        )
        out, k_cache, v_cache = _paged_by_group(
            *operands, new, scale=scale, n_kv=n_kv, P=P, interpret=interpret
        )
        return _unprep_out(out, B, K, Hq, D), k_cache, v_cache
    if D % 128:
        out = _paged_by_block(*operands, scale=scale, n_kv=n_kv, interpret=interpret)
    else:
        out = _paged_by_group(*operands, scale=scale, n_kv=n_kv, P=P, interpret=interpret)
    out = _unprep_out(out, B, K, Hq, D)
    if quantized:
        out = _apply_v_dequant(out, v_quant, layer_idx, n_rep).astype(out_dtype)
    return out


# ---------------------------------------------------------------------------
# per-shard launch (parallel/sharding.shard_over_heads): what the model calls
# ---------------------------------------------------------------------------


def _cache_heads(cache, axis: int):
    """Head-axis positions of one cache stream for ``shard_over_heads``: the
    code/value array's ``axis``, and the (L, H) scales of a quantized one."""
    return QuantizedKV(data=axis, scale=1) if isinstance(cache, QuantizedKV) else axis


def dispatch_tkg_decode(
    q, k_cache, v_cache, layer_idx, mask, sink=None, *, scale, interpret
):
    """:func:`tkg_decode_attention` once per head shard of the ambient mesh:
    q, the sink logits and the output split on the q heads, the stacked
    contiguous cache ``(L, R, S_max, Hkv, D)`` on the kv heads, layer index
    and mask replicated; no collective inside. The plain call at degree 1."""
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_over_heads

    def per_shard(q_s, k_s, v_s, li, m, sink_s):
        return tkg_decode_attention(
            q_s, k_s, v_s, li, m, sink_s,
            scale=scale, n_kv=k_s.shape[3], interpret=interpret,
        )

    heads = _cache_heads(k_cache, 3)
    return shard_over_heads(
        per_shard, (q, k_cache, v_cache, layer_idx, mask, sink),
        in_heads=(2, heads, heads, None, None, 0), out_heads=2,
    )


def dispatch_paged_tkg_decode(
    q, k_cache, v_cache, layer_idx, block_table, mask, sink=None, new_kv=None,
    *, scale, interpret
):
    """:func:`paged_tkg_decode_attention` once per head shard: the stacked
    block pool ``(L, NB+1, Hkv, bs, D)`` splits on the kv heads exactly as
    the layer scan carries it (block_kvcache.block_cache_spec), block table
    and mask are replicated. At tp = 4 a chip's kernel reads its own 2 of
    Qwen3-14B's 8 kv heads for its own 10 of 40 q heads. With ``new_kv``
    (``k_new, v_new (B, 1, Hkv, D)``, ``slot_mapping``) each shard's kernel
    also places its own heads of the pass's one token a row, and the two
    pools come back with the output, each shard's heads in place.

    A pool that holds ``g`` KV heads side by side in a 128-lane row
    (block_kvcache.kv_streams: head_dim 64, two) is attended as the GQA model
    it then is, ``H_kv / g`` KV heads at ``g x D``: the queries go in laid in
    their own head's lanes, the token's new K and V as the pool row they are,
    and each query head's lanes are cut from the output
    (block_kvcache.fold_queries); the kernel is the head_dim-128 one."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        fold_queries,
        pool_fold,
        pool_rows,
        unfold_outputs,
    )
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_over_heads

    def per_shard(q_s, k_s, v_s, li, bt, m, sink_s, new_s):
        pool = k_s.data if isinstance(k_s, QuantizedKV) else k_s
        n_kv = pool.shape[2]
        g = pool_fold(pool.shape[4], q_s.shape[-1])
        n_rep = q_s.shape[2] // (n_kv * g)
        if new_s is not None:
            k_new, v_new, slots = new_s
            new_s = (pool_rows(k_new, pool), pool_rows(v_new, pool), slots)
        out = paged_tkg_decode_attention(
            fold_queries(q_s, g, n_rep), k_s, v_s, li, bt, m, sink_s, new_s,
            scale=scale, n_kv=n_kv, interpret=interpret,
        )
        if new_s is None:
            return unfold_outputs(out, g, n_rep)
        return (unfold_outputs(out[0], g, n_rep),) + tuple(out[1:])

    heads = _cache_heads(k_cache, 2)
    return shard_over_heads(
        per_shard, (q, k_cache, v_cache, layer_idx, block_table, mask, sink, new_kv),
        in_heads=(2, heads, heads, None, None, None, 0, (2, 2, None)),
        out_heads=2 if new_kv is None else (2, heads, heads),
    )
