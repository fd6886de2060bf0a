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

Grid layout: (B, kv_tiles). Each step DMAs one (bs, Hkv, D) cache tile — all
KV heads at once, so the last two block dims stay full-size for Mosaic — and
an unrolled loop over the Hkv head groups runs the online softmax for that
group's n_rep*K query rows (GQA needs NO repeat_kv: queries are pre-grouped
rep-major). The cache is read exactly once, in tile-sized DMAs.

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
    kernel, grid, in_specs, out_specs, operands, out_shape, scratch, interpret, name
):
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
            dimension_semantics=("parallel", "arbitrary"),
        ),
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


@functools.partial(jax.jit, static_argnames=("scale", "n_kv", "interpret"))
def paged_tkg_decode_attention(
    q: jax.Array,  # (B, K, Hq, D)
    k_cache: jax.Array,  # (L, NB+1, Hkv, bs, D) FULL stacked head-major paged cache
    v_cache: jax.Array,
    layer_idx: jax.Array,  # int32 scalar
    block_table: jax.Array,  # (B, MB) int32
    mask: jax.Array,  # (B, 1, K, MB*bs) bool decode mask over the block view
    sink: jax.Array = None,
    *,
    scale: float,
    n_kv: int,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention: cache blocks are DMA'd straight via the block
    table (scalar prefetch) — kills the materializing
    read_block_cache_at_layer gather on the serving decode path
    (reference attention_block_tokengen kernel, attention_base.py:1609).
    Quantized caches DMA the code blocks and dequantize in-register.
    Returns (B, K, Hq, D)."""
    B, K, Hq, D = q.shape
    _, _, Hkv, bs, _ = k_cache.shape
    MB = block_table.shape[1]
    assert mask.shape[-1] == MB * bs, (mask.shape, MB, bs)
    n_rep = Hq // n_kv
    rk = n_rep * K
    out_dtype = q.dtype
    quantized = isinstance(k_cache, QuantizedKV)
    if quantized:
        q = _fold_k_dequant(q, k_cache, layer_idx, n_rep)
        k_cache, v_quant = k_cache.data, v_cache
        v_cache = v_cache.data
    qr = _prep_q(q)
    m, tile_any = _mask_tiles(mask, MB, bs)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    kernel = functools.partial(
        _tkg_kernel, scale=scale, n_kv=n_kv, rk=rk, K=K, nkv=MB,
        has_sink=sink is not None, n_prefetch=3, head_major=True,
    )
    in_specs = [
        pl.BlockSpec((1, Hq * K, D), lambda b, j, li, bt, ta: (b, 0, 0)),
        pl.BlockSpec((1, 1, K, bs), lambda b, j, li, bt, ta: (b, j, 0, 0)),
    ]
    tensors = [qr, m]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, Hq), lambda b, j, li, bt, ta: (0, 0)))
        tensors.append(sink.reshape(1, Hq))
    in_specs += [
        pl.BlockSpec(
            (1, 1, n_kv, bs, D), lambda b, j, li, bt, ta: (li[0], bt[b, j], 0, 0, 0)
        ),
        pl.BlockSpec(
            (1, 1, n_kv, bs, D), lambda b, j, li, bt, ta: (li[0], bt[b, j], 0, 0, 0)
        ),
    ]
    tensors += [k_cache, v_cache]

    out = _common_call(
        kernel,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq * K, D), lambda b, j, li, bt, ta: (b, 0, 0)),
        operands=([li, block_table.astype(jnp.int32), tile_any], tensors),
        out_shape=jax.ShapeDtypeStruct((B, Hq * K, D), q.dtype),
        scratch=[
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, 1), jnp.float32),
            pltpu.VMEM((Hq * K, D), jnp.float32),
        ],
        interpret=interpret,
        name="paged_tkg_decode_attention",
    )
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
    q, k_cache, v_cache, layer_idx, block_table, mask, sink=None, *, scale, interpret
):
    """:func:`paged_tkg_decode_attention` once per head shard: the stacked
    block pool ``(L, NB+1, Hkv, bs, D)`` splits on the kv heads exactly as
    the layer scan carries it (block_kvcache.block_cache_spec), block table
    and mask are replicated. At tp = 4 a chip's kernel reads its own 2 of
    Qwen3-14B's 8 kv heads for its own 10 of 40 q heads."""
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_over_heads

    def per_shard(q_s, k_s, v_s, li, bt, m, sink_s):
        return paged_tkg_decode_attention(
            q_s, k_s, v_s, li, bt, m, sink_s,
            scale=scale, n_kv=k_s.shape[2], interpret=interpret,
        )

    heads = _cache_heads(k_cache, 2)
    return shard_over_heads(
        per_shard, (q, k_cache, v_cache, layer_idx, block_table, mask, sink),
        in_heads=(2, heads, heads, None, None, None, 0), out_heads=2,
    )
