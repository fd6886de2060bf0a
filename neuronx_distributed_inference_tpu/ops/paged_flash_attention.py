"""Pallas paged (block-KV) flash attention for chunked / prefix prefill.

TPU-native re-design of the reference's schedule-driven paged flash kernel
(reference: modules/chunked_prefill/flash_pa_with_schedule.py:157 +
flash_attn_core.py:70, driven by the host GridTileScheduler,
scheduler.py:274-420).

Design: the reference builds an explicit host-side tile schedule because NKI
kernels address SBUF manually. On TPU the same thing falls out of the Pallas
grid + scalar-prefetch index maps: grid = (B, Hq, q_tiles, kv_blocks); the
KV BlockSpec's index_map reads the per-sequence ``block_table`` (a scalar
prefetch operand) to DMA the right cache block per grid step — no gather
materialization, no schedule arrays. Tiles that are entirely above the causal
frontier or beyond the sequence's populated length are skipped via
``pl.when`` on scalar-prefetched per-tile maxima (the scheduler's
skip-fully-masked-tiles optimization).

Numerics: online-softmax flash attention over the query's full prior context
(prefix blocks + causal among the new tokens) — the mask the native path
builds from masks.spec_token_gen_mask, fused into the kernel.
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


# kernel/native dispatch gate: consolidated in ops/kernel_mode.py (one
# tested predicate per kernel); the historical name stays importable here
from neuronx_distributed_inference_tpu.ops.kernel_mode import (  # noqa: E402
    use_paged_flash as _use_paged_flash,
)


def _paged_kernel(
    # scalar prefetch
    block_table_ref,  # (B, MB) int32
    kv_limit_ref,  # (B,) int32 valid cache length per row
    tile_max_ref,  # (B, nq) int32 max q position per q tile
    # blocked operands
    q_ref,  # (1, 1, tq, D)
    pos_ref,  # (1, 1, tq) int32 q positions (dummy middle axis for Mosaic)
    k_ref,  # (1, 1, bs, D) one head's cache block
    v_ref,  # (1, 1, bs, D)
    o_ref,  # (1, 1, tq, D)
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    tq: int,
    bs: int,
    nkv: int,
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
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (tq, bs)

        q_pos = pos_ref[0, 0]  # (tq,)
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        mask = (kv_pos <= q_pos[:, None]) & (kv_pos < kv_limit_ref[b])
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
        v = v_ref[0, 0].astype(jnp.float32)  # (bs, D)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "n_rep", "tq", "interpret")
)
def paged_flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k_cache: jax.Array,  # (NB+1, Hkv, bs, D) one layer's head-major paged cache
    v_cache: jax.Array,
    block_table: jax.Array,  # (B, MB) int32
    positions: jax.Array,  # (B, Sq) int32 query positions
    kv_limit: jax.Array,  # (B,) int32 valid cache length per row
    *,
    scale: float,
    n_rep: int,
    tq: int = None,
    k_scale: jax.Array = None,  # (Hkv,) per-head dequant factor (scale/qmax)
    v_scale: jax.Array = None,  # for int8/fp8 caches; None = plain cache
    interpret: bool = False,
) -> jax.Array:
    """Prefix/chunked-prefill attention straight off the paged cache.

    Returns (B, Sq, Hq, D). Query token t of row b attends cache positions
    p <= positions[b, t] with p < kv_limit[b] — prior context plus causal
    among the new tokens (KV for the new tokens must already be written;
    write-then-attend as everywhere else).

    Quantized caches pass the raw int8/fp8 code blocks plus this layer's
    per-head dequant factors: the K factor folds into q (scaling the QKᵀ
    product), the V factor scales the per-head output after the online
    softmax — the kernel DMAs narrow code tiles, converts to fp32
    in-register, and never materializes a dequantized cache.
    """
    B, Sq, Hq, D = q.shape
    _, Hkv, bs, _ = k_cache.shape
    MB = block_table.shape[1]
    if tq is None:
        # q-tile default through the tuning table (KERN704), keyed by the
        # prefill chunk length and the cache dtype (int8 codes DMA narrower)
        tq = tile_default(
            "paged_flash_attention", f"sq{Sq}", k_cache.dtype, "tq", 128
        )
    tq = min(tq, Sq)
    nq = pl.cdiv(Sq, tq)

    out_dtype = q.dtype
    if k_scale is not None:
        q = q.astype(jnp.float32) * jnp.repeat(k_scale, n_rep)[None, None, :, None]
    qt = jnp.swapaxes(q, 1, 2)  # (B, Hq, Sq, D)
    # per-(row, q-tile) causal frontier for tile skipping
    pos_pad = jnp.pad(positions, ((0, 0), (0, nq * tq - Sq)))
    tile_max = jnp.max(pos_pad.reshape(B, nq, tq), axis=-1).astype(jnp.int32)

    kernel = functools.partial(
        _paged_kernel, scale=scale, tq=tq, bs=bs, nkv=MB
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hq, nq, MB),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, iq, j, bt, lim, tm: (b, h, iq, 0)),
            # dummy middle axis: block (1, tq) over a (B, Sq) array violates
            # Mosaic's (8, 128) last-two-dims rule for B > 1
            pl.BlockSpec((1, 1, tq), lambda b, h, iq, j, bt, lim, tm: (b, 0, iq)),
            # head-major cache: one head's block is a (bs, D) tile whose
            # last-two block dims equal the array dims
            pl.BlockSpec(
                (1, 1, bs, D),
                lambda b, h, iq, j, bt, lim, tm: (bt[b, j], h // n_rep, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, bs, D),
                lambda b, h, iq, j, bt, lim, tm: (bt[b, j], h // n_rep, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tq, D), lambda b, h, iq, j, bt, lim, tm: (b, h, iq, 0)
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
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * tq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_flash_attention",
    )(
        block_table.astype(jnp.int32),
        kv_limit.astype(jnp.int32),
        tile_max,
        qt,
        positions.astype(jnp.int32)[:, None, :],
        k_cache,
        v_cache,
    )
    out = jnp.swapaxes(out, 1, 2)[:, :Sq]
    if v_scale is not None:
        out = (out * jnp.repeat(v_scale, n_rep)[None, None, :, None]).astype(out_dtype)
    return out


def dispatch_paged_flash(
    q, k_cache, v_cache, block_table, positions, kv_limit,
    *, scale, n_rep, k_scale=None, v_scale=None, interpret,
):
    """:func:`paged_flash_attention` once per head shard of the ambient mesh
    (parallel/sharding.shard_over_heads): q and the output split on the q
    heads, one layer's block pool ``(NB+1, Hkv, bs, D)`` and the per-head
    dequant factors on the kv heads, block table, positions and ``kv_limit``
    replicated; no collective inside. The plain call at degree 1."""
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_over_heads

    def per_shard(q_s, k_s, v_s, bt, pos, lim, ks_s, vs_s):
        return paged_flash_attention(
            q_s, k_s, v_s, bt, pos, lim,
            scale=scale, n_rep=n_rep, k_scale=ks_s, v_scale=vs_s,
            interpret=interpret,
        )

    return shard_over_heads(
        per_shard, (q, k_cache, v_cache, block_table, positions, kv_limit, k_scale, v_scale),
        in_heads=(2, 1, 1, None, None, None, 0, 0), out_heads=2,
    )
