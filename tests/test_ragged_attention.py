"""Ragged paged attention (ISSUE 6 tentpole) — kernel-vs-native parity.

The Pallas kernel (interpret mode on CPU) must agree with the native
gather fallback — which is itself the exact math the legacy split serving
dispatch runs — across:
- pure-decode batches (every row query_len == 1),
- pure-prefill batches (chunk rows only),
- mixed batches (the serving regime the kernel exists for),
- odd row counts / inactive rows,
- int8 + fp8 quantized caches (in-register dequant, scales folded into
  q / the output),
plus TPU-target AOT lowering at the 1B bench shapes.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.attention import AttnSpec
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    init_block_cache,
    slot_mapping_from_block_table,
    update_block_cache_at_layer,
)
from neuronx_distributed_inference_tpu.modules.kvcache import (
    QuantizedKV,
    layer_dequant_factors,
)
from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
    RAGGED_Q_TILE,
    _dispatch_ragged_kernel,
    _use_ragged_kernel,
    ragged_attention_native,
    ragged_paged_attention,
)

L, HQ, HKV, D = 2, 8, 2, 64
NB, BS, MB = 16, 16, 8


def _pack(ctx, qlen):
    """Host-side packing mirror of ServingSession._ragged_step: q-tile
    aligned row segments. Returns (row_start, T, positions)."""
    tq = RAGGED_Q_TILE
    row_start, cur = [], 0
    for n in qlen:
        row_start.append(cur)
        cur += -(-n // tq) * tq if n else 0
    T = max(cur, tq)
    positions = np.full(T, -1, np.int32)
    for r, n in enumerate(qlen):
        if n:
            positions[row_start[r] : row_start[r] + n] = np.arange(
                ctx[r] - n, ctx[r]
            )
    return np.asarray(row_start, np.int32), T, positions


def _build_case(ctx, qlen, dtype, seed=0):
    """Populated paged cache + packed queries for rows with context lengths
    ``ctx`` of which the last ``qlen`` tokens are this step's queries."""
    rng = np.random.RandomState(seed)
    R = len(ctx)
    bc = init_block_cache(L, NB, BS, HKV, D, dtype=dtype)
    kb, vb = bc.k, bc.v
    bt = np.zeros((R, MB), np.int32)
    free = list(range(1, NB + 1))
    for r, c in enumerate(ctx):
        for i in range(-(-c // BS) if c else 0):
            bt[r, i] = free.pop(0)
    bt = jnp.asarray(bt)
    s_max = max(max(ctx), 1)
    posb = np.full((R, s_max), -1, np.int32)
    for r, c in enumerate(ctx):
        posb[r, :c] = np.arange(c)
    sm = slot_mapping_from_block_table(
        bt, jnp.asarray(np.maximum(posb, 0)), BS, valid=jnp.asarray(posb >= 0)
    )
    k_new = jnp.asarray(rng.randn(R, s_max, HKV, D).astype(np.float32) * 0.3)
    v_new = jnp.asarray(rng.randn(R, s_max, HKV, D).astype(np.float32) * 0.3)
    for li in range(L):
        kb, vb = update_block_cache_at_layer(
            kb, vb, k_new, v_new, jnp.int32(li), sm
        )
    row_start, T, positions = _pack(ctx, qlen)
    q = jnp.asarray(rng.randn(T, HQ, D).astype(np.float32) * 0.3)
    return (
        kb, vb, bt, q,
        jnp.asarray(positions),
        jnp.asarray(row_start),
        jnp.asarray(qlen, jnp.int32),
        jnp.asarray(ctx, jnp.int32),
    )


def _kernel_vs_native(ctx, qlen, dtype, layer=1):
    kb, vb, bt, q, positions, row_start, row_len, ctx_len = _build_case(
        ctx, qlen, dtype
    )
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    ref = ragged_attention_native(
        q, kb, vb, jnp.int32(layer), bt, positions, row_start, row_len,
        ctx_len, spec,
    )
    ks = vs = None
    if isinstance(kb, QuantizedKV):
        ks = layer_dequant_factors(kb, jnp.int32(layer))
        vs = layer_dequant_factors(vb, jnp.int32(layer))
        k_l, v_l = kb.data[layer], vb.data[layer]
    else:
        k_l, v_l = kb[layer], vb[layer]
    # through the dispatcher: the pool holds two heads of 64 a 128-lane row
    # (block_kvcache.kv_streams) and the queries go in laid in their head's
    # lanes; a quantised pool keeps a head a row and is the plain call
    assert k_l.shape[-2:] == ((BS, D) if ks is not None else (BS, 2 * D))
    out = _dispatch_ragged_kernel(
        q, k_l, v_l, bt, row_start, row_len, ctx_len,
        scale=spec.softmax_scale, n_rep=HQ // HKV,
        k_scale=ks, v_scale=vs, interpret=True,
    )
    valid = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], atol=3e-5, rtol=3e-5
    )


def test_pure_decode_batch():
    _kernel_vs_native(ctx=[17, 45, 9, 31], qlen=[1, 1, 1, 1], dtype=jnp.float32)


def test_pure_prefill_batch():
    # chunk rows only: 16 new tokens each over differing prior context
    _kernel_vs_native(ctx=[48, 23], qlen=[16, 16], dtype=jnp.float32)


def test_mixed_batch_with_inactive_rows():
    # one prefill chunk + two decode rows + one inactive slot
    _kernel_vs_native(ctx=[48, 23, 5, 0], qlen=[16, 1, 1, 0], dtype=jnp.float32)


def test_odd_row_counts():
    # 3 rows (odd), non-tile-multiple chunk lengths (9, 3)
    _kernel_vs_native(ctx=[40, 12, 7], qlen=[9, 3, 1], dtype=jnp.float32)


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_quantized_cache_parity(dt):
    _kernel_vs_native(ctx=[48, 23, 5, 0], qlen=[16, 1, 1, 0], dtype=dt)


def test_bf16_queries():
    kb, vb, bt, q, positions, row_start, row_len, ctx_len = _build_case(
        [48, 23, 5], [16, 1, 1], jnp.bfloat16
    )
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    ref = ragged_attention_native(
        q.astype(jnp.bfloat16), kb, vb, jnp.int32(0), bt, positions,
        row_start, row_len, ctx_len, spec,
    )
    out = _dispatch_ragged_kernel(
        q.astype(jnp.bfloat16), kb[0], vb[0], bt, row_start, row_len, ctx_len,
        scale=spec.softmax_scale, n_rep=HQ // HKV, k_scale=None, v_scale=None,
        interpret=True,
    )
    valid = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[valid],
        np.asarray(ref, np.float32)[valid],
        atol=2e-2, rtol=2e-2,
    )


def test_kernel_gate():
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    # auto path: off-TPU hosts take the native fallback
    assert not _use_ragged_kernel(spec, 64)
    # force-on honors the shape guards
    forced = AttnSpec(
        num_heads=HQ, num_kv_heads=HKV, head_dim=D, use_flash_kernel=True
    )
    assert _use_ragged_kernel(forced, 64)
    assert not _use_ragged_kernel(forced, 64 + 1)  # unaligned packing
    odd_d = AttnSpec(
        num_heads=HQ, num_kv_heads=HKV, head_dim=80, use_flash_kernel=True
    )
    assert not _use_ragged_kernel(odd_d, 64)
    off = AttnSpec(
        num_heads=HQ, num_kv_heads=HKV, head_dim=D, use_flash_kernel=False
    )
    assert not _use_ragged_kernel(off, 64)


# ---------------------------------------------------------------------------
# TPU-target AOT lowering at the 1B bench shapes
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn])
def test_lower_ragged_kernel_1b_shapes(dt):
    """1B bench serving shape: Hq=32, Hkv=8, D=64, 512-block pool at bs=32,
    8 slots; packed axis = 8 x 128-token prefill chunks + 8 decode tiles."""
    from jax import export

    NBb, bsb, MBb, Hq, Hkv, Db, R = 512, 32, 258, 32, 8, 64, 8
    T = 8 * 128 + 8 * RAGGED_Q_TILE
    fn = functools.partial(
        ragged_paged_attention, scale=Db**-0.5, n_rep=Hq // Hkv,
        interpret=False,
    )
    kw = {}
    if dt != jnp.bfloat16:
        kw = dict(
            k_scale=_sds((Hkv,), jnp.float32), v_scale=_sds((Hkv,), jnp.float32)
        )
    export.export(jax.jit(fn), platforms=["tpu"])(
        _sds((T, Hq, Db), jnp.bfloat16),
        _sds((NBb + 1, Hkv, bsb, Db), dt),
        _sds((NBb + 1, Hkv, bsb, Db), dt),
        _sds((R, MBb), jnp.int32),
        _sds((R,), jnp.int32),
        _sds((R,), jnp.int32),
        _sds((R,), jnp.int32),
        **kw,
    )


@pytest.mark.slow
def test_lower_whole_mixed_step_program():
    """The WHOLE mixed_step program (embed -> layer scan with the forced
    ragged kernel + fused quantized scatters -> per-row gather -> lm head)
    AOT-lowers for the TPU target — catches breaks in how mixed_forward
    feeds the kernel, not just the kernel in isolation."""
    from tests.conftest import make_tiny_config

    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.models.base import (
        MixedStepInputs,
        mixed_forward,
    )
    from neuronx_distributed_inference_tpu.models.llama import LlamaModelBuilder
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
    )
    from neuronx_distributed_inference_tpu.ops.kernel_mode import (
        force_compiled_kernels,
    )

    cfg = make_tiny_config(
        hidden_size=256,
        intermediate_size=512,
        tpu=dict(
            batch_size=4, seq_len=256, dtype="bfloat16",
            is_continuous_batching=True,
            is_block_kv_layout=True, pa_block_size=32, pa_num_blocks=32,
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=32
            ),
            serving_ragged=True, kv_cache_dtype="int8",
            attn_kernel_enabled=True,
        ),
    )
    builder = LlamaModelBuilder(cfg)
    spec = builder.model_spec()
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype), builder.random_params()
    )
    cache = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype),
        init_block_cache(
            spec.num_layers, 32, 32, spec.attn.num_kv_heads,
            spec.attn.head_dim, dtype=jnp.int8,
        ),
    )
    R, T, mb = 4, 128, 256 // 32
    inputs = MixedStepInputs(
        input_ids=_sds((1, T), jnp.int32),
        position_ids=_sds((1, T), jnp.int32),
        slot_mapping=_sds((1, T), jnp.int32),
        block_table=_sds((R, mb), jnp.int32),
        row_start=_sds((R,), jnp.int32),
        row_len=_sds((R,), jnp.int32),
        ctx_len=_sds((R,), jnp.int32),
        sampling_params=_sds((R, 3), jnp.float32),
        # chained-id gather inputs (async_mode): always present in
        # the SERVED program (inert in sync mode) — export what serving runs
        chain_src=_sds((1, T), jnp.int32),
        chain_tokens=_sds((R, 1), jnp.int32),
    )
    from jax import export

    fn = functools.partial(mixed_forward, spec=spec)
    with force_compiled_kernels():
        export.export(jax.jit(fn), platforms=["tpu"])(
            params, cache, inputs, None
        )
