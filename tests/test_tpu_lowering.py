"""AOT Mosaic-lowering tests: every Pallas entry point must LOWER for the TPU
target — from this CPU-only host — across batch sizes and the bench shapes.

Why: all kernel-numerics tests run ``interpret=True`` (pure-Python emulation),
so no CPU test can hit a **Mosaic lowering** error. Two of the first three
rounds shipped a bench-only hardware crash the suite could not see (r1
``_pick_chunk`` NameError; r3 the flash ``key_valid`` BlockSpec that only
lowers at batch 1 — VERDICT r3). ``jax.export(..., platforms=["tpu"])``
triggers the full Pallas→Mosaic lowering pipeline on any host, which is
exactly the class of failure interpret mode skips.

These tests were red on the r3 tree (flash B>1; paged flash B>1 and Hkv>1)
before the fixes they now pin: the key_valid dummy axis, the positions dummy
axis, and the head-major paged-cache layout.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from neuronx_distributed_inference_tpu.ops.decode_attention import (
    paged_tkg_decode_attention,
    tkg_decode_attention,
)
from neuronx_distributed_inference_tpu.ops.flash_attention import flash_attention_bhsd
from neuronx_distributed_inference_tpu.ops.kernel_mode import force_compiled_kernels
from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
    paged_flash_attention,
)


def lower_tpu(fn, *abstract_args):
    """AOT-lower ``fn`` for the TPU target from the CPU host. Raises on any
    Mosaic lowering failure (BlockSpec tiling, VMEM layout, unsupported op)."""
    return export.export(jax.jit(fn), platforms=["tpu"])(*abstract_args)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# flash attention (CTE prefill kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 2, 4, 8])
@pytest.mark.parametrize("S,D", [(128, 64), (1024, 128)])
def test_lower_flash_attention_batches(B, S, D):
    H = 8
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False
    )
    lower_tpu(fn, q, q, q, kv)


@pytest.mark.parametrize("window,chunk", [(256, None), (None, 256)])
def test_lower_flash_attention_masked_flavors(window, chunk):
    B, H, S, D = 4, 8, 1024, 64
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, window=window,
        chunk=chunk, interpret=False,
    )
    lower_tpu(fn, q, q, q, kv)


def test_lower_flash_attention_long_seq():
    # long-context prefill shape (8k) — VERDICT r3 weak #7
    B, H, S, D = 1, 8, 8192, 128
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False
    )
    lower_tpu(fn, q, q, q, kv)


# ---------------------------------------------------------------------------
# head-packed flash attention (D<=64 pairs per 128-lane tile, ISSUE 2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("S", [512, 8192])
def test_lower_packed_flash(B, S):
    """Packed kernel lowers for the TPU target at the prefill-profile shapes
    (S=512 short bucket, S=8192 long-context)."""
    H, D = 8, 64
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False,
        packed=True,
    )
    lower_tpu(fn, q, q, q, kv)


def test_lower_packed_flash_odd_heads():
    # H=7: the pad-and-slice wrapper path must also survive Mosaic lowering
    B, H, S, D = 2, 7, 512, 64
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False,
        packed=True,
    )
    lower_tpu(fn, q, q, q, kv)


@pytest.mark.parametrize("window,chunk", [(256, None), (None, 256)])
def test_lower_packed_flash_masked_flavors(window, chunk):
    B, H, S, D = 4, 8, 1024, 64
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, window=window,
        chunk=chunk, interpret=False, packed=True,
    )
    lower_tpu(fn, q, q, q, kv)


def test_lower_packed_flash_bench_shape_8k():
    # the 1B bench attention shape (H=32 post-repeat, D=64) at 8k — the
    # exact shape the PERF.md round-6 MFU claim is about
    B, H, S, D = 1, 32, 8192, 64
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False,
        packed=True,
    )
    lower_tpu(fn, q, q, q, kv)


# ---------------------------------------------------------------------------
# TKG decode kernels (contiguous + paged)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 4, 8])
@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("has_sink", [False, True])
def test_lower_tkg_decode(B, K, has_sink):
    L, R, S_max, Hq, Hkv, D = 2, B + 2, 1024, 8, 2, 64
    bucket = 512
    q = sds((B, K, Hq, D), jnp.bfloat16)
    cache = sds((L, R, S_max, Hkv, D), jnp.bfloat16)
    li = sds((), jnp.int32)
    mask = sds((B, 1, K, bucket), jnp.bool_)
    sink = sds((Hq,), jnp.float32) if has_sink else None
    fn = functools.partial(
        tkg_decode_attention, scale=D**-0.5, n_kv=Hkv, interpret=False
    )
    if has_sink:
        lower_tpu(lambda q, k, v, l, m, s: fn(q, k, v, l, m, s), q, cache, cache, li, mask, sink)
    else:
        lower_tpu(lambda q, k, v, l, m: fn(q, k, v, l, m), q, cache, cache, li, mask)


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("D", [64, 128])  # a block a step; a group of blocks copied by hand
def test_lower_paged_tkg_decode(B, bs, D):
    L, NB, MB, K, Hq, Hkv = 2, 32, 8, 4, 8, 2
    q = sds((B, K, Hq, D), jnp.bfloat16)
    cache = sds((L, NB + 1, Hkv, bs, D), jnp.bfloat16)
    li = sds((), jnp.int32)
    bt = sds((B, MB), jnp.int32)
    mask = sds((B, 1, K, MB * bs), jnp.bool_)
    fn = functools.partial(
        paged_tkg_decode_attention, scale=D**-0.5, n_kv=Hkv, interpret=False
    )
    exported = lower_tpu(
        lambda q, k, v, l, b, m: fn(q, k, v, l, b, m), q, cache, cache, li, bt, mask
    )
    # the name the benchmark's roofline reader finds the traced op by
    assert "paged_tkg_decode_attention" in exported.mlir_module()


@pytest.mark.parametrize("B", [1, 2, 4])
@pytest.mark.parametrize("Hkv", [1, 2, 8])
def test_lower_paged_flash(B, Hkv):
    NB, bs, MB, Sq, D = 32, 16, 8, 128, 64
    Hq = Hkv * 4
    q = sds((B, Sq, Hq, D), jnp.bfloat16)
    cache = sds((NB + 1, Hkv, bs, D), jnp.bfloat16)
    bt = sds((B, MB), jnp.int32)
    pos = sds((B, Sq), jnp.int32)
    lim = sds((B,), jnp.int32)
    fn = functools.partial(
        paged_flash_attention, scale=D**-0.5, n_rep=4, interpret=False
    )
    lower_tpu(lambda q, k, v, b, p, l: fn(q, k, v, b, p, l), q, cache, cache, bt, pos, lim)


# the served shapes of the group launch (head_dim 128, blocks of 32, 8 rows
# of 128): (q heads, kv heads) a chip of Qwen3-1.7B, Qwen3-14B at tp = 4,
# ZAYA1-8B and SDAR-30B-A3B: n_rep 2 / 5 / 4 / 8, the last two in parts
@pytest.mark.parametrize("Hq,Hkv", [(16, 8), (10, 2), (8, 2), (32, 4)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_lower_paged_flash_by_group(Hq, Hkv, dtype):
    L, NB, bs, MB, B, Sq, D = 2, 64, 32, 64, 8, 128, 128
    q = sds((B, Sq, Hq, D), jnp.bfloat16)
    cache = sds((L, NB + 1, Hkv, bs, D), dtype)
    li = sds((), jnp.int32)
    bt = sds((B, MB), jnp.int32)
    pos = sds((B, Sq), jnp.int32)
    lim = sds((B,), jnp.int32)
    scale = sds((Hkv,), jnp.float32)
    fn = functools.partial(
        paged_flash_attention, scale=D**-0.5, n_rep=Hq // Hkv, interpret=False
    )
    if dtype == jnp.int8:
        exported = lower_tpu(
            lambda q, k, v, l, b, p, m, ks, vs: fn(
                q, k, v, b, p, m, layer_idx=l, k_scale=ks, v_scale=vs
            ),
            q, cache, cache, li, bt, pos, lim, scale, scale,
        )
    else:
        exported = lower_tpu(
            lambda q, k, v, l, b, p, m: fn(q, k, v, b, p, m, layer_idx=l),
            q, cache, cache, li, bt, pos, lim,
        )
    # the name the benchmark's roofline reader finds the traced op by
    assert "paged_flash_attention" in exported.mlir_module()


# ---------------------------------------------------------------------------
# the 1B program set — the kernel shapes chip_smoke.py drives
# (llama-3.2-1B: Hq=32, Hkv=8, D=64; prefill 128/512; decode buckets 512/1024)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S", [(1, 128), (1, 512), (4, 128)])
def test_lower_bench_prefill_shapes(B, S):
    H, D = 32, 64  # post-repeat_kv head count
    q = sds((B, H, S, D), jnp.bfloat16)
    kv = sds((B, S), jnp.int32)
    fn = functools.partial(
        flash_attention_bhsd, scale=D**-0.5, causal=True, interpret=False
    )
    lower_tpu(fn, q, q, q, kv)


@pytest.mark.parametrize("B,bucket", [(1, 512), (1, 1024), (4, 512)])
def test_lower_bench_decode_shapes(B, bucket):
    L, Hq, Hkv, D = 16, 32, 8, 64
    R = B + 1
    q = sds((B, 1, Hq, D), jnp.bfloat16)
    cache = sds((L, R, 1024, Hkv, D), jnp.bfloat16)
    li = sds((), jnp.int32)
    mask = sds((B, 1, 1, bucket), jnp.bool_)
    fn = functools.partial(
        tkg_decode_attention, scale=D**-0.5, n_kv=Hkv, interpret=False
    )
    lower_tpu(lambda q, k, v, l, m: fn(q, k, v, l, m), q, cache, cache, li, mask)


# ---------------------------------------------------------------------------
# whole-model programs: CTE + TKG forward with kernels FORCED on, lowered for
# TPU — catches lowering breaks in how the model calls the kernels (specs,
# reshapes, donation), not just the kernels in isolation
# ---------------------------------------------------------------------------


def _kernel_model(batch):
    import sys, os

    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import make_tiny_config

    from neuronx_distributed_inference_tpu.models.llama import LlamaModelBuilder

    cfg = make_tiny_config(
        hidden_size=256,
        intermediate_size=512,
        num_attention_heads=4,
        num_key_value_heads=2,
        tpu=dict(
            batch_size=batch,
            seq_len=256,
            dtype="bfloat16",
            attn_kernel_enabled=True,
            attn_block_tkg_kernel_enabled=True,
        ),
    )
    return LlamaModelBuilder(cfg)


@pytest.mark.slow
@pytest.mark.parametrize("B", [1, 4])
def test_lower_model_cte_with_kernels(B):
    from neuronx_distributed_inference_tpu.models.base import (
        PHASE_CONTEXT_ENCODING,
        StepInputs,
        forward,
        gated_mlp,
    )
    from neuronx_distributed_inference_tpu.modules.kvcache import init_cache

    builder = _kernel_model(B)
    spec = builder.model_spec()
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), builder.random_params()
    )
    S = 128
    cache = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        init_cache(spec.num_layers, B + 1, 256, spec.attn.num_kv_heads,
                   spec.attn.head_dim, dtype=jnp.bfloat16),
    )
    inputs = StepInputs(
        input_ids=sds((B, S), jnp.int32),
        attention_mask=sds((B, S), jnp.int32),
        position_ids=sds((B, S), jnp.int32),
        seq_ids=sds((B,), jnp.int32),
        sampling_params=sds((B, 3), jnp.float32),
    )
    fn = functools.partial(
        forward, spec=spec, phase=PHASE_CONTEXT_ENCODING, mlp_fn=gated_mlp
    )
    with force_compiled_kernels():
        lower_tpu(fn, params, cache, inputs, None)


@pytest.mark.slow
@pytest.mark.parametrize("B", [1, 4])
def test_lower_model_tkg_with_kernels(B):
    from neuronx_distributed_inference_tpu.models.base import (
        PHASE_TOKEN_GENERATION,
        StepInputs,
        forward,
        gated_mlp,
    )
    from neuronx_distributed_inference_tpu.modules.kvcache import init_cache

    builder = _kernel_model(B)
    spec = builder.model_spec()
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), builder.random_params()
    )
    bucket = 256
    cache = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        init_cache(spec.num_layers, B + 1, 256, spec.attn.num_kv_heads,
                   spec.attn.head_dim, dtype=jnp.bfloat16),
    )
    inputs = StepInputs(
        input_ids=sds((B, 1), jnp.int32),
        attention_mask=sds((B, bucket), jnp.int32),
        position_ids=sds((B, 1), jnp.int32),
        seq_ids=sds((B,), jnp.int32),
        sampling_params=sds((B, 3), jnp.float32),
    )
    fn = functools.partial(
        forward, spec=spec, phase=PHASE_TOKEN_GENERATION, mlp_fn=gated_mlp
    )
    with force_compiled_kernels():
        lower_tpu(fn, params, cache, inputs, None)


# ---------------------------------------------------------------------------
# ragged paged attention (mixed prefill-chunk + decode, ISSUE 12)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,R", [(128, 2), (512, 8)])
def test_lower_ragged_paged_attention(T, R):
    from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention,
    )

    Hq, Hkv, D, MB, bs = 32, 8, 64, 16, 128
    q = sds((T, Hq, D), jnp.bfloat16)
    cache = sds((65, Hkv, bs, D), jnp.bfloat16)
    bt = sds((R, MB), jnp.int32)
    row = sds((R,), jnp.int32)
    fn = functools.partial(
        ragged_paged_attention, scale=D**-0.5, n_rep=Hq // Hkv, interpret=False
    )
    lower_tpu(lambda *a: fn(*a), q, cache, cache, bt, row, row, row)


def test_lower_ragged_paged_attention_quantized():
    from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention,
    )

    T, R, Hq, Hkv, D, MB, bs = 512, 8, 32, 8, 64, 16, 128
    q = sds((T, Hq, D), jnp.bfloat16)
    cache = sds((65, Hkv, bs, D), jnp.int8)
    bt = sds((R, MB), jnp.int32)
    row = sds((R,), jnp.int32)
    scale = sds((Hkv,), jnp.float32)
    fn = functools.partial(
        ragged_paged_attention, scale=D**-0.5, n_rep=Hq // Hkv, interpret=False
    )
    lower_tpu(
        lambda q, k, v, bt, rs, rl, cl, ks, vs: fn(
            q, k, v, bt, rs, rl, cl, k_scale=ks, v_scale=vs
        ),
        q, cache, cache, bt, row, row, row, scale, scale,
    )


# ---------------------------------------------------------------------------
# int4 fused-dequant weight-streaming matmul (ISSUE 17)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bn", [128, 256, 512])
@pytest.mark.parametrize("K,N", [(2048, 8192), (4096, 14336)])
def test_lower_quant_matmul_bench_shapes(K, N, bn):
    """quant_matmul lowers for the TPU target at the committed registry
    shapes — the 1B MLP up/gate (k2048_n8192) and the 8B (k4096_n14336) —
    across every gate-legal output tile ``bn`` from the kernel audit."""
    from neuronx_distributed_inference_tpu.ops.quant_matmul import (
        INT4_GROUP,
        quant_matmul,
    )

    x = sds((8, K), jnp.bfloat16)
    w = sds((K // 2, N), jnp.uint8)
    s = sds((K // INT4_GROUP, N), jnp.float32)
    fn = functools.partial(quant_matmul, bn=bn, interpret=False)
    lower_tpu(lambda x, w, s: fn(x, w, s), x, w, s)


def test_lower_quant_matmul_single_row():
    # bs=1 decode: a single activation row still occupies one (8, 128) f32
    # sublane tile — the shape the int4_8b_bs1 bench point streams
    from neuronx_distributed_inference_tpu.ops.quant_matmul import (
        INT4_GROUP,
        quant_matmul,
    )

    K, N = 2048, 8192
    x = sds((1, K), jnp.bfloat16)
    w = sds((K // 2, N), jnp.uint8)
    s = sds((K // INT4_GROUP, N), jnp.float32)
    fn = functools.partial(quant_matmul, interpret=False)
    lower_tpu(lambda x, w, s: fn(x, w, s), x, w, s)


def test_lower_paged_flash_quantized():
    # int8 paged cache through the chunked-prefill kernel (the dequant
    # scaling folds into q and the epilogue — must not break lowering)
    B, Hkv, NB, bs, MB, Sq, D = 1, 8, 64, 128, 16, 512, 64
    Hq = Hkv * 4
    q = sds((B, Sq, Hq, D), jnp.bfloat16)
    cache = sds((NB + 1, Hkv, bs, D), jnp.int8)
    bt = sds((B, MB), jnp.int32)
    pos = sds((B, Sq), jnp.int32)
    lim = sds((B,), jnp.int32)
    scale = sds((Hkv,), jnp.float32)
    fn = functools.partial(
        paged_flash_attention, scale=D**-0.5, n_rep=4, interpret=False
    )
    lower_tpu(
        lambda q, k, v, b, p, l, ks, vs: fn(
            q, k, v, b, p, l, k_scale=ks, v_scale=vs
        ),
        q, cache, cache, bt, pos, lim, scale, scale,
    )
