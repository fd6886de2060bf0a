"""Quantized KV cache (int8/fp8 codes + per-(layer, head) running-absmax
scales) — ISSUE 3 parity/contract suite.

Covers the full vertical slice:
- unit semantics: symmetric roundtrip error bound, running-absmax monotone
  growth, earlier codes never rescaled by later writes;
- kernel-vs-native agreement: the TKG decode kernels (contiguous + paged)
  on quantized caches vs the dequantize-after-gather native path, across
  decode/speculation q widths, sinks, and windowed decode masks;
- end-to-end logit-deviation bounds vs the bf16/fp32 cache across the
  contiguous, ring (sliding-window) and paged cache variants, plus fused
  speculation (commit/rollback rides the same scatter paths);
- graph contract: the forced-kernel TKG program materializes NO
  dequantized cache-sized tensor (jaxpr inspection; the same detector
  flags the native path, proving it detects);
- serving accounting: a byte-budgeted block pool admits ~2x the blocks
  under int8 KV;
- TPU-target AOT lowering of the quantized TKG + paged kernels at the 1B
  bench shapes (int8 and fp8).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.modules.attention import (
    AttnSpec,
    attention_decode,
)
from neuronx_distributed_inference_tpu.modules.kvcache import (
    QuantizedKV,
    cache_nbytes,
    dequantize_kv,
    init_cache,
    kv_qmax,
    read_cache_at_layer,
    update_cache_at_layer,
)
from neuronx_distributed_inference_tpu.ops.decode_attention import (
    paged_tkg_decode_attention,
    tkg_decode_attention,
)
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM

# committed end-to-end logit-deviation tolerances vs the unquantized cache
# (greedy decode, tiny seeded fp32 model, logit scale ~1): int8 keeps ~8 bit
# of per-head range, fp8 e4m3 ~3 mantissa bits
LOGIT_TOL = {"int8": 0.25, "fp8": 0.75}

L, B, S_MAX, HQ, HKV, D = 3, 2, 256, 8, 2, 64


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)


# ---------------------------------------------------------------------------
# unit semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_roundtrip_error_bound(dt):
    rng = np.random.RandomState(0)
    cache = init_cache(L, B, S_MAX, HKV, D, dtype=dt)
    k_new = _rand(rng, B, 32, HKV, D)
    pos = jnp.broadcast_to(jnp.arange(32)[None], (B, 32))
    slots = jnp.arange(B, dtype=jnp.int32)
    kq, vq = update_cache_at_layer(cache.k, cache.v, k_new, k_new, jnp.int32(1), slots, pos)
    back = dequantize_kv(kq.data[1, :B, :32], kq.scale[1])
    err = np.abs(np.asarray(back) - np.asarray(k_new)).max()
    # symmetric per-head quantization: error <= absmax / qmax per step for
    # int8 (round-to-nearest halves it); fp8 adds mantissa rounding ~2^-3
    amax = np.abs(np.asarray(k_new)).max()
    bound = amax / kv_qmax(dt) if dt == jnp.int8 else amax * 0.125
    assert err <= bound + 1e-6, (err, bound)
    # untouched layers stay zero-scaled and zero-coded
    assert np.asarray(kq.scale)[0].max() == 0.0
    assert np.asarray(kq.data)[0].any() == False  # noqa: E712


def test_running_absmax_never_rescales_earlier_codes():
    """The write path's running absmax only GROWS, and a later, larger write
    must leave earlier positions' codes untouched — the no-cache-re-read
    contract of the steady-state decode step."""
    rng = np.random.RandomState(1)
    cache = init_cache(L, B, S_MAX, HKV, D, dtype=jnp.int8)
    kq, vq = cache.k, cache.v
    slots = jnp.arange(B, dtype=jnp.int32)
    first = _rand(rng, B, 16, HKV, D)
    pos0 = jnp.broadcast_to(jnp.arange(16)[None], (B, 16))
    kq, vq = update_cache_at_layer(kq, vq, first, first, jnp.int32(0), slots, pos0)
    s0 = np.asarray(kq.scale)[0].copy()
    codes0 = np.asarray(kq.data)[0, :B, :16].copy()
    # 10x larger values at later positions
    second = _rand(rng, B, 4, HKV, D) * 10.0
    pos1 = jnp.broadcast_to(16 + jnp.arange(4)[None], (B, 4))
    kq, vq = update_cache_at_layer(kq, vq, second, second, jnp.int32(0), slots, pos1)
    s1 = np.asarray(kq.scale)[0]
    assert (s1 >= s0).all() and s1.max() > s0.max()
    np.testing.assert_array_equal(np.asarray(kq.data)[0, :B, :16], codes0)


def test_padded_writes_do_not_inflate_scale():
    """Sentinel-position (padded) tokens are dropped by the scatter AND
    excluded from the absmax — garbage must not blow up the scale."""
    from neuronx_distributed_inference_tpu.modules.kvcache import (
        PAD_POSITION_SENTINEL,
    )

    rng = np.random.RandomState(2)
    cache = init_cache(L, B, S_MAX, HKV, D, dtype=jnp.int8)
    k_new = _rand(rng, B, 8, HKV, D)
    k_new = k_new.at[:, 4:].set(k_new[:, 4:] * 100.0)  # huge junk in the pad tail
    pos = np.broadcast_to(np.arange(8)[None], (B, 8)).copy()
    pos[:, 4:] = PAD_POSITION_SENTINEL
    kq, _ = update_cache_at_layer(
        cache.k, cache.v, k_new, k_new, jnp.int32(0),
        jnp.arange(B, dtype=jnp.int32), jnp.asarray(pos),
    )
    valid_amax = np.abs(np.asarray(k_new[:, :4])).max()
    assert np.asarray(kq.scale)[0].max() <= valid_amax + 1e-6


def test_garbage_slot_writes_do_not_inflate_scale():
    """A garbage-line write (invalid seq id routed to the last cache row)
    with IN-RANGE positions must not feed the monotone absmax — junk can
    never be un-learned by the scale."""
    rng = np.random.RandomState(7)
    cache = init_cache(L, 2, S_MAX, HKV, D, dtype=jnp.int8)  # rows = 2 + garbage
    real = _rand(rng, 2, 4, HKV, D)
    junk = jnp.concatenate([real[:1], real[1:] * 100.0], axis=0)
    pos = jnp.broadcast_to(jnp.arange(4)[None], (2, 4))
    # row 1 routed to the garbage line (slot == rows - 1)
    slots = jnp.asarray([0, cache.k.shape[1] - 1], jnp.int32)
    kq, _ = update_cache_at_layer(
        cache.k, cache.v, junk, junk, jnp.int32(0), slots, pos
    )
    real_amax = np.abs(np.asarray(real[:1])).max()
    assert np.asarray(kq.scale)[0].max() <= real_amax + 1e-6


def test_dp_shard_garbage_rows_do_not_inflate_scale():
    """Attention-DP layout: EVERY shard's interleaved garbage line (not just
    the last row) is excluded from the scale update."""
    from neuronx_distributed_inference_tpu.modules.kvcache import (
        slot_ids_from_seq_ids,
    )

    rng = np.random.RandomState(8)
    dp, batch = 2, 4
    cache = init_cache(L, batch, S_MAX, HKV, D, dtype=jnp.int8, dp=dp)
    # rows 0 and 2 invalid -> shard-local garbage lines (slot 2 for shard 0)
    seq_ids = jnp.asarray([-1, 0, -1, 3], jnp.int32)
    slots = slot_ids_from_seq_ids(seq_ids, batch, dp=dp)
    x = _rand(rng, batch, 4, HKV, D)
    junk = x.at[0].set(x[0] * 100.0).at[2].set(x[2] * 100.0)
    pos = jnp.broadcast_to(jnp.arange(4)[None], (batch, 4))
    kq, _ = update_cache_at_layer(
        cache.k, cache.v, junk, junk, jnp.int32(0), slots, pos, dp=dp
    )
    real_amax = np.abs(np.asarray(junk[jnp.asarray([1, 3])])).max()
    assert np.asarray(kq.scale)[0].max() <= real_amax + 1e-6


def test_paged_garbage_block_writes_do_not_inflate_scale():
    """Paged layout: writes landing in the reserved garbage block 0 (idle
    serving rows carry all-zero block tables with slot >= 0) must not feed
    the pool-wide running absmax."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
        slot_mapping_from_block_table,
        update_block_cache_at_layer,
    )

    rng = np.random.RandomState(9)
    NB, bs = 4, 16
    bc = init_block_cache(L, NB, bs, HKV, D, dtype=jnp.int8)
    # row 0 live (block 2); row 1 idle: all-zero table -> garbage block 0
    bt = jnp.asarray([[2], [0]], jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    sm = slot_mapping_from_block_table(bt, pos, bs)
    assert int(sm[1, 0]) == 0  # idle row maps INTO block 0 with slot >= 0
    x = _rand(rng, 2, 1, HKV, D)
    junk = x.at[1].set(x[1] * 100.0)
    kq, _ = update_block_cache_at_layer(bc.k, bc.v, junk, junk, jnp.int32(0), sm)
    real_amax = np.abs(np.asarray(x[0])).max()
    assert np.asarray(kq.scale)[0].max() <= real_amax + 1e-6


# ---------------------------------------------------------------------------
# kernel vs native agreement (interpret mode)
# ---------------------------------------------------------------------------


def _decode_mask(B_, K, S, valid_len):
    pos = np.stack([np.arange(valid_len[b] - K, valid_len[b]) for b in range(B_)])
    cols = np.arange(S)[None, None, :]
    return jnp.asarray(cols <= pos[:, :, None])[:, None], pos


def _filled_contiguous(dt, rng, S=100):
    cache = init_cache(L, B, S_MAX, HKV, D, dtype=dt)
    kq, vq = cache.k, cache.v
    k_new = _rand(rng, B, S, HKV, D)
    v_new = _rand(rng, B, S, HKV, D)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    slots = jnp.arange(B, dtype=jnp.int32)
    for li in range(L):
        kq, vq = update_cache_at_layer(kq, vq, k_new, v_new, jnp.int32(li), slots, pos)
    return kq, vq


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
@pytest.mark.parametrize("K,sink", [(1, False), (4, False), (1, True)])
def test_tkg_kernel_matches_native_dequant(dt, K, sink):
    rng = np.random.RandomState(3)
    kq, vq = _filled_contiguous(dt, rng)
    bucket, layer = 128, 1
    q = _rand(rng, B, K, HQ, D)
    mask, _ = _decode_mask(B, K, bucket, [100, 37])
    sink_w = _rand(rng, HQ) if sink else None
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D, has_sink=sink)

    k_r, v_r = read_cache_at_layer(kq, vq, jnp.int32(layer), B, bucket)
    ref = attention_decode(q, k_r, v_r, mask, spec, sink=sink_w)
    out = tkg_decode_attention(
        q, kq, vq, jnp.int32(layer), mask, sink_w,
        scale=spec.softmax_scale, n_kv=HKV, bs=64, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


def test_tkg_kernel_windowed_mask_quantized():
    """Window-flavored decode masks work unchanged on the quantized kernel
    (mask-driven; the dequant fold is mask-independent)."""
    rng = np.random.RandomState(4)
    kq, vq = _filled_contiguous(jnp.int8, rng)
    bucket, W = 128, 16
    q = _rand(rng, B, 1, HQ, D)
    mask, pos = _decode_mask(B, 1, bucket, [90, 50])
    cols = jnp.arange(bucket)[None, None, None, :]
    mask = mask & (cols > jnp.asarray(pos)[:, None, :, None] - W)
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    k_r, v_r = read_cache_at_layer(kq, vq, jnp.int32(0), B, bucket)
    ref = attention_decode(q, k_r, v_r, mask, spec)
    out = tkg_decode_attention(
        q, kq, vq, jnp.int32(0), mask, None,
        scale=spec.softmax_scale, n_kv=HKV, bs=64, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_paged_tkg_kernel_matches_native_dequant(dt):
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
        read_block_cache_at_layer,
        slot_mapping_from_block_table,
        update_block_cache_at_layer,
    )

    rng = np.random.RandomState(5)
    NB, bs, MB = 12, 16, 8
    bc = init_block_cache(L, NB, bs, HKV, D, dtype=dt)
    kb, vb = bc.k, bc.v
    bt = np.zeros((B, MB), np.int32)
    bt[0, :7] = rng.permutation(np.arange(1, NB + 1))[:7]
    bt[1, :3] = rng.permutation(np.arange(1, NB + 1))[:3]
    bt = jnp.asarray(bt)
    valid = [7 * bs - 3, 3 * bs - 9]
    Sb = max(valid)
    posb = np.full((B, Sb), -1, np.int32)
    for b, v in enumerate(valid):
        posb[b, :v] = np.arange(v)
    sm = slot_mapping_from_block_table(
        bt, jnp.asarray(np.maximum(posb, 0)), bs, valid=jnp.asarray(posb >= 0)
    )
    k_new = _rand(rng, B, Sb, HKV, D)
    v_new = _rand(rng, B, Sb, HKV, D)
    for li in range(L):
        kb, vb = update_block_cache_at_layer(kb, vb, k_new, v_new, jnp.int32(li), sm)
    assert isinstance(kb, QuantizedKV) and kb.data.dtype == jnp.dtype(dt)

    q = _rand(rng, B, 1, HQ, D)
    mask, _ = _decode_mask(B, 1, MB * bs, valid)
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    k_r, v_r = read_block_cache_at_layer(kb, vb, jnp.int32(2), bt)
    ref = attention_decode(q, k_r, v_r, mask, spec)
    out = paged_tkg_decode_attention(
        q, kb, vb, jnp.int32(2), bt, mask, None,
        scale=spec.softmax_scale, n_kv=HKV, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


def test_paged_flash_prior_kv_quantized():
    """The chunked/prefix-prefill paged flash kernel dequantizes the prior-KV
    code blocks in-register (scales folded into q / the output)."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
        read_block_cache_at_layer,
        slot_mapping_from_block_table,
        update_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.modules.kvcache import (
        layer_dequant_factors,
    )
    from neuronx_distributed_inference_tpu.modules.masks import spec_token_gen_mask
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
        paged_flash_attention,
    )

    rng = np.random.RandomState(6)
    NB, bs, MB, Sq = 12, 16, 8, 16
    bc = init_block_cache(L, NB, bs, HKV, D, dtype=jnp.int8)
    kb, vb = bc.k, bc.v
    bt = np.zeros((B, MB), np.int32)
    bt[0, :6] = np.arange(1, 7)
    bt[1, :4] = np.arange(7, 11)
    bt = jnp.asarray(bt)
    prior = [48, 23]  # prior context per row; the Sq new tokens follow
    total = [p + Sq for p in prior]
    Sb = max(total)
    posb = np.full((B, Sb), -1, np.int32)
    for b, t in enumerate(total):
        posb[b, :t] = np.arange(t)
    sm = slot_mapping_from_block_table(
        bt, jnp.asarray(np.maximum(posb, 0)), bs, valid=jnp.asarray(posb >= 0)
    )
    k_new = _rand(rng, B, Sb, HKV, D)
    v_new = _rand(rng, B, Sb, HKV, D)
    layer = 1
    for li in range(L):
        kb, vb = update_block_cache_at_layer(kb, vb, k_new, v_new, jnp.int32(li), sm)

    q = _rand(rng, B, Sq, HQ, D)
    qpos = np.stack([np.arange(p, p + Sq) for p in prior])
    kv_limit = jnp.asarray(total, jnp.int32)

    # native oracle: gather+dequant the paged cache, spec_token_gen mask
    k_r, v_r = read_block_cache_at_layer(kb, vb, jnp.int32(layer), bt)
    am = np.zeros((B, MB * bs), np.int32)
    for b, t in enumerate(total):
        am[b, :t] = 1
    mask = spec_token_gen_mask(jnp.asarray(am), jnp.asarray(qpos))
    spec = AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D)
    ref = attention_decode(q, k_r, v_r, mask, spec)

    ks = layer_dequant_factors(kb, jnp.int32(layer))
    vs = layer_dequant_factors(vb, jnp.int32(layer))
    k_l = kb.data[layer]
    v_l = vb.data[layer]
    out = paged_flash_attention(
        q, k_l, v_l, bt, jnp.asarray(qpos, jnp.int32), kv_limit,
        scale=spec.softmax_scale, n_rep=HQ // HKV, tq=16,
        k_scale=ks, v_scale=vs, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# end-to-end parity: contiguous / ring / paged / speculation
# ---------------------------------------------------------------------------

PROMPTS = np.array([[5, 17, 92, 41, 7, 3, 2, 9], [64, 3, 27, 9, 14, 33, 5, 1]], np.int32)


def _gen(app, n=8):
    out = app.generate(PROMPTS, np.ones_like(PROMPTS), max_new_tokens=n)
    return np.asarray(out.sequences), np.asarray(out.logits)


@pytest.mark.parametrize("kvd", ["int8", "fp8"])
def test_contiguous_e2e_logit_deviation(kvd):
    sd = None
    outs = {}
    for dtype in (None, kvd):
        cfg = make_tiny_config(tpu=dict(kv_cache_dtype=dtype, output_logits=True))
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
        if dtype:
            assert isinstance(app.kv_cache.k, QuantizedKV)
        outs[dtype] = _gen(app)
    seq_ref, logits_ref = outs[None]
    seq_q, logits_q = outs[kvd]
    # greedy tokens agree on the seeded tiny model, logits within tolerance
    np.testing.assert_array_equal(seq_ref, seq_q)
    dev = np.abs(logits_ref - logits_q).max()
    assert dev <= LOGIT_TOL[kvd], (dev, LOGIT_TOL[kvd])
    assert dev > 0  # the quantized cache is actually in the loop


def test_ring_sliding_window_e2e():
    """Ring-bounded (sliding-window) cache variant: prompt > window so the
    ring wraps; decode crosses window boundaries (prior-read + mod-W write
    paths both quantize/dequantize)."""
    # mistral consumes the HF sliding_window attr and bounds the cache
    attrs = dict(model_type="mistral", sliding_window=8, max_position_embeddings=256)
    sd = None
    outs = {}
    for dtype in (None, "int8"):
        cfg = make_tiny_config(
            tpu=dict(kv_cache_dtype=dtype, output_logits=True), **attrs
        )
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
        assert app.spec.bounded_window == 8  # the ring variant is active
        if dtype:
            assert isinstance(app.kv_cache.k, QuantizedKV)
            assert app.kv_cache.k.shape[2] == 8  # W ring slots only
        outs[dtype] = _gen(app, n=12)
    np.testing.assert_array_equal(outs[None][0], outs["int8"][0])
    dev = np.abs(outs[None][1] - outs["int8"][1]).max()
    assert 0 < dev <= LOGIT_TOL["int8"], dev


def test_repeated_generate_settles():
    """Running-absmax semantics on one live app: the FIRST generate may
    grow the scale mid-run (so run 2, prefilling under the settled scale,
    may differ in the last quantization bit), but once settled repeated
    generates are bit-deterministic, and init_kv_cache() restores the
    fresh-cache run exactly (docs/KV_QUANT.md determinism contract)."""
    cfg = make_tiny_config(tpu=dict(kv_cache_dtype="int8"))
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    mask = np.ones_like(PROMPTS)
    runs = [
        np.asarray(app.generate(PROMPTS, mask, max_new_tokens=8).sequences)
        for _ in range(3)
    ]
    np.testing.assert_array_equal(runs[1], runs[2])  # settled == deterministic
    scale = np.asarray(app.kv_cache.k.scale)
    app.init_kv_cache()
    fresh = np.asarray(app.generate(PROMPTS, mask, max_new_tokens=8).sequences)
    np.testing.assert_array_equal(fresh, runs[0])  # reset == fresh behavior
    assert np.asarray(app.kv_cache.k.scale).max() <= scale.max() + 1e-6


def test_batch_coupling_bounded():
    """Scales are batch-shared (per layer/head — the paged pool requires
    it), so a row decoded alone vs co-batched couples by ≤ one quantization
    step: FIRST-STEP logits stay within the committed tolerance (greedy
    paths may then diverge — documented in docs/KV_QUANT.md)."""
    cfg = make_tiny_config(tpu=dict(kv_cache_dtype="int8", output_logits=True))
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    both = app.generate(PROMPTS, np.ones_like(PROMPTS), max_new_tokens=4)
    app.init_kv_cache()
    solo = app.generate(
        PROMPTS[1:], np.ones_like(PROMPTS[1:]), max_new_tokens=4
    )
    dev = np.abs(
        np.asarray(both.logits)[1, 0] - np.asarray(solo.logits)[0, 0]
    ).max()
    assert dev <= LOGIT_TOL["int8"], dev


def test_chunked_attention_mask_e2e():
    """Chunked-attention decode masks (llama4 flavor) over the quantized
    contiguous cache — the third decode mask flavor next to plain/windowed."""
    sd = None
    outs = {}
    for dtype in (None, "int8"):
        cfg = make_tiny_config(
            tpu=dict(
                kv_cache_dtype=dtype, output_logits=True, attention_chunk_size=8
            )
        )
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
        outs[dtype] = _gen(app, n=12)
    np.testing.assert_array_equal(outs[None][0], outs["int8"][0])
    dev = np.abs(outs[None][1] - outs["int8"][1]).max()
    assert 0 < dev <= LOGIT_TOL["int8"], dev


def test_paged_serving_e2e_matches_contiguous_quantized():
    """Block-KV serving with int8 KV produces the same tokens as
    contiguous-cache serving with int8 KV (same math, paged layout), and the
    paged cache is actually quantized."""
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    sd = None
    results = {}
    for block in (False, True):
        tpu = dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            kv_cache_dtype="int8",
        )
        if block:
            tpu.update(is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16)
        cfg = make_tiny_config(tpu=tpu)
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
        assert isinstance(app.kv_cache.k, QuantizedKV)
        sess = ServingSession(app)
        prompts = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33]}
        for rid, p in prompts.items():
            assert sess.add_request(rid, p, max_new_tokens=8)
        results[block] = sess.run_to_completion()
    assert results[False] == results[True]


@pytest.mark.parametrize("kvd", ["int8"])
def test_fused_speculation_quantized_kv(kvd):
    """Fused speculation with quantized draft+target caches: the spec
    commit/rollback overwrites ride the quantized scatter; greedy output
    matches the bf16-cache fused-spec run on the seeded tiny model."""
    from neuronx_distributed_inference_tpu.config import FusedSpecConfig
    from neuronx_distributed_inference_tpu.runtime.fused_spec import (
        TpuFusedSpecModelForCausalLM,
    )

    target_sd = draft_sd = None
    seqs = {}
    for dtype in (None, kvd):
        draft_cfg = make_tiny_config()
        spec_cfg = make_tiny_config(tpu=dict(kv_cache_dtype=dtype))
        spec_cfg.tpu_config.speculation_length = 4
        spec_cfg.tpu_config.enable_fused_speculation = True
        spec_cfg.fused_spec_config = FusedSpecConfig(
            draft_model_name="tiny-draft", draft_config=draft_cfg
        )
        if target_sd is None:
            target_sd = make_random_hf_state_dict(spec_cfg, seed=0)
            draft_sd = make_random_hf_state_dict(draft_cfg, seed=7)
        app = TpuFusedSpecModelForCausalLM(None, spec_cfg)
        app.load(target_state_dict=target_sd, draft_state_dict=draft_sd)
        if dtype:
            assert isinstance(app.target_cache.k, QuantizedKV)
            assert isinstance(app.draft_cache.k, QuantizedKV)
        out = app.generate(PROMPTS, np.ones_like(PROMPTS), max_new_tokens=10)
        seqs[dtype] = np.asarray(out.sequences)
    np.testing.assert_array_equal(seqs[None], seqs[kvd])


# ---------------------------------------------------------------------------
# graph contract: no dequantized cache materialization on the kernel path
# ---------------------------------------------------------------------------


def _kernel_app(kv_dtype, tkg_kernel):
    """Tiny D=64 model so the TKG kernel is shape-eligible (head_dim 64,
    bucket 128); tkg_kernel forces the kernel on the CPU host (interpret)."""
    cfg = make_tiny_config(
        hidden_size=256,
        intermediate_size=512,
        tpu=dict(
            kv_cache_dtype=kv_dtype,
            seq_len=128,
            token_generation_buckets=[128],
            context_encoding_buckets=[64, 128],
            attn_block_tkg_kernel_enabled=tkg_kernel,
        ),
    )
    sd = make_random_hf_state_dict(cfg)
    return TpuModelForCausalLM(None, cfg).load(state_dict=sd)


def _float_aval_sizes(jaxpr, skip_prims=("pallas_call",)):
    """All float-dtype output aval sizes in a jaxpr, excluding kernel
    bodies (the in-register dequant lives there by design)."""
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip_prims:
            continue
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                sizes.append(int(np.prod(v.aval.shape)) if v.aval.shape else 1)
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                inner = getattr(inner, "jaxpr", inner)
                sizes.extend(_float_aval_sizes(inner, skip_prims))
    return sizes


def _max_float_size(app):
    runner = app.token_generation_model
    inputs = runner.example_inputs(runner.buckets[-1])
    with jax.set_mesh(app.mesh):
        traced = runner.program_for(inputs).trace(app.params, app.kv_cache, inputs, None)
    return max(_float_aval_sizes(traced.jaxpr.jaxpr))


def test_no_dequantized_cache_materialization_on_kernel_path():
    """With the TKG kernel forced on an int8 cache, the decode program must
    not materialize any float tensor as large as one layer's cache view —
    the dequant happens in-register inside the kernel. The SAME detector
    flags the native path (which legitimately dequantizes after the slice),
    proving it can see the materialization it bans."""
    app = _kernel_app("int8", tkg_kernel=True)
    # one layer's bucket-sized dequantized view: (B, S_bucket, Hkv, D)
    tc = app.config.tpu_config
    bucket_view = tc.batch_size * 128 * app.spec.attn.num_kv_heads * 64
    assert _max_float_size(app) < bucket_view

    native = _kernel_app("int8", tkg_kernel=False)
    assert _max_float_size(native) >= bucket_view


def test_kernel_and_native_paths_agree_in_model():
    """Same weights, same prompts: the forced-TKG-kernel program and the
    native-dequant program produce identical greedy tokens and near-equal
    logits on a quantized cache."""
    outs = {}
    for kernel in (True, False):
        app = _kernel_app("int8", tkg_kernel=kernel)
        out = app.generate(PROMPTS, np.ones_like(PROMPTS), max_new_tokens=8)
        outs[kernel] = np.asarray(out.sequences)
    np.testing.assert_array_equal(outs[True], outs[False])


# ---------------------------------------------------------------------------
# serving block-pool byte accounting
# ---------------------------------------------------------------------------


def test_pool_bytes_admit_2x_blocks_for_int8():
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    pool = 1 << 20  # 1 MiB budget
    apps = {}
    for kvd in (None, "int8"):
        cfg = make_tiny_config(
            tpu=dict(
                is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                is_block_kv_layout=True, pa_block_size=16, pa_pool_bytes=pool,
                kv_cache_dtype=kvd, dtype="bfloat16",
            )
        )
        sd = make_random_hf_state_dict(cfg)
        apps[kvd] = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    nb_bf16 = apps[None].config.tpu_config.pa_num_blocks
    nb_int8 = apps["int8"].config.tpu_config.pa_num_blocks
    assert nb_int8 == 2 * nb_bf16, (nb_bf16, nb_int8)

    sess = ServingSession(apps["int8"])
    sess_ref = ServingSession(apps[None])
    # same byte budget reported either way (+/- block granularity)...
    assert abs(sess.kv_pool_bytes - sess_ref.kv_pool_bytes) <= sess_ref.block_bytes
    # ...but the quantized pool holds 2x the blocks/tokens
    assert sess.allocator.num_blocks == 2 * sess_ref.allocator.num_blocks
    assert sess.block_bytes * 2 == sess_ref.block_bytes


def test_pa_pool_bytes_validation():
    from neuronx_distributed_inference_tpu.config import TpuConfig

    with pytest.raises(ValueError, match="pa_pool_bytes requires"):
        TpuConfig(pa_pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="not both"):
        TpuConfig(is_block_kv_layout=True, pa_num_blocks=8, pa_pool_bytes=1 << 20)


# ---------------------------------------------------------------------------
# config validation + unsupported-variant gates
# ---------------------------------------------------------------------------


def test_unknown_kv_cache_dtype_rejected():
    from neuronx_distributed_inference_tpu.config import TpuConfig

    with pytest.raises(ValueError, match="unknown kv_cache_dtype"):
        TpuConfig(kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="unknown kv_cache_dtype"):
        TpuConfig(kv_cache_dtype="bf17")
    # every documented name is accepted
    from neuronx_distributed_inference_tpu.config import KV_CACHE_DTYPES

    for name in KV_CACHE_DTYPES:
        tc = TpuConfig(kv_cache_dtype=name)
        assert tc.kv_quantized == (name in ("int8", "fp8", "float8_e4m3", "float8_e5m2"))


def test_demo_cli_kv_cache_dtype_flag():
    from neuronx_distributed_inference_tpu.inference_demo import build_parser

    p = build_parser()
    args = p.parse_args(
        ["run", "--model-path", "x", "--kv-cache-dtype", "int8",
         "--pa-pool-bytes", "1048576"]
    )
    assert args.kv_cache_dtype == "int8"
    assert args.pa_pool_bytes == 1 << 20
    with pytest.raises(SystemExit):
        p.parse_args(["run", "--model-path", "x", "--kv-cache-dtype", "int4"])


def test_interleaved_cache_rejects_kv_quant():
    """GPT-OSS interleaved full+ring stacks have no scale streams — the
    builder must fail fast instead of allocating scaleless int8 junk."""
    pytest.importorskip("transformers")
    from neuronx_distributed_inference_tpu.models.registry import MODEL_REGISTRY

    if "gpt_oss" not in MODEL_REGISTRY:
        pytest.skip("gpt_oss not registered")
    # construction goes through the model plugin; cheapest is the builder gate
    from neuronx_distributed_inference_tpu.models.gpt_oss import GptOssModelBuilder

    class _FakeSpec:
        ring_window = 8

    class _B(GptOssModelBuilder):
        def __init__(self):
            pass

        def model_spec(self):
            return _FakeSpec()

        @property
        def config(self):
            class _C:
                class tpu_config:
                    kv_quantized = True

            return _C()

    with pytest.raises(NotImplementedError, match="interleaved"):
        _B().init_kv_cache(mesh=None)


def test_cache_nbytes_halved():
    bf16 = init_cache(L, B, S_MAX, HKV, D, dtype=jnp.bfloat16)
    q8 = init_cache(L, B, S_MAX, HKV, D, dtype=jnp.int8)
    # int8 codes are half of bf16; scales add a negligible float32 sliver
    assert cache_nbytes(q8) < cache_nbytes(bf16) * 0.51


# ---------------------------------------------------------------------------
# TPU-target AOT lowering at the 1B bench shapes
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lower_tpu(fn, *args, **kw):
    from jax import export

    return export.export(jax.jit(fn), platforms=["tpu"])(*args, **kw)


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_lower_quantized_tkg_contiguous_1b_shapes(dt):
    """1B bench decode shape: L=16, Hq=32, Hkv=8, D=64, 8k bucket (8704 =
    17*512, the 512-aligned long-context TKG bucket)."""
    Lb, R, S, Hq, Hkv, Db = 16, 2, 8704, 32, 8, 64
    q = _sds((1, 1, Hq, Db), jnp.bfloat16)
    kc = QuantizedKV(
        data=_sds((Lb, R, S, Hkv, Db), dt), scale=_sds((Lb, Hkv), jnp.float32)
    )
    mask = _sds((1, 1, 1, S), jnp.bool_)
    fn = functools.partial(
        tkg_decode_attention, scale=Db**-0.5, n_kv=Hkv, interpret=False
    )
    _lower_tpu(fn, q, kc, kc, _sds((), jnp.int32), mask, None)


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_lower_quantized_tkg_paged_1b_shapes(dt):
    Lb, NB, bs, MB, Hq, Hkv, Db = 16, 512, 32, 258, 32, 8, 64
    q = _sds((8, 1, Hq, Db), jnp.bfloat16)
    kc = QuantizedKV(
        data=_sds((Lb, NB + 1, Hkv, bs, Db), dt), scale=_sds((Lb, Hkv), jnp.float32)
    )
    bt = _sds((8, MB), jnp.int32)
    mask = _sds((8, 1, 1, MB * bs), jnp.bool_)
    fn = functools.partial(
        paged_tkg_decode_attention, scale=Db**-0.5, n_kv=Hkv, interpret=False
    )
    _lower_tpu(fn, q, kc, kc, _sds((), jnp.int32), bt, mask, None)


@pytest.mark.parametrize("dt", [jnp.int8, jnp.float8_e4m3fn])
def test_lower_quantized_paged_flash(dt):
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
        paged_flash_attention,
    )

    NB, bs, MB, Hq, Hkv, Db = 512, 32, 258, 32, 8, 64
    q = _sds((2, 128, Hq, Db), jnp.bfloat16)
    kc = _sds((NB + 1, Hkv, bs, Db), dt)
    fn = functools.partial(
        paged_flash_attention, scale=Db**-0.5, n_rep=Hq // Hkv, interpret=False
    )
    _lower_tpu(
        fn, q, kc, kc, _sds((2, MB), jnp.int32), _sds((2, 128), jnp.int32),
        _sds((2,), jnp.int32),
        k_scale=_sds((Hkv,), jnp.float32), v_scale=_sds((Hkv,), jnp.float32),
    )


@pytest.mark.slow
def test_lower_whole_model_tkg_quantized():
    """The whole TKG program (scan over layers, int8 cache with scale
    streams, forced TKG kernel) AOT-lowers for the TPU target — catches
    breaks in how the model feeds the quantized cache to the kernel (specs,
    folds, donation), not just the kernel in isolation."""
    from neuronx_distributed_inference_tpu.models.base import (
        PHASE_TOKEN_GENERATION,
        StepInputs,
        forward,
        gated_mlp,
    )
    from neuronx_distributed_inference_tpu.models.llama import LlamaModelBuilder
    from neuronx_distributed_inference_tpu.ops.kernel_mode import (
        force_compiled_kernels,
    )

    Bm = 2
    cfg = make_tiny_config(
        hidden_size=256,
        intermediate_size=512,
        tpu=dict(
            batch_size=Bm, seq_len=256, dtype="bfloat16",
            kv_cache_dtype="int8", attn_block_tkg_kernel_enabled=True,
        ),
    )
    builder = LlamaModelBuilder(cfg)
    spec = builder.model_spec()
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype), builder.random_params())
    cache = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype),
        init_cache(spec.num_layers, Bm + 1, 256, spec.attn.num_kv_heads,
                   spec.attn.head_dim, dtype=jnp.int8),
    )
    bucket = 256
    inputs = StepInputs(
        input_ids=_sds((Bm, 1), jnp.int32),
        attention_mask=_sds((Bm, bucket), jnp.int32),
        position_ids=_sds((Bm, 1), jnp.int32),
        seq_ids=_sds((Bm,), jnp.int32),
        sampling_params=_sds((Bm, 3), jnp.float32),
    )
    fn = functools.partial(
        forward, spec=spec, phase=PHASE_TOKEN_GENERATION, mlp_fn=gated_mlp
    )
    with force_compiled_kernels():
        _lower_tpu(fn, params, cache, inputs, None)
