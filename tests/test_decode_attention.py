"""Kernel-parity tests for the Pallas TKG decode-attention kernels
(VERDICT r2 next #1) — oracle is the native masked-softmax decode path, at
q=1 (decode) and q=4 (speculation), with GQA, sinks, and paged block tables.
Runs in interpret mode on CPU (same pattern as tests/test_chunked_prefill.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.attention import (
    AttnSpec,
    attention_decode,
)
from neuronx_distributed_inference_tpu.ops.decode_attention import (
    paged_tkg_decode_attention,
    tkg_decode_attention,
    use_tkg_kernel,
)

L, R, S_MAX = 3, 5, 256
HQ, HKV, D = 8, 2, 64


def _spec(**kw):
    return AttnSpec(num_heads=HQ, num_kv_heads=HKV, head_dim=D, **kw)


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)


def _decode_mask(rng, B, K, S, valid_len):
    """Standard decode mask: cols <= per-token position, per row."""
    pos = np.stack(
        [np.arange(valid_len[b] - K, valid_len[b]) for b in range(B)]
    )  # (B, K)
    cols = np.arange(S)[None, None, :]
    return jnp.asarray(cols <= pos[:, :, None])[:, None], pos


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("sink", [False, True])
def test_tkg_contiguous_parity(K, sink):
    rng = np.random.RandomState(0 if K == 1 else 1)
    B, bucket = 2, 128
    layer = 1
    q = _rand(rng, B, K, HQ, D)
    k_cache = _rand(rng, L, R, S_MAX, HKV, D)
    v_cache = _rand(rng, L, R, S_MAX, HKV, D)
    valid = [100, 37]
    mask, _ = _decode_mask(rng, B, K, bucket, valid)
    sink_w = _rand(rng, HQ) if sink else None

    spec = _spec(has_sink=sink)
    k_r = k_cache[layer, :B, :bucket]
    v_r = v_cache[layer, :B, :bucket]
    ref = attention_decode(q, k_r, v_r, mask, spec, sink=sink_w)

    out = tkg_decode_attention(
        q, k_cache, v_cache, jnp.int32(layer), mask, sink_w,
        scale=spec.softmax_scale, n_kv=HKV, bs=64, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_tkg_contiguous_windowed_mask():
    """Window-flavored decode masks work unchanged (mask-driven kernel)."""
    rng = np.random.RandomState(2)
    B, K, bucket, W = 2, 1, 128, 16
    q = _rand(rng, B, K, HQ, D)
    k_cache = _rand(rng, L, R, S_MAX, HKV, D)
    v_cache = _rand(rng, L, R, S_MAX, HKV, D)
    mask, pos = _decode_mask(rng, B, K, bucket, [90, 50])
    cols = jnp.arange(bucket)[None, None, None, :]
    mask = mask & (cols > jnp.asarray(pos)[:, None, :, None] - W)

    spec = _spec()
    ref = attention_decode(
        q, k_cache[0, :B, :bucket], v_cache[0, :B, :bucket], mask, spec
    )
    out = tkg_decode_attention(
        q, k_cache, v_cache, jnp.int32(0), mask, None,
        scale=spec.softmax_scale, n_kv=HKV, bs=64, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# what the paged kernel's unit of work (a group of ``pages_per_step`` pool
# blocks of one row, none past the row's last live block) can get wrong.
# ``valid`` = context tokens per row, a number or (groups, blocks, tokens) of
# the case's own group width (0: a row with no live block). bs = 16: 2 heads
# of 128 give P = 32 (the token limit), 8 heads P = 16 in float32 (the byte
# limit), a table of 8 entries P = 8 = MB; at head_dim 64 blocks come one a
# grid step. ``window``: a sliding-window mask, so a row's first live group
# need not be group 0. The ``tile_*`` cases put a context inside the first 128
# tokens (the score tile's lanes) of a group of 256 or 512, at a tile's edge
# and one token past it, at 2 / 8 / 16 KV heads.
_E = 16  # a block's tokens
PAGED_CASES = {
    # the two cases this test had (K 1 and 4, contexts that end inside a block)
    "k1": dict(K=1, valid=[6 * _E - 5, 3 * _E - 9]),
    "k4": dict(K=4, valid=[6 * _E - 5, 3 * _E - 9]),
    "d128_mb_eq_p": dict(D=128, valid=[6 * _E - 5, 3 * _E - 9]),
    "d128_block_edge": dict(D=128, valid=[5 * _E, _E]),
    "d128_mb_gt_p_group_edge": dict(D=128, MB=64, valid=[(1, 0, 0), (1, 0, 1), (2, 0, 0)]),
    "d128_inside_second_group": dict(D=128, MB=64, valid=[(1, 7, -3), 9]),
    "d128_dead_row_among_live": dict(D=128, MB=64, valid=[(1, 0, -1), 0, 40, 0, (1, 0, 5)]),
    "d128_first_rows_dead": dict(D=128, MB=64, valid=[0, 0, 3 * _E]),
    "d128_table_no_multiple_of_p": dict(D=128, MB=40, valid=[40 * _E, (1, 1, -2)]),
    "d128_table_of_12": dict(D=128, MB=12, valid=[12 * _E, 9 * _E - 2, 0, 3]),
    "d128_k4_sink": dict(D=128, MB=64, K=4, sink=True, valid=[(1, 0, 3), 0, 50]),
    "d128_k4": dict(D=128, K=4, valid=[6 * _E - 5, 3 * _E - 9]),
    "d128_sink": dict(D=128, sink=True, valid=[6 * _E - 5, 0]),
    "d128_8kv": dict(D=128, HKV=8, HQ=16, MB=32, valid=[(1, 1, 4), 0, (2, 0, -1)]),
    "d128_8kv_k4_sink": dict(D=128, HKV=8, HQ=16, K=4, sink=True, valid=[100, 7 * _E]),
    "d128_bf16": dict(D=128, MB=64, dtype="bfloat16", valid=[(1, 1, 4), 0, 77]),
    "d128_bf16_8kv_k4": dict(D=128, HKV=8, HQ=16, MB=64, K=4, dtype="bfloat16", valid=[(1, 0, 0), 31]),
    "d128_bf16_q_bf16": dict(D=128, MB=64, dtype="bfloat16", q_dtype="bfloat16", valid=[(1, 1, 4), 0, 77]),
    "d128_int8": dict(D=128, MB=64, dtype="int8", valid=[(1, 1, 4), 0, 77]),
    "d128_int8_8kv_sink": dict(D=128, HKV=8, HQ=16, dtype="int8", sink=True, valid=[6 * _E - 5, 0]),
    "d128_tile_inside_the_first": dict(D=128, MB=64, valid=[100, 5, 127]),
    "d128_tile_edge": dict(D=128, MB=64, valid=[128, 256, 384, (1, 0, 0), (1, 0, 128)]),
    "d128_tile_edge_plus_one": dict(D=128, MB=64, valid=[129, 257, (1, 0, 129), (1, 0, 1)]),
    "d128_first_live_group_not_0": dict(
        D=128, MB=64, window=150, valid=[(1, 0, 200), (1, 0, 1), 40, (1, 0, 385)]),
    "d128_tile_k4_sink": dict(D=128, MB=64, K=4, sink=True, valid=[130, 128, (1, 0, 131)]),
    "d128_tile_8kv": dict(D=128, HKV=8, HQ=16, MB=32, valid=[129, 128, 1, (1, 0, 129)]),
    "d128_tile_16kv_bf16": dict(D=128, HKV=16, HQ=16, MB=32, dtype="bfloat16", valid=[129, 255, 256, 0, 3]),
    "d128_tile_int8": dict(D=128, MB=64, dtype="int8", valid=[129, 128, (1, 0, 1)]),
    # 32 query rows a KV head over groups of 512 tokens: 8 heads' scores are
    # more than SCORE_VREGS holds, so they are attended in two chunks of 4
    "d128_8kv_k4_heads_in_two_chunks": dict(
        D=128, HKV=8, HQ=64, K=4, MB=64, dtype="bfloat16", valid=[(1, 0, 5), 130, 0]),
    "d64_8kv_sink": dict(HKV=8, HQ=16, sink=True, valid=[6 * _E - 5, 0, 8 * _E]),
    "d64_bf16_dead_row": dict(dtype="bfloat16", valid=[0, 5 * _E]),
    "d64_int8_k4": dict(dtype="int8", K=4, valid=[6 * _E - 5, 3 * _E - 9]),
}


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_tkg_paged_parity(case):
    """Interpret mode against the native gather path. The block table's
    entries are a permutation (never consecutive) and zero past a row's last
    block; a dead row reads zeros from the kernel (the native softmax over an
    all-masked row is a mean over garbage: not compared)."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        read_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.modules.kvcache import QuantizedKV
    from neuronx_distributed_inference_tpu.ops.decode_attention import pages_per_step

    c = dict(K=1, sink=False, HQ=HQ, HKV=HKV, D=D, MB=8, dtype="float32", q_dtype="float32", window=None)
    c.update(PAGED_CASES[case])
    K, hq, hkv, d, MB = c["K"], c["HQ"], c["HKV"], c["D"], c["MB"]
    bs, layer = _E, 2
    P = pages_per_step(hkv, bs, d, c["dtype"], MB)
    limit = 2**20 // (hkv * bs * d * jnp.dtype(c["dtype"]).itemsize)  # 1 MiB a stream
    most = min(32, limit, MB)
    assert P == (1 if d == 64 else 1 << (most.bit_length() - 1))
    if case.endswith("heads_in_two_chunks"):
        from neuronx_distributed_inference_tpu.ops.decode_attention import SCORE_VREGS

        assert SCORE_VREGS // (hq // hkv * K // 8 * (P * bs // 128)) == hkv // 2
    valid = [
        v if isinstance(v, int) else v[0] * P * bs + v[1] * bs + v[2] for v in c["valid"]
    ]
    assert max(valid) <= MB * bs
    rng = np.random.RandomState(3 + K)
    B = len(valid)
    NB = sum(-(-v // bs) for v in valid) + 5
    q = _rand(rng, B, K, hq, d).astype(c["q_dtype"])
    shape = (L, NB + 1, hkv, bs, d)  # head-major paged layout
    if c["dtype"] == "int8":
        k_cache, v_cache = (
            QuantizedKV(
                data=jnp.asarray(rng.randint(-127, 128, size=shape), jnp.int8),
                scale=jnp.asarray(rng.uniform(0.5, 2.0, size=(L, hkv)), jnp.float32),
            )
            for _ in range(2)
        )
    else:
        k_cache, v_cache = (_rand(rng, *shape).astype(c["dtype"]) for _ in range(2))
    bt = np.zeros((B, MB), np.int32)
    pages = iter(rng.permutation(np.arange(1, NB + 1)))
    for b, v in enumerate(valid):
        n = -(-v // bs)
        bt[b, :n] = [next(pages) for _ in range(n)]
    block_table = jnp.asarray(bt)
    mask, pos = _decode_mask(rng, B, K, MB * bs, valid)
    if c["window"]:
        cols = jnp.arange(MB * bs)[None, None, None, :]
        mask = mask & (cols > jnp.asarray(pos)[:, None, :, None] - c["window"])
    sink_w = _rand(rng, hq) if c["sink"] else None

    spec = AttnSpec(num_heads=hq, num_kv_heads=hkv, head_dim=d, has_sink=c["sink"])
    k_r, v_r = read_block_cache_at_layer(k_cache, v_cache, jnp.int32(layer), block_table)
    ref = attention_decode(
        q.astype(jnp.float32), k_r.astype(jnp.float32), v_r.astype(jnp.float32),
        mask, spec, sink=sink_w,
    )
    out = paged_tkg_decode_attention(
        q, k_cache, v_cache, jnp.int32(layer), block_table, mask, sink_w,
        scale=spec.softmax_scale, n_kv=hkv, interpret=True,
    )
    assert out.dtype == q.dtype and out.shape == q.shape
    out = np.asarray(out.astype(jnp.float32))
    live = np.asarray(valid) > 0
    tol = dict(atol=2e-2, rtol=2e-2) if c["q_dtype"] == "bfloat16" else (
        dict(atol=2e-3, rtol=2e-4) if c["dtype"] == "int8" else dict(atol=2e-5, rtol=2e-5))
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], **tol)
    assert not out[~live].any()


def test_use_tkg_kernel_gates():
    spec = _spec(use_tkg_kernel=True)
    assert use_tkg_kernel(spec, 1, 512)
    assert use_tkg_kernel(spec, 1, 128)
    assert not use_tkg_kernel(spec, 32, 512)  # q too long
    assert not use_tkg_kernel(spec, 1, 96)  # non-tileable width
    off = _spec(use_tkg_kernel=False)
    assert not use_tkg_kernel(off, 1, 512)
    auto = _spec()
    # auto mode requires a real TPU backend
    assert use_tkg_kernel(auto, 1, 512) == (jax.default_backend() == "tpu")


def test_tkg_kernel_e2e_token_match():
    """generate() with the forced TKG kernel (interpret mode on CPU) matches
    the native decode path bit-for-bit on tokens and logits."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import make_tiny_config, make_random_hf_state_dict

    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    prompts = np.array([[5, 17, 92, 41, 33, 88, 2, 11], [64, 3, 27, 9, 0, 0, 0, 0]])
    mask = np.array([[1, 1, 1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 0, 0, 0, 0]])

    outs = {}
    for flag in (False, True):
        cfg = make_tiny_config(
            tpu=dict(
                seq_len=128,
                token_generation_buckets=[128],
                output_logits=True,
                attn_block_tkg_kernel_enabled=flag,
            )
        )
        sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=sd)
        outs[flag] = app.generate(prompts, mask, max_new_tokens=6)
    np.testing.assert_array_equal(outs[True].sequences, outs[False].sequences)
    np.testing.assert_allclose(
        outs[True].logits, outs[False].logits, atol=2e-5, rtol=2e-5
    )


def test_tkg_kernel_serving_paged_decode():
    """ServingSession block-KV decode with the forced paged TKG kernel matches
    the native gather path token-for-token (the serving path the kernel was
    built for — VERDICT r2 next #1)."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import make_tiny_config, make_random_hf_state_dict

    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    results = {}
    for flag in (False, True):
        cfg = make_tiny_config(
            tpu=dict(
                seq_len=128,
                token_generation_buckets=[128],
                is_continuous_batching=True,
                is_block_kv_layout=True,
                pa_block_size=16,
                pa_num_blocks=64,
                batch_size=2,
                ctx_batch_size=1,
                attn_block_tkg_kernel_enabled=flag,
            )
        )
        sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=sd)
        sess = ServingSession(app)
        assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=5)
        assert sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=5)
        results[flag] = sess.run_to_completion()
    assert results[True] == results[False]


@pytest.mark.parametrize("head_dim", [128, 32])
def test_decode_kv_block_counter_reads_what_the_host_knows(head_dim):
    """``nxdi_decode_kv_blocks_total``: per decode dispatch the pool blocks the
    decoding rows' contexts hold (``live``) and the block-table entries the
    paged kernel's kv axis attends for them (``walked``: whole groups of
    ``pages_per_step`` blocks up to a row's last live one; every entry of the
    table at a head_dim off the 128 lanes). Rows of KNOWN lengths: prompts of
    40 and 6 tokens in blocks of 16, so decode dispatch i has contexts of
    41 + i and 7 + i tokens."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import make_tiny_config, make_random_hf_state_dict

    from neuronx_distributed_inference_tpu.ops.decode_attention import pages_per_step
    from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    cfg = make_tiny_config(
        hidden_size=2 * head_dim, num_attention_heads=2, num_key_value_heads=1,
        tpu=dict(
            seq_len=128, token_generation_buckets=[128], is_continuous_batching=True,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=64, batch_size=2,
            ctx_batch_size=1, attn_block_tkg_kernel_enabled=True, async_mode=False,
        ),
    )
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=make_random_hf_state_dict(cfg))
    tel = TelemetrySession(enabled=True)
    sess = ServingSession(app, telemetry=tel)
    seen = []
    dispatch = sess._dispatch_decode
    sess._dispatch_decode = lambda rows, *a, **k: (
        seen.append([p + 1 for _, p in rows]), dispatch(rows, *a, **k))[1]
    assert sess.add_request("long", list(range(1, 41)), max_new_tokens=10)
    assert sess.add_request("short", [5, 17, 92, 41, 33, 88], max_new_tokens=5)
    while sess.active:  # step by step, as a server is driven: the split step
        sess.step()
    # the lengths are the known ones: both rows decode together, then one alone
    assert [41 + i for i in range(9)] == [c[0] for c in seen if c[0] > 40][:9]
    assert [7, 8, 9, 10] == sorted(n for c in seen for n in c if n < 40)

    MB, bs = 128 // 16, 16
    P = pages_per_step(1, bs, head_dim, cfg.tpu_config.kv_dtype, MB)
    assert P == (MB if head_dim == 128 else 1)
    blocks = [-(-n // bs) for c in seen for n in c]
    walked = [-(-n // P) * P if head_dim == 128 else MB for n in blocks]
    snap = tel.registry.snapshot()["nxdi_decode_kv_blocks_total"]["samples"]
    got = {x["labels"]["kind"]: x["value"] for x in snap}
    assert got == {"live": sum(blocks), "walked": sum(walked)}
    assert got["live"] == 3 * 8 + 4 * 1 + 1 * 4  # 41-48 tokens are 3 blocks, 49 is 4; 7-10 are 1


@pytest.mark.parametrize("shape", ["8kv_r8", "16kv_r8_bf16", "8kv_r32", "2kv_r16_int8_codes"])
def test_a_group_is_attended_stage_by_stage_as_head_by_head(shape, monkeypatch):
    """``_attend_group`` writes the KV heads' online-softmax updates stage by
    stage across as many heads as ``SCORE_VREGS`` admits at once (all 8 of the
    1.7B's over 512 tokens, 4 of 8 at 32 query rows). The values are those
    of the update written head by head (``SCORE_VREGS`` = 1, the order the
    kernel had), bit for bit, and both are the plain softmax's."""
    from neuronx_distributed_inference_tpu.ops import decode_attention as da

    n_kv, R, G, dtype, at_once = {
        "8kv_r8": (8, 8, 512, jnp.float32, 8),
        "16kv_r8_bf16": (16, 8, 256, jnp.bfloat16, 16),
        "8kv_r32": (8, 32, 512, jnp.float32, 4),
        "2kv_r16_int8_codes": (2, 16, 512, jnp.int8, 2),
    }[shape]
    assert min(n_kv, max(1, da.SCORE_VREGS // ((R // 8) * (G // 128)))) == at_once
    rng = np.random.RandomState(n_kv)
    q = _rand(rng, n_kv, R, 128)
    if dtype == jnp.int8:
        k, v = (jnp.asarray(rng.randint(-127, 128, size=(n_kv, G, 128)), jnp.int8) for _ in range(2))
    else:
        k, v = (_rand(rng, n_kv, G, 128).astype(dtype) for _ in range(2))
    mask = jnp.asarray(rng.rand(R, G) < 0.7).at[:, 0].set(True)
    # a carry as a second group meets it: statistics of an earlier group
    carry = tuple(
        (_rand(rng, R, 1), jnp.abs(_rand(rng, R, 1)) + 1.0, _rand(rng, R, 128)) for _ in range(n_kv)
    )
    kw = dict(scale=128**-0.5, q_dtype=jnp.float32)
    staged = da._attend_group(q, mask, k, v, carry, **kw)
    monkeypatch.setattr(da, "SCORE_VREGS", 1)
    by_head = da._attend_group(q, mask, k, v, carry, **kw)
    for got, want in zip(jax.tree_util.tree_leaves(staged), jax.tree_util.tree_leaves(by_head)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and from an empty carry the update is the masked softmax itself
    empty = tuple(
        (jnp.full((R, 1), da.NEG_INF), jnp.zeros((R, 1)), jnp.zeros((R, 128))) for _ in range(n_kv)
    )
    for h, (m, l, acc) in enumerate(da._attend_group(q, mask, k, v, empty, **kw)):
        s = jnp.where(mask, q[h] @ k[h].astype(jnp.float32).T * kw["scale"], -jnp.inf)
        want = jax.nn.softmax(s, axis=-1) @ v[h].astype(jnp.float32)
        tol = 2e-3 if dtype == jnp.int8 else 2e-5
        np.testing.assert_allclose(np.asarray(acc / l), np.asarray(want), atol=tol, rtol=tol)


# the in-kernel KV write: ONE new token a row, placed by the paged decode
# kernel in the block it holds for the row anyway (block_kvcache.write_form).
# ``valid`` = context tokens a row WITH the new token in (so the token sits at
# position valid - 1, offset (valid - 1) % 32 of its block); ``slots``: "tok" =
# that position's slot, an int = that slot whatever the context, -1 = dropped.
_W = 32  # a block's tokens here: offsets 0 / 15 / 16 / 31 are the tile's edges
FUSED_WRITE_CASES = {
    "offsets_0_15_16_31": dict(valid=[2 * _W + 1, _W + 16, 17, 2 * _W]),
    "row_opens_a_block": dict(valid=[_W + 1, 1, 3 * _W + 1]),
    "no_live_block_and_negative_slots": dict(
        valid=[0, 40, 0, 70], slots=[-1, -1, 3 * _W + 7, "tok"]),
    "token_not_in_the_last_block": dict(valid=[70, 40], slots=["tok", 5]),
    "slot_past_the_pool": dict(valid=[33, 20], slots=[10**6, "tok"]),
    "8kv_rep1": dict(HKV=8, HQ=8, valid=[3 * _W + 5, _W]),
    "16kv_rep1_bf16": dict(HKV=16, HQ=16, dtype="bfloat16", valid=[2 * _W + 16, 7, 0], slots=["tok", "tok", -1]),
    "2kv_rep5": dict(HKV=2, HQ=10, valid=[4 * _W - 1, 2 * _W + 1]),
    "8kv_rep2_bf16_q_bf16": dict(HKV=8, HQ=16, dtype="bfloat16", q_dtype="bfloat16", valid=[100, 64, 17]),
    "table_no_multiple_of_p": dict(MB=12, valid=[12 * _W, 9 * _W - 2, 3]),
    "second_and_third_group": dict(MB=24, valid=[8 * _W + 1, 17 * _W + 16, 24 * _W, 5]),
    "sink": dict(sink=True, valid=[2 * _W + 3, 0, _W], slots=["tok", -1, "tok"]),
    # a context that ends inside a group's first 128 tokens, at their edge,
    # and one token past it (the new token opens a score tile AND a block)
    "tile_edges": dict(valid=[100, 128, 129, 256]),
    "tile_edges_in_the_second_group": dict(MB=24, valid=[16 * _W + 129, 16 * _W + 128, 16 * _W + 1, 129]),
    "tile_edges_8kv": dict(HKV=8, HQ=16, valid=[129, 128, 3]),
    "tile_edges_16kv_bf16": dict(HKV=16, HQ=16, dtype="bfloat16", valid=[129, 128, 256, 0], slots=["tok", "tok", "tok", -1]),
}


@pytest.mark.parametrize("case", list(FUSED_WRITE_CASES))
def test_paged_kernel_places_the_new_token_as_write_then_attend_does(case):
    """The fused call (``new_kv``) against the per-head write followed by the
    same kernel: the attention output AND the whole pool afterwards, bit for
    bit (the tile written back carries what the pool held at its other 15
    rows). A row whose slot is negative or past the pool writes nothing; a
    token whose block is not the row's last live one is stored and not
    attended (the write-then-attend side attends nothing there either: the
    mask does not admit it)."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        update_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.ops.decode_attention import pages_per_step

    c = dict(sink=False, HQ=4, HKV=2, MB=8, dtype="float32", q_dtype="float32", slots=None)
    c.update(FUSED_WRITE_CASES[case])
    hq, hkv, MB, bs, d, layer = c["HQ"], c["HKV"], c["MB"], _W, 128, 1
    valid = c["valid"]
    want_slots = c["slots"] or ["tok"] * len(valid)
    rng = np.random.RandomState(len(case))
    B = len(valid)
    NB = sum(-(-v // bs) for v in valid) + 5
    P = pages_per_step(hkv, bs, d, c["dtype"], MB)
    if case == "table_no_multiple_of_p":
        assert MB % P
    q = _rand(rng, B, 1, hq, d).astype(c["q_dtype"])
    shape = (L, NB + 1, hkv, bs, d)
    k_cache, v_cache = (_rand(rng, *shape).astype(c["dtype"]) for _ in range(2))
    k_new, v_new = (_rand(rng, B, 1, hkv, d).astype(c["dtype"]) for _ in range(2))
    bt = np.zeros((B, MB), np.int32)
    pages = iter(rng.permutation(np.arange(1, NB + 1)))
    slots = np.full((B, 1), -1, np.int64)
    for b, v in enumerate(valid):
        n = -(-v // bs)
        bt[b, :n] = [next(pages) for _ in range(n)]
        if want_slots[b] == "tok":
            slots[b, 0] = bt[b, (v - 1) // bs] * bs + (v - 1) % bs
            # the contract of a decode pass: the token's block is the row's last live one
            assert slots[b, 0] // bs == bt[b, n - 1]
        else:
            slots[b, 0] = want_slots[b]
    block_table, slot_mapping = jnp.asarray(bt), jnp.asarray(slots, jnp.int32)
    mask, _ = _decode_mask(rng, B, 1, MB * bs, valid)
    sink_w = _rand(rng, hq) if c["sink"] else None
    spec = AttnSpec(num_heads=hq, num_kv_heads=hkv, head_dim=d, has_sink=c["sink"])
    kw = dict(scale=spec.softmax_scale, n_kv=hkv, interpret=True)
    li = jnp.int32(layer)

    k_want, v_want = update_block_cache_at_layer(k_cache, v_cache, k_new, v_new, li, slot_mapping)
    want = paged_tkg_decode_attention(q, k_want, v_want, li, block_table, mask, sink_w, **kw)
    got, k_got, v_got = paged_tkg_decode_attention(
        q, k_cache, v_cache, li, block_table, mask, sink_w, (k_new, v_new, slot_mapping), **kw
    )
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))
    for g, w, held in ((k_got, k_want, k_cache), (v_got, v_want, v_cache)):
        assert g.dtype == held.dtype
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32)))
        # and the write is the rows' tokens and nothing else: one row a kept slot a head
        kept = [s for s in slots[:, 0] if 0 <= s < (NB + 1) * bs]
        changed = np.asarray((g != held).any(axis=-1))  # (L, NB+1, Hkv, bs)
        assert changed.sum() == len(kept) * hkv and not changed[[0, 2]].any()


@pytest.mark.parametrize("heads,kv_heads", [(2, 1), (4, 2)], ids=["head_dim_128", "head_dim_64_two_a_row"])
def test_serving_decode_writes_in_kernel_and_commits_the_same_tokens(monkeypatch, heads, kv_heads):
    """A short closed loop on the paged cache at head_dim 128, and at head_dim
    64 over a pool of two heads a 128-lane row (``block_kvcache.kv_streams``),
    with the paged
    decode kernel forced on (interpret mode): every decode row's KV write is
    the kernel's (``nxdi_decode_kv_write_rows_total{form="kernel"}``; no
    scatter is traced in the decode program), and the tokens are those of the
    same loop with the decision patched off (the per-head scatter, then the
    same kernel)."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import make_tiny_config, make_random_hf_state_dict

    from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
    from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    def loop(tel):
        cfg = make_tiny_config(
            hidden_size=256, num_attention_heads=heads, num_key_value_heads=kv_heads,
            tpu=dict(
                seq_len=128, token_generation_buckets=[128], is_continuous_batching=True,
                is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=64, batch_size=3,
                ctx_batch_size=1, attn_block_tkg_kernel_enabled=True,
            ),
        )
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=make_random_hf_state_dict(cfg))
        assert app.kv_cache.k.shape[2:] == (1, 16, 128)
        sess = ServingSession(app, telemetry=tel)
        assert sess.add_request("long", list(range(1, 31)), max_new_tokens=6)
        assert sess.add_request("short", [5, 17, 92, 41, 33, 88], max_new_tokens=12)
        return sess.run_to_completion()

    def rows_by_form(tel):
        samples = tel.registry.snapshot()["nxdi_decode_kv_write_rows_total"]["samples"]
        return {x["labels"]["form"]: x["value"] for x in samples if x["value"]}

    scatters = []
    per_head = bk._scatter_per_head
    monkeypatch.setattr(
        bk, "_scatter_per_head", lambda data, rows, *a: (scatters.append(rows.shape), per_head(data, rows, *a))[1]
    )
    jax.clear_caches()
    tel = TelemetrySession(enabled=True)
    fused = loop(tel)
    assert all(len(v) > 0 for v in fused.values())
    counted = rows_by_form(tel)
    assert set(counted) == {"kernel"} and counted["kernel"] >= 12
    assert scatters == []  # the prompts go in whole blocks, the decode rows in the kernel

    form = bk.write_form
    monkeypatch.setattr(bk, "write_form", lambda *a, **kw: form(*a, **{**kw, "kernel_runs": False}))
    jax.clear_caches()
    scatters.clear()
    tel = TelemetrySession(enabled=True)
    assert loop(tel) == fused
    assert rows_by_form(tel) == {"per_head": counted["kernel"]}
    assert {shape[0] for shape in scatters} == {3}  # the decode program's 3 rows x 1
