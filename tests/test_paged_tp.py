"""The paged path of the SPLIT serving step at tp > 1 (ISSUE 33): the per-head
KV write and the two paged read kernels run once per head shard
(parallel/sharding.shard_over_heads), as the ragged kernel already did
(tests/test_ragged_tp.py).

The acceptance pins, on a model_parallel=2 virtual CPU mesh (kernels in
interpret mode: the identical per-shard math the chip compiles):
- the decode dispatch and the 8-row chunk dispatch launch
  ``paged_tkg_decode_attention`` / ``paged_flash_attention`` on HALF the
  heads each, and the native gather never fires;
- greedy streams: tp=2 kernel == tp=2 native == tp=1, plain and int8-KV;
- zero steady-state recompiles at tp=2;
- the KV write itself: per-head form per shard at decode widths and wherever
  a shard holds fewer than 8 heads, window form for chunks over 8 heads a
  shard, both bit-identical to the unsharded write.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.ops.kernel_mode import CHUNK_ROWS
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

PROMPTS = {
    "r1": [5, 17, 92, 41],
    "r2": list(range(30, 52)),  # 22 tokens: two chunk passes
    "r3": [7, 7, 7],
}
KERNELS = dict(attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)
Q_HEADS, KV_HEADS = 4, 2
SLOTS = 12  # more than CHUNK_ROWS: the decode program 12 rows, the chunk program 8


def _cfg(tp=1, **extra):
    tpu = dict(
        is_continuous_batching=True, batch_size=SLOTS, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=40,
        is_chunked_prefill=True, enable_bucketing=True,
        context_encoding_buckets=[128], token_generation_buckets=[128],
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=SLOTS, kernel_q_tile_size=16
        ),
        seq_len=128,
    )
    tpu.update(extra)
    # head_dim 64 (lane-aligned, what the kernels' gates ask): 256 over 4 q
    # heads / 2 kv heads, both divide tp=2; kv bucket 128 = one forced tile
    cfg = make_tiny_config(hidden_size=256, intermediate_size=512, tpu=tpu)
    cfg.tpu_config.tp_degree = tp
    return cfg


@pytest.fixture(scope="module")
def state_dict():
    return make_random_hf_state_dict(_cfg())


def _load(cfg, sd):
    return TpuModelForCausalLM(None, cfg).load(state_dict=sd)


def _standard_mix(app):
    app.init_kv_cache()
    sess = ServingSession(app)
    assert sess.add_request("r1", PROMPTS["r1"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r2", PROMPTS["r2"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r3", PROMPTS["r3"], max_new_tokens=5)
    return sess.run_to_completion()


@pytest.mark.parametrize("extra", [{}, {"kv_cache_dtype": "int8"}],
                         ids=["plain", "kv_int8"])
def test_tp2_kernels_match_native_and_tp1(state_dict, extra):
    """Forced kernels (the auto gates ask for a TPU backend) at tp=2 against
    the native gather at tp=2 and at tp=1: byte-identical greedy streams.
    The tp=2 native run already takes the per-shard per-head KV write."""
    out_tp1 = _standard_mix(_load(_cfg(1, **extra), state_dict))
    out_tp2_native = _standard_mix(_load(_cfg(2, **extra), state_dict))
    out_tp2_kernel = _standard_mix(_load(_cfg(2, **KERNELS, **extra), state_dict))
    assert all(len(v) > 0 for v in out_tp1.values())
    assert out_tp2_native == out_tp1
    assert out_tp2_kernel == out_tp1


def test_tp2_both_dispatches_launch_their_kernel_per_shard(state_dict, monkeypatch):
    from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
    from neuronx_distributed_inference_tpu.ops import decode_attention as da
    from neuronx_distributed_inference_tpu.ops import paged_flash_attention as pf

    seen = {"decode": set(), "chunk": set(), "native": 0}
    tkg, flash, gather = (
        da.paged_tkg_decode_attention, pf.paged_flash_attention,
        bk.read_block_cache_at_layer,
    )

    def counting_tkg(q, k_cache, *a, **kw):
        seen["decode"].add((q.shape[0], q.shape[2], k_cache.shape[2], kw["n_kv"]))
        return tkg(q, k_cache, *a, **kw)

    def counting_flash(q, k_pool, *a, **kw):
        # the stacked pool (L, NB+1, Hkv, bs, D): the kv heads a launch holds
        seen["chunk"].add((q.shape[0], q.shape[2], k_pool.shape[2]))
        return flash(q, k_pool, *a, **kw)

    def counting_gather(*a, **kw):
        seen["native"] += 1
        return gather(*a, **kw)

    monkeypatch.setattr(da, "paged_tkg_decode_attention", counting_tkg)
    monkeypatch.setattr(pf, "paged_flash_attention", counting_flash)
    monkeypatch.setattr(bk, "read_block_cache_at_layer", counting_gather)
    # the jit cache is process-global and earlier tests compiled these exact
    # programs: drop it so both step programs TRACE inside the patch
    jax.clear_caches()
    out = _standard_mix(_load(_cfg(2, **KERNELS), state_dict))
    assert all(len(v) > 0 for v in out.values())
    # every launch saw ONE shard's heads: 2 of 4 q heads, 1 of 2 kv heads
    assert seen["decode"] == {(SLOTS, Q_HEADS // 2, KV_HEADS // 2, KV_HEADS // 2)}
    assert seen["chunk"] == {(CHUNK_ROWS, Q_HEADS // 2, KV_HEADS // 2)}
    assert seen["native"] == 0  # the gather fallback never fired


def test_tp2_zero_steady_state_recompiles(state_dict):
    from neuronx_distributed_inference_tpu.analysis import RetraceGuard

    app = _load(_cfg(2, **KERNELS), state_dict)
    golden = _standard_mix(app)  # warm the mix
    with RetraceGuard() as guard:
        out = _standard_mix(app)
    assert out == golden
    assert guard.traces == []


# (S, H, packed) -> the form at a head a pool row: a head_dim that fills no
# 128-lane row with whole heads (24), and any quantised pool
HEAD_A_ROW_FORMS = {
    (1, 16, False): "per_head",  # decode
    (4, 16, False): "per_head",  # speculation width
    (32, 16, False): "window",  # a chunk, 8 heads a shard: a full tile
    (32, 4, False): "per_head",  # a chunk, 2 heads a shard (14B at tp=4)
    (16, 16, True): "window",  # the mixed step's packed axis
}
# head_dim 16, bfloat16: eight heads a row, (2, 128) for 16 heads and one pool
# head a shard (4 heads fill no row): the chunk is on the lanes and moves whole
# blocks, the packed axis has one head a shard, under the tile
EIGHT_A_ROW_FORMS = {**HEAD_A_ROW_FORMS, (32, 16, False): "blocks", (16, 16, True): "per_head"}


@pytest.mark.parametrize(
    "S,H,packed,D,form",
    [(*case, 24, form) for case, form in HEAD_A_ROW_FORMS.items()]
    + [(*case, 16, form) for case, form in EIGHT_A_ROW_FORMS.items()],
)
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_kv_write_under_a_sharded_head_axis(S, H, packed, D, form, quantized):
    """``update_block_cache_at_layer`` on a tp=2 mesh: at decode and
    speculation widths, and wherever a shard holds fewer heads than the
    tile has sublanes, the per-head scatter runs inside ``shard_map`` (each
    shard its own heads), and so does the block form of a pool whose rows
    hold eight heads of 16 (``kv_streams``); a chunk over 8 heads a shard
    keeps the window form under GSPMD; either way the pool comes out bit for
    bit what the unsharded write leaves."""
    from jax.sharding import NamedSharding

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        block_cache_spec,
        init_block_cache,
        update_block_cache_at_layer,
        write_form,
    )
    from neuronx_distributed_inference_tpu.parallel.mesh import build_mesh

    L, NB, bs, B = 2, 16, 8, 3
    cache = init_block_cache(L, NB, bs, H, D, dtype=jnp.int8 if quantized else jnp.bfloat16)
    pool = cache.k.data if quantized else cache.k
    folded = D == 16 and H == 16 and not quantized
    assert pool.shape[2:] == ((H // 8, bs, 128) if folded else (H, bs, D))
    if quantized:  # keeps a head a row whatever the head_dim
        form = HEAD_A_ROW_FORMS[S, H, packed]
    assert write_form(S, pool.shape[4], pool.shape[2] // 2, packed=packed, quantised=quantized) == form
    rng = np.random.default_rng(33)
    k_new = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    if form == "blocks":
        # the block form's rows: a prefix of valid slots at consecutive positions
        blocks = rng.permutation(np.arange(1, NB + 1))[: B * 5].reshape(B, 5)
        t = 3 + np.arange(S)
        slots = np.take_along_axis(blocks, np.broadcast_to(t // bs, (B, S)), axis=1) * bs + t % bs
        slots[np.arange(S)[None, :] >= S - np.arange(B)[:, None]] = -1  # a dropped tail
    else:
        slots = rng.permutation(np.arange(bs, (NB + 1) * bs))[: B * S].reshape(B, S)
        slots[0, 0] = -1  # a dropped token
    slots = jnp.asarray(slots, jnp.int32)

    def write(k, v, kn, vn, sm):
        return update_block_cache_at_layer(k, v, kn, vn, jnp.int32(1), sm, packed=packed)

    want = jax.jit(write)(cache.k, cache.v, k_new, v_new, slots)

    mesh = build_mesh(tp_degree=2, devices=jax.devices()[:2])
    specs = block_cache_spec(quantized)
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))  # noqa: E731
    k_sh, v_sh = jax.tree.map(put, cache.k, specs.k), jax.tree.map(put, cache.v, specs.v)
    with jax.set_mesh(mesh):
        got = jax.jit(write)(k_sh, v_sh, k_new, v_new, slots)
        jaxpr = str(jax.make_jaxpr(write)(k_sh, v_sh, k_new, v_new, slots))
    assert ("shard_map" in jaxpr) == (form in ("per_head", "blocks"))
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    assert got[0].data.sharding.spec == specs.k.data if quantized else (
        got[0].sharding.spec == specs.k
    )


@pytest.mark.parametrize("tp", [2, 4])
def test_paged_kernel_places_the_token_per_head_shard(tp):
    """The in-kernel KV write on a head-sharded mesh
    (``dispatch_paged_tkg_decode`` with ``new_kv``): each shard's kernel
    places its own heads of the row's one token, the two pools come back in
    the layout the layer scan carries them (``block_cache_spec``), no
    collective is compiled, and output and WHOLE pool are bit for bit what
    the unsharded write-then-attend leaves."""
    from jax.sharding import NamedSharding

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        block_cache_spec,
        update_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.ops.decode_attention import (
        dispatch_paged_tkg_decode,
        paged_tkg_decode_attention,
    )
    from neuronx_distributed_inference_tpu.parallel.mesh import build_mesh

    L, NB, bs, D, B, MB, hq, hkv = 2, 12, 16, 128, 4, 4, 8, 4
    rng = np.random.default_rng(52)
    k_pool, v_pool = (
        jnp.asarray(rng.standard_normal((L, NB + 1, hkv, bs, D)), jnp.bfloat16) for _ in range(2)
    )
    q = jnp.asarray(rng.standard_normal((B, 1, hq, D)) * 0.3, jnp.bfloat16)
    k_new, v_new = (jnp.asarray(rng.standard_normal((B, 1, hkv, D)), jnp.bfloat16) for _ in range(2))
    valid = [3 * bs + 1, bs, 0, 2 * bs + 9]  # offsets 0 and 15, a dead row, mid-tile
    bt = np.zeros((B, MB), np.int32)
    pages = iter(rng.permutation(np.arange(1, NB + 1)))
    slots = np.full((B, 1), -1, np.int32)
    for b, n in enumerate(valid):
        bt[b, : -(-n // bs)] = [next(pages) for _ in range(-(-n // bs))]
        if n:
            slots[b, 0] = bt[b, (n - 1) // bs] * bs + (n - 1) % bs
    mask = jnp.asarray(np.arange(MB * bs)[None, :] < np.asarray(valid)[:, None])[:, None, None, :]
    bt, slots, li = jnp.asarray(bt), jnp.asarray(slots), jnp.int32(1)
    kw = dict(scale=D**-0.5, interpret=True)

    k_want, v_want = update_block_cache_at_layer(k_pool, v_pool, k_new, v_new, li, slots)
    want = paged_tkg_decode_attention(q, k_want, v_want, li, bt, mask, n_kv=hkv, **kw)

    def fused(q, k, v, kn, vn):
        return dispatch_paged_tkg_decode(q, k, v, li, bt, mask, None, (kn, vn, slots), **kw)

    mesh = build_mesh(tp_degree=tp, devices=jax.devices()[:tp])
    spec = block_cache_spec()
    k_sh, v_sh = (jax.device_put(x, NamedSharding(mesh, spec.k)) for x in (k_pool, v_pool))
    with jax.set_mesh(mesh):
        got, k_got, v_got = jax.jit(fused)(q, k_sh, v_sh, k_new, v_new)
        assert "shard_map" in str(jax.make_jaxpr(fused)(q, k_sh, v_sh, k_new, v_new))
        hlo = jax.jit(fused).lower(q, k_sh, v_sh, k_new, v_new).compile().as_text()
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter"):
        assert op not in hlo, op
    for pool in (k_got, v_got):  # each chip keeps its own heads, as the scan carries them
        assert pool.sharding.is_equivalent_to(NamedSharding(mesh, spec.k), pool.ndim)
    for g, w in ((got, want), (k_got, k_want), (v_got, v_want)):
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        )
