"""Paged (block) KV cache tests
(reference: block_kv_cache_manager tests; vLLM slot-mapping semantics)."""

import numpy as np
import pytest

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.modules.block_kvcache import BlockAllocator
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession


def test_allocator_lifecycle():
    a = BlockAllocator(num_blocks=8, block_size=4)
    blocks = a.alloc_seq(0, 10)  # 3 blocks
    assert len(blocks) == 3 and 0 not in blocks
    assert len(a.free) == 5
    sm = a.slot_mapping(0, [0, 4, 9])
    assert sm[0] == blocks[0] * 4
    assert sm[1] == blocks[1] * 4
    assert sm[2] == blocks[2] * 4 + 1
    a.free_seq(0)
    assert len(a.free) == 8
    with pytest.raises(RuntimeError):
        a.alloc_seq(1, 100)  # too many tokens


def test_update_drops_negative_slots():
    """Invalid (-1) slots must write NOWHERE — in particular not wrap to the
    LAST block (jnp negative-index normalization happens before mode=\"drop\",
    so a naive -1 block index corrupts a real allocatable block)."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
        update_block_cache_at_layer,
    )

    L, NB, bs, H, D = 2, 4, 4, 2, 8
    cache = init_block_cache(L, NB, bs, H, D, dtype=jnp.float32)
    k0 = np.asarray(cache.k)
    # one valid slot (block 2, off 1) + one invalid (-1) per row
    slot_mapping = jnp.asarray([[2 * bs + 1, -1]], jnp.int32)
    k_new = jnp.ones((1, 2, H, D), jnp.float32)
    k_up, v_up = update_block_cache_at_layer(
        cache.k, cache.v, k_new, k_new, jnp.int32(0), slot_mapping
    )
    k_up = np.array(k_up)
    assert (k_up[0, 2, :, 1] == 1.0).all()  # valid slot written
    k_up[0, 2, :, 1] = k0[0, 2, :, 1]
    np.testing.assert_array_equal(k_up, k0)  # NOTHING else (esp. last block)


def _with_junk(cache, rng):
    """The cache with junk in every stream (and plausible running scales)."""
    import jax
    import jax.numpy as jnp

    def junk(x):
        if x.ndim == 2:  # (L, H) running scales
            return jnp.asarray(rng.uniform(0.5, 1.5, x.shape), x.dtype)
        if jnp.issubdtype(x.dtype, jnp.integer):
            return jnp.asarray(rng.integers(-100, 100, x.shape), x.dtype)
        return jnp.asarray(rng.normal(size=x.shape), jnp.float32).astype(x.dtype)

    return jax.tree.map(junk, cache)


def _write_case(dtype, S, seed=0):
    """A 3-layer cache with junk in it, a (B, S) write whose slots cover every
    kind: real blocks, the garbage block (slot >= 0 inside block 0), negative
    (dropped), and the LAST block's last offset (where a wrapped -1 would
    land)."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
    )
    L, NB, bs, H, D, B = 3, 12, 32, 4, 16, 3
    rng = np.random.default_rng(seed)
    cache = _with_junk(init_block_cache(L, NB, bs, H, D, dtype=dtype), rng)
    slots = np.full((B, S), -1, np.int64)
    n = max(1, (3 * S) // 4)  # the tail of every row stays negative
    slots[0, :n] = 2 * bs + 5 + np.arange(n)  # real blocks, crossing borders
    slots[1, :n] = np.arange(n) % bs  # idle row: INTO the garbage block
    slots[2, :n] = (NB + 1) * bs - 1 - np.arange(n)  # down from the last slot
    if S > bs:  # keep garbage-block offsets distinct (scatter order is free)
        slots[1, bs:] = -1
    k_new = jnp.asarray(rng.normal(size=(B, S, H, D)) * 2.0, jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.5, jnp.float32)
    return cache, jnp.asarray(slots, jnp.int32), k_new, v_new


def _loop_write(data, rows, layer, slots):
    """The plain reference: one assignment per (layer, block, head, offset)."""
    out = np.array(data)
    rows = np.asarray(rows)
    bs = out.shape[3]
    B, S = slots.shape
    for b in range(B):
        for s in range(S):
            slot = int(slots[b, s])
            if slot < 0:
                continue
            for h in range(out.shape[2]):
                out[layer, slot // bs, h, slot % bs] = rows[b, s, h]
    return out


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _chunk_write_case(dtype, S, H, seed=0):
    """A chunk pass as the serving path builds it, at the pool's real block
    shape (blocks of 32 x 128 lanes): 8 rows of ``S`` tokens, each row the
    consecutive positions ``start .. start + n - 1`` of its own sequence
    whose blocks lie anywhere in the pool, the tail of the row negative.
    The rows cover: a whole chunk from a block boundary; a start 8 tokens
    into a block that earlier tokens part filled, ending mid-block; one
    token at a block's last offset; ``n`` = 0; an idle row INTO the garbage
    block; a whole chunk from an offset (one block more than ``S / bs``);
    100 tokens from a boundary; a row that ends on the pool's last slot."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
    )
    L, NB, bs, D, B = 3, 40, 32, 128, 8
    rng = np.random.default_rng(seed)
    cache = _with_junk(init_block_cache(L, NB, bs, H, D, dtype=dtype), rng)
    free = list(rng.permutation(np.arange(1, NB)))  # block NB is row 7's last
    slots = np.full((B, S), -1, np.int64)
    rows = {0: (0, S), 1: (40, min(100, S)), 2: (95, 1), 3: (64, 0),
            5: (17, S), 6: (32, min(100, S)), 7: (5 * bs - min(40, S), min(40, S))}
    for row, (start, n) in rows.items():
        blocks = [free.pop() for _ in range(-(-(start + n) // bs))]
        if row == 7:
            blocks[-1] = NB
        pos = start + np.arange(n)
        slots[row, :n] = np.asarray(blocks, np.int64)[pos // bs] * bs + pos % bs
    slots[4, : min(S, bs)] = np.arange(min(S, bs))  # block 0, each offset once
    k_new = jnp.asarray(rng.normal(size=(B, S, H, D)) * 2.0, jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.5, jnp.float32)
    return cache, jnp.asarray(slots, jnp.int32), k_new, v_new


WRITE_CASES = {
    "decode_S1": lambda dt: _write_case(dt, 1),
    "chunk_S128": lambda dt: _write_case(dt, 128),
    "blocks_8x128_H8": lambda dt: _chunk_write_case(dt, 128, 8),
    "blocks_8x32_H8": lambda dt: _chunk_write_case(dt, 32, 8),
    "blocks_8x128_H4": lambda dt: _chunk_write_case(dt, 128, 4),
    "blocks_8x128_H2": lambda dt: _chunk_write_case(dt, 128, 2),
}


@pytest.mark.parametrize("case", list(WRITE_CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "fp8"])
def test_paged_write_matches_plain_loop(dtype, case):
    """Every form of update_block_cache_at_layer against a NumPy loop, the
    WHOLE pool BIT-equal (per-head scatter at S=1, token window at S=128 off
    the lanes, whole blocks at chunk widths on them): real slots,
    garbage-block slots, negative slots dropped, untouched offsets of a
    part-written block and the last block intact. A quantized cache's codes
    are placed the same way and its scales are exactly what the shared
    quantizer returns — the write never touches them."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.config import to_dtype
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        GARBAGE_BLOCK,
        update_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.modules.kvcache import (
        QuantizedKV,
        _quantized_update,
    )

    cache, slots, k_new, v_new = WRITE_CASES[case](to_dtype(dtype))
    layer = 1
    k_up, v_up = update_block_cache_at_layer(
        cache.k, cache.v, k_new, v_new, jnp.int32(layer), slots
    )
    sl = np.asarray(slots)
    for stream, new, up in ((cache.k, k_new, k_up), (cache.v, v_new, v_up)):
        if isinstance(stream, QuantizedKV):
            bs = stream.data.shape[3]
            valid = (slots >= 0) & (slots // bs != GARBAGE_BLOCK)
            codes, scale = _quantized_update(stream, new, jnp.int32(layer), valid)
            np.testing.assert_array_equal(np.asarray(up.scale), np.asarray(scale))
            # layers the write does not address keep their scale
            np.testing.assert_array_equal(
                np.asarray(up.scale)[[0, 2]], np.asarray(stream.scale)[[0, 2]]
            )
            want, got = _loop_write(stream.data, codes, layer, sl), up.data
        else:
            want, got = _loop_write(stream, new.astype(stream.dtype), layer, sl), up
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("case", ["decode_S1", "chunk_S128", "blocks_8x128_H8"])
def test_paged_write_on_head_sharded_cache_has_no_collective(case):
    """tp=4 with the cache head-sharded (block_cache_spec): the compiled
    write holds NO collective in any form — each shard writes its own heads
    (per-head and whole-block forms run per shard; with the head in a
    scatter's indices GSPMD gathers the updates) — and writes what the loop
    writes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        block_cache_spec,
        update_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.parallel.mesh import MODEL_AXES, build_mesh

    cache, slots, k_new, v_new = WRITE_CASES[case](jnp.bfloat16)
    mesh = build_mesh(tp_degree=4)
    spec = block_cache_spec()
    cache_sh = NamedSharding(mesh, spec.k)
    new_sh = NamedSharding(mesh, P(None, None, MODEL_AXES, None))
    rep = NamedSharding(mesh, P())
    fn = jax.jit(
        update_block_cache_at_layer,
        in_shardings=(cache_sh, cache_sh, new_sh, new_sh, rep, rep),
        out_shardings=(cache_sh, cache_sh),
    )
    args = (cache.k, cache.v, k_new, v_new, jnp.int32(1), slots)
    with jax.set_mesh(mesh):
        hlo = fn.lower(*args).compile().as_text()
        k_up, _ = fn(*args)
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert op not in hlo, op
    want = _loop_write(cache.k, k_new.astype(jnp.bfloat16), 1, np.asarray(slots))
    np.testing.assert_array_equal(_bits(k_up), _bits(want))


# (q_len, head_dim, heads a device, what else the call shows) -> the form
WRITE_FORM_TABLE = {
    "decode_on_the_kernel": ((1, 128, 8, dict(kernel_runs=True)), "kernel"),
    "decode_2_heads_a_device": ((1, 128, 2, dict(kernel_runs=True)), "kernel"),
    "kernels_off": ((1, 128, 8, dict(kernel_runs=False)), "per_head"),
    "block_step_S4": ((4, 128, 4, dict(kernel_runs=True)), "per_head"),
    "speculation_S16": ((16, 128, 8, dict(kernel_runs=True)), "per_head"),
    "head_dim_64": ((1, 64, 8, dict(kernel_runs=True)), "per_head"),
    "quantised": ((1, 128, 8, dict(kernel_runs=True, quantised=True)), "per_head"),
    "packed": ((1, 128, 8, dict(kernel_runs=True, packed=True)), "window"),
    "packed_few_heads": ((1, 128, 2, dict(kernel_runs=True, packed=True)), "per_head"),
    "batch_sharded": ((1, 128, 8, dict(kernel_runs=True, batch_sharded=True)), "window"),
    "chunk_S128": ((128, 128, 8, dict(kernel_runs=False)), "blocks"),
    "chunk_S128_2_heads": ((128, 128, 2, dict()), "blocks"),
    "chunk_head_dim_64": ((128, 64, 8, dict()), "window"),
    "chunk_head_dim_64_few_heads": ((128, 64, 4, dict()), "per_head"),
    "chunk_batch_sharded": ((128, 128, 8, dict(batch_sharded=True)), "window"),
}


@pytest.mark.parametrize("case", list(WRITE_FORM_TABLE))
def test_write_form_is_decided_by_what_a_call_shows(case):
    """``block_kvcache.write_form``: the one decision among the four forms of
    the paged KV write. ``kernel`` only for one token a row at a head_dim on
    the 128 lanes, an unquantised pool, no packed axis, no sharded batch, and
    an attention that IS the paged decode kernel; every other call keeps the
    form it had (``takes_block_form`` and the writer read the same table)."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        WRITE_FORMS,
        takes_block_form,
        write_form,
    )

    (q_len, head_dim, heads, shows), want = WRITE_FORM_TABLE[case]
    got = write_form(q_len, head_dim, heads, **shows)
    assert got == want and got in WRITE_FORMS
    shows = {k: v for k, v in shows.items() if k in ("packed", "batch_sharded")}
    assert (got == "blocks") == takes_block_form(q_len, head_dim, **shows)


def test_serving_rows_are_consecutive_positions_of_one_sequence(monkeypatch):
    """The contract of the paged write's block form
    (update_block_cache_at_layer): every row a ServingSession hands a step
    program wider than a decode step — a chunk pass (``_prefill_chunks``,
    prefix reuse included) and the whole-prompt paged prefill
    (``_full_prefill``) — has its valid slots as a PREFIX of the row, at
    consecutive positions of one sequence: slot ``block * bs + position %
    bs``, the block constant inside a block of positions."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.runtime.model_runner import SubModelRunner

    seen = []
    prepare_host = SubModelRunner.prepare_host  # the host half of prepare(): every pass builds its rows here

    def spy(self, *args, **kwargs):
        arrs, batch = prepare_host(self, *args, **kwargs)
        slots = arrs.get("slot_mapping")
        if slots is not None and slots.shape[1] > 1:
            seen.append((np.asarray(arrs["position_ids"]), np.asarray(slots)))
        return arrs, batch

    monkeypatch.setattr(SubModelRunner, "prepare_host", spy)
    bs = 16
    paged = dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                 is_block_kv_layout=True, pa_block_size=bs, pa_num_blocks=24)
    chunked = dict(paged, is_chunked_prefill=True, is_prefix_caching=True, seq_len=128,
                   chunked_prefill_config=ChunkedPrefillConfig(
                       max_num_seqs=2, kernel_q_tile_size=32))
    long_prompt = [(7 * i) % 90 + 3 for i in range(75)]
    widths = []
    for tpu, prompts in ((chunked, [long_prompt, long_prompt[:24] + [5, 6, 7] * 9]),
                         (paged, [long_prompt[:41]])):
        cfg = make_tiny_config(tpu=tpu)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=make_random_hf_state_dict(cfg))
        sess = ServingSession(app)
        for i, prompt in enumerate(prompts):
            assert sess.add_request(f"r{i}", prompt, max_new_tokens=2)
            sess.run_to_completion()
        assert seen
        for positions, slots in seen:
            widths.append(slots.shape[1])
            for pos, row in zip(positions, slots):
                n = int((row >= 0).sum())
                assert (row[:n] >= 0).all() and (row[n:] < 0).all()  # a prefix
                np.testing.assert_array_equal(pos[:n], pos[0] + np.arange(n))
                np.testing.assert_array_equal(row[:n] % bs, pos[:n] % bs)
                blocks = dict(zip(pos[:n] // bs, row[:n] // bs))  # one block a block of positions
                np.testing.assert_array_equal(row[:n] // bs, [blocks[p // bs] for p in pos[:n]])
        seen.clear()
    # chunk passes of 32 (the second prompt resumes at 16, past the block it
    # shares with the first) and a context program over the whole prompt
    assert 32 in widths and max(widths) >= 64


def test_forward_refuses_rows_the_block_form_cannot_write():
    """A slot mapping handed to the public ``forward`` at a width the write
    takes a block at a time (more than 16 tokens a row, head_dim on the 128
    lanes) is checked on the host: a hole in a row, slots out of order or a
    block that changes between two block boundaries raise ``ValueError``
    before anything is dispatched; the rows the serving path builds pass."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        check_block_form_rows,
    )

    _, slots, _, _ = _chunk_write_case("bfloat16", 128, 2)
    slots = np.asarray(slots)
    check_block_form_rows(slots, 32)
    hole, swapped, moved = slots.copy(), slots.copy(), slots.copy()
    hole[0, 3] = -1
    swapped[0, [1, 2]] = swapped[0, [2, 1]]
    moved[5, 20] += 3 * 32  # row 5 starts 17 into a block: token 15 opened this one
    for bad in (hole, swapped, moved):
        with pytest.raises(ValueError, match="consecutive positions"):
            check_block_form_rows(bad, 32)

    cfg = make_tiny_config(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                 is_block_kv_layout=True, pa_block_size=32, pa_num_blocks=8, seq_len=128),
    )
    app = TpuModelForCausalLM(None, cfg).load(state_dict=make_random_hf_state_dict(cfg))
    ids = np.arange(1, 41, dtype=np.int32)[None, :]
    pos = np.arange(40, dtype=np.int32)[None, :]
    sm = 32 + np.arange(40, dtype=np.int32)[None, :]
    sm[0, 7] = -1
    with pytest.raises(ValueError, match="consecutive positions"):
        app.forward(ids, pos, np.zeros(1, np.int32), slot_mapping=sm, phase="cte")


def _session_apps():
    sd = None
    apps = []
    for block in (False, True):
        tpu = dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
        if block:
            tpu.update(is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16)
        cfg = make_tiny_config(tpu=tpu)
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=sd)
        apps.append(app)
    return apps


def test_block_serving_matches_contiguous():
    """Block-KV serving must produce the same tokens as contiguous-cache
    serving (identical math, different memory layout)."""
    contiguous, block = _session_apps()

    prompts = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33]}
    results = {}
    for name, app in (("contiguous", contiguous), ("block", block)):
        sess = ServingSession(app)
        for rid, p in prompts.items():
            assert sess.add_request(rid, p, max_new_tokens=8)
        results[name] = sess.run_to_completion()

    for rid in prompts:
        assert results["contiguous"][rid] == results["block"][rid], rid


def test_block_kv_warmup_compiles():
    """compile()/warmup() must work in block-KV mode (regression: warmup
    example inputs previously lacked slot_mapping/block_table)."""
    tpu = dict(
        is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16,
    )
    cfg = make_tiny_config(tpu=tpu)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=make_random_hf_state_dict(cfg))
    app.warmup()  # must not raise


def test_block_kv_bucket_not_multiple_of_block_size():
    """TKG buckets are rounded up to the block size (regression: seq_len=40
    with bs=16 produced mismatched gather/mask widths)."""
    tpu = dict(
        is_continuous_batching=True, batch_size=1, ctx_batch_size=1, seq_len=40,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=8,
    )
    cfg = make_tiny_config(tpu=tpu)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=make_random_hf_state_dict(cfg))
    assert all(b % 16 == 0 for b in app.token_generation_model.buckets)
    sess = ServingSession(app)
    assert sess.add_request("r", [1, 2, 3], max_new_tokens=20)
    out = sess.run_to_completion()["r"]
    assert len(out) == 20


def test_block_pool_exhaustion_preempts_not_crashes():
    """Out-of-blocks mid-decode preempts that request; others keep going."""
    tpu = dict(
        is_continuous_batching=True, batch_size=2, ctx_batch_size=1, seq_len=64,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=3,
    )
    cfg = make_tiny_config(tpu=tpu)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=make_random_hf_state_dict(cfg))
    sess = ServingSession(app)
    # r1 takes 1 block (15 tokens), r2 takes 1; pool has 3 -> decoding past
    # boundaries exhausts it for someone
    assert sess.add_request("r1", list(range(1, 16)), max_new_tokens=40)
    assert sess.add_request("r2", list(range(1, 16)), max_new_tokens=40)
    results = sess.run_to_completion()
    pre = [r for r in sess.requests.values() if r.preempted]
    assert pre, "expected at least one preemption"
    # every request still returned the tokens it generated before preemption
    assert all(len(t) >= 1 for t in results.values())


def test_block_serving_long_decode_crosses_blocks():
    """Decode must stay correct while crossing multiple block boundaries."""
    _, block = _session_apps()
    sess = ServingSession(block)
    assert sess.add_request("r", [5, 17, 92], max_new_tokens=40)
    out = sess.run_to_completion()["r"]
    assert len(out) == 40
    # all blocks returned to the pool after completion
    assert len(sess.allocator.free) == 16
