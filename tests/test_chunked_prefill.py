"""Chunked prefill + prefix caching (VERDICT r1 next #3).

- paged flash kernel parity vs the native gathered-block path;
- prefix-prefill (prior-KV multi-token pass) matches full CTE token-for-token;
- prefix-cache hit skips recompute (allocator reuse) with identical outputs;
- chunked serving of a long prompt matches one-shot serving;
- in-graph TKG slot-mapping generation matches host-provided mappings;
- PrefixCachingAllocator lifecycle (match/commit/refcount/evict).
"""

import numpy as np
import pytest

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    PrefixCachingAllocator,
)
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

PROMPT_LONG = [((i * 37) % 100) + 2 for i in range(44)]


def _block_app(sd=None, **tpu_over):
    tpu = dict(
        is_continuous_batching=True, batch_size=2, ctx_batch_size=1, seq_len=128,
        is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=48,
    )
    tpu.update(tpu_over)
    cfg = make_tiny_config(tpu=tpu)
    if sd is None:
        sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=sd)
    return app, sd


# ---------------------------------------------------------------------------
# paged flash kernel
# ---------------------------------------------------------------------------


def _flash_reference(q, k_pool, v_pool, block_table, positions, kv_limit, n_rep, scale):
    """Native attention over the blocks a table names, gathered: (B, Sq, Hq, D)."""
    B, MB = block_table.shape
    _, Hkv, bs, D = k_pool.shape

    def gathered(pool):  # (B, Hq, MB * bs, D)
        x = np.nan_to_num(pool)[block_table].transpose(0, 2, 1, 3, 4)
        return np.repeat(x.reshape(B, Hkv, MB * bs, D), n_rep, axis=1)

    s = np.einsum("bqhd,bhkd->bhqk", q, gathered(k_pool)) * scale
    kv_pos = np.arange(MB * bs)
    mask = (kv_pos <= positions[:, None, :, None]) & (kv_pos < kv_limit[:, None, None, None])
    s = np.where(mask, s, -1e30)
    p = np.where(mask, np.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    p = p / np.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return np.einsum("bhqk,bhkd->bqhd", p, gathered(v_pool))


# name: (B, Sq, Hq, Hkv, D, bs, MB, tq, prior context per row, what else)
#   "pages": the kernel's group of blocks (through the tuning table's
#   override, as a committed entry would give it); "new": real tokens of the
#   chunk per row (kv_limit = prior + new; 0 with prior 0 = a padded row);
#   "frontier": block length of a block-step model's positions; "nan": pool
#   blocks past every row's frontier and the null block hold NaN
FLASH_CASES = {
    # head_dim 64: a block a grid step through the BlockSpec
    "d64_by_block": (2, 16, 4, 2, 64, 8, 6, 8, [20, 5], {}),
    # the n_rep q heads of a KV head stacked on the query axis
    "n_rep1": (2, 16, 2, 2, 128, 8, 6, 8, [20, 5], {}),
    "n_rep2": (2, 16, 4, 2, 128, 8, 6, 8, [20, 5], {}),
    "n_rep4": (2, 16, 8, 2, 128, 8, 6, 8, [20, 5], {}),
    # ... and taken in parts where n_rep x tq passes Q_ROWS: 5 parts of 1, 4 of 2
    "n_rep5_in_parts": (1, 128, 5, 1, 128, 8, 20, 128, [19], {}),
    "n_rep8_in_parts": (1, 128, 8, 1, 128, 8, 20, 128, [19], {}),
    # a padded row between live rows: one empty step, its output zeros
    "empty_row_between": (3, 16, 4, 2, 128, 8, 6, 8, [20, 0, 5], {"new": [16, 0, 16]}),
    # a context that ends mid-block and mid-group (groups of 2 blocks)
    "ends_mid_block_mid_group": (2, 16, 4, 2, 128, 8, 8, 16, [21, 3], {"pages": 2, "new": [14, 9]}),
    # a table narrower than one group, and one no multiple of it
    "table_under_a_group": (2, 16, 4, 2, 128, 8, 3, 8, [5, 0], {"pages": 4, "new": [16, 12]}),
    "table_no_multiple": (2, 16, 4, 2, 128, 8, 7, 8, [37, 11], {"pages": 4}),
    # several q tiles whose frontiers lie in different groups
    "q_tiles_across_groups": (2, 32, 4, 2, 128, 8, 8, 8, [30, 2], {"pages": 2}),
    # a block-step model: each query's frontier at its block's end
    "block_frontier": (2, 16, 4, 2, 128, 8, 6, 8, [20, 4], {"frontier": 4, "pages": 2}),
    # nothing past a row's frontier reaches a product
    "nan_past_frontier": (2, 16, 4, 2, 128, 8, 8, 8, [20, 5], {"nan": True, "pages": 2}),
    "nan_past_frontier_d64": (2, 16, 4, 2, 64, 8, 8, 8, [20, 5], {"nan": True}),
}


@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_paged_flash_kernel_parity(case):
    from neuronx_distributed_inference_tpu.modules import masks
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
        paged_flash_attention,
    )
    from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_overrides
    import jax.numpy as jnp

    B, Sq, Hq, Hkv, D, bs, MB, tq, prior, extra = FLASH_CASES[case]
    rng = np.random.RandomState(0)
    n_rep = Hq // Hkv
    q = (rng.randn(B, Sq, Hq, D) * 0.3).astype(np.float32)
    # head-major paged layout (NB+1, Hkv, bs, D), block 0 the null block
    NB = B * MB
    k_cache = (rng.randn(NB + 1, Hkv, bs, D) * 0.3).astype(np.float32)
    v_cache = (rng.randn(NB + 1, Hkv, bs, D) * 0.3).astype(np.float32)
    starts = np.array(prior)
    positions = starts[:, None] + np.arange(Sq)[None, :]
    kv_limit = starts + np.array(extra.get("new", [Sq] * B))
    if "frontier" in extra:
        positions = np.asarray(masks.block_frontier(jnp.asarray(positions), extra["frontier"]))
        kv_limit = positions[:, -1] + 1
    # a row's blocks are its own, in order, up to its context; null past it
    block_table = np.zeros((B, MB), np.int32)
    for b in range(B):
        n = -(-int(kv_limit[b]) // bs)
        block_table[b, :n] = 1 + b * MB + np.arange(n)
    if extra.get("nan"):
        dead = np.setdiff1d(np.arange(NB + 1), block_table[block_table > 0])
        k_cache[dead] = np.nan
        v_cache[dead] = np.nan
        block_table[1, -1] = dead[-1]  # a dead entry need not be the null block

    raw = paged_flash_attention.__wrapped__  # the override is read at trace time
    with tile_overrides("paged_flash_attention", {"pages": extra["pages"]} if "pages" in extra else {}):
        out = raw(
            jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.asarray(block_table), jnp.asarray(positions), jnp.asarray(kv_limit),
            scale=D**-0.5, n_rep=n_rep, tq=tq, interpret=True,
        )

    ref = _flash_reference(
        q, k_cache, v_cache, block_table, positions, kv_limit, n_rep, D**-0.5
    )
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


#: the registered pool blocks of the GQA prefill kernel (analysis/kernel_registry)
#: and its group of blocks at each, a table of 256 blocks wide
GQA_GROUPS = [
    (8, 128, 64, "bfloat16", 1), (8, 128, 64, "int8", 1), (8, 32, 128, "bfloat16", 16),
    (8, 32, 128, "int8", 16), (2, 32, 128, "bfloat16", 16), (4, 32, 128, "bfloat16", 16),
    (16, 32, 128, "bfloat16", 8),
    # a latent pool's block under THIS kernel's name: what the latent chunk
    # kernel borrowed until it got a rule of its own (ops/latent_attention.py)
    (1, 32, 512, "bfloat16", 16),
]


@pytest.mark.parametrize("n_kv,bs,head_dim,dtype,pages", GQA_GROUPS)
def test_the_gqa_prefill_kernels_tiles_are_its_own(n_kv, bs, head_dim, dtype, pages):
    """The paged prefill kernel's group (512 tokens, at most 1 MiB a stream)
    and its part's row cap (``Q_ROWS`` 256: bounded by ``n_rep``) stand as they
    stood, whatever the latent chunk kernel takes for itself."""
    from neuronx_distributed_inference_tpu.ops import paged_flash_attention as pf

    assert pf.blocks_per_group(n_kv, bs, head_dim, dtype, 256) == pages
    assert pf.Q_ROWS == 256
    live = [3, 40, 256]
    walked = pf.kv_blocks_walked(live, 256, n_kv=n_kv, bs=bs, head_dim=head_dim, cache_dtype=dtype)
    assert walked == (3 * 256 if head_dim % 128 else sum(-(-n // pages) * pages for n in live))


# ---------------------------------------------------------------------------
# prefix caching
# ---------------------------------------------------------------------------


def test_prefix_allocator_lifecycle():
    a = PrefixCachingAllocator(num_blocks=16, block_size=4)
    toks = np.arange(100, 114)  # 14 tokens: 3 full blocks + tail
    a.alloc_seq(0, len(toks))
    a.commit_seq(0, toks)
    assert len(a.hash_of_block) == 3

    # same prefix matches all 3 full blocks, capped to leave >=1 token
    n = a.match_prefix(1, toks)
    assert n == 12
    assert a.seq_blocks[1] == a.seq_blocks[0][:3]

    # different first block -> no match
    other = np.arange(200, 214)
    assert a.match_prefix(2, other) == 0

    # free original; shared blocks stay live (refcounted by seq 1)
    a.free_seq(0)
    assert not a.evictable
    a.free_seq(1)
    assert len(a.evictable) == 3  # now evictable but still matchable
    assert a.match_prefix(3, toks) == 12
    a.free_seq(3)

    # exhausting the pool evicts LRU cached blocks
    a.free_seq(2)
    a.alloc_seq(9, 16 * 4)  # needs every block
    assert len(a.hash_of_block) == 0


@pytest.mark.slow
def test_prefix_prefill_matches_full_cte():
    """A prefix-cache hit (suffix-only prior-KV prefill) must generate the
    same tokens as a fresh full prefill."""
    prompts = {"a": PROMPT_LONG, "b": PROMPT_LONG[:24] + [7, 7, 7, 9]}

    app1, sd = _block_app()
    plain = ServingSession(app1)
    for rid, p in prompts.items():
        assert plain.add_request(rid, p, max_new_tokens=8)
    ref = plain.run_to_completion()

    app2, _ = _block_app(sd=sd, is_prefix_caching=True)
    sess = ServingSession(app2)
    # first request populates the prefix cache
    assert sess.add_request("a", prompts["a"], max_new_tokens=8)
    # second shares 24 tokens = 3 full blocks with "a"
    assert sess.add_request("b", prompts["b"], max_new_tokens=8)
    assert sess.requests["b"].prefill_pos >= sess.requests["b"].prompt_len
    out = sess.run_to_completion()
    assert out["a"] == ref["a"]
    assert out["b"] == ref["b"]


def test_prefix_cache_actually_reuses_blocks():
    app, _ = _block_app(is_prefix_caching=True)
    sess = ServingSession(app)
    assert sess.add_request("a", PROMPT_LONG, max_new_tokens=2)
    first_a = sess.requests["a"].generated[0]
    sess.run_to_completion()
    assert sess.allocator.block_by_hash  # prompt blocks registered

    # identical prompt: every full block below prompt_len matches
    matched = {}
    orig = sess.allocator.match_prefix

    def spy(seq_id, tokens):
        n = orig(seq_id, tokens)
        matched["n"] = n
        return n

    sess.allocator.match_prefix = spy
    assert sess.add_request("b", PROMPT_LONG, max_new_tokens=2)
    n_full = (len(PROMPT_LONG) // 8) * 8
    expected = n_full if n_full < len(PROMPT_LONG) else n_full - 8
    assert matched["n"] == expected
    # and the recomputed suffix still reproduces the same first token
    assert sess.requests["b"].generated[0] == first_a


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chunked_serving_matches_unchunked():
    app1, sd = _block_app()
    plain = ServingSession(app1)
    assert plain.add_request("r", PROMPT_LONG, max_new_tokens=8)
    assert plain.add_request("s", PROMPT_LONG[5:31], max_new_tokens=8)
    ref = plain.run_to_completion()

    app2, _ = _block_app(
        sd=sd,
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=4, kernel_q_tile_size=16
        ),
    )
    sess = ServingSession(app2)
    assert sess.add_request("r", PROMPT_LONG, max_new_tokens=8)
    assert sess.add_request("s", PROMPT_LONG[5:31], max_new_tokens=8)
    # prompts are chunked: nothing prefilled at admission
    assert sess.requests["r"].prefilling
    out = sess.run_to_completion()
    assert out["r"] == ref["r"]
    assert out["s"] == ref["s"]


@pytest.mark.slow
def test_chunked_prefill_overlaps_decode():
    """A decoding request keeps producing tokens while another's long prompt
    is still being chunk-prefilled."""
    app, _ = _block_app(
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=8),
    )
    sess = ServingSession(app)
    assert sess.add_request("short", [4, 9, 2], max_new_tokens=20)
    # drain the short request's prefill (chunk pass) so it starts decoding
    sess.step()
    assert not sess.requests["short"].prefilling
    assert sess.add_request("long", PROMPT_LONG, max_new_tokens=4)
    gen_before = len(sess.requests["short"].generated)
    # async 1-ahead decode: step k dispatches decode k+1 and consumes decode
    # k, so the first decode token lands one step later
    sess.step()  # long gets a chunk; short's first decode is DISPATCHED
    assert sess.requests["long"].prefill_pos > 0
    sess.step()  # long gets a chunk; short's first decode token lands
    assert len(sess.requests["short"].generated) >= gen_before + 1
    sess.run_to_completion()
    assert len(sess.requests["long"].generated) == 4


# ---------------------------------------------------------------------------
# in-graph slot mapping
# ---------------------------------------------------------------------------


def test_in_graph_slot_mapping_matches_host():
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        slot_mapping_from_block_table,
    )

    bs = 8
    block_table = np.array([[3, 5, 9, 0], [2, 0, 0, 0]], np.int32)
    positions = np.array([[17], [4]], np.int32)
    slots = slot_mapping_from_block_table(
        jnp.asarray(block_table), jnp.asarray(positions), bs
    )
    # row 0: pos 17 -> block idx 2 -> block 9 -> slot 9*8+1
    # row 1: pos 4 -> block 2 -> slot 2*8+4
    np.testing.assert_array_equal(np.asarray(slots), [[9 * 8 + 1], [2 * 8 + 4]])


@pytest.mark.slow
def test_paged_kernel_integrated_serving_parity():
    """Chunked serving with the paged flash kernel force-enabled must match
    the native gathered-block path token-for-token (head_dim 64 model)."""
    hf = dict(hidden_size=256, intermediate_size=256)
    results = {}
    sd = None
    for force in (None, True):
        tpu = dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            seq_len=128, is_block_kv_layout=True, pa_block_size=8,
            pa_num_blocks=48, is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=16
            ),
            attn_kernel_enabled=force,
        )
        cfg = make_tiny_config(tpu=tpu, **hf)
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=sd)
        sess = ServingSession(app)
        assert sess.add_request("r", PROMPT_LONG, max_new_tokens=6)
        results[force] = sess.run_to_completion()["r"]
    assert results[True] == results[None]


def test_chunked_single_request_out_of_blocks_preempts():
    """A lone prefilling request that exhausts the KV pool must be preempted,
    never livelock run_to_completion (r2 review finding)."""
    app, _ = _block_app(
        pa_num_blocks=4,  # 32 usable tokens < 44-token prompt
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=16),
    )
    sess = ServingSession(app)
    assert sess.add_request("r", PROMPT_LONG, max_new_tokens=4)
    sess.run_to_completion()  # must terminate
    assert sess.requests["r"].preempted


def test_step_reports_prefill_completion_token_once():
    """The first generated token (prefill completion) must not be overwritten
    by a decode token in the same step's results (r2 review finding)."""
    app, _ = _block_app(
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=16),
    )
    sess = ServingSession(app)
    assert sess.add_request("r", [4, 9, 2], max_new_tokens=5)
    streamed = []
    while sess.active:
        res = sess.step()
        if "r" in res:
            streamed.append(res["r"])
    assert streamed == sess.requests["r"].generated


@pytest.mark.slow
def test_warmup_covers_chunk_prefill_programs():
    """warmup() must compile the 2-D chunk-prefill programs so the first long
    prompt doesn't pay a serving-time JIT (r2 review finding)."""
    app, _ = _block_app(
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=16),
    )
    app.warmup()
    tkg = app.token_generation_model
    # the warmup example for (q=16, largest kv bucket) must have EXACTLY the
    # aval tree of the real chunk pass (shape/dtype/field presence), else the
    # warmed program is never reused
    ex = tkg.example_inputs(tkg.buckets[-1], q_len=16)
    captured = {}
    to_device = tkg.to_device  # the device half of prepare(): what a pass dispatches

    def spy(arrs):
        captured["inputs"] = to_device(arrs)
        return captured["inputs"]

    tkg.to_device = spy
    sess = ServingSession(app)
    assert sess.add_request("r", PROMPT_LONG[:30], max_new_tokens=2)
    sess.step()  # chunk pass: q=16 at the largest kv bucket
    real = captured["inputs"]
    import dataclasses as dc

    for f in dc.fields(type(real)):
        a, b = getattr(ex, f.name), getattr(real, f.name)
        assert (a is None) == (b is None), f.name
        if a is not None:
            assert a.shape == b.shape and a.dtype == b.dtype, f.name
