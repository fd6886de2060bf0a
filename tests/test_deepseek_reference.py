"""MLA on the serving path against the benchmark's plain reference, in tier-1.

``models/deepseek.py`` serves a ``deepseek_v3`` stack (the language model of
Kimi-VL-A3B: one dense layer, then expert layers with a shared MLP) through
the paged cache with chunked prefill: a token leaves one compressed latent
and one rotary key in a layer of the pool, and both step programs attend in
the ABSORBED form. Here that path — ``ServingSession``, the chunk program,
1-ahead decode, the latent pool — is held by logits to
``benchmark/harness/references/deepseek_mla.py``, which is written in the
EXPANDED form (per-head keys and values from ``W_kvb``, no cache, no line of
the program's code), and the reference itself is held once to the installed
``transformers`` ``deepseek_v3`` module. Small size, CPU, float32, weights
from ``system.make_weights``. ``benchmark/selftest/test_correct_kimi.py``
proves the benchmark's RULE on the bf16 model with faults planted.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import system
from benchmark.harness.references import deepseek_mla as ref
from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
from neuronx_distributed_inference_tpu.ops import latent_attention as la
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import LogitSpy, drain

CHUNK = 32  # two blocks: a chunk boundary and a block boundary are different places
BLOCK = 16
SLOTS = 8
VOCAB = 512
SEED = 4400000017
#: of the logits' scale, float32 served against the float32 reference: the
#: program absorbs W_kvb into q and into the attended latent, the reference
#: expands the latent per head, so the two sum the same products in another
#: order; 7 such layers at float32 read ~3e-6 (dense.py's bound is 2e-5)
TOL = 2e-5

#: the issue's small size: hidden 256, 4 heads, latent 64 + rope 16, one dense
#: and three expert layers, 8 experts top-2, one shared
MODEL = dict(
    model_type="deepseek_v3", hidden_size=256, intermediate_size=512, moe_intermediate_size=64,
    num_hidden_layers=4, first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=None, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.446, scoring_func="sigmoid",
    topk_method="noaux_tc", vocab_size=VOCAB, rms_norm_eps=1e-5, rope_theta=800000.0,
    rope_scaling=None, hidden_act="silu", max_position_embeddings=256, tie_word_embeddings=False,
)
#: benchmark/configs/kimi-vl-a3b.json's rules
WEIGHTS = [{"match": "router/e_score_correction_bias$", "std": 0.1},
           {"match": "^embed_tokens/weight$", "std": 0.5},
           {"match": "self_attn/q_proj/weight$", "std": 0.1},
           {"match": "self_attn/kv_a_layernorm/weight$", "mean": 2.0, "std": 0.1}]


def make_app(dtype="float32", **tpu):
    cfg = dict(
        MODEL,
        tpu_config=dict(dict(
            dtype=dtype, tp_degree=1, batch_size=SLOTS, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=48, is_chunked_prefill=True, output_logits=True,
        ), **tpu),
        chunked_prefill=dict(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
    )
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED, WEIGHTS))
    return app


@pytest.fixture(scope="module")
def app():
    return make_app()


def reference_rows(app, prompt, generated):
    geo = ref.geometry(MODEL, 1)
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    return positions, ref.reference_logits(
        app.params, geo, list(prompt) + list(generated[:-1]), positions)


def served_rows(spy, slots, positions, want):
    best = None
    for slot in slots:
        try:
            got = np.stack([spy.at(slot, p) for p in positions]).astype(np.float32)
        except AssertionError:
            continue
        if best is None or np.abs(got - want).max() < np.abs(best - want).max():
            best = got
    assert best is not None, "no slot served these positions"
    return best


def assert_is_the_reference(got, want):
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * max(1.0, np.abs(want).max()))


def test_the_pool_holds_what_the_builder_declares(app):
    """One latent and one rotary key a token a layer, in the cache dtype,
    whatever the head count: the session's block bytes, the pool's arrays and,
    at the published widths, 1152 B."""
    streams = app.builder.cache_streams()
    assert [(s.heads, s.width, s.name) for s in streams] == [(1, 64, "latent"), (1, 16, "rope_key")]
    per_token = bk.kv_block_bytes(1, 1, dtype=jnp.float32, streams=streams)
    assert per_token == (64 + 16) * 4
    s = ServingSession(app)
    assert s.block_bytes == 4 * BLOCK * per_token
    assert s.kv_pool_bytes == 48 * s.block_bytes and s.kv_free_bytes == s.kv_pool_bytes
    pool = app.kv_cache
    assert pool.k.shape == (4, 49, 1, BLOCK, 64)
    assert pool.v.shape == (4, 49, 1, BLOCK // streams[1].pack, 16 * streams[1].pack)
    assert pool.k.nbytes + pool.v.nbytes == 49 * s.block_bytes  # no lane of padding declared
    published = (bk.CacheStream(1, 512), bk.CacheStream(1, 64, pack=2))
    assert bk.kv_block_bytes(1, 1, dtype=jnp.bfloat16, streams=published) == 1152
    assert published[1].pool_shape(7, 12288, 32) == (7, 12289, 1, 16, 128)


def test_chunked_prefill_then_decode_is_the_reference(app):
    """A prompt of 2.5 chunks (5 blocks) through the latent pool, then 8
    decode steps: absorbed (program) against expanded (reference)."""
    app.init_kv_cache()
    prompt = np.random.default_rng(11).integers(0, VOCAB, size=int(2.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        assert s.add_request("r", prompt, max_new_tokens=9)
        drain(s)
        generated = s.requests["r"].generated
        assert len(generated) == 9
        positions, want = reference_rows(app, prompt, generated)
        assert_is_the_reference(served_rows(spy, [0], positions, want), want)
    assert [int(t) for t in generated] == [int(t) for t in want.argmax(-1)]


def test_rows_of_unlike_lengths_share_a_pass(app):
    """Three prompts that end inside a block, at a block edge and at a chunk
    edge prefill and decode together."""
    app.init_kv_cache()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (CHUNK + 5, BLOCK, 2 * CHUNK)]
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=6)
        slots = [s.requests[f"r{i}"].slot for i in range(3)]
        drain(s)
        for i, p in enumerate(prompts):
            positions, want = reference_rows(app, p, s.requests[f"r{i}"].generated)
            assert_is_the_reference(served_rows(spy, [slots[i]], positions, want), want)


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(app):
    """A preempted request's latents are freed and its prompt re-prefilled
    into other blocks of the pool: the same logits."""
    app.init_kv_cache()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(1.5 * CHUNK) + 3, CHUNK - 5)]
    with LogitSpy(app) as spy:
        s = ServingSession(app, fault_injector=FaultInjector().exhaust_pool(4))
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=8)
        drain(s)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        assert s.kv_free_bytes == s.kv_pool_bytes
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            assert len(generated) == 8
            positions, want = reference_rows(app, p, generated)
            assert_is_the_reference(served_rows(spy, range(s.num_slots), positions, want), want)


def test_the_step_returns_its_expert_choices_and_counts_its_latents():
    """``output_choices``: the expert layers' selections as ``forward``'s
    third value, (B, S, expert layers, k); the session counts the latents a
    pass writes and the rows it routes."""
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    app = make_app(output_choices=True)
    ids = np.random.default_rng(14).integers(0, VOCAB, size=(SLOTS, CHUNK)).astype(np.int32)
    pos = np.tile(np.arange(CHUNK, dtype=np.int32), (SLOTS, 1))
    table = (1 + np.arange(SLOTS * 8).reshape(SLOTS, 8)).astype(np.int32)[:, : 128 // BLOCK]
    sm = table[:, pos[0] // BLOCK] * BLOCK + pos % BLOCK
    mask = (np.arange(128)[None, :] < CHUNK).astype(np.int32).repeat(SLOTS, 0)
    _, _, aux = app.forward(ids, pos, np.arange(SLOTS, dtype=np.int32), attention_mask=mask,
                            slot_mapping=sm.astype(np.int32), block_table=table, phase="tkg")
    chose = np.asarray(aux["experts"])
    assert chose.shape == (SLOTS, CHUNK, 3, 2) and chose.min() >= 0 and chose.max() < 8
    geo = ref.geometry(MODEL, 1)
    _, _, own = ref.forward(app.params, geo, ids[0], [0])
    agree = np.mean(np.sort(np.transpose(own, (1, 0, 2)), -1) == np.sort(chose[0], -1))
    assert agree > 0.99  # float32 both: a near-tie may fall the other way

    with TelemetrySession() as tel:
        s = ServingSession(app, telemetry=tel)
        assert s.add_request("r", ids[0][:20], max_new_tokens=3)
        drain(s)
        snap = tel.registry.snapshot()

    by = lambda name: {x["labels"]["program"]: x["value"] for x in snap[name]["samples"]}
    latents, rows = by("nxdi_latent_tokens_written_total"), by("nxdi_moe_rows_routed_total")
    assert latents["chunk"] == 20 * 4 and latents["decode"] >= 2 * 4
    assert rows["chunk"] == 20 * 3 * 2  # real prompt tokens x expert layers x top-2


# ---------------------------------------------------------------------------
# the two forms, the kernels, the write
# ---------------------------------------------------------------------------


def test_absorbed_attention_is_expanded_attention():
    """``q_c = W_uk^T q_nope`` against the latent and ``W_uv`` after the
    attended latent give what per-head keys and values from ``W_kvb`` give."""
    rng = np.random.default_rng(21)
    S, H, r, dn, dr, dv = 24, 4, 64, 32, 16, 32
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q_nope, q_pe, c, k_r = f(1, S, H, dn), f(1, S, H, dr), f(1, S, r), f(1, S, dr)
    w_uk, w_uv = f(H, dn, r) / 8, f(H, r, dv) / 8
    mask = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None, None]
    scale = (dn + dr) ** -0.5
    with jax.default_matmul_precision("highest"):
        latent = la.native_latent_attention(
            jnp.einsum("bshd,hdr->bshr", q_nope, w_uk), q_pe, c, k_r, mask, scale)
        absorbed = jnp.einsum("bshr,hrd->bshd", latent, w_uv)
        k_nope, v = jnp.einsum("bwr,hdr->bwhd", c, w_uk), jnp.einsum("bwr,hrd->bwhd", c, w_uv)
        scores = (jnp.einsum("bshd,bwhd->bhsw", q_nope, k_nope)
                  + jnp.einsum("bshd,bwd->bhsw", q_pe, k_r)) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        expanded = jnp.einsum("bhsw,bwhd->bshd", probs, v)
    np.testing.assert_allclose(absorbed, expanded, rtol=0, atol=2e-5)


def _filled_pool(rng, r, w, pack, bs, contexts, step, MB=8):
    """A two-layer pool whose layer 1 holds ``contexts`` tokens a row (written
    in chunks of 24: the block form) and then ``step`` more (a pass of that
    width), the block tables ``MB`` wide, and what was written, in token order."""
    L = 2
    B, NB = len(contexts), MB * len(contexts)
    streams = (bk.CacheStream(1, r), bk.CacheStream(1, w, pack))
    cache = bk.init_block_cache(L, NB, bs, dtype=jnp.float32, streams=streams)
    tables = np.zeros((B, MB), np.int32)
    free = list(rng.permutation(np.arange(1, NB + 1)))
    for b, n in enumerate(contexts):
        for i in range(-(-(n + step) // bs)):
            tables[b, i] = free.pop()
    want_c = np.zeros((B, MB * bs, r), np.float32)
    want_k = np.zeros((B, MB * bs, w), np.float32)
    c, kr, li = cache.k, cache.v, jnp.int32(1)

    def write(c, kr, start, counts):
        S = max(counts)
        cn = rng.standard_normal((B, S, r)).astype(np.float32)
        kn = rng.standard_normal((B, S, w)).astype(np.float32)
        sm = -np.ones((B, S), np.int32)
        for b in range(B):
            for t in range(counts[b]):
                p = start[b] + t
                sm[b, t] = tables[b, p // bs] * bs + p % bs
                want_c[b, p], want_k[b, p] = cn[b, t], kn[b, t]
        return bk.update_latent_cache_at_layer(c, kr, jnp.asarray(cn), jnp.asarray(kn), li,
                                               jnp.asarray(sm))

    at = [0] * B
    while any(a < n for a, n in zip(at, contexts)):
        counts = [min(24, n - a) for a, n in zip(at, contexts)]
        c, kr = write(c, kr, at, counts)
        at = [a + k for a, k in zip(at, counts)]
    c, kr = write(c, kr, at, [step] * B)
    return c, kr, li, jnp.asarray(tables), want_c, want_k


@pytest.mark.parametrize("r,w,pack,bs,step", [
    (512, 64, 2, 32, 1),   # the published widths: a 576-wide token, two rotary keys a row
    (512, 64, 2, 32, 24),  # the block form over a row that starts inside a block
    (64, 16, 8, 16, 3),    # the tier-1 size, a speculation-width pass
    (64, 16, 1, 16, 1),    # a block too small to pack: a stream like any other
])
def test_latent_write_then_read_is_the_identity(r, w, pack, bs, step):
    """The block-form write of the chunk program and the per-row write of
    the decode step place a token's latent and its packed rotary key where
    the table read finds them, through rows that start and end inside
    blocks; nothing else of the pool moves."""
    rng = np.random.default_rng(31)
    contexts = [3 * bs + 5, 7, 0]
    c, kr, li, tables, want_c, want_k = _filled_pool(rng, r, w, pack, bs, contexts, step)
    got_c, got_k = bk.read_latent_cache_at_layer(c, kr, li, tables)
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_array_equal(got_k, want_k)
    assert not np.asarray(c[0]).any() and not np.asarray(kr[0]).any()  # the other layer
    assert not np.asarray(c[1, 0]).any()  # the garbage block: nothing dropped lands


@pytest.mark.parametrize("r,w,pack,bs,step", [
    (128, 64, 2, 32, 1), (128, 64, 2, 32, 3), (128, 64, 2, 32, 40), (64, 16, 8, 32, 24),
])
def test_latent_kernels_are_the_native_attention(r, w, pack, bs, step):
    """Both Pallas kernels (interpret mode), attending the packed pool a lane
    group at a time, against blocks gathered by the table and attended
    natively: decode widths under the decode mask, a chunk causal by position."""
    rng = np.random.default_rng(41)
    contexts = [3 * bs + 5, 7, 8 * bs - step]
    c, kr, li, tables, _, _ = _filled_pool(rng, r, w, pack, bs, contexts, step)
    H = 4
    q_c = jnp.asarray(rng.standard_normal((3, step, H, r)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((3, step, H, w)), jnp.float32)
    positions = jnp.asarray([[n + t for t in range(step)] for n in contexts], jnp.int32)
    mask = jnp.arange(8 * bs)[None, None, None, :] <= positions[:, None, :, None]
    scale = 0.3 * (r + w) ** -0.5
    want = la.native_latent_attention(
        q_c, q_pe, *bk.read_latent_cache_at_layer(c, kr, li, tables), mask, scale)
    if step > 16:
        got = la.paged_latent_flash_attention(
            q_c, q_pe, c, kr, li, tables, positions, positions[:, -1] + 1,
            scale=scale, tq=16, interpret=True)
    else:
        got = la.paged_latent_decode_attention(
            q_c, q_pe, c, kr, li, tables, mask, scale=scale, interpret=True)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


#: a selection over the chunk kernel (interpret mode): (pool shape ``r, w,
#: pack, bs, MB``, q width, the rows' contexts before the pass (None: a padded
#: row), what is made of a random selection of the live keys). At r = 64 a
#: group is ONE block (32 tokens), at r = 128 eight (256 tokens).
_SMALL, _WIDE = (64, 16, 8, 32, 8), (128, 64, 2, 32, 16)
SELECTIONS = {
    "q32": (_SMALL, 32, [3 * 32 + 5, 7, 8 * 32 - 32], None),
    "q64": (_SMALL, 64, [3 * 32 + 5, 7, 8 * 32 - 64], None),
    "q128_groups_of_8_blocks": (_WIDE, 128, [300, 7, 16 * 32 - 128], None),
    # the width the rule gives the chunk kernel under a selection only
    "q8_narrow": (_SMALL, 8, [3 * 32 + 5, 7, 8 * 32 - 8], None),
    "rows_of_unlike_progress_two_padded": (_SMALL, 32, [None, 5 * 32 + 9, 0, None, 70], None),
    "nothing_picked_in_the_first_groups": (_SMALL, 32, [6 * 32 + 3, 4 * 32], "not_first"),
    "nothing_picked_in_the_last_group": (_SMALL, 32, [6 * 32 + 3, 4 * 32], "not_last"),
    "nothing_picked_at_all": (_SMALL, 32, [3 * 32 + 5, 40], "one_query_nothing"),
    # 300 + 128 tokens: the live keys end 172 tokens into the second group of 256
    "live_keys_end_inside_a_group": (_WIDE, 128, [300, 130], None),
    "every_live_key_is_no_predicate": (_SMALL, 32, [3 * 32 + 5, 7, 8 * 32 - 32], "all"),
    # a bf16 pool: the probabilities go to the matrix unit in bf16, as the native form rounds them
    "bf16_pool": (_WIDE, 32, [300, 7, 16 * 32 - 32], "bf16"),
}


@pytest.mark.parametrize("case", list(SELECTIONS))
def test_the_chunk_kernel_attends_a_selection(case):
    """``paged_latent_flash_attention`` under a predicate ``chosen`` (what
    ``sparse_index.select`` returns: live AND picked) against the native
    attention under the same predicate: every picked key, no other, at each
    chunk width, for rows of unlike progress beside padded ones, for a query
    that picks nothing in the groups it meets first or last (its running
    maximum stays finite and a masked score adds exactly 0), for a row whose
    live keys end inside a group; and a predicate of every live key IS the
    call without one (to the last bits: 2e-7 where the others are held to 2e-6)."""
    (r, w, pack, bs, MB), step, contexts, shape = SELECTIONS[case]
    rng = np.random.default_rng(43)
    live_row = np.array([n is not None for n in contexts])
    c, kr, li, tables, _, _ = _filled_pool(
        rng, r, w, pack, bs, [n or 0 for n in contexts], step, MB)
    B, H, W = len(contexts), 4, MB * bs
    q_c = jnp.asarray(rng.standard_normal((B, step, H, r)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((B, step, H, w)), jnp.float32)
    positions = np.array([[(n or 0) + t for t in range(step)] for n in contexts])
    positions = jnp.asarray(np.where(live_row[:, None], positions, 0), jnp.int32)
    kv_limit = jnp.asarray(np.where(live_row, positions[:, -1] + 1, 0), jnp.int32)
    live = (np.arange(W)[None, None, :] <= np.asarray(positions)[:, :, None]) & live_row[:, None, None]
    chosen = live & (rng.random((B, step, W)) < 0.3)
    group = bs * (8 if r == 128 else 1)
    if shape == "not_first":
        chosen[:, ::2, : 3 * group] = False  # every other query: nothing in its first three groups
    elif shape == "not_last":
        for b, n in enumerate(contexts):
            chosen[b, ::2, (n + step - 1) // group * group:] = False
    elif shape == "one_query_nothing":
        chosen[:, 5] = False
    elif shape == "all":
        chosen = live
    assert chosen[live_row].any(axis=-1).mean() > 0.9  # the cases are not empty
    scale = 0.3 * (r + w) ** -0.5
    atol = 2e-6
    if shape == "bf16":
        q_c, q_pe, c, kr = (x.astype(jnp.bfloat16) for x in (q_c, q_pe, c, kr))
        atol = 2e-2  # of outputs of order 1: bf16's rounding of the probabilities and of the result
    args = (q_c, q_pe, c, kr, li, tables, positions, kv_limit)
    got = la.paged_latent_flash_attention(*args, jnp.asarray(chosen), scale=scale, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    if shape == "all":
        # the same sums over the same keys; the CPU's compiler orders a row's
        # sum by the fusion it sits in, so to the last bits and not bit for bit
        plain = la.paged_latent_flash_attention(*args, scale=scale, interpret=True)
        np.testing.assert_allclose(got, plain, rtol=0, atol=2e-7)
    want = la.native_latent_attention(
        q_c, q_pe, *bk.read_latent_cache_at_layer(c, kr, li, tables),
        jnp.asarray(chosen)[:, None], scale)
    # a query that picks nothing reads zeros (the native softmax of nothing is a mean)
    picks = chosen.any(axis=-1)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[picks], want[picks], rtol=0, atol=atol)
    assert not got[~picks].any()


@functools.lru_cache(maxsize=None)
def _random_pool(r, w, bs, MB, B, dtype):
    """A two-layer pool of random latents and rotary keys, every block filled,
    and tables that scatter ``B`` rows of ``MB`` blocks over it."""
    rng = np.random.default_rng(53)
    pack, NB = 128 // w, B * MB
    c = jnp.asarray(rng.standard_normal((2, NB + 1, 1, bs, r)), dtype)
    kr = jnp.asarray(rng.standard_normal((2, NB + 1, 1, bs // pack, pack * w)), dtype)
    return c, kr, jnp.asarray(1 + rng.permutation(NB).reshape(B, MB), jnp.int32)


#: the chunk kernel at its OWN tiles (a part of up to 512 query rows, a group
#: of 32 blocks = 1024 tokens: what ``la.Q_ROWS`` / ``la.GROUP_TOKENS`` give a
#: pool of blocks of 32, and the tuning table the registered shape): the
#: rows' contexts BEFORE the pass as a function of the q width (None: a padded
#: row) and the table's width in blocks
_TILE_ROWS = {
    # 37+ live blocks (no multiple of the group) | padded, between two live
    # rows | ends inside its first group | ends with the table, 1.5 groups wide
    "table_of_48_blocks": (48, lambda q: [36 * 32 + 7, None, 200 - q, 48 * 32 - q]),
    # narrower than one group: the group IS the table, 8 blocks
    "table_of_8_blocks": (8, lambda q: [100, None, 8 * 32 - q]),
}
_TILE_CASES = [
    (heads, q, selected, "table_of_48_blocks")
    for heads in (64, 16) for q in (8, 16, 64, 128) for selected in (False, True)
] + [(heads, 64, selected, "table_of_8_blocks") for heads in (64, 16) for selected in (False, True)]


@pytest.mark.parametrize(
    "heads,q,selected,rows", _TILE_CASES,
    ids=[f"h{h}-q{q}-{'selection' if s else 'dense'}-{rows}" for h, q, s, rows in _TILE_CASES],
)
def test_the_chunk_kernel_at_its_own_tiles_is_the_native_attention(heads, q, selected, rows):
    """``paged_latent_flash_attention`` (interpret mode) at the tiles its own
    rule gives, against the native attention over the gathered table: at 64
    and at 16 heads a latent (parts of 4 heads x 128 positions, of all the
    heads x 8), with and without a predicate, over a row whose live blocks are
    no multiple of the group, one that ends inside its first group, a padded
    row between two live ones, a table no multiple of the group wide or
    narrower than one group, and under a predicate queries that pick nothing
    in the first group they meet."""
    r, w, bs = 128, 64, 32
    MB, contexts = _TILE_ROWS[rows]
    contexts = contexts(q)
    B, W = len(contexts), MB * bs
    # the tiles are the registered shape's: the fallback and the table agree
    assert la.blocks_per_group(1, bs, r, jnp.float32, 528) == la.blocks_per_group(
        1, bs, 512, jnp.bfloat16, 528) == la.GROUP_TOKENS // bs == 32
    assert la.blocks_per_group(1, bs, r, jnp.float32, MB) == min(32, MB)
    c, kr, tables = _random_pool(r, w, bs, MB, B, jnp.float32)
    rng = np.random.default_rng(59)
    q_c = jnp.asarray(rng.standard_normal((B, q, heads, r)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((B, q, heads, w)), jnp.float32)
    live_row = np.array([n is not None for n in contexts])
    positions = np.array([[(n or 0) + t for t in range(q)] for n in contexts])
    positions = np.where(live_row[:, None], positions, 0)
    kv_limit = jnp.asarray(np.where(live_row, positions[:, -1] + 1, 0), jnp.int32)
    live = (np.arange(W)[None, None, :] <= positions[:, :, None]) & live_row[:, None, None]
    chosen = live
    if selected:
        chosen = live & (rng.random((B, q, W)) < 0.3)
        group = min(32, MB) * bs
        if W > group:  # every other query of row 0: nothing in the first group it meets
            chosen[0, ::2, :group] = False
        chosen[0, :, contexts[0]] = True  # and something after it
    scale = 0.3 * (r + w) ** -0.5
    li = jnp.int32(1)
    got = la.paged_latent_flash_attention(
        q_c, q_pe, c, kr, li, tables, jnp.asarray(positions, jnp.int32), kv_limit,
        jnp.asarray(chosen) if selected else None, scale=scale, interpret=True)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    c_all, kr_all = bk.read_latent_cache_at_layer(c, kr, li, tables)
    picks = chosen.any(axis=-1)
    for b in np.flatnonzero(live_row):  # a row at a time: (heads, q, W) float32 scores
        want = la.native_latent_attention(
            q_c[b:b + 1], q_pe[b:b + 1], c_all[b:b + 1], kr_all[b:b + 1],
            jnp.asarray(chosen[b:b + 1])[:, None], scale)
        np.testing.assert_allclose(got[b][picks[b]], np.asarray(want)[0][picks[b]], rtol=0, atol=2e-6)
    assert not got[~picks].any()  # a padded row, a query that picks nothing: zeros


def test_the_chunk_kernel_reads_its_tiles_from_the_table_at_the_registered_shape():
    """The registered shape class (``blk1x32x512``, bfloat16: both cells' pool)
    takes its tiles from the tuning table under the kernel's own name, and at
    them the kernel is the native attention to bf16's rounding: 16 heads x 32
    positions = one part of 512 rows, groups of 32 blocks over a table of 40."""
    from neuronx_distributed_inference_tpu.ops.tile_defaults import table_entry

    r, w, bs, MB, heads, q = 512, 64, 32, 40, 16, 32
    tiles = table_entry(la.CHUNK_KERNEL, f"blk1x{bs}x{r}", "bfloat16")["tiles"]
    assert tiles == {"rows": la.Q_ROWS, "pages": la.GROUP_TOKENS // bs}
    contexts = [33 * bs + 5, 40]
    c, kr, tables = _random_pool(r, w, bs, MB, len(contexts), jnp.bfloat16)
    rng = np.random.default_rng(61)
    q_c = jnp.asarray(rng.standard_normal((2, q, heads, r)) * 0.5, jnp.bfloat16)
    q_pe = jnp.asarray(rng.standard_normal((2, q, heads, w)), jnp.bfloat16)
    positions = jnp.asarray([[n + t for t in range(q)] for n in contexts], jnp.int32)
    mask = jnp.arange(MB * bs)[None, None, None, :] <= positions[:, None, :, None]
    scale = (r + w) ** -0.5
    li = jnp.int32(1)
    seen = {}
    call = la._da._common_call

    def common_call(kernel, *a, **k):
        seen["P"], seen["parts"] = kernel.keywords["P"], k["out_shape"].shape[1:3]
        return call(kernel, *a, **k)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la._da, "_common_call", common_call)
        got = la.paged_latent_flash_attention.__wrapped__(
            q_c, q_pe, c, kr, li, tables, positions, positions[:, -1] + 1, scale=scale, interpret=True)
    assert seen == {"P": tiles["pages"], "parts": (1, tiles["rows"])}
    want = la.native_latent_attention(
        q_c, q_pe, *bk.read_latent_cache_at_layer(c, kr, li, tables), mask, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0, atol=2e-2)


@pytest.mark.parametrize("step,kernel", [(1, "decode"), (8, "chunk"), (16, "chunk"), (40, "chunk")])
def test_one_rule_picks_the_kernel_for_a_selection(step, kernel, monkeypatch):
    """``latent_attend`` under a selection: the decode kernel with the
    predicate as its mask for a one-token pass, the chunk kernel with the
    predicate as its operand at every wider pass (the decode kernel's mask is
    a slab a (head, position) row, which no VMEM holds over a long row), and
    either way the native attention under the predicate."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    r, w, pack, bs, MB = 128, 64, 2, 32, 16
    rng = np.random.default_rng(47)
    contexts = [300, 7, MB * bs - step]
    c, kr, li, tables, _, _ = _filled_pool(rng, r, w, pack, bs, contexts, step, MB)
    q_c = jnp.asarray(rng.standard_normal((3, step, 4, r)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((3, step, 4, w)), jnp.float32)
    positions = jnp.asarray([[n + t for t in range(step)] for n in contexts], jnp.int32)
    mask = jnp.arange(MB * bs)[None, None, None, :] <= positions[:, None, :, None]
    chosen = mask[:, 0] & jnp.asarray(rng.random((3, step, MB * bs)) < 0.4)
    chosen = chosen.at[:, :, 0].set(True)  # every query picks something
    called = []
    for name in ("paged_latent_decode_attention", "paged_latent_flash_attention"):
        def spy(*a, _fn=getattr(la, name), _name=name, **k):
            called.append(_name)
            return _fn(*a, **k)
        monkeypatch.setattr(la, name, spy)
    scale = 0.3 * (r + w) ** -0.5
    got = la.latent_attend(
        q_c, q_pe, c, kr, li, mask, tables, positions[:, -1] + 1, positions, chosen,
        scale=scale, interpret=True)
    assert called == [f"paged_latent_{'decode' if kernel == 'decode' else 'flash'}_attention"]
    want = la.native_latent_attention(
        q_c, q_pe, *bk.read_latent_cache_at_layer(c, kr, li, tables), chosen[:, None], scale)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# the reference is the family's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rope_interleave", [False, True])
def test_the_reference_is_the_installed_deepseek_v3(rope_interleave):
    """``references/deepseek_mla.py`` on a converted checkpoint against
    ``transformers``' ``DeepseekV3ForCausalLM``: the equations are the
    family's, not this repository's memory of them."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.deepseek import (
        DeepseekV3InferenceConfig,
        DeepseekV3ModelBuilder,
    )

    hf_cfg = DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, n_shared_experts=2,
        n_routed_experts=8, routed_scaling_factor=2.446, kv_lora_rank=16, q_lora_rank=None,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=1, topk_group=1,
        num_experts_per_tok=3, first_k_dense_replace=1, norm_topk_prob=True,
        rope_interleave=rope_interleave, attention_bias=False, rms_norm_eps=1e-5,
        rope_theta=800000.0, max_position_embeddings=256, eos_token_id=None, bos_token_id=None,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = DeepseekV3ForCausalLM(hf_cfg).eval().float()
    with torch.no_grad():
        for layer in hf.model.layers[1:]:  # a selection bias that changes the choice
            layer.mlp.gate.e_score_correction_bias.normal_(0.0, 0.1)
    tokens = np.random.default_rng(51).integers(0, 128, size=40)
    with torch.no_grad():
        want = hf(torch.tensor(tokens[None])).logits[0].numpy()

    attrs = dict(hf_cfg.to_dict(), model_type="deepseek_v3")
    cfg = DeepseekV3InferenceConfig(
        TpuConfig(batch_size=1, seq_len=64, dtype="float32"),
        load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])
    sd = {k: v.float().numpy() for k, v in hf.state_dict().items()}
    params = DeepseekV3ModelBuilder(cfg).convert_hf_state_dict(sd)
    got = ref.reference_logits(params, ref.geometry(attrs, 1), tokens, list(range(40)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(want).max())
