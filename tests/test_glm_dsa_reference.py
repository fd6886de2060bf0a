"""Learned sparse attention on the serving path against the benchmark's
plain reference, in tier-1.

``models/glm_moe_dsa.py`` serves a ``glm_moe_dsa`` stack (GLM-5: DeepSeek-V3's
layers, each with an indexer whose top-``index_topk`` keys its latent
attention attends) through the paged cache: a token leaves THREE streams in a
layer of the pool (latent, rotary key, indexer key), and past ``index_topk``
both step programs attend the chosen keys alone. Here that path — context
encoding, ``ServingSession``, the chunk program, 1-ahead decode, the pool's
third stream, a reused slot, a held share of the experts — is held by logits
and by the selection itself to ``benchmark/harness/references/glm_dsa.py``
(full causal index scores, a top-k, a masked softmax in the expanded form, no
cache, no line of the program's code). Small size, CPU, weights from
``system.make_weights``. ``benchmark/selftest/test_correct_glm5.py`` proves
the benchmark's RULE on the bf16 model with faults planted.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import system
from benchmark.harness.references import glm_dsa as ref
from neuronx_distributed_inference_tpu.config import LatentAttentionError, SparseAttentionError
from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
from neuronx_distributed_inference_tpu.modules import sparse_index
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import LogitSpy, drain

CHUNK = 16  # two blocks: a chunk boundary and a block boundary are different places
BLOCK = 8
SLOTS = 4
VOCAB = 256
TOPK = 16
SEED = 5400000017
#: of the logits' scale, float32 served against the float32 reference (the
#: program absorbs W_kvb, the reference expands it: another order of the same
#: sums; dense.py's bound)
TOL = 2e-5

#: the issue's small size: hidden 128, 4 heads, nope 24 + rope 8, v 32, q latent
#: 48, kv latent 32, 2 index heads of 16, index_topk 16, one dense and two
#: expert layers of 8 experts top-2, one shared
MODEL = dict(
    model_type="glm_moe_dsa", hidden_size=128, intermediate_size=256, moe_intermediate_size=48,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
    index_n_heads=2, index_head_dim=16, index_topk=TOPK, indexer_rope_interleave=True,
    rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    scoring_func="sigmoid", topk_method="noaux_tc", vocab_size=VOCAB, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, hidden_act="silu",
    max_position_embeddings=256, tie_word_embeddings=False, num_nextn_predict_layers=1,
)
#: benchmark/configs/glm-5.json's rules
WEIGHTS = [{"match": "router/e_score_correction_bias$", "std": 0.1},
           {"match": "^embed_tokens/weight$", "std": 0.5},
           {"match": "self_attn/q_b_proj/weight$", "std": 0.1},
           {"match": "self_attn/kv_a_layernorm/weight$", "mean": 2.0, "std": 0.1},
           {"match": "indexer/k_norm/bias$", "std": 0.1}]


def make_app(dtype="float32", model=None, chunk=CHUNK, **tpu):
    cfg = dict(
        model or MODEL,
        tpu_config=dict(dict(
            dtype=dtype, tp_degree=1, batch_size=SLOTS, seq_len=128, enable_bucketing=True,
            context_encoding_buckets=[16, 64], token_generation_buckets=[16, 64, 128],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=48, is_chunked_prefill=True, output_logits=True,
        ), **tpu),
        chunked_prefill=dict(max_num_seqs=SLOTS, kernel_q_tile_size=chunk),
    )
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED, WEIGHTS))
    return app


@pytest.fixture(scope="module")
def app():
    return make_app()


GEO = ref.geometry(MODEL, 1)


def assert_is_the_reference(got, want, tol=TOL):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(1.0, np.abs(want).max()))


def serve(app, prompts, new_tokens=9):
    """Each prompt through ONE session, one request after another (so a slot
    is reused): per request (generated tokens, served logits at the last
    prompt position and after each generated token but the last, the pool
    blocks the slot held last)."""
    app.init_kv_cache()
    out = []
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, prompt in enumerate(prompts):
            assert s.add_request(f"r{i}", prompt, max_new_tokens=new_tokens)
            blocks = []
            for _ in range(200):
                if not (s.active or s._readmit):
                    break
                s.step()
                blocks = list(s.allocator.seq_blocks.get(0, blocks))
            generated = [int(t) for t in s.requests[f"r{i}"].generated]
            assert len(generated) == new_tokens
            positions = [len(prompt) - 1 + k for k in range(new_tokens)]
            got = np.stack([spy.at(0, p) for p in positions]).astype(np.float32)
            out.append((generated, positions, got, blocks))
            spy.rows.clear()
    return out, s


def test_the_pool_holds_three_streams_a_token(app):
    """Latent, rotary key and indexer key a token a layer, in the cache
    dtype: the session's block bytes, the pool's arrays, the gauges and, at
    the published widths, 1408 B."""
    streams = app.builder.cache_streams()
    assert [(s.heads, s.width, s.name) for s in streams] == [
        (1, 32, "latent"), (1, 8, "rope_key"), (1, 16, "index_key")]
    per_token = bk.kv_block_bytes(1, 1, dtype=jnp.float32, streams=streams)
    assert per_token == (32 + 8 + 16) * 4
    s = ServingSession(app)
    assert s.block_bytes == 3 * BLOCK * per_token
    assert s.kv_pool_bytes == 48 * s.block_bytes and s.kv_free_bytes == s.kv_pool_bytes
    pool = app.kv_cache
    assert pool.k.shape == (3, 49, 1, BLOCK, 32) and len(pool.extra) == 1
    assert pool.extra[0].shape == (3, 49, 1, BLOCK, 16)
    assert pool.k.nbytes + pool.v.nbytes + pool.extra[0].nbytes == 49 * s.block_bytes
    published = (bk.CacheStream(1, 512), bk.CacheStream(1, 64, pack=2), bk.CacheStream(1, 128))
    assert bk.kv_block_bytes(1, 1, dtype=jnp.bfloat16, streams=published) == 1408
    # a cache of two streams has the leaves it always had
    two = bk.init_block_cache(2, 4, 8, 2, 16)
    assert two.extra == () and len(jax.tree.leaves(two)) == 2


@pytest.mark.parametrize("length", [12, 40])
def test_context_encoding_is_the_reference(length):
    """A whole prompt through the context-encoding program on the paged
    cache, shorter and longer than ``index_topk``: the pass's own keys are
    its context, scored and selected as the reference selects."""
    app = make_app(is_chunked_prefill=False)
    prompt = np.random.default_rng(length).integers(0, VOCAB, size=length)
    width = 16 if length <= 16 else 64
    ids = np.zeros((1, width), np.int32)
    ids[0, :length] = prompt
    mask = (np.arange(width) < length).astype(np.int32)[None]
    table = 1 + np.arange(width // BLOCK, dtype=np.int32)[None]
    slots = np.full((1, width), -1, np.int32)
    slots[0, :length] = BLOCK + np.arange(length)
    _, logits, *_ = app.forward(
        ids, np.arange(width, dtype=np.int32)[None], np.zeros(1, np.int32), attention_mask=mask,
        slot_mapping=slots, block_table=table, phase="cte")
    want = ref.reference_logits(app.params, GEO, prompt, [length - 1])
    assert_is_the_reference(np.asarray(logits, np.float32)[0, -1:], want)


def test_the_contiguous_cache_is_refused_by_type():
    """``generate()`` through the contiguous cache keeps two streams a token
    and no indexer key: refused at config time, as is what the latent pool
    cannot do."""
    with pytest.raises(SparseAttentionError, match="contiguous cache"):
        make_app(is_block_kv_layout=False, is_chunked_prefill=False, is_continuous_batching=False)
    with pytest.raises(LatentAttentionError, match="is_prefix_caching"):
        make_app(is_prefix_caching=True)
    with pytest.raises(LatentAttentionError, match="quantisation"):
        make_app(kv_cache_dtype="int8")
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        make_app(model=dict(MODEL, q_lora_rank=None))
    with pytest.raises(NotImplementedError, match="shared between layers"):
        make_app(model=dict(MODEL, index_topk_freq=4))


@pytest.fixture(scope="module")
def two_requests(app):
    """Two prompts of 2.5 chunks (they cross ``index_topk`` in their second
    chunk) through one reused slot, 1-ahead decode, over a pool of 8 blocks:
    a request holds 6, so the second tenant writes over most of the first's."""
    small = make_app(pa_num_blocks=8)
    small.params = app.params
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, VOCAB, size=int(2.5 * CHUNK)) for _ in range(2)]
    served, session = serve(small, prompts)
    return prompts, served, session


def test_chunked_prefill_then_decode_is_the_reference(app, two_requests):
    """The chunk program crosses ``index_topk`` mid-chunk, the decode program
    runs past it: both attend exactly the reference's chosen set, for the
    second tenant of the slot as for the first."""
    prompts, served, _ = two_requests
    for prompt, (generated, positions, got, _) in zip(prompts, served):
        want = ref.reference_logits(
            app.params, GEO, list(prompt) + generated[:-1], positions)
        assert_is_the_reference(got, want)
        assert generated == [int(t) for t in want.argmax(-1)]


def _forced(app, prompt, steps=3, **tpu):
    """``correct._forced_pass`` on one row: (logits (1 + steps, V), choices)."""
    from benchmark.harness import correct

    probe = make_app(output_choices=True, **tpu)
    probe.params = app.params
    rng = np.random.default_rng(5)
    forced = [[int(t) for t in rng.integers(0, VOCAB, size=correct.PROBE_DECODE_STEPS)]]
    logits, choices = correct._forced_pass(probe, [np.asarray(prompt, np.int32)], forced, 64)
    return forced[0], logits[0], choices[0]


def test_the_step_returns_the_selection_and_it_is_the_references(app):
    """Under ``output_choices`` the step returns, beside the experts', the
    positions each (layer, position) attended, ascending and -1 padded: at
    float32 the reference's own selection; replayed, the reference's logits
    are the served ones and every margin is 0."""
    prompt = np.random.default_rng(3).integers(0, VOCAB, size=int(2.5 * CHUNK))
    forced, logits, choices = _forced(app, prompt)
    tokens = list(prompt) + forced
    assert set(choices) == {"experts", "selection"}
    assert choices["selection"].shape == (len(tokens), 3, TOPK)
    assert choices["experts"].shape == (len(tokens), 2, 2)
    own = ref.own_selection(app.params, GEO, tokens)
    np.testing.assert_array_equal(choices["selection"], own)
    positions = [len(prompt) - 1 + k for k in range(len(forced) + 1)]
    replayed = ref.reference_logits(app.params, GEO, tokens, positions, choices=choices)
    assert_is_the_reference(logits, replayed)
    regret, floor, differing = ref.choice_margins(app.params, GEO, tokens, choices)
    assert regret.shape == floor.shape == (2 + 3,)
    assert (regret == 0).all() and (differing == 0).all() and (floor[2:] > 0).all()
    # a selection that is not the indexer's: the margin sees it
    wrong = dict(choices, selection=np.where(choices["selection"] >= 0,
                                             np.arange(TOPK)[None, None, :], -1).astype(np.int32))
    regret, floor, _ = ref.choice_margins(app.params, GEO, tokens, wrong)
    assert (regret[2:] > 3.0 * floor[2:]).all()


def test_the_third_stream_holds_the_references_index_key(app, two_requests):
    """After two tenants of one slot the pool's third stream holds, at every
    (layer, token) of the SECOND, the reference's ``k_I``, and none of the
    first tenant's at a token of the second."""
    prompts, served, session = two_requests
    generated, blocks = served[1][0], np.asarray(served[1][3])
    tokens = list(prompts[1]) + generated[:-1]
    keys = {}

    def keep(l, h, w, _selected):
        with jax.default_matmul_precision("highest"):
            x, _, cq = ref._layer_inputs(h, w, GEO, None)
            keys[l] = np.asarray(ref._index_parts(x, cq, w, GEO, None, None)[1]).T  # (S, D)

    ref.forward(app.params, GEO, tokens, [0], per_layer=keep)
    pool = np.asarray(session.app.kv_cache.extra[0])  # (L, NB+1, 1, bs, D)
    assert len(set(blocks) & set(served[0][3])) >= 5  # the first tenant's blocks, written over
    held = pool[:, blocks, 0].reshape(3, -1, 16)[:, : len(tokens)]
    for l in range(3):
        np.testing.assert_allclose(held[l], keys[l], rtol=0, atol=2e-5)


@pytest.mark.parametrize("fault", ["attend_all", "topk_halved", "relu_dropped",
                                   "index_rotary_dropped", "index_q_unnormed"])
def test_a_wrong_selection_fails_the_tolerance(app, two_requests, fault):
    """The controls: the reference with one part of the mechanism wrong
    (attend every live token; half the top-k; no ReLU; no rotation in the
    indexer; the indexer fed the q latent without its norm) is NOT what the
    program serves, by a hundred tolerances and more."""
    prompts, served, _ = two_requests
    generated, positions, got, _ = served[0]
    wrong = ref.reference_logits(
        app.params, GEO, list(prompts[0]) + generated[:-1], positions, fault=fault)
    assert np.abs(got - wrong).max() > 100 * TOL * max(1.0, np.abs(wrong).max())


def test_bf16_serving_is_within_the_twins_noise():
    """The bf16 program through the session against the float32 reference,
    both kinds of choice replayed: within K x the twin's own error, every
    margin within 2 K x its floor (``harness/correct.py``'s rule)."""
    from benchmark.harness import correct

    app = make_app(dtype="bfloat16")
    prompt = np.random.default_rng(7).integers(0, VOCAB, size=int(2.5 * CHUNK))
    forced, logits, choices = _forced(app, prompt, dtype="bfloat16")
    tokens = list(prompt) + forced
    positions = [len(prompt) - 1 + k for k in range(len(forced) + 1)]
    want = ref.reference_logits(app.params, GEO, tokens, positions, choices=choices)
    twin = ref.twin_logits(app.params, GEO, tokens, positions, choices=choices)
    err, floor = np.abs(logits - want).max(), np.abs(twin - want).max()
    assert err <= correct.K * floor, (err, floor)
    regret, score_floor, _ = ref.choice_margins(app.params, GEO, tokens, choices)
    assert (regret <= 2 * correct.K * score_floor).all(), (regret, score_floor)


@pytest.mark.parametrize("first", range(16))
def test_the_held_shares_add_up_to_the_whole_expert_layer(first):
    """Over all 16 values of ``expert_share.first``: the routed parts of the
    16 shares add up, with the shared expert counted once, to the uncut
    reference's expert layer (each share computed by the program's layer and
    by the reference's, which agree)."""
    whole, parts = _expert_layer_parts()
    got, want = parts[first]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    if first == 15:
        routed = sum(g for g, _ in parts) - 15 * whole["shared"]
        np.testing.assert_allclose(routed, whole["all"], rtol=0, atol=1e-4)


_PARTS = {}


def _expert_layer_parts():
    """One expert layer at a small size, 32 experts published: the uncut
    reference's update (routed + shared), the shared expert's alone, and per
    share (program's update, reference's update)."""
    if _PARTS:
        return _PARTS["whole"], _PARTS["parts"]
    from neuronx_distributed_inference_tpu.modules.moe import ExpertMlp, MoESpec, shared_expert_mlp

    rng = np.random.default_rng(54)
    H, I, E, k, S = 32, 16, 32, 4, 24
    x = jnp.asarray(rng.normal(size=(1, S, H)), jnp.float32)
    f = lambda *shape, s=0.3: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    router = {"weight": f(H, E, s=0.5), "e_score_correction_bias": f(E, s=0.1)}
    experts = {n: {"weight": f(E, *dims)} for n, dims in
               (("gate_proj", (H, I)), ("up_proj", (H, I)), ("down_proj", (I, H)))}
    shared = {n: {"weight": f(*dims)} for n, dims in
              (("gate_proj", (H, I)), ("up_proj", (H, I)), ("down_proj", (I, H)))}
    geo = ref.Geometry(
        hidden=H, heads=1, q_lora_rank=8, kv_lora_rank=8, nope=8, rope=8, v_dim=8,
        rope_theta=1e6, layers=1, first_dense=0, vocab=8, rms_eps=1e-5, experts=E, held=E,
        first=0, top_k=k, shared=1, norm_topk=True, scaling=2.5, index_heads=1, index_dim=8,
        index_topk=4, degree=1)
    ones = jnp.ones((H,), jnp.float32)

    def reference(first, held, with_shared=True):
        import dataclasses

        w = dict(ln2=ones, router=router["weight"], bias=router["e_score_correction_bias"],
                 **{n: experts[p]["weight"][first:first + held]
                    for n, p in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))})
        if with_shared:
            w.update(sgate=shared["gate_proj"]["weight"], sup=shared["up_proj"]["weight"],
                     sdown=shared["down_proj"]["weight"])
        g = dataclasses.replace(geo, held=held, first=first)
        with jax.default_matmul_precision("highest"):
            # the reference's sublayer norms its input with ln2 = 1: hand it the
            # normed rows' preimage by norming here too
            out = ref._experts(x[0], w, g, None, None)[0]
        return np.asarray(out - x[0])

    def program(first, held):
        from neuronx_distributed_inference_tpu.modules.norm import rms_norm

        spec = MoESpec(num_experts=E, top_k=k, normalize_top_k_affinities=True, act="silu",
                       scoring_func="sigmoid", routed_scaling_factor=2.5,
                       held_experts=held if held < E else None, first_expert=first)
        mlp = ExpertMlp(spec, lambda p, h: shared_expert_mlp(p, h, "silu"))
        params = {"router": router, "shared_experts": shared,
                  "experts": {n: {"weight": experts[n]["weight"][first:first + held]}
                              for n in experts}}

        class _Spec:
            output_choices = False

        with jax.default_matmul_precision("highest"):
            return np.asarray(mlp(params, rms_norm(x, ones, 1e-5), _Spec())[0])

    whole = {"all": reference(0, E), "shared": None}
    whole["shared"] = whole["all"] - reference(0, E, with_shared=False)
    parts = [(program(2 * r, 2), reference(2 * r, 2)) for r in range(16)]
    _PARTS.update(whole=whole, parts=parts)
    return whole, parts


def test_the_selection_is_exact_and_breaks_ties_like_top_k():
    """``sparse_index.select`` against ``lax.top_k`` on scores with many ties
    and rows with fewer live keys than k."""
    rng = np.random.default_rng(2)
    scores = jnp.asarray(np.round(rng.normal(size=(3, 5, 40)) * 2) / 2, jnp.float32)
    live = jnp.asarray(rng.random((3, 5, 40)) < 0.7).at[0, 0].set(False).at[1, 1, 6:].set(False)
    got = np.asarray(sparse_index.select(scores, live, 8))
    ranked = jnp.where(live, scores, -jnp.inf)
    idx = np.asarray(jax.lax.top_k(ranked, 8)[1])
    want = np.zeros_like(got)
    np.put_along_axis(want, idx, True, axis=-1)
    want &= np.asarray(live)
    np.testing.assert_array_equal(got, want)
    pos = np.asarray(sparse_index.chosen_positions(jnp.asarray(got), 8))
    for b in range(3):
        for s in range(5):
            at = np.flatnonzero(got[b, s])
            assert list(pos[b, s, : len(at)]) == list(at) and (pos[b, s, len(at):] == -1).all()


def test_rows_taken_one_after_another_skip_the_padded_ones(app, monkeypatch):
    """At the published widths a pass's rows are scored and attended one
    after another and a padded row is not computed: the same logits for two
    requests of unlike lengths that share their passes with two idle rows."""
    monkeypatch.setattr(sparse_index, "ROWS_AT_ONCE_BYTES", 0)
    fresh = make_app()
    fresh.params = app.params
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(2.5 * CHUNK), int(1.2 * CHUNK))]
    fresh.init_kv_cache()
    with LogitSpy(fresh) as spy:
        s = ServingSession(fresh)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=5)
        drain(s)
        for i, p in enumerate(prompts):
            generated = [int(t) for t in s.requests[f"r{i}"].generated]
            positions = [len(p) - 1 + k for k in range(5)]
            want = ref.reference_logits(app.params, GEO, list(p) + generated[:-1], positions)
            got = np.stack([spy.at(i, q) for q in positions]).astype(np.float32)
            assert_is_the_reference(got, want)


#: MODEL at widths the latent kernels' gate admits (a latent of whole lanes,
#: two rotary keys a 128-lane row, a lane group of whole sublane tiles)
KERNEL_MODEL = dict(MODEL, kv_lora_rank=128, qk_rope_head_dim=64, qk_nope_head_dim=32, index_head_dim=64)


def test_the_chunk_program_attends_its_picked_keys_inside_the_chunk_kernel(monkeypatch):
    """Past ``index_topk`` a chunk pass the gate admits attends through
    ``latent_attend`` with the selection as the chunk kernel's predicate
    (interpret mode here), walking each row's live block groups: two requests
    of unlike lengths beside two idle rows give the reference's logits, no
    row's bucket is gathered and ``attend_selected`` serves no chunk pass."""
    from neuronx_distributed_inference_tpu.ops import latent_attention

    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    calls = {"with_selection": 0, "walk": 0}
    kernel = latent_attention.paged_latent_flash_attention

    def chunk_kernel(*a, **k):
        calls["with_selection"] += len(a) > 8 and a[8] is not None
        return kernel(*a, **k)

    walk = sparse_index.attend_selected

    def dense_walk(q_c, *a, **k):  # the decode step's, whose kv width the gate refuses here
        calls["walk"] += q_c.shape[1] > 1
        return walk(q_c, *a, **k)

    monkeypatch.setattr(latent_attention, "paged_latent_flash_attention", chunk_kernel)
    monkeypatch.setattr(sparse_index, "attend_selected", dense_walk)
    chunk = 32
    served = make_app(model=KERNEL_MODEL, chunk=chunk, pa_block_size=16, pa_num_blocks=40)
    geo = ref.geometry(KERNEL_MODEL, 1)
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (3 * chunk, 2 * chunk)]
    served.init_kv_cache()
    with LogitSpy(served) as spy:
        s = ServingSession(served)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=3)
        drain(s)
        for i, p in enumerate(prompts):
            generated = [int(t) for t in s.requests[f"r{i}"].generated]
            positions = [len(p) - 1 + k for k in range(3)]
            want = ref.reference_logits(served.params, geo, list(p) + generated[:-1], positions)
            got = np.stack([spy.at(i, q) for q in positions]).astype(np.float32)
            assert_is_the_reference(got, want)
    # traced once a program: the chunk programs past index_topk hold the kernel under a predicate
    assert calls["with_selection"] >= 1 and calls["walk"] == 0


def test_the_chunk_walk_counter_is_the_kernels_walk_past_index_topk(monkeypatch):
    """``nxdi_chunk_kv_blocks_total`` for a model with an indexer: ``live``
    the blocks the prefilling rows' contexts hold, ``walked`` the whole groups
    of the LATENT chunk kernel's own ``blocks_per_group`` blocks up to each
    row's last live one, which is what the kernel copies and attends under a
    selection too: the session asks the kernel its pool serves (one function,
    two callers), and the call itself is read here for its ``P`` and its
    ``end`` operand. Not the kv bucket's width, and not the GQA prefill
    kernel's group."""
    from neuronx_distributed_inference_tpu.ops import latent_attention
    from neuronx_distributed_inference_tpu.ops.latent_attention import blocks_per_group
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    chunk, bs = 32, 16
    served = make_app(model=KERNEL_MODEL, chunk=chunk, pa_block_size=bs, pa_num_blocks=40)
    served.init_kv_cache()
    tel = TelemetrySession(enabled=True)
    s = ServingSession(served, telemetry=tel)
    assert s._chunk_kv_blocks_walked.func is latent_attention.kv_blocks_walked
    rng = np.random.default_rng(23)
    lengths = (3 * chunk + 8, 2 * chunk)
    for i, n in enumerate(lengths):
        assert s.add_request(f"r{i}", rng.integers(0, VOCAB, size=n), max_new_tokens=2)
    drain(s)
    snap = tel.registry.snapshot()["nxdi_chunk_kv_blocks_total"]["samples"]
    tel.stop()
    got = {x["labels"]["kind"]: x["value"] for x in snap}
    # the passes: both rows at 32 and 64 tokens (kv bucket 64: a table of 4
    # blocks, one group), then the longer row alone at 96 and 104 (bucket 128)
    passes = [(64, [32, 32]), (64, [64, 64]), (128, [96]), (128, [104])]
    live = [[-(-n // bs) for n in rows] for _, rows in passes]
    assert got["live"] == sum(map(sum, live)) == 25
    walked = 0
    for (bucket, _), blocks in zip(passes, live):
        P = blocks_per_group(1, bs, KERNEL_MODEL["kv_lora_rank"], jnp.float32, bucket // bs)
        walked += sum(-(-n // P) * P for n in blocks)
    assert got["walked"] == walked == 32
    # and the kernel itself ends a row's walk at its last live block
    c, kr = served.kv_cache.k, served.kv_cache.v
    n = 96  # r0's third pass, past index_topk
    positions = jnp.arange(n - chunk, n, dtype=jnp.int32)[None]
    seen = {}
    call = latent_attention._da._common_call

    def common_call(kernel, *a, operands, **k):
        seen["P"], seen["end"] = kernel.keywords["P"], operands[0][2]
        return call(kernel, *a, operands=operands, **k)

    monkeypatch.setattr(latent_attention._da, "_common_call", common_call)
    latent_attention.paged_latent_flash_attention.__wrapped__(
        jnp.zeros((1, chunk, 4, 128)), jnp.zeros((1, chunk, 4, 64)), c, kr, jnp.int32(0),
        jnp.zeros((1, 128 // bs), jnp.int32), positions, jnp.asarray([n], jnp.int32),
        jnp.ones((1, chunk, 128), bool), scale=1.0, interpret=True)
    assert int(seen["end"][0]) == -(-n // bs) == live[2][0]
    # the session's count of that pass is NG_live x P of the call's own P
    groups = -(-int(seen["end"][0]) // seen["P"])
    assert s._chunk_kv_blocks_walked(live[2], 128 // bs) == groups * seen["P"] == 8


#: (blocks of a pool, latent width, pool dtype, table width): the registered
#: pool of both latent cells at glm-5's two wide buckets and kimi's widest,
#: and tier-1's float32 pools
@pytest.mark.parametrize("bs,r,dtype,MB", [
    (32, 512, "bfloat16", 528), (32, 512, "bfloat16", 384), (32, 512, "bfloat16", 256),
    (32, 128, "float32", 48), (16, 128, "float32", 8),
])
def test_the_sessions_chunk_walk_is_the_latent_kernels_own_group(bs, r, dtype, MB):
    """For a latent pool the walked-block count of a chunk pass is ``NG_live x
    P`` of the ``P`` the kernel call computes for the same pool and table
    (``latent_attention.kv_blocks_walked`` over ``blocks_per_group``: the
    kernel's launch reads the same function), whatever the GQA prefill
    kernel's group is at that block shape."""
    from neuronx_distributed_inference_tpu.ops import latent_attention as la

    B, q, heads = 3, 16, 2
    live = [MB, 1, -(-MB // 3)]  # blocks a row's context holds with the chunk in
    seen = {}

    def common_call(kernel, *, operands, out_shape, **k):
        seen["P"], seen["table"] = kernel.keywords["P"], operands[0][1].shape
        return jnp.zeros(out_shape.shape, out_shape.dtype)

    sds = jax.ShapeDtypeStruct
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la._da, "_common_call", common_call)
        jax.eval_shape(
            functools.partial(la.paged_latent_flash_attention.__wrapped__, scale=1.0),
            sds((B, q, heads, r), dtype), sds((B, q, heads, 64), dtype),
            sds((2, 9, 1, bs, r), dtype), sds((2, 9, 1, bs // 2, 128), dtype), sds((), jnp.int32),
            sds((B, MB), jnp.int32), sds((B, q), jnp.int32), sds((B,), jnp.int32))
    P = seen["P"]
    assert P == la.blocks_per_group(1, bs, r, dtype, MB) == min(1024 // bs, 1 << (MB.bit_length() - 1))
    assert seen["table"] == (B, -(-MB // P) * P)  # whole groups
    walked = la.kv_blocks_walked(live, MB, n_kv=1, bs=bs, head_dim=r, cache_dtype=dtype)
    assert walked == sum(-(-n // P) * P for n in live)


def test_the_session_counts_what_the_selection_scores_and_attends(app):
    """``nxdi_sparse_keys_scored_total`` / ``..._attended_total`` /
    ``nxdi_index_keys_written_total`` from a recording session: the sums the
    arithmetic gives for one prompt and its decode steps."""
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    app.init_kv_cache()
    tel = TelemetrySession(enabled=True)
    s = ServingSession(app, telemetry=tel)
    n, new = 40, 4
    assert s.add_request("r", np.arange(n) % VOCAB, max_new_tokens=new)
    drain(s)
    snap = tel.registry.snapshot()
    tel.stop()

    from benchmark.harness.readers.counter import total as read_total

    grown = lambda name: read_total(snap, name, {})
    L = 3
    # chunks of 16 at kv buckets 16, 64, 64: the first scores nothing
    live = np.arange(1, n + 1)
    # the first token comes from the last chunk; the decode pass dispatched
    # 1-ahead behind the last one needed is counted too (its row is discarded)
    passes = int(grown("nxdi_decode_rows_total"))
    assert passes in (new - 1, new)
    decode_live = np.arange(n + 1, n + 1 + passes)
    scored = live[16:].sum() + decode_live.sum()
    attended = np.minimum(live, TOPK).sum() + np.minimum(decode_live, TOPK).sum()
    assert grown("nxdi_index_keys_written_total") == L * (n + passes)
    assert grown("nxdi_index_keys_written_total") == grown("nxdi_latent_tokens_written_total")
    assert grown("nxdi_sparse_keys_scored_total") == L * scored
    assert grown("nxdi_sparse_keys_attended_total") == L * attended
