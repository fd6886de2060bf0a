"""Window and full attention mixed in one stack, on the serving path, against
the benchmark's plain reference, in tier-1.

``models/mellum.py`` serves a ``mellum`` stack (Mellum 2: Qwen3-MoE layers,
each a ``sliding_attention`` or a ``full_attention`` layer with its own
rotary table) through a paged cache of TWO lifetimes: the full layers page
over the allocator's pool, the window layers keep a ring of blocks a slot
(``modules/block_kvcache.WindowRing``) that both paged kernels read through
a table made in the graph. Here that path — ``ServingSession``, the chunk
program, 1-ahead decode, the ring's wraps, a reused slot, preemption and
resume, the kernels under a lower frontier — is held by logits to
``benchmark/harness/references/mellum.py`` (a dense mask over the whole
sequence, YaRN written out from the config's keys, no cache, no line of the
program's code). Small size, CPU, weights from ``system.make_weights``.
``benchmark/selftest/test_correct_mellum.py`` proves the benchmark's RULE on
the bf16 model with faults planted.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import system
from benchmark.harness.references import mellum as ref
from neuronx_distributed_inference_tpu.config import TwoLifetimeCacheError
from neuronx_distributed_inference_tpu.models import mellum
from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import LogitSpy, drain

WINDOW = 16
BLOCK = 4
SLOTS = 4
VOCAB = 256
SEED = 5800000017
#: float32 served against the float32 reference, absolute on logits of scale
#: ~0.2: another order of the same sums, and two rotary tables that differ in
#: their last bit (the program's in float32, the reference's rounded from
#: float64), which a position of 100 and q/k norms of 2.5 carry into the
#: attention's logits. A window off by one key reads 50 times this
#: (test_a_window_off_by_one_key_is_seen)
TOL = 1e-4

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}

#: the issue's small size: window 16, block 4, two periods of [W, W, W, F], 8
#: experts top-2; the published rope keys with the original context cut to 32
#: so that a context of ~100 lies past it as 16k lies past 8192
MODEL = dict(
    model_type="mellum", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, vocab_size=VOCAB,
    rms_norm_eps=1e-6, hidden_act="silu", tie_word_embeddings=False, attention_bias=False,
    sliding_window=WINDOW, use_sliding_window=True, max_window_layers=0,
    layer_types=["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 8, max_position_embeddings=4096,
    rope_parameters={"full_attention": YARN,
                     "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
)
#: a head on the 128 lanes: what the paged kernels' group path takes
LANES = dict(MODEL, head_dim=128, num_attention_heads=2, num_key_value_heads=1)
#: benchmark/configs/mellum2-12b-a2.5b.json's rules
WEIGHTS = [{"match": "self_attn/[qk]_norm/weight$", "mean": 1.5, "std": 0.1},
           {"match": "^embed_tokens/weight$", "std": 0.5}]


def make_app(model=None, chunk=8, dtype="float32", **tpu):
    cfg = dict(
        model or MODEL,
        tpu_config=dict(dict(
            dtype=dtype, tp_degree=1, batch_size=SLOTS, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=160, is_chunked_prefill=True, fused_qkv=True,
            output_logits=True,
        ), **tpu),
        chunked_prefill=dict(max_num_seqs=SLOTS, kernel_q_tile_size=chunk),
    )
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED, WEIGHTS))
    return app


@pytest.fixture(scope="module")
def app():
    return make_app()


@pytest.fixture(scope="module")
def kernel_app():
    """Both paged kernels forced (interpret mode here), a chunk wider than the window."""
    return make_app(LANES, chunk=32, attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)


GEO = ref.geometry(MODEL, 1)


def assert_is_the_reference(got, want, tol=TOL):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(1.0, np.abs(want).max()))


def prompt_of(n, k=0):
    return np.random.default_rng([SEED, n, k]).integers(0, VOCAB, size=n).astype(np.int32)


def serve(app, prompts, new_tokens=9, together=False):
    """Each prompt through ONE session, one request after another (so slot 0
    is reused) or all ``together``: per request (generated tokens, positions
    read, served logits at the last prompt position and after each generated
    token but the last, the request)."""
    app.init_kv_cache()
    out = []
    with LogitSpy(app) as spy:
        s = ServingSession(app)

        def read(i, prompt, slot):
            req = s.requests[f"r{i}"]
            generated = [int(t) for t in req.generated]
            assert req.status == "finished" and len(generated) == new_tokens
            n = len(prompt)
            positions = [n - 1 + k for k in range(new_tokens)]
            got = np.stack([spy.at(slot, p) for p in positions]).astype(np.float32)
            return generated, positions, got, req

        if together:
            for i, prompt in enumerate(prompts):
                assert s.add_request(f"r{i}", prompt, max_new_tokens=new_tokens)
            slots = {i: s.requests[f"r{i}"].slot for i in range(len(prompts))}
            drain(s, limit=2000)
            out = [read(i, p, slots[i]) for i, p in enumerate(prompts)]
        else:
            for i, prompt in enumerate(prompts):
                assert s.add_request(f"r{i}", prompt, max_new_tokens=new_tokens)
                drain(s, limit=2000)
                out.append(read(i, prompt, 0))
                spy.rows.clear()
    return out, s


def reference_at(app, geo, prompt, generated, positions):
    tokens = list(prompt) + list(generated[:-1])
    return ref.reference_logits(app.params, geo, tokens, positions)


# ---------------------------------------------------------------------------
# the ring: its bound, its table, its write slots
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,chunk,block,want", [
    (16, 8, 4, 7), (16, 32, 4, 13), (1024, 128, 32, 37), (1024, 8, 32, 34), (5, 3, 4, 3)])
def test_a_ring_holds_the_window_and_one_chunk_rounded_up_plus_one(window, chunk, block, want):
    R = bk.window_ring_blocks(window, chunk, block)
    assert R == want
    # whatever the position a pass starts at, what it overwrites lies behind
    # the window of its first query
    for p in range(0, 3 * R * block):
        last_lost = ((p + chunk - 1) // block - R + 1) * block - 1
        assert last_lost < p - window + 1


def test_the_builder_declares_two_lifetimes_and_the_pool_spans_the_full_layers(app):
    b = app.builder
    assert b.cache_layers() == (bk.WINDOW_KV,) * 3 + (bk.PAGED_KV,) + (bk.WINDOW_KV,) * 3 + (bk.PAGED_KV,)
    assert app.paged_layers == 2 and b.ring_blocks() == 7
    cache = app.kv_cache
    assert isinstance(cache, bk.HybridBlockCache) and isinstance(cache.state, bk.WindowRing)
    assert cache.k.shape == (2, 161, 2, BLOCK, 16)
    ring = cache.state
    assert ring.k.shape == ring.v.shape == (6, SLOTS * 7 + 1, 2, BLOCK, 16)
    assert (ring.num_slots, ring.num_layers, ring.block_size, ring.KIND) == (SLOTS, 6, BLOCK, "window_ring")
    assert ring.slot_bytes * SLOTS == ring.nbytes - ring.nbytes // ring.k.shape[1]
    # a session counts what a slot holds whatever its context
    s = ServingSession(app)
    assert s.block_bytes == bk.kv_block_bytes(2, BLOCK, 2, 16, dtype=jnp.float32)
    assert s.slot_state and s.slot_state_kind == "window_ring"
    assert s.window_layers == 6 and s.full_layers == 2 and s.window == WINDOW
    # every other model's cache has the fields it had
    plain = bk.init_block_cache(2, 4, 8, 2, 16)
    assert [f for f in plain.__dataclass_fields__] == ["k", "v", "extra"]
    # the weights: one tree a run of like layers, the Qwen3-MoE tree cut at load
    runs = app.params["layers"]
    assert [r["input_layernorm"]["weight"].shape[0] for r in runs] == [3, 1, 3, 1]
    assert mellum.layer_runs(b.kinds) == (("window", 0, 3), ("full", 0, 1), ("window", 3, 3), ("full", 1, 1))
    assert [s["mlp"]["experts"]["gate_proj"]["weight"] for s in b.param_shapes()["layers"]] == [
        (3, 8, 64, 32), (1, 8, 64, 32), (3, 8, 64, 32), (1, 8, 64, 32)]


def test_the_rings_table_and_slots_are_arithmetic_on_the_slot():
    ring = bk.init_window_ring(2, 3, 5, 4, 1, 8, jnp.float32)
    seq_ids = jnp.asarray([2, -1, 0], jnp.int32)
    table = np.asarray(ring.block_table(seq_ids, 12))
    assert table[0].tolist() == [11 + j % 5 for j in range(12)]
    assert table[1].tolist() == [0] * 12 and table[2].tolist() == [1 + j % 5 for j in range(12)]
    positions = jnp.asarray([[17, 18, 19, 20], [0, 1, 2, 3], [38, 39, 40, 41]], jnp.int32)
    valid = jnp.asarray([[1, 1, 1, 0], [1, 1, 1, 1], [1, 1, 1, 1]], bool)
    slots = np.asarray(ring.slot_mapping(seq_ids, positions, valid))
    assert slots[1].tolist() == [-1] * 4 and slots[0, 3] == -1
    for row, s in ((0, 2), (2, 0)):
        for q in range(4):
            p = int(positions[row, q])
            if valid[row, q]:
                assert slots[row, q] == table[row, p // 4] * 4 + p % 4
                assert 1 + s * 5 <= slots[row, q] // 4 <= (s + 1) * 5
    filled = ring.fill_slots([2], 7.0)
    assert float(filled.k[:, 11:16].min()) == 7.0 and float(filled.k[:, :11].max()) == 0.0
    assert filled.ring_blocks == 5


# ---------------------------------------------------------------------------
# the two rotary tables
# ---------------------------------------------------------------------------


def test_yarn_times_attention_factor_is_the_closed_form(app):
    """The builder's two tables against the formula written out: pair i of
    the full layers' table turns ``original / (2 pi) * theta ** (-2i / d)``
    times over the original context; above ``beta_fast`` turns it keeps its
    frequency, under ``beta_slow`` it is slowed by ``factor``, between them a
    ramp over the pair index; cos and sin carry ``attention_factor`` = 0.1
    ln(factor) + 1."""
    tables = app.builder.rope_tables()
    assert set(tables) == {"window", "full"}
    d, theta = 16, 500000.0
    plain = theta ** (-np.arange(0, d, 2) / d)
    inv, factor = tables["window"]
    np.testing.assert_allclose(inv, plain, rtol=1e-6)
    assert factor == 1.0
    inv, factor = tables["full"]
    assert factor == pytest.approx(0.1 * math.log(16) + 1) == YARN["attention_factor"]
    low = math.floor(d * math.log(32 / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(d * math.log(32 / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    ramp = np.clip((np.arange(d // 2) - max(low, 0)) / (min(high, d - 1) - max(low, 0)), 0, 1)
    np.testing.assert_allclose(inv, plain / 16 * ramp + plain * (1 - ramp), rtol=1e-6)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(plain[-1] / 16)
    # the reference writes it out on its own and agrees
    mine, f = ref.rotary_table(GEO.rope(ref.FULL), d)
    np.testing.assert_allclose(mine, inv, rtol=1e-6)
    assert f == factor
    # at the published keys: 64 pairs, the ramp from pair 18 to pair 35
    pub = dict(YARN, original_max_position_embeddings=8192)
    inv, _ = mellum.rope_table(pub, 128)
    plain = theta ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert plain[19] / 16 < inv[19] < plain[19] and plain[34] / 16 < inv[34] < plain[34]
    assert np.all(np.diff(inv) < 0)


# ---------------------------------------------------------------------------
# the serving path against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [10, 14, 20, 100], ids=["under", "across", "past", "wraps"])
def test_prefill_then_decode_is_the_reference(app, n):
    """Contexts under the window (10 + 9 tokens), across it while decoding
    (14), past it already in the prompt (20) and past several wraps of a
    window layer's ring of 28 tokens (100): the chunk narrower than the
    window."""
    prompt = prompt_of(n)
    (generated, positions, got, _), = serve(app, [prompt])[0]
    assert_is_the_reference(got, reference_at(app, GEO, prompt, generated, positions))


@pytest.mark.parametrize("fault", ["window_off_by_one", "window_ignored", "default_rope_in_full"])
def test_a_window_off_by_one_key_is_seen(app, fault):
    """What the comparison above can see: the reference with one part wrong
    is far from the served logits, by the same measure."""
    prompt = prompt_of(100)
    (generated, positions, got, _), = serve(app, [prompt])[0]
    tokens = list(prompt) + list(generated[:-1])
    wrong = ref.reference_logits(app.params, GEO, tokens, positions, fault=fault)
    assert np.abs(got - wrong).max() > 20 * TOL


def test_a_reused_slot_and_rows_served_together_are_the_reference(app):
    """Slot 0 serves a long request, then a short one over the same ring
    (what the first left there lies past the second's keys); and four
    requests of unlike lengths share the chunk and decode programs' rows."""
    prompts = [prompt_of(90), prompt_of(7)]
    for (generated, positions, got, _), prompt in zip(serve(app, prompts)[0], prompts):
        assert_is_the_reference(got, reference_at(app, GEO, prompt, generated, positions))
    prompts = [prompt_of(n, 1) for n in (5, 33, 60, 17)]
    out, s = serve(app, prompts, together=True)
    for (generated, positions, got, _), prompt in zip(out, prompts):
        assert_is_the_reference(got, reference_at(app, GEO, prompt, generated, positions))
    assert not s.allocator.seq_blocks  # every full-lifetime block came back


def test_a_chunk_wider_than_the_window_is_the_reference():
    wide = make_app(chunk=32)
    assert wide.builder.ring_blocks() == 13
    prompt = prompt_of(75)
    (generated, positions, got, _), = serve(wide, [prompt])[0]
    assert_is_the_reference(got, reference_at(wide, GEO, prompt, generated, positions))


@pytest.mark.parametrize("n", [12, 70], ids=["under", "wraps"])
def test_both_kernels_serve_the_reference_through_the_ring(kernel_app, n):
    """The chunk program's paged flash kernel under its lower frontier and
    the decode kernel (its in-kernel KV write too) over the ring's table, a
    head on the lanes, the chunk (32) wider than the window (16)."""
    geo = ref.geometry(LANES, 1)
    prompt = prompt_of(n, 2)
    (generated, positions, got, _), = serve(kernel_app, [prompt])[0]
    assert_is_the_reference(got, reference_at(kernel_app, geo, prompt, generated, positions))


def test_preemption_and_resume_restore_both_lifetimes():
    """A full-lifetime pool too small for two requests at once: one is
    preempted as it decodes, re-prefills BOTH lifetimes when the other has
    left, and every token it is served is the reference's."""
    small = make_app(pa_num_blocks=34)
    prompts = [prompt_of(60, 3), prompt_of(60, 4)]
    out, s = serve(small, prompts, new_tokens=24, together=True)
    assert sum(req.preemptions for *_, req in out) >= 1
    for (generated, positions, got, _), prompt in zip(out, prompts):
        assert_is_the_reference(got, reference_at(small, GEO, prompt, generated, positions))
    snap = s.tel.registry.snapshot() if s.tel.enabled else None
    assert snap is None or "nxdi_kv_window_blocks_held" in snap


def test_the_step_returns_its_choices_layer_by_layer():
    """``output_choices``: the experts every layer chose at every position,
    in MODEL order whatever a layer's kind, and the reference that follows
    them agrees with them."""
    app = make_app(output_choices=True)
    prompt = prompt_of(40, 5)
    tc = app.config.tpu_config
    table = np.arange(1, 1 + 256 // BLOCK, dtype=np.int32)[None, :]
    chose = []
    for start in range(0, 40, 8):
        pos = start + np.arange(8, dtype=np.int32)[None, :]
        mask = (np.arange(256)[None, :] < start + 8).astype(np.int32)
        sm = table[0, pos // BLOCK] * BLOCK + pos % BLOCK
        _, _, aux = app.forward(prompt[None, start : start + 8], pos, np.zeros(1, np.int32),
                                attention_mask=mask, slot_mapping=sm, block_table=table, phase="tkg")
        chose.append(np.asarray(aux["experts"][0]))
    chose = np.concatenate(chose)
    assert chose.shape == (40, 8, 2)
    _, scores, own = ref.forward(app.params, GEO, prompt, [39])
    assert (np.sort(chose, axis=-1) == np.sort(np.transpose(own, (1, 0, 2)), axis=-1)).mean() > 0.99
    assert tc.output_choices


# ---------------------------------------------------------------------------
# the kernels alone, under a lower frontier, against the dense mask
# ---------------------------------------------------------------------------


def _pool(rng, blocks, n_kv, bs, D):
    shape = (2, blocks + 1, n_kv, bs, D)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _dense(q, k_cache, v_cache, layer, table, positions, kv_limit, window, scale):
    """Plain attention over the gathered blocks under the dense mask."""
    k, v = bk.read_block_cache_at_layer(k_cache, v_cache, layer, table)
    n_rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, n_rep, axis=2), jnp.repeat(v, n_rep, axis=2)
    cols = jnp.arange(k.shape[1])[None, None, :]
    pos = positions[:, :, None]
    seen = (cols <= pos) & (cols < kv_limit[:, None, None])
    if window is not None:
        seen = seen & (cols > pos - window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.where(seen[:, None], p, 0.0), v)


#: (window, first query position a row): an edge inside a block (window 40 is
#: no whole number of blocks of 32), a frontier inside a group of 16 blocks
#: (rows that start past 512 + window), no frontier to speak of (a context
#: under the window: kv_start 0), and a window wider than the bucket
FRONTIERS = [(40, (700, 90, 0)), (100, (1200, 513, 612)), (1024, (300, 0, 1300)), (4096, (1400, 64, 5))]


@pytest.mark.parametrize("window,starts", FRONTIERS)
def test_the_chunk_kernel_under_a_lower_frontier_is_the_dense_mask(window, starts):
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import paged_flash_attention

    rng = np.random.default_rng(window)
    bs, D, n_kv, n_rep, Sq, MB = 32, 128, 1, 2, 128, 48
    k_cache, v_cache = _pool(rng, 3 * MB, n_kv, bs, D)
    B = len(starts)
    table = jnp.asarray(1 + np.arange(B * MB).reshape(B, MB), jnp.int32)
    positions = jnp.asarray([s + np.arange(Sq) for s in starts], jnp.int32)
    # the last row feeds fewer tokens than the program is wide: its tail is padding
    fed = np.asarray([Sq, Sq, 37])
    kv_limit = jnp.asarray(np.asarray(starts) + fed, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Sq, n_kv * n_rep, D)), jnp.float32)
    got = paged_flash_attention(q, k_cache, v_cache, table, positions, kv_limit, scale=0.09,
                                n_rep=n_rep, layer_idx=jnp.int32(1), interpret=True, window=window)
    want = _dense(q, k_cache, v_cache, 1, table, positions, kv_limit, window, 0.09)
    for b in range(B):
        np.testing.assert_allclose(got[b, : fed[b]], want[b, : fed[b]], atol=2e-5)
    # and it is not the causal answer, in a row whose context is past the window
    deep = int(np.argmax(starts))
    if window < starts[deep]:
        causal = paged_flash_attention(q, k_cache, v_cache, table, positions, kv_limit, scale=0.09,
                                       n_rep=n_rep, layer_idx=jnp.int32(1), interpret=True)
        assert float(jnp.abs(causal[deep] - got[deep])[: fed[deep]].max()) > 1e-3


def test_the_chunk_kernel_reads_nothing_behind_a_rows_window():
    """Blocks behind the lower frontier's GROUP hold NaN: a kernel that
    copied them (or a table that sent a live entry there) would show it."""
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import paged_flash_attention

    rng = np.random.default_rng(7)
    bs, D, Sq, MB, window = 32, 128, 128, 48, 100
    k_cache, v_cache = _pool(rng, MB, 1, bs, D)
    table = jnp.asarray(1 + np.arange(MB)[None, :], jnp.int32)
    start = 1200  # first key 1101: block 34, group 2 of (16 blocks = 512 tokens)
    dead = np.arange(1, 1 + 32)  # the blocks of groups 0 and 1
    k_cache, v_cache = k_cache.at[:, dead].set(jnp.nan), v_cache.at[:, dead].set(jnp.nan)
    positions = jnp.asarray(start + np.arange(Sq)[None, :], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, Sq, 2, D)), jnp.float32)
    got = paged_flash_attention(q, k_cache, v_cache, table, positions, jnp.asarray([start + Sq]),
                                scale=0.09, n_rep=2, layer_idx=jnp.int32(0), interpret=True,
                                window=window)
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("window,contexts", [(40, (700, 33, 1)), (100, (1500, 600, 513)), (1024, (1536, 90, 1025))])
def test_the_decode_kernel_walks_a_windows_groups_off_its_mask(window, contexts):
    """The decode kernel reads a row's first and last live block off the
    mask it is given: under a window's mask it starts at the group of the
    first key in the window, and what lies in the groups before it (NaN
    here) is never read."""
    from neuronx_distributed_inference_tpu.ops.decode_attention import paged_tkg_decode_attention

    rng = np.random.default_rng(window)
    bs, D, MB = 32, 128, 48
    B = len(contexts)
    k_cache, v_cache = _pool(rng, B * MB, 1, bs, D)
    table = np.asarray(1 + np.arange(B * MB).reshape(B, MB), np.int32)
    for b, n in enumerate(contexts):  # the groups wholly behind a row's window
        dead = table[b, : max(n - window, 0) // 512 * 16]
        k_cache, v_cache = k_cache.at[:, dead].set(jnp.nan), v_cache.at[:, dead].set(jnp.nan)
    pos = jnp.asarray(contexts, jnp.int32)[:, None] - 1
    cols = jnp.arange(MB * bs)[None, :]
    mask = ((cols <= pos) & (cols > pos - window))[:, None, None, :]
    q = jnp.asarray(rng.normal(size=(B, 1, 2, D)), jnp.float32)
    got = paged_tkg_decode_attention(q, k_cache, v_cache, jnp.int32(1), jnp.asarray(table), mask,
                                     scale=0.09, n_kv=1, interpret=True)
    clean = jnp.nan_to_num(k_cache), jnp.nan_to_num(v_cache)
    want = _dense(q, *clean, 1, jnp.asarray(table), pos, pos[:, 0] + 1, window, 0.09)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_decode_kernel_asks_for_vmem_only_where_its_mask_slab_needs_it(monkeypatch):
    """8 q heads a KV head x 16 positions over 16384 keys is a mask slab of 18
    MiB with its twin: the launch asks the compiler for it; every shape
    served before (a slab under the compiler's own 16 MiB) asks nothing."""
    from neuronx_distributed_inference_tpu.ops import decode_attention as da

    asked = []
    real = da._common_call
    monkeypatch.setattr(da, "_common_call", lambda *a, **kw: asked.append(kw.get("vmem_limit_bytes")) or real(*a, **kw))
    pool = jax.ShapeDtypeStruct((2, 65, 4, 32, 128), jnp.bfloat16)

    def lower(K, MB, n_rep):
        q = jax.ShapeDtypeStruct((8, K, 4 * n_rep, 128), jnp.bfloat16)
        mask = jax.ShapeDtypeStruct((8, 1, K, MB * 32), jnp.bool_)
        table = jax.ShapeDtypeStruct((8, MB), jnp.int32)
        jax.eval_shape(lambda q, k, v, t, m: da.paged_tkg_decode_attention(
            q, k, v, jnp.int32(0), t, m, scale=0.1, n_kv=4, interpret=True), q, pool, pool, table, mask)

    lower(16, 512, 8)  # mellum's 16-position chunk pass at kv 16384
    lower(16, 256, 8)  # at kv 8192
    lower(1, 512, 8)  # its decode step
    lower(16, 256, 2)  # the 1.7B's widest
    assert asked[0] is not None and asked[0] > da.SCOPED_VMEM_BYTES and asked[1:] == [None, None, None]
    assert da.pages_per_step(4, 32, 128, jnp.bfloat16, 512) == 16
    assert da.kv_blocks_walked([3, 40, 500], 512, n_kv=4, bs=32, head_dim=128, cache_dtype=jnp.bfloat16) == 16 + 48 + 512


# ---------------------------------------------------------------------------
# what a cache of two lifetimes refuses, by type
# ---------------------------------------------------------------------------

REFUSED = {
    "prefix_caching": dict(is_prefix_caching=True),
    "speculation": dict(speculation_length=4),
    "quantised_pool": dict(kv_cache_dtype="int8"),
    "tp_degree": dict(tp_degree=2),
    "ep_degree": dict(ep_degree=2),
    "hand_off": dict(is_prefill_stage=True),
    "contiguous_cache": dict(is_block_kv_layout=False, is_chunked_prefill=False),
    "whole_prompt_prefill": dict(is_chunked_prefill=False),
    "a_window_of_the_configs": dict(sliding_window=8),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_two_lifetimes_cannot_do_is_refused_by_type(name):
    with pytest.raises(TwoLifetimeCacheError, match="two\n? ?lifetimes"):
        make_app(**REFUSED[name])


def test_the_ragged_step_is_refused_by_type():
    with pytest.raises((TwoLifetimeCacheError, NotImplementedError, ValueError)) as e:
        make_app(serving_ragged=True)
    assert isinstance(e.value, TwoLifetimeCacheError) or "ragged" in str(e.value)


def test_a_layer_list_the_builder_does_not_know_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        make_app(dict(MODEL, layer_types=["sliding_attention"] * 7 + ["chunked_attention"]))
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        make_app(dict(MODEL, mlp_layer_types=["sparse"] * 7 + ["dense"]))
    with pytest.raises(NotImplementedError, match="rope_type"):
        make_app(dict(MODEL, rope_parameters=dict(MODEL["rope_parameters"],
                                                  full_attention={"rope_type": "llama3", "rope_theta": 1e4})))


def test_a_stack_of_full_layers_alone_keeps_one_lifetime():
    """No window layer: a plain pool, no ring, and the reference still."""
    model = dict(MODEL, num_hidden_layers=2, layer_types=["full_attention"] * 2,
                 mlp_layer_types=["sparse"] * 2)
    plain = make_app(model)
    assert type(plain.kv_cache) is bk.BlockKVCache and plain.paged_layers == 2
    prompt = prompt_of(30, 6)
    (generated, positions, got, _), = serve(plain, [prompt])[0]
    assert_is_the_reference(got, reference_at(plain, ref.geometry(model, 1), prompt, generated, positions))


# ---------------------------------------------------------------------------
# what the session counts
# ---------------------------------------------------------------------------


def test_the_session_counts_live_and_attended_keys_by_kind():
    from neuronx_distributed_inference_tpu.telemetry.tracing import TelemetrySession

    app = make_app()
    tel = TelemetrySession()
    s = ServingSession(app, telemetry=tel)
    prompt = prompt_of(40, 8)
    assert s.add_request("r", prompt, max_new_tokens=3)
    drain(s)
    snap = tel.registry.snapshot()

    def total(name, **labels):
        return sum(x["value"] for x in snap[name]["samples"]
                   if all(x["labels"].get(k) == v for k, v in labels.items()))

    # the prompt: query t has t + 1 live keys and attends min(t + 1, 16) in a window layer
    live = sum(range(1, 41))
    seen = sum(min(t, WINDOW) for t in range(1, 41))
    assert total("nxdi_attn_keys_live_total", program="chunk", layer_kind="full") == 2 * live
    assert total("nxdi_attn_keys_attended_total", program="chunk", layer_kind="full") == 2 * live
    assert total("nxdi_attn_keys_live_total", program="chunk", layer_kind="window") == 6 * live
    assert total("nxdi_attn_keys_attended_total", program="chunk", layer_kind="window") == 6 * seen
    # decode passes at contexts 41 and 42 (the third token needs no pass of
    # its own; a 1-ahead pass dispatched before the last token is known counts too)
    passes = total("nxdi_steps_total", kind="decode")
    assert passes >= 2
    assert total("nxdi_attn_keys_attended_total", program="decode", layer_kind="window") == 6 * WINDOW * passes
    assert total("nxdi_attn_keys_live_total", program="decode", layer_kind="full") == 2 * sum(
        41 + i for i in range(int(passes)))
    # the ring: held by a live slot, none when the session is drained
    assert snap["nxdi_kv_window_blocks_held"]["samples"][0]["value"] == 0
    assert total("nxdi_kv_window_blocks_recycled_total") > 0
