"""Continuous-batching serving session tests
(reference: seq-id masking + continuous batching integration tests)."""

import numpy as np
import pytest

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession


@pytest.fixture
def app():
    cfg = make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=4, ctx_batch_size=1)
    )
    sd = make_random_hf_state_dict(cfg)
    a = TpuModelForCausalLM(None, cfg)
    a.load(state_dict=sd)
    return a


def _plain_golden(app, prompt, n):
    """Golden: the same app's batch generate for a single prompt."""
    ids = np.asarray(prompt)[None, :]
    out = app.generate(ids, np.ones_like(ids), max_new_tokens=n)
    return out.sequences[0, ids.shape[1]:].tolist()


def test_interleaved_requests_match_batch_generate(app):
    """Requests added at different times on different slots must generate the
    same tokens as isolated runs (KV line isolation under continuous
    batching)."""
    p1 = [5, 17, 92, 41]
    p2 = [64, 3, 27, 9, 14, 33]
    p3 = [7, 7, 7]
    g1 = _plain_golden(app, p1, 6)
    g2 = _plain_golden(app, p2, 6)
    g3 = _plain_golden(app, p3, 6)

    sess = ServingSession(app)
    assert sess.add_request("r1", p1, max_new_tokens=6)
    sess.step()  # r1 decodes alone
    assert sess.add_request("r2", p2, max_new_tokens=6)
    sess.step()  # r1 + r2
    assert sess.add_request("r3", p3, max_new_tokens=6)
    results = sess.run_to_completion()

    assert results["r1"] == g1
    assert results["r2"] == g2
    assert results["r3"] == g3


def test_slot_reuse_after_finish(app):
    sess = ServingSession(app)
    for i in range(4):
        assert sess.add_request(f"a{i}", [1 + i, 2, 3], max_new_tokens=3)
    assert not sess.add_request("overflow", [9], max_new_tokens=2)  # full
    sess.run_to_completion()
    assert len(sess.free_slots) == 4
    # freed slots accept new requests and produce correct tokens
    golden = _plain_golden(app, [42, 10, 11], 4)
    assert sess.add_request("b0", [42, 10, 11], max_new_tokens=4)
    results = sess.run_to_completion()
    assert results["b0"] == golden


def test_eos_frees_slot(app):
    sess = ServingSession(app)
    golden = _plain_golden(app, [5, 6, 7], 8)
    eos = golden[2]  # force an early stop at the 3rd generated token
    sess.add_request("e", [5, 6, 7], max_new_tokens=8, eos_token_id=eos)
    results = sess.run_to_completion()
    assert results["e"] == golden[:3]
    assert len(sess.free_slots) == 4


def test_async_one_ahead_matches_sync():
    """The 1-ahead pipelined decode (async_mode) must produce exactly the
    tokens of the per-step synchronous path (VERDICT r2 next #5)."""
    outs = {}
    for async_mode in (False, True):
        cfg = make_tiny_config(
            tpu=dict(
                is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
                async_mode=async_mode,
            )
        )
        sd = make_random_hf_state_dict(cfg)
        a = TpuModelForCausalLM(None, cfg)
        a.load(state_dict=sd)
        sess = ServingSession(a)
        assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=6)
        sess.step()
        assert sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=6)
        outs[async_mode] = sess.run_to_completion()
    assert outs[True] == outs[False]


def test_drain_mixed_positions_no_eos():
    """Mixed-position no-EOS drain: a row near the position bound must not
    cap other rows' token counts (it finishes at its bound and the drain
    goes on without it)."""
    cfg = make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
                 seq_len=64)
    )
    sd = make_random_hf_state_dict(cfg)
    a = TpuModelForCausalLM(None, cfg)
    a.load(state_dict=sd)
    p_long = list(range(1, 51))  # near the 64-position bound
    p_short = [5, 17, 92, 41]
    g_short = _plain_golden(a, p_short, 40)
    sess = ServingSession(a)
    assert sess.add_request("r1", p_long, max_new_tokens=5)
    assert sess.add_request("r2", p_short, max_new_tokens=40)
    out = sess.run_to_completion()
    assert len(out["r2"]) == 40, len(out["r2"])
    assert out["r2"] == g_short
    assert len(out["r1"]) == 5


# ---------------------------------------------------------------------------
# round-4 serving hardening (VERDICT r3 weak #5): attention-DP x paged cache,
# ring-cache serving of over-window prompts, sampled assisted decoding
# ---------------------------------------------------------------------------


def test_attention_dp_paged_serving_matches():
    """Serving on the PAGED cache under attention-DP: same tokens as dp=1
    (the block pool replicates over dp; the batch shards around attention)."""
    prompts = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33]}
    results = {}
    sd = None
    for dp, tp in ((1, 1), (2, 4)):
        cfg = make_tiny_config(
            tpu=dict(
                is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                tp_degree=tp, attention_dp_degree=dp,
                is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16,
            )
        )
        if sd is None:
            sd = make_random_hf_state_dict(cfg)
        app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
        sess = ServingSession(app)
        assert sess.add_request("r1", prompts["r1"], max_new_tokens=6)
        assert sess.add_request("r2", prompts["r2"], max_new_tokens=8)
        while sess.active:
            sess.step()
        results[dp] = {rid: r.generated for rid, r in sess.requests.items()}
    assert results[1] == results[2]


def test_serving_over_window_prompt_matches_generate():
    """A prompt LONGER than the ring-bounded sliding window admits via the
    app's windowed prefill and generates the same tokens as generate()."""
    W = 16
    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            sliding_window=W, seq_len=64,
        )
    )
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    assert app.spec.bounded_window == W
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 120, size=24).tolist()  # 24 > W
    golden = _plain_golden(app, prompt, 6)

    app.init_kv_cache()
    sess = ServingSession(app)
    # occupy slot 0 first: the windowed admission must be SLOT-ALIGNED (a
    # row/line mismatch reproduces only at slot != 0)
    assert sess.add_request("first", [9, 9, 9], max_new_tokens=3)
    assert sess.add_request("long", prompt, max_new_tokens=6)
    results = sess.run_to_completion()
    assert results["long"] == golden


@pytest.mark.slow
def test_assisted_sampled_decoding():
    """Sampled assisted decoding: multinomial accept/reject path runs, is
    seed-deterministic, stays in-vocab, and raises a guided error when the
    apps are not configured for it."""
    from neuronx_distributed_inference_tpu.runtime.assisted import assisted_generate

    def _make(seed, do_sample):
        from neuronx_distributed_inference_tpu.config import OnDeviceSamplingConfig

        tpu = dict(output_logits=do_sample, seed=3)
        if do_sample:
            tpu["on_device_sampling_config"] = OnDeviceSamplingConfig(do_sample=True)
        cfg = make_tiny_config(tpu=tpu)
        sd = make_random_hf_state_dict(cfg, seed=seed)
        return TpuModelForCausalLM(None, cfg).load(state_dict=sd), sd

    target, _ = _make(0, True)
    draft, _ = _make(7, True)
    prompts = np.array([[5, 17, 92, 41], [64, 3, 27, 9]])
    mask = np.ones_like(prompts)
    out1 = assisted_generate(
        target, draft, prompts, mask, max_new_tokens=10,
        speculation_length=4, temperature=5.0, top_k=50,
    )
    assert out1.num_generated == 10
    gen = out1.sequences[:, prompts.shape[1]:]
    assert (gen >= 0).all() and (gen < target.config.vocab_size).all()

    # same seeds -> same tokens
    target.init_kv_cache()
    draft.init_kv_cache()
    out2 = assisted_generate(
        target, draft, prompts, mask, max_new_tokens=10,
        speculation_length=4, temperature=5.0, top_k=50,
    )
    np.testing.assert_array_equal(out1.sequences, out2.sequences)

    # high temperature must actually diversify vs greedy assisted
    tg, _ = _make(0, False)
    dg, _ = _make(7, False)
    greedy = assisted_generate(
        tg, dg, prompts, mask, max_new_tokens=10, speculation_length=4
    )
    assert greedy.sequences.tolist() != out1.sequences.tolist()

    # misconfiguration: sampling without logits raises a guided ValueError
    from neuronx_distributed_inference_tpu.config import OnDeviceSamplingConfig

    bad_cfg = make_tiny_config(
        tpu=dict(
            on_device_sampling_config=OnDeviceSamplingConfig(do_sample=True), seed=3
        )
    )
    bad_sd = make_random_hf_state_dict(bad_cfg, seed=0)
    bad = TpuModelForCausalLM(None, bad_cfg).load(state_dict=bad_sd)
    with pytest.raises(ValueError, match="output_logits"):
        assisted_generate(bad, dg, prompts, mask, max_new_tokens=4)


@pytest.mark.slow
def test_speculative_serving_matches_plain_serving():
    """Speculation under continuous batching: greedy verification must emit
    the same tokens as the plain session, with mid-stream request turnover
    and a (wrong-weights) draft that forces rejections."""
    from neuronx_distributed_inference_tpu.runtime.serving import (
        SpeculativeServingSession,
    )

    target_cfg = make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
    )
    target_sd = make_random_hf_state_dict(target_cfg, seed=0)
    plain_app = TpuModelForCausalLM(None, target_cfg).load(state_dict=target_sd)
    golden = {}
    for rid, prompt in (("r1", [5, 17, 92, 41]), ("r2", [64, 3, 27, 9, 14, 33]),
                        ("r3", [7, 8])):
        ids = np.asarray(prompt)[None, :]
        golden[rid] = plain_app.generate(
            ids, np.ones_like(ids), max_new_tokens=8
        ).sequences[0, ids.shape[1]:].tolist()

    for draft_seed in (0, 7):  # identical draft (full accept) + wrong draft
        target = TpuModelForCausalLM(
            None, make_tiny_config(
                tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
            )
        ).load(state_dict=target_sd)
        draft = TpuModelForCausalLM(
            None, make_tiny_config(
                tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
            )
        ).load(state_dict=make_random_hf_state_dict(target_cfg, seed=draft_seed))
        sess = SpeculativeServingSession(target, draft, speculation_length=4)
        assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=8)
        assert sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
        results = {}
        while sess.active:
            sess.step()
            if "r3" not in sess.requests and sess.free_slots:
                assert sess.add_request("r3", [7, 8], max_new_tokens=8)
        results = {rid: r.generated for rid, r in sess.requests.items()}
        assert results == golden, f"draft_seed={draft_seed}"


def test_speculative_serving_gates():
    from neuronx_distributed_inference_tpu.runtime.serving import (
        SpeculativeServingSession,
    )

    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16,
        )
    )
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    with pytest.raises(NotImplementedError, match="contiguous"):
        SpeculativeServingSession(app, app)


def test_speculative_serving_near_limit_matches():
    """Requests within k-1 positions of the limit must keep emitting the
    plain session's tokens via single-step fallback (no early truncation)."""
    from neuronx_distributed_inference_tpu.runtime.serving import (
        SpeculativeServingSession,
    )

    mk = lambda: make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
    )
    sd = make_random_hf_state_dict(mk(), seed=0)
    prompt = list(range(40, 90))  # 50 tokens; seq_len 64 -> ~13 positions left
    plain = TpuModelForCausalLM(None, mk()).load(state_dict=sd)
    sess_p = ServingSession(plain)
    assert sess_p.add_request("r", prompt, max_new_tokens=30)
    golden = sess_p.run_to_completion()["r"]
    assert len(golden) < 30  # hit the position bound, not the budget

    target = TpuModelForCausalLM(None, mk()).load(state_dict=sd)
    draft = TpuModelForCausalLM(None, mk()).load(
        state_dict=make_random_hf_state_dict(mk(), seed=5)
    )
    sess = SpeculativeServingSession(target, draft, speculation_length=4)
    assert sess.add_request("r", prompt, max_new_tokens=30)
    out = sess.run_to_completion()["r"]
    assert out == golden


# ---------------------------------------------------------------------------
# the speculative session round by round, over a draft that always agrees
# (the target's own weights) and one that seldom does (other weights)
# ---------------------------------------------------------------------------

SPEC_K = 4
SPEC_PROMPTS = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33], "r3": [7, 8]}


def _spec_cfg():
    return make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
    )


@pytest.fixture(scope="module")
def spec_apps():
    """(plain app, target, {draft's quality: draft}); plain and target share weights."""
    sd = make_random_hf_state_dict(_spec_cfg(), seed=0)
    load = lambda state: TpuModelForCausalLM(None, _spec_cfg()).load(state_dict=state)
    drafts = {
        "same_weights": load(sd),
        "wrong_weights": load(make_random_hf_state_dict(_spec_cfg(), seed=7)),
    }
    return load(sd), load(sd), drafts


def _spec_session(spec_apps, quality, **kw):
    from neuronx_distributed_inference_tpu.runtime.serving import SpeculativeServingSession

    _, target, drafts = spec_apps
    target.init_kv_cache()
    drafts[quality].init_kv_cache()
    return SpeculativeServingSession(target, drafts[quality], speculation_length=SPEC_K, **kw)


def _turnover(sess, n=8):
    """Two requests at once and a third into the first slot that frees."""
    assert sess.add_request("r1", SPEC_PROMPTS["r1"], max_new_tokens=n)
    assert sess.add_request("r2", SPEC_PROMPTS["r2"], max_new_tokens=n)
    while sess.active:
        sess.step()
        if "r3" not in sess.requests and sess.free_slots:
            assert sess.add_request("r3", SPEC_PROMPTS["r3"], max_new_tokens=n)
    return {rid: list(r.generated) for rid, r in sess.requests.items()}


@pytest.mark.parametrize("quality", ["same_weights", "wrong_weights"])
def test_speculative_turnover_matches_plain(spec_apps, quality):
    """Greedy verification emits the plain session's streams whatever the
    draft proposes, with a request joining mid-stream."""
    plain = spec_apps[0]
    golden = {rid: _plain_golden(plain, p, 8) for rid, p in SPEC_PROMPTS.items()}
    assert _turnover(_spec_session(spec_apps, quality)) == golden


@pytest.mark.parametrize("quality", ["same_weights", "wrong_weights"])
def test_speculative_round_stops_at_eos_inside_the_window(spec_apps, quality):
    """An EOS inside a round's accepted window ends the stream THERE: the
    tokens the round accepted after it are dropped and the slot is freed."""
    golden = _plain_golden(spec_apps[0], [5, 6, 7], 8)
    eos = golden[2]  # the first round's window is golden[1:5] under a draft that agrees
    assert eos not in golden[:2]
    sess = _spec_session(spec_apps, quality)
    assert sess.add_request("e", [5, 6, 7], max_new_tokens=8, eos_token_id=eos)
    assert sess.run_to_completion()["e"] == golden[:3]
    assert len(sess.free_slots) == sess.num_slots


@pytest.mark.parametrize("quality", ["same_weights", "wrong_weights"])
def test_speculative_slot_reuse_after_finish(spec_apps, quality):
    """A request into a freed slot (the draft's cache line reused with the
    target's, stale candidates of the last holder in both) decodes as alone."""
    golden = _plain_golden(spec_apps[0], [42, 10, 11], 6)
    sess = _spec_session(spec_apps, quality)
    for i in range(sess.num_slots):
        assert sess.add_request(f"w{i}", [1 + i, 2, 3, 4, 5, 6, 7], max_new_tokens=9)
    sess.run_to_completion()
    assert len(sess.free_slots) == sess.num_slots
    assert sess.add_request("probe", [42, 10, 11], max_new_tokens=6)
    assert sess.run_to_completion()["probe"] == golden


def _counted_turnover(spec_apps, quality):
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    with TelemetrySession() as tel:
        sess = _spec_session(spec_apps, quality, telemetry=tel)
        out = _turnover(sess)
    return sess, out, tel.registry.snapshot()


@pytest.mark.parametrize("quality", ["same_weights", "wrong_weights"])
def test_speculative_acceptance_histograms_sum_to_what_was_delivered(spec_apps, quality):
    """The acceptance-length histogram sums to the decode tokens delivered
    (a request's first token is its prefill's), the draft-length histogram
    to the tokens drafted, one EWMA observation a round."""
    _, out, snap = _counted_turnover(spec_apps, quality)
    accepted = snap["nxdi_spec_accept_len"]["samples"][0]
    assert accepted["sum"] == sum(len(v) for v in out.values()) - len(out)
    drafted = snap["nxdi_spec_draft_len"]["samples"][0]
    assert drafted["sum"] == drafted["count"] * (SPEC_K - 1) > 0
    assert snap["nxdi_spec_accept_ewma"]["samples"][0]["count"] == drafted["count"]


def test_speculative_acceptance_ewma_tells_the_drafts_apart(spec_apps):
    """The session's acceptance EWMA (the router's placement signal) and the
    tokens a round delivers separate a draft that agrees from one that does
    not, on the same requests with the same streams."""
    same, out_same, snap_same = _counted_turnover(spec_apps, "same_weights")
    wrong, out_wrong, snap_wrong = _counted_turnover(spec_apps, "wrong_weights")
    assert out_same == out_wrong
    assert same.acceptance_ewma > wrong.acceptance_ewma + 0.2
    rounds = lambda snap: snap["nxdi_spec_accept_len"]["samples"][0]["count"]
    assert rounds(snap_same) < rounds(snap_wrong)  # the same tokens in fewer rounds


@pytest.mark.slow
def test_gpt_oss_class_serving_session():
    """ServingSession end-to-end on a GPT-OSS-class model (interleaved
    sliding/global ring caches, sinks, MoE): per-request tokens must match
    isolated generate() runs, including an over-window prompt (VERDICT r3
    next #7 done criteria)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers import GptOssConfig, GptOssForCausalLM

    from neuronx_distributed_inference_tpu.models.gpt_oss import GptOssInferenceConfig
    from neuronx_distributed_inference_tpu.config import TpuConfig

    hf_cfg = GptOssConfig(
        vocab_size=128, hidden_size=64, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_local_experts=4, num_experts_per_tok=2,
        sliding_window=4, max_position_embeddings=256,
        rope_scaling=None, attn_implementation="eager",
        eos_token_id=None, pad_token_id=0, tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    hf = GptOssForCausalLM(hf_cfg).eval().float()
    sd = {k: v.float().numpy() for k, v in hf.state_dict().items()}

    def load_config(cfg):
        cfg.model_type = "gpt_oss"
        for k, v in hf_cfg.to_dict().items():
            setattr(cfg, k, v)

    def build():
        tc = TpuConfig(
            batch_size=2, ctx_batch_size=1, seq_len=64, dtype="float32",
            is_continuous_batching=True,
        )
        cfg = GptOssInferenceConfig(tc, load_config=load_config)
        app = TpuModelForCausalLM(None, cfg)
        app.load(state_dict=sd)
        return app

    app = build()
    prompts = {
        "short": [5, 17, 92, 41],
        "long": list(range(30, 44)),  # 14 tokens > sliding_window=4
    }
    golden = {}
    for rid, p in prompts.items():
        ids = np.asarray(p)[None, :]
        golden[rid] = app.generate(
            ids, np.ones_like(ids), max_new_tokens=6
        ).sequences[0, ids.shape[1]:].tolist()

    app2 = build()
    sess = ServingSession(app2)
    assert sess.add_request("short", prompts["short"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("long", prompts["long"], max_new_tokens=6)
    results = sess.run_to_completion()
    assert results["short"] == golden["short"]
    assert results["long"] == golden["long"]


def _drain_config(paged: bool, num_blocks: int = 16):
    tpu = dict(
        is_continuous_batching=True, batch_size=2, ctx_batch_size=1, seq_len=64,
    )
    if paged:
        tpu.update(
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=num_blocks
        )
    return make_tiny_config(tpu=tpu)


def _drain_session(cfg, sd, prompts, budget, eos=None):
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    sess = ServingSession(app)
    for rid, p in prompts.items():
        assert sess.add_request(
            rid, p, max_new_tokens=budget, eos_token_id=(eos or {}).get(rid)
        )
    return app, sess


@pytest.mark.slow
def test_paged_chunked_drain_matches_per_step():
    """run_to_completion on the PAGED cache over 20 tokens a row crosses a
    block boundary: the tokens of a hand-written step loop, with and without
    an EOS observed mid-stream."""
    cfg = _drain_config(paged=True)
    sd = make_random_hf_state_dict(cfg)
    prompts = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33]}

    _, s1 = _drain_session(cfg, sd, prompts, 20)
    while s1.active:
        s1.step()
    golden = {rid: r.generated for rid, r in s1.requests.items()}
    assert all(len(v) == 20 for v in golden.values())

    _, s2 = _drain_session(cfg, sd, prompts, 20)
    assert s2.run_to_completion() == golden

    eos = golden["r1"][9]
    stop = golden["r1"].index(eos)  # first occurrence is where EOS stops
    _, s3 = _drain_session(cfg, sd, prompts, 20, eos={"r1": eos})
    out = s3.run_to_completion()
    assert out["r1"] == golden["r1"][: stop + 1]
    assert out["r2"] == golden["r2"]


@pytest.mark.parametrize(
    "case", ["paged_eos", "paged_no_eos", "contiguous", "paged_small_pool"]
)
def test_run_to_completion_is_the_step_loop(case):
    """run_to_completion drains by step(): its tokens are those of a
    hand-written ``while s.active: s.step()`` session on the same requests
    (an EOS mid-stream, none, the contiguous cache, and a pool too small for
    both rows, where a row is preempted and re-admitted during the drain),
    and the token-generation runner has compiled no multi-step program."""
    paged = case != "contiguous"
    small = case == "paged_small_pool"
    cfg = _drain_config(paged, num_blocks=3 if small else 16)
    sd = make_random_hf_state_dict(cfg)
    if small:
        # one block a prompt of the three the pool has: decoding past the
        # block boundary runs the pool out for one of the two rows
        prompts = {"r1": list(range(1, 16)), "r2": list(range(2, 17))}
        budget = 6
    else:
        prompts = {"r1": [5, 17, 92, 41], "r2": [64, 3, 27, 9, 14, 33]}
        budget = 8

    _, by_hand = _drain_session(cfg, sd, prompts, budget)
    while by_hand.active or by_hand._readmit:
        by_hand.step()
    golden = {rid: r.generated for rid, r in by_hand.requests.items()}
    assert all(len(v) == budget for v in golden.values())

    eos = None
    if case == "paged_eos":
        tok = golden["r1"][3]
        eos = {"r1": tok}
        golden["r1"] = golden["r1"][: golden["r1"].index(tok) + 1]
        # the hand-written loop observes the same EOS
        _, by_hand = _drain_session(cfg, sd, prompts, budget, eos=eos)
        while by_hand.active or by_hand._readmit:
            by_hand.step()
        assert {r: q.generated for r, q in by_hand.requests.items()} == golden

    app, sess = _drain_session(cfg, sd, prompts, budget, eos=eos)
    assert sess.run_to_completion() == golden
    assert sess._step_index == by_hand._step_index
    if small:
        assert max(r.preemptions for r in sess.requests.values()) >= 1
    assert app.token_generation_model._decode_fns == {}
