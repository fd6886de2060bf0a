"""Kimi-Linear (``model_type: "kimi_linear"``) on the paged, chunked serving
path, held to the benchmark's plain reference
(``benchmark/harness/references/kimi_linear.py``: float32, no cache, no kernel,
the delta rule token by token, MLA expanded, a loop over experts) in logits
and in choices, and the reference's parts held to what is installed. Small
size, CPU, seeded random weights.

What is new in this model and what holds it here: a KDA mixer whose state is
READ before it is written and decays a vector a head (the chunked form with
its carry across sub-chunks and chunks, the decode kernel), a LATENT pool
beside a per-slot state in one ``HybridBlockCache``, MLA without rotation,
every layer two blocks of ``HybridStack`` (a mixer, then a dense or an expert
MLP), the expert layer under a held share, and the typed refusals.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness.references import deepseek_mla
from benchmark.harness.references import kimi_linear as ref
from neuronx_distributed_inference_tpu.config import (
    ChunkedPrefillConfig,
    LatentAttentionError,
    SlotStateServingError,
    TpuConfig,
)
from neuronx_distributed_inference_tpu.models import get_model_builder
from neuronx_distributed_inference_tpu.models.granite_hybrid import DENSE, KDA, MLA, MOE, layer_plan
from neuronx_distributed_inference_tpu.modules import kda, moe
from neuronx_distributed_inference_tpu.modules.block_kvcache import PAGED_KV, SLOT_STATE, HybridBlockCache
from neuronx_distributed_inference_tpu.ops.kda_state_update import kda_state_update
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector, fill_slot_state
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import LogitSpy, drain

CHUNK = 16
ATTRS = dict(
    model_type="kimi_linear", hidden_size=128, intermediate_size=192, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=4, head_dim=24, vocab_size=512,
    kv_lora_rank=64, q_lora_rank=None, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    mla_use_nope=True, rope_theta=10000, rope_scaling=None, rms_norm_eps=1e-5, hidden_act="silu",
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=4,
                            head_dim=32, short_conv_kernel_size=4),
    first_k_dense_replace=1, moe_layer_freq=1, moe_intermediate_size=48, num_experts=4,
    num_experts_published=8, expert_share={"first": 0, "of": 2}, num_experts_per_token=2,
    num_shared_experts=1, moe_renormalize=True, moe_router_activation_func="sigmoid",
    num_expert_group=1, topk_group=1, use_grouped_topk=True, routed_scaling_factor=2.446,
    tie_word_embeddings=False, num_nextn_predict_layers=0,
)


def make_config(attrs=ATTRS, **tpu):
    opts = dict(
        dtype="float32", batch_size=4, seq_len=256, enable_bucketing=True,
        context_encoding_buckets=[256], token_generation_buckets=[128, 256],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_block_size=16, pa_num_blocks=48, is_chunked_prefill=True,
        output_logits=True, output_choices=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=4, kernel_q_tile_size=CHUNK),
    )
    opts.update(tpu)
    cls = get_model_builder("kimi_linear").config_cls
    return cls(TpuConfig(**opts), load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])


@pytest.fixture(scope="module")
def app():
    return TpuModelForCausalLM(None, make_config()).load(random_weights=True)


def served_is_reference(app, spy, slot, prompt, generated, tol=3e-5):
    """Whether the logits served for ``slot`` at the last prompt position and
    after every generated token but the last are the reference's full pass."""
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    want = ref.reference_logits(app.params, ref.geometry(ATTRS, 1),
                                list(prompt) + list(generated[:-1]), positions)
    try:
        got = np.stack([spy.at(slot, p) for p in positions])
    except AssertionError:
        return False
    return bool(np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()))


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def test_the_stack_is_a_mixer_and_an_mlp_a_layer_over_a_latent_pool_and_a_state(app):
    b = app.builder
    assert b.layer_types[:8] == (KDA, DENSE, KDA, MOE, KDA, MOE, MLA, MOE) and len(b.layer_types) == 16
    assert b.counts == {KDA: 6, MLA: 2, DENSE: 1, MOE: 7}
    keeps = b.cache_layers()
    assert keeps.count(SLOT_STATE) == 6 and keeps.count(PAGED_KV) == 2 and keeps.count(None) == 8
    assert [s.name for s in b.cache_streams()] == ["latent", "rope_key"]
    cache = app.kv_cache
    assert isinstance(cache, HybridBlockCache) and app.paged_layers == 2
    # the pool's two streams are a latent of 64 and the key of 16 packed 8 tokens a lane row
    assert cache.k.shape == (2, 49, 1, 16, 64) and cache.v.shape == (2, 49, 1, 2, 128)
    assert isinstance(cache.state, kda.DeltaState) and cache.state.KIND == "kda"
    assert cache.state.ssm.shape == (6, 4, 4, 32, 32) and cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (6, 3, 4, 3 * 128)
    # every block of the 16 is covered by the plan's segments, in order
    covered = sum(r * sum(n for _, _, n in runs) for r, runs, _ in layer_plan(b.layer_types))
    assert covered == 16
    assert b.mla_spec().use_rope is False and b.mla_spec().scale == 48 ** -0.5
    assert "rope" not in b.param_shapes()


def test_chunked_prefill_then_decode_is_the_reference_as_rows_join_and_leave(app):
    """A prompt of 3.5 chunks (the conv tail and the matrix state carried from
    chunk to chunk, a last chunk with invalid positions), then decode through
    the state kernel and the latent pool; a second request is admitted into
    the slot the first has left (its state is there still: the position-0 rule
    zeroes it) beside a third, which joins while the second decodes."""
    app.init_kv_cache()
    rng = np.random.default_rng(2)
    first = rng.integers(0, 512, size=int(3.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("first", first, max_new_tokens=6)
        drain(s)
        assert s.requests["first"].slot == -1
        assert np.abs(np.asarray(app.kv_cache.state.ssm[:, 0])).max() > 0  # left behind
        assert served_is_reference(app, spy, 0, first, s.requests["first"].generated)
        spy.rows.clear()
        second, third = rng.integers(0, 512, size=21), rng.integers(0, 512, size=70)
        s.add_request("second", second, max_new_tokens=4)
        for _ in range(3):
            s.step()
        s.add_request("third", third, max_new_tokens=9)
        slots = {r: s.requests[r].slot for r in ("second", "third")}
        assert slots["second"] == 0  # the slot "first" held, state and all
        drain(s)
        assert served_is_reference(app, spy, slots["second"], second, s.requests["second"].generated)
        assert served_is_reference(app, spy, slots["third"], third, s.requests["third"].generated)


def test_the_carry_between_chunks_reaches_the_logits(app):
    """The control of the test above: with the carried state zeroed before
    the prompt's last chunk the logits move by many times its tolerance."""
    app.init_kv_cache()
    prompt = np.random.default_rng(2).integers(0, 512, size=int(2.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("r", prompt, max_new_tokens=1)
        s.step(), s.step()
        app.kv_cache = fill_slot_state(app.kv_cache, [0], 0.0)
        drain(s)
        got = spy.at(0, len(prompt) - 1)
    want = ref.reference_logits(app.params, ref.geometry(ATTRS, 1), prompt, [len(prompt) - 1])[0]
    assert np.abs(got - want).max() > 5 * 3e-5 * max(1.0, np.abs(want).max())


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(app):
    app.init_kv_cache()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, size=n) for n in (40, 27)]
    with LogitSpy(app) as spy:
        s = ServingSession(app, fault_injector=FaultInjector().exhaust_pool(5))
        for i, p in enumerate(prompts):
            s.add_request(f"r{i}", p, max_new_tokens=8)
        drain(s)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        for i, p in enumerate(prompts):
            req = s.requests[f"r{i}"]
            assert len(req.generated) == 8
            # re-admission may land in either slot: find it by the logits' row
            assert any(served_is_reference(app, spy, slot, p, req.generated)
                       for slot in range(s.num_slots)), f"r{i}: no slot served the reference's logits"


def test_scrub_zeroes_the_slots_state_and_only_it(app):
    app.init_kv_cache()
    rng = np.random.default_rng(4)
    s = ServingSession(app)
    s.add_request("victim", rng.integers(0, 512, size=30), max_new_tokens=6)
    s.add_request("bystander", rng.integers(0, 512, size=30), max_new_tokens=6)
    for _ in range(4):
        s.step()
    state = app.kv_cache.state
    before = (np.asarray(state.ssm[:, 1]), np.asarray(state.conv[:, :, 1]))
    assert np.abs(np.asarray(state.ssm[:, 0])).max() > 0
    s._finish(s.requests["victim"], reason="test", scrub=True)
    state = app.kv_cache.state
    assert isinstance(state, kda.DeltaState)
    assert not np.asarray(state.ssm[:, 0]).any() and not np.asarray(state.conv[:, :, 0]).any()
    assert np.array_equal(np.asarray(state.ssm[:, 1]), before[0])
    assert np.array_equal(np.asarray(state.conv[:, :, 1]), before[1])
    # the slot is reused after the scrub and serves the reference
    with LogitSpy(app) as spy:
        again = rng.integers(0, 512, size=19)
        s.add_request("again", again, max_new_tokens=3)
        slot = s.requests["again"].slot
        drain(s)
        assert slot == 0 and served_is_reference(app, spy, slot, again, s.requests["again"].generated)
    assert len(s.requests["bystander"].generated) == 6


def _forward_chunks(app, prompt, chunk=CHUNK, slot=0, width=128):
    """The prompt through ``app.forward`` in chunks; (the logits at its last
    position, the choices of every token (S, L_moe, k))."""
    bs = app.config.tpu_config.pa_block_size
    table = (1 + slot * (width // bs) + np.arange(width // bs))[None].astype(np.int32)
    last, chose = None, []
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        ids, sm = np.zeros((1, chunk), np.int32), np.full((1, chunk), -1, np.int32)
        pos = (start + np.arange(chunk))[None].astype(np.int32)
        ids[0, :n] = prompt[start : start + n]
        sm[0, :n] = table[0, pos[0, :n] // bs] * bs + pos[0, :n] % bs
        mask = (np.arange(width)[None] < start + n).astype(np.int32)
        _, logits, aux = app.forward(ids, pos, np.asarray([slot], np.int32), attention_mask=mask,
                                     slot_mapping=sm, block_table=table, phase="tkg")
        last = np.asarray(logits[0, n - 1])
        chose.append(np.asarray(aux["experts"][0, :n]))
    return last, np.concatenate(chose)


def test_the_choices_the_step_returns_are_the_references_and_replay_follows_them(app):
    """``forward``'s third value: every token's experts in every expert layer,
    over the PUBLISHED width (0..7 with 4 held), the reference's own top-k;
    the reference replaying them gives the logits it gives choosing."""
    app.init_kv_cache()
    prompt = np.random.default_rng(5).integers(0, 512, size=40)
    logits, chose = _forward_chunks(app, prompt)
    assert chose.shape == (40, 7, 2) and chose.max() >= 4 and chose.min() >= 0
    geo = ref.geometry(ATTRS, 1)
    own_logits, _, own = ref.forward(app.params, geo, list(prompt), [39])
    assert np.array_equal(np.sort(chose, -1), np.sort(np.transpose(own, (1, 0, 2)), -1))
    replayed = ref.reference_logits(app.params, geo, list(prompt), [39], choices={ref.NAME: chose})
    np.testing.assert_allclose(replayed, own_logits, rtol=0, atol=1e-6)
    np.testing.assert_allclose(logits[None], own_logits, rtol=0, atol=3e-5 * max(1.0, np.abs(own_logits).max()))
    regret, floor, differing = ref.choice_margins(app.params, geo, list(prompt), {ref.NAME: chose})
    assert regret.shape == floor.shape == (7,) and regret.max() == 0 and differing.sum() == 0


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_references_logits(app, fault):
    """Each fault of the selftest is a different function at this size too."""
    prompt = np.random.default_rng(6).integers(0, 512, size=3 * ref.FAULT_CHUNK // 2)
    geo = ref.geometry(ATTRS, 1)
    sound = ref.reference_logits(app.params, geo, list(prompt), [len(prompt) - 1])
    faulty = ref.reference_logits(app.params, geo, list(prompt), [len(prompt) - 1], fault=fault)
    assert np.abs(faulty - sound).max() > 1e-3 * np.abs(sound).max()


def test_a_pass_is_counted_under_the_states_family_and_the_experts(app):
    """``nxdi_kda_*`` (the state's KIND), ``nxdi_latent_*`` (the pool's first
    stream) and ``nxdi_moe_*`` (``expert_layers()``) in one session."""
    app.init_kv_cache()
    assert app.builder.expert_layers() == (7, 4, 2)
    assert app.builder.moe_spec().num_experts == 8 and app.builder.moe_spec().holds_share
    with TelemetrySession() as tel:
        s = ServingSession(app, telemetry=tel)
        assert s.slot_state_kind == "kda" and s.latent_layers == 2
        s.add_request("a", np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
        drain(s)
        snap = tel.registry.snapshot()
    total = lambda name, **labels: sum(
        x["value"] for x in snap[name]["samples"]
        if all(x["labels"].get(k) == v for k, v in labels.items()))
    decodes = total("nxdi_steps_total", kind="decode")
    assert decodes >= 2
    assert total("nxdi_kda_rows_advanced_total", program="decode") == decodes
    assert total("nxdi_kda_rows_advanced_total", program="chunk") == 2  # 19 tokens in 2 chunks of 16
    assert total("nxdi_kda_state_resets_total") == 1
    assert total("nxdi_kda_state_bytes") == app.kv_cache.state.nbytes
    assert total("nxdi_latent_tokens_written_total", program="chunk") == 19 * 2
    assert total("nxdi_moe_experts_hit_total", program="decode") == decodes * 7 * 4
    assert [(x["labels"], x["value"]) for x in snap["nxdi_moe_experts_held"]["samples"]] == [({"of": "8"}, 4.0)]
    assert "nxdi_ssm_rows_advanced_total" not in snap or not total("nxdi_ssm_rows_advanced_total")


# ---------------------------------------------------------------------------
# the three forms of the recurrence
# ---------------------------------------------------------------------------


def _delta_inputs(seed, R, Q, H, D, strong=False):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = kda.l2_normalize(n(R, Q, H, D), 1e-6) * D ** -0.5
    k = kda.l2_normalize(n(R, Q, H, D), 1e-6)
    # log decays from nearly none to e^-6 a token (what exp(G_i) exp(-G_j) overflows on)
    g = -jnp.asarray(rng.uniform(1e-3, 6.0 if strong else 0.3, (R, Q, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (R, Q, H)), jnp.float32)
    return q, k, n(R, Q, H, D), g, beta, n(R, H, D, D)


def _token_by_token(q, k, v, g, beta, state, valid):
    outs = []
    for t in range(q.shape[1]):
        o, state = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state, valid[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("Q,sub,strong", [(37, 16, False), (48, 16, True), (16, 8, False), (5, 16, False),
                                          (40, 64, False)])
def test_the_chunked_form_is_the_recurrence(Q, sub, strong):
    """Across sub-chunk boundaries, at lengths that are no multiple of the
    sub-chunk, from a non-zero incoming state, with decays strong enough
    that ``e^{-G}`` alone would overflow; a row whose valid positions stop
    early and a row with none keep what the recurrence keeps."""
    q, k, v, g, beta, s0 = _delta_inputs(Q, 3, Q, 2, 16, strong)
    n_valid = np.array([Q, max(1, Q // 2), 0])
    valid = jnp.asarray(np.arange(Q)[None, :] < n_valid[:, None])
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0, valid)
    got_o, got_s = kda.kda_chunk(q, k, v, g, beta, s0, valid, chunk_size=sub)
    live = np.asarray(valid)[:, :, None, None]
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o) * live, np.asarray(want_o) * live, rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=0, atol=2e-5)
    assert np.array_equal(np.asarray(got_s[2]), np.asarray(s0[2]))  # no valid position: bit for bit


def test_the_state_carried_from_chunk_to_chunk_is_the_recurrence():
    """Two chunk calls, the second from the first's state (a prompt that is
    no multiple of the chunk), against one pass token by token."""
    q, k, v, g, beta, _ = _delta_inputs(7, 2, 41, 2, 16)
    s0 = jnp.zeros((2, 2, 16, 16), jnp.float32)
    everything = jnp.ones((2, 41), bool)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0, everything)
    cut = lambda a, lo, hi: a[:, lo:hi]
    o1, s1 = kda.kda_chunk(*(cut(a, 0, 32) for a in (q, k, v, g, beta)), s0, everything[:, :32])
    pad = lambda a: jnp.pad(a[:, 32:], ((0, 0), (0, 23)) + ((0, 0),) * (a.ndim - 2))
    o2, s2 = kda.kda_chunk(*(pad(a) for a in (q, k, v, g, beta)), s1,
                           jnp.asarray(np.arange(32)[None] < 9).repeat(2, 0))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2[:, :9]], 1)), np.asarray(want_o),
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(want_s), rtol=0, atol=2e-5)


@pytest.mark.parametrize("hb", [1, 2, 4])
def test_the_decode_kernel_is_the_step_on_the_stacked_state_in_place(hb):
    """One layer of the stacked state advanced, every other byte where it
    was; an invalid row rewritten bit for bit; a reset row from zero even
    where what stood there was not finite."""
    q, k, v, g, beta, _ = _delta_inputs(9, 4, 1, 4, 32, strong=True)
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.standard_normal((3, 4, 4, 32, 32)), jnp.float32)
    state = state.at[1, 1].set(jnp.nan)
    valid = jnp.asarray([True, True, False, True])
    reset = jnp.asarray([False, True, True, False])
    o, new = kda_state_update(state, jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              valid, reset, heads_per_block=hb, interpret=True)
    start = jnp.where((reset & valid)[:, None, None, None], 0.0, state[1])
    want_o, want_s = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], start, valid)
    np.testing.assert_allclose(np.asarray(o)[[0, 1, 3]], np.asarray(want_o)[[0, 1, 3]], rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1])[[0, 1, 3]], np.asarray(want_s)[[0, 1, 3]], rtol=0, atol=1e-5)
    assert np.array_equal(np.asarray(new[1, 2]), np.asarray(state[1, 2]))
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))


def test_the_references_recurrence_is_the_installed_gated_delta_rule_at_a_scalar_decay():
    """``transformers.models.qwen3_next`` ``torch_recurrent_gated_delta_rule``
    (4.57.6): the same delta rule with ONE decay a head; this file's with
    ``g`` constant over a head's channels, the l2 normalisation and the
    ``1 / sqrt(d)`` on q included. With ``g`` varying over the channels the
    two differ: the decay is a vector here."""
    torch = pytest.importorskip("torch")
    from transformers.models.qwen3_next.modeling_qwen3_next import torch_recurrent_gated_delta_rule

    rng = np.random.default_rng(0)
    S, H, D = 23, 3, 16
    q, k, v = (rng.standard_normal((S, H, D)).astype(np.float32) for _ in range(3))
    g = -rng.uniform(0.01, 1.0, (S, H)).astype(np.float32)
    beta = rng.uniform(0, 1, (S, H)).astype(np.float32)
    want, _ = torch_recurrent_gated_delta_rule(
        *(torch.from_numpy(a[None]) for a in (q, k, v, g, beta)), None, False,
        use_qk_l2norm_in_kernel=True)
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    ours = lambda g3: np.asarray(ref.delta_rule(
        jnp.asarray(unit(q) / np.sqrt(D)), jnp.asarray(unit(k)), jnp.asarray(v), jnp.asarray(g3),
        jnp.asarray(beta)))
    np.testing.assert_allclose(ours(np.repeat(g[..., None], D, -1)), want.numpy()[0], rtol=0, atol=2e-6)
    uneven = g[..., None] * rng.uniform(0.5, 1.5, (S, H, D)).astype(np.float32)
    assert np.abs(ours(uneven) - want.numpy()[0]).max() > 1e-3
    # and the program's step is the reference's
    o, _ = _token_by_token(*(jnp.asarray(a)[None] for a in (unit(q) / np.sqrt(D), unit(k), v, uneven, beta)),
                           jnp.zeros((1, H, D, D)), jnp.ones((1, S), bool))
    np.testing.assert_allclose(np.asarray(o[0]), ours(uneven), rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# MLA without rotation
# ---------------------------------------------------------------------------


def test_the_mla_layer_without_rotation_is_deepseek_v3s_at_cos_one_sin_zero(monkeypatch):
    """The program's ``mla_decoder_layer`` with ``use_rope`` False against the
    same layer rotating with ``cos = 1, sin = 0``; the reference's MLA mixer
    against the ``deepseek_mla`` reference's attention with its rotation made
    the identity; and the rotation, when applied, is another function."""
    from neuronx_distributed_inference_tpu.models import deepseek
    from neuronx_distributed_inference_tpu.models.base import ModelSpec
    from neuronx_distributed_inference_tpu.modules.attention import AttnSpec

    rng = np.random.default_rng(0)
    n = lambda *s, std=0.2: jnp.asarray(rng.standard_normal(s) * std, jnp.float32)
    Hd, H, r, dn, dr, dv, S = 64, 4, 32, 16, 8, 16, 12
    sa = {"q_proj": {"weight": n(Hd, H * (dn + dr))}, "kv_a_proj": {"weight": n(Hd, r + dr)},
          "kv_a_layernorm": {"weight": 1 + n(r)}, "k_absorb": {"weight": n(H, dn, r)},
          "v_absorb": {"weight": n(H, r, dv)}, "o_proj": {"weight": n(H * dv, Hd)}}
    lp = {"input_layernorm": {"weight": 1 + n(Hd)}, "self_attn": sa}
    mla = deepseek.MLASpec(num_heads=H, q_lora_rank=None, kv_lora_rank=r, qk_nope_head_dim=dn,
                           qk_rope_head_dim=dr, v_head_dim=dv, scale=(dn + dr) ** -0.5, rms_eps=1e-5)
    spec = ModelSpec(num_layers=1, hidden_size=Hd, vocab_size=8, padded_vocab_size=8, intermediate_size=8,
                     attn=AttnSpec(num_heads=H, num_kv_heads=H, head_dim=dn), rms_eps=1e-5)
    h = n(1, S, Hd, std=1.0)
    cache = lambda w: jnp.zeros((1, 1, S, 1, w), jnp.float32)
    pos = jnp.arange(S)[None]
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    run = lambda m, cos, sin: deepseek.mla_decoder_layer(
        lp, h, cos, sin, cache(r), cache(dr), jnp.int32(0), mask, jnp.zeros((1,), jnp.int32), pos,
        spec, "context_encoding", None, mla=m)[0]
    ones, zeros = jnp.ones((1, S, dr // 2)), jnp.zeros((1, S, dr // 2))
    with jax.default_matmul_precision("highest"):
        plain = run(dataclasses.replace(mla, use_rope=False), None, None)
        unit_rotation = run(mla, ones, zeros)
        angle = jnp.arange(S, dtype=jnp.float32)[None, :, None] * jnp.ones((1, 1, dr // 2))
        rotated = run(mla, jnp.cos(angle), jnp.sin(angle))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(unit_rotation), rtol=0, atol=1e-6)
    assert np.abs(np.asarray(rotated) - np.asarray(plain)).max() > 1e-3
    # the references: this file's mixer + residual is deepseek_mla's attention sublayer unrotated
    geo = ref.Geometry(hidden=Hd, mixers=(MLA,), first_dense=0, heads=H, kv_lora_rank=r, nope=dn, rope=dr,
                       v_dim=dv, rope_theta=1e4, k_heads=1, k_dim=1, k_conv=4, vocab=8, rms_eps=1e-5,
                       experts=1, held=1, first=0, top_k=1, norm_topk=True, scaling=1.0, degree=1)
    w = {"ln": lp["input_layernorm"]["weight"], "ln1": lp["input_layernorm"]["weight"],
         "q": sa["q_proj"]["weight"], "kva": sa["kv_a_proj"]["weight"], "wc": sa["kv_a_layernorm"]["weight"],
         "uk": sa["k_absorb"]["weight"], "uv": sa["v_absorb"]["weight"], "o": sa["o_proj"]["weight"]}
    theirs = deepseek_mla.Geometry(
        hidden=Hd, heads=H, q_lora_rank=None, kv_lora_rank=r, nope=dn, rope=dr, v_dim=dv, rope_theta=1e4,
        layers=1, first_dense=0, vocab=8, rms_eps=1e-5, experts=1, top_k=1, shared=0, norm_topk=True,
        scaling=1.0, degree=1)
    monkeypatch.setattr(deepseek_mla, "_rotary", lambda x, positions, geo, rounding: x)
    with jax.default_matmul_precision("highest"):
        x = ref._rmsnorm(h[0], w["ln"], 1e-5)
        ours = h[0] + ref.mla_mixer(x, w, geo)
        want = deepseek_mla._attention(h[0], w, theirs, None, None)
        wrongly = h[0] + ref.mla_mixer(x, w, geo, fault="mla_rotated")
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(plain[0]), np.asarray(ours), rtol=0, atol=2e-5)
    assert np.abs(np.asarray(wrongly) - np.asarray(ours)).max() > 1e-3


# ---------------------------------------------------------------------------
# the held share
# ---------------------------------------------------------------------------

Hs, Is, E, K = 32, 24, 8, 2


def _expert_layer(seed=0, tokens=40):
    rng = np.random.default_rng(seed)
    n = lambda *s, std=0.3: jnp.asarray(rng.standard_normal(s) * std, jnp.float32)
    params = {
        "router": {"weight": n(Hs, E, std=1.0), "e_score_correction_bias": n(E, std=0.1)},
        "experts": {"gate_proj": {"weight": n(E, Hs, Is)}, "up_proj": {"weight": n(E, Hs, Is)},
                    "down_proj": {"weight": n(E, Is, Hs)}},
        "shared_experts": {"gate_proj": {"weight": n(Hs, Is)}, "up_proj": {"weight": n(Hs, Is)},
                           "down_proj": {"weight": n(Is, Hs)}},
    }
    return params, n(1, tokens, Hs, std=1.0)


def _share_of(params, first, held):
    cut = lambda e: {"weight": e["weight"][first : first + held]}
    return dict(params, experts={k: cut(v) for k, v in params["experts"].items()})


def _reference_layer(params, x, first=0, held=E):
    geo = ref.Geometry(hidden=Hs, mixers=(), first_dense=0, heads=1, kv_lora_rank=1, nope=1, rope=1, v_dim=1,
                       rope_theta=1e4, k_heads=1, k_dim=1, k_conv=4, vocab=1, rms_eps=1e-5, experts=E,
                       held=held, first=first, top_k=K, norm_topk=True, scaling=2.446, degree=1)
    ex, sh = params["experts"], params["shared_experts"]
    w = {"router": params["router"]["weight"], "bias": params["router"]["e_score_correction_bias"],
         "gate": ex["gate_proj"]["weight"], "up": ex["up_proj"]["weight"], "down": ex["down_proj"]["weight"],
         "sgate": sh["gate_proj"]["weight"], "sup": sh["up_proj"]["weight"], "sdown": sh["down_proj"]["weight"]}
    with jax.default_matmul_precision("highest"):
        return ref.experts_mlp(x[0], w, geo)


@pytest.mark.parametrize("rows", [40, 128])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """8 experts in two shares of 4 through the decode strategy (at 128 rows
    its batched form): the two shares' routed parts plus the shared expert
    counted once equal the uncut reference's whole layer; each share equals
    the reference given the same share; a token none of whose choices lie in
    a share gets the shared part alone there."""
    params, x = _expert_layer(seed=2, tokens=rows)
    shared = lambda p, t: moe.shared_expert_mlp(p, t, "silu")
    whole, _, chosen = _reference_layer(params, x)
    elsewhere = np.flatnonzero((np.asarray(chosen) >= 4).all(axis=1))
    assert len(elsewhere)
    with jax.default_matmul_precision("highest"):
        shared_part = np.asarray(shared(params["shared_experts"], x[0]))
    outs = []
    for first in (0, 4):
        spec = moe.MoESpec(num_experts=E, top_k=K, scoring_func="sigmoid", routed_scaling_factor=2.446,
                           held_experts=4, first_expert=first, sparse_dispatch_threshold=10 ** 6)
        mine = _share_of(params, first, 4)
        with jax.default_matmul_precision("highest"):
            out = moe.moe_layer(mine, x, spec, shared_mlp_fn=shared)
        want, _, _ = _reference_layer(mine, x, first=first, held=4)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=0, atol=2e-4)
        outs.append(np.asarray(out[0]))
    np.testing.assert_allclose(outs[0] + outs[1] - shared_part, np.asarray(whole), rtol=0, atol=3e-4)
    np.testing.assert_allclose(outs[0][elsewhere], shared_part[elsewhere], rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# refusals, names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change,error,what", [
    (dict(mla_use_nope=False), NotImplementedError, "mla_use_nope"),
    (dict(num_expert_group=2), NotImplementedError, "group-limited"),
    (dict(moe_layer_freq=2), NotImplementedError, "moe_layer_freq"),
    (dict(moe_router_activation_func="softmax"), NotImplementedError, "moe_router_activation_func"),
    (dict(tie_word_embeddings=True), NotImplementedError, "tie_word_embeddings"),
    (dict(num_nextn_predict_layers=1), NotImplementedError, "num_nextn_predict_layers"),
    (dict(linear_attn_config=dict(ATTRS["linear_attn_config"], full_attn_layers=[4])), ValueError,
     "kda_layers and full_attn_layers"),
    (dict(num_experts_published=16), ValueError, "expert_share"),
    (dict(expert_share={"first": 2, "of": 2}), ValueError, "expert_share"),
])
def test_what_the_model_does_not_build_is_refused_at_config_time(change, error, what):
    with pytest.raises(error, match=what):
        make_config(dict(ATTRS, **change))


@pytest.mark.parametrize("tpu,error,what", [
    (dict(is_prefix_caching=True), (SlotStateServingError, LatentAttentionError), "is_prefix_caching"),
    (dict(serving_ragged=True), (SlotStateServingError, LatentAttentionError), "serving_ragged"),
    (dict(speculation_length=4), (SlotStateServingError, LatentAttentionError), "speculation"),
    (dict(kv_cache_dtype="int8"), (SlotStateServingError, LatentAttentionError), "quantisation"),
    (dict(tp_degree=2), (SlotStateServingError, LatentAttentionError), "degree > 1"),
    (dict(fused_qkv=True), LatentAttentionError, "fused_qkv"),
    (dict(is_block_kv_layout=False, is_chunked_prefill=False), NotImplementedError, "paged, chunked path"),
])
def test_what_a_state_beside_a_latent_pool_cannot_be_served_with_is_refused(tpu, error, what):
    with pytest.raises(error, match=what):
        cfg = make_config(**tpu)
        get_model_builder("kimi_linear")(cfg)


def test_the_checkpoint_names_fill_the_tree():
    """``convert_hf_state_dict`` from the published names: every leaf of
    ``param_shapes`` at its shape, q, k and v side by side, the three convs
    as one, ``kv_b_proj`` split into the absorption tensors, the held
    experts taken from ``first_expert`` on."""
    attrs = dict(ATTRS, expert_share={"first": 1, "of": 2})
    b = get_model_builder("kimi_linear")(make_config(attrs))
    rng, sd = np.random.default_rng(0), {}
    n = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    for i in range(8):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"], sd[p + "post_attention_layernorm.weight"] = n(128), n(128)
        if (i + 1) % 4:
            for name in "qkv":
                sd[p + f"self_attn.{name}_proj.weight"] = n(128, 128)
                sd[p + f"self_attn.{name}_conv1d.weight"] = n(128, 1, 4)
            sd.update({p + "self_attn.A_log": n(1, 1, 4, 1), p + "self_attn.dt_bias": n(128),
                       p + "self_attn.f_a_proj.weight": n(32, 128), p + "self_attn.f_b_proj.weight": n(128, 32),
                       p + "self_attn.g_a_proj.weight": n(32, 128), p + "self_attn.g_b_proj.weight": n(128, 32),
                       p + "self_attn.b_proj.weight": n(4, 128), p + "self_attn.o_norm.weight": n(32),
                       p + "self_attn.o_proj.weight": n(128, 128)})
        else:
            sd.update({p + "self_attn.q_proj.weight": n(4 * 48, 128),
                       p + "self_attn.kv_a_proj_with_mqa.weight": n(64 + 16, 128),
                       p + "self_attn.kv_a_layernorm.weight": n(64),
                       p + "self_attn.kv_b_proj.weight": n(4 * (32 + 32), 64),
                       p + "self_attn.o_proj.weight": n(128, 4 * 32)})
        if i == 0:
            sd.update({p + "mlp.gate_proj.weight": n(192, 128), p + "mlp.up_proj.weight": n(192, 128),
                       p + "mlp.down_proj.weight": n(128, 192)})
        else:
            m = p + "block_sparse_moe."
            sd.update({m + "gate.weight": n(8, 128), m + "gate.e_score_correction_bias": n(8),
                       m + "shared_experts.gate_proj.weight": n(48, 128),
                       m + "shared_experts.up_proj.weight": n(48, 128),
                       m + "shared_experts.down_proj.weight": n(128, 48)})
            for e in range(8):
                sd.update({m + f"experts.{e}.w1.weight": n(48, 128), m + f"experts.{e}.w3.weight": n(48, 128),
                           m + f"experts.{e}.w2.weight": n(128, 48)})
    sd.update({"model.embed_tokens.weight": n(512, 128), "model.norm.weight": n(128), "lm_head.weight": n(512, 128)})
    params = b.convert_hf_state_dict(sd, dtype=jnp.float32)
    assert jax.tree.map(lambda a: tuple(a.shape), params) == b.param_shapes()
    mixer = params["layers"][KDA]["mixer"]
    np.testing.assert_array_equal(np.asarray(mixer["qkv_proj"]["weight"][1, :, 128:256]),
                                  sd["model.layers.1.self_attn.k_proj.weight"].T)
    np.testing.assert_array_equal(np.asarray(mixer["conv1d"]["weight"][0, :, 256:]),
                                  sd["model.layers.0.self_attn.v_conv1d.weight"][:, 0, :].T)
    wkv = sd["model.layers.3.self_attn.kv_b_proj.weight"].reshape(4, 64, 64)
    np.testing.assert_array_equal(np.asarray(params["layers"][MLA]["self_attn"]["k_absorb"]["weight"][0]),
                                  wkv[:, :32, :])
    experts = params["layers"][MOE]["mlp"]["experts"]
    np.testing.assert_array_equal(np.asarray(experts["gate_proj"]["weight"][0, 0]),
                                  sd["model.layers.1.block_sparse_moe.experts.4.w1.weight"].T)
    np.testing.assert_array_equal(np.asarray(experts["down_proj"]["weight"][6, 3]),
                                  sd["model.layers.7.block_sparse_moe.experts.7.w2.weight"].T)
