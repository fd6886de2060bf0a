"""Nemotron-H (``model_type: "nemotron_h"``) on the paged, chunked serving
path, held to the benchmark's plain reference
(``benchmark/harness/references/nemotron_h.py``: float32, no cache, no kernel,
the recurrence token by token, a loop over experts) in logits and in choices,
and the reference's parts held to what ``transformers`` has installed. Small
size, CPU, seeded random weights.

What is new in this model and what holds it here: single-part blocks of three
kinds in one stack (granite_hybrid.layer_plan), Mamba-2 with groups of B/C
through the state kernel and the chunk scan with a carry from chunk to chunk,
the gated norm by group, two-matrix relu^2 experts through the dense and the
grouped strategy, a held share of the experts (the shares add up to the uncut
layer), state-space state beside paged K/V beside routed experts in one
session and its counters, and the typed refusals.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness.references import nemotron_h as ref
from neuronx_distributed_inference_tpu.config import (
    ChunkedPrefillConfig,
    SlotStateServingError,
    TpuConfig,
)
from neuronx_distributed_inference_tpu.models import get_model_builder
from neuronx_distributed_inference_tpu.modules import moe
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import LogitSpy, drain

CHUNK = 16
ATTRS = dict(
    model_type="nemotron_h", hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, num_hidden_layers=13, hybrid_override_pattern="MEMEM*EMEMEM*", vocab_size=512,
    mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=32, n_groups=4, conv_kernel=4,
    chunk_size=8, n_routed_experts=4, n_routed_experts_published=8,
    expert_share={"first": 0, "of": 2}, num_experts_per_tok=2, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, intermediate_size=48, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, layer_norm_epsilon=1e-5,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", tie_word_embeddings=False, expand=2,
    rope_theta=10000, use_conv_bias=True,
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
    cls = get_model_builder("nemotron_h").config_cls
    return cls(TpuConfig(**opts), load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])


@pytest.fixture(scope="module")
def app():
    return TpuModelForCausalLM(None, make_config()).load(random_weights=True)


def check_request(app, spy, slot, prompt, generated, attrs=ATTRS, tol=3e-5):
    """Served logits at the last prompt position and after every generated
    token but the last, against the reference's full forward pass."""
    geo = ref.geometry(attrs, 1)
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    want = ref.reference_logits(app.params, geo, list(prompt) + list(generated[:-1]), positions)
    got = np.stack([spy.at(slot, p) for p in positions])
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(1.0, np.abs(want).max()))


def test_chunked_prefill_then_decode_is_the_reference_as_rows_join_and_leave(app):
    """A prompt of 3.5 chunks (a carry of conv tail and state from chunk to
    chunk, a last chunk with invalid positions), then decode through the state
    kernel; a second request is admitted into the slot the first has left
    (its state is there still: the position-0 rule zeroes it) beside a third,
    which joins while the second decodes and outlives it."""
    app.init_kv_cache()
    rng = np.random.default_rng(2)
    first = rng.integers(0, 512, size=int(3.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("first", first, max_new_tokens=6)
        drain(s)
        assert s.requests["first"].slot == -1
        assert np.abs(np.asarray(app.kv_cache.state.ssm[:, 0])).max() > 0  # left behind
        check_request(app, spy, 0, first, s.requests["first"].generated)
        spy.rows.clear()
        second, third = rng.integers(0, 512, size=21), rng.integers(0, 512, size=70)
        s.add_request("second", second, max_new_tokens=4)
        for _ in range(3):
            s.step()
        s.add_request("third", third, max_new_tokens=9)
        slots = {r: s.requests[r].slot for r in ("second", "third")}
        assert slots["second"] == 0  # the slot "first" held, state and all
        drain(s)
        check_request(app, spy, slots["second"], second, s.requests["second"].generated)
        check_request(app, spy, slots["third"], third, s.requests["third"].generated)


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
    """``forward``'s third value: every token's experts in every expert block,
    over the PUBLISHED width (0..7 with 4 held), the reference's own top-k;
    the reference replaying them gives the logits it gives choosing."""
    app.init_kv_cache()
    prompt = np.random.default_rng(5).integers(0, 512, size=40)
    logits, chose = _forward_chunks(app, prompt)
    assert chose.shape == (40, 5, 2) and chose.max() >= 4 and chose.min() >= 0
    geo = ref.geometry(ATTRS, 1)
    own_logits, _, own = ref.forward(app.params, geo, list(prompt), [39])
    assert np.array_equal(np.sort(chose, -1), np.sort(np.transpose(own, (1, 0, 2)), -1))
    replayed = ref.reference_logits(app.params, geo, list(prompt), [39], choices={ref.NAME: chose})
    np.testing.assert_allclose(replayed, own_logits, rtol=0, atol=1e-6)
    np.testing.assert_allclose(logits[None], own_logits, rtol=0, atol=3e-5 * np.abs(own_logits).max())
    regret, floor, differing = ref.choice_margins(app.params, geo, list(prompt), {ref.NAME: chose})
    assert regret.shape == floor.shape == (5,) and regret.max() == 0 and differing.sum() == 0


# ---------------------------------------------------------------------------
# the held share: two-matrix experts through both strategies; the shares add up
# ---------------------------------------------------------------------------

H, I, E, K = 32, 24, 8, 2


def _expert_layer(seed=0, tokens=40, published=E):
    rng = np.random.default_rng(seed)
    n = lambda *s, std=0.3: jnp.asarray(rng.standard_normal(s) * std, jnp.float32)
    params = {
        "router": {"weight": n(H, published, std=1.0), "e_score_correction_bias": n(published, std=0.1)},
        "experts": {"up_proj": {"weight": n(published, I, H)}, "down_proj": {"weight": n(published, I, H)}},
        "shared_experts": {"up_proj": {"weight": n(H, 2 * I)}, "down_proj": {"weight": n(2 * I, H)}},
    }
    return params, n(1, tokens, H, std=1.0)


def _share_of(params, first, held):
    cut = lambda e: {"weight": e["weight"][first : first + held]}
    return dict(params, experts={k: cut(v) for k, v in params["experts"].items()})


def _spec(held=None, first=0, **kw):
    return moe.MoESpec(num_experts=E, top_k=K, act="relu2", scoring_func="sigmoid",
                       routed_scaling_factor=2.5, held_experts=held, first_expert=first, **kw)


def _reference_layer(params, x, first=0, held=E):
    geo = ref.Geometry(hidden=H, pattern="E", heads=1, kv_heads=1, head_dim=H, vocab=1, rms_eps=1e-5,
                       m_heads=1, m_head_dim=1, m_state=1, m_groups=1, m_conv=1, experts=E, held=held,
                       first=first, top_k=K, norm_topk=True, scaling=2.5, rope_theta=1e4, degree=1)
    w = {"router": params["router"]["weight"], "bias": params["router"]["e_score_correction_bias"],
         "up": params["experts"]["up_proj"]["weight"], "down": params["experts"]["down_proj"]["weight"],
         "sup": params["shared_experts"]["up_proj"]["weight"],
         "sdown": params["shared_experts"]["down_proj"]["weight"]}
    with jax.default_matmul_precision("highest"):
        return ref.experts_mixer(x[0], w, geo)


@pytest.mark.parametrize("path", ["dense", "ragged_dot", "kernel"])
def test_two_matrix_experts_through_each_strategy_are_the_reference(path):
    """down(relu(up x)^2), no gate, told by what the tree holds; the grouped
    strategies with an expert no token chose (an empty group)."""
    params, x = _expert_layer(seed=1, tokens=24)
    params["router"]["e_score_correction_bias"] = params["router"]["e_score_correction_bias"].at[3].set(-10.0)
    spec = _spec()
    assert moe.two_matrix(params["experts"]) and moe.expert_projs(params["experts"]) == ("up_proj", "down_proj")
    shared = lambda p, t: moe.shared_expert_mlp(p, t, "relu2")
    want, _, chosen = _reference_layer(params, x)
    assert 3 not in np.asarray(chosen)
    aff, sel = moe.linear_router(params, x[0], spec)
    if path == "dense":
        routed = moe.expert_mlps_dense(params["experts"], x[0], aff, spec, sel)
    else:
        with jax.default_matmul_precision("highest"):
            routed = moe.expert_mlps_grouped(params["experts"], x[0], aff, spec, kernel=path == "kernel")
    with jax.default_matmul_precision("highest"):
        got = routed + shared(params["shared_experts"], x[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-4)


@pytest.mark.parametrize("path", ["dense", "ragged_dot", "kernel"])
def test_the_shares_add_up_to_the_uncut_layer(path):
    """8 experts in two shares of 4: the two shares' routed parts plus the
    shared expert counted once equal the uncut reference's whole layer; each
    share equals the reference given the same share; a token none of whose
    choices lie in a share gets the shared part alone there; a held expert
    that no token chose is an empty group."""
    params, x = _expert_layer(seed=2, tokens=40)
    # expert 1 is chosen by no token; tokens exist whose two choices both lie in 4..7
    params["router"]["e_score_correction_bias"] = params["router"]["e_score_correction_bias"].at[1].set(-10.0)
    shared = lambda p, t: moe.shared_expert_mlp(p, t, "relu2")
    whole, _, chosen = _reference_layer(params, x)
    chosen = np.asarray(chosen)
    elsewhere = np.flatnonzero((chosen >= 4).all(axis=1))
    assert len(elsewhere) and 1 not in chosen
    with jax.default_matmul_precision("highest"):
        shared_part = np.asarray(shared(params["shared_experts"], x[0]))
    outs = []
    for first in (0, 4):
        spec = _spec(held=4, first=first, sparse_dispatch_threshold=1 if path != "dense" else 10 ** 6)
        mine = _share_of(params, first, 4)
        if path == "dense":
            out = moe.moe_layer(mine, x, spec, shared_mlp_fn=shared)
        else:
            aff, _ = moe.linear_router(mine, x[0], spec)
            with jax.default_matmul_precision("highest"):
                out = moe.expert_mlps_grouped(mine["experts"], x[0], aff, spec, kernel=path == "kernel")
            out = (out + shared_part)[None]
        want, _, _ = _reference_layer(mine, x, first=first, held=4)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=0, atol=2e-4)
        outs.append(np.asarray(out[0]))
    np.testing.assert_allclose(outs[0] + outs[1] - shared_part, np.asarray(whole), rtol=0, atol=3e-4)
    np.testing.assert_allclose(outs[0][elsewhere], shared_part[elsewhere], rtol=0, atol=1e-6)


def test_the_strategy_is_chosen_from_the_held_count_and_the_expected_rows():
    from neuronx_distributed_inference_tpu.ops.kernel_mode import grouped_beats_dense

    # 64 of 128 held, top-6, 8 x 128 positions: 24 + 63 visits = 1.4 passes against 4.3
    assert grouped_beats_dense(64, 6, 1024, share=0.5)
    assert not grouped_beats_dense(64, 6, 128, share=0.5)
    # a whole layer decides as it did
    assert grouped_beats_dense(64, 6, 1024) and grouped_beats_dense(128, 8, 1024)
    assert not grouped_beats_dense(16, 1, 240)
    spec = _spec(held=4)
    assert spec.held == 4 and spec.holds_share and not _spec().holds_share and _spec().held == E
    params, _ = _expert_layer()
    assert moe.expert_path(spec, params["experts"], 1, 64, jnp.float32) == "dense"  # decode: the held columns


@pytest.mark.parametrize("kw,what", [
    (dict(held=4, ep_degree=2), "ep_degree > 1"),
    (dict(held=4, hybrid_cte_full_tp=True), "hybrid_sharding_config"),
    (dict(quantized=True), "quantised experts"),
])
def test_what_two_matrix_experts_or_a_held_share_cannot_serve_is_refused_by_name(kw, what):
    params, _ = _expert_layer()
    quantized = kw.pop("quantized", False)
    with pytest.raises(moe.ExpertLayerError, match=what):
        moe.validate_expert_layer(_spec(**kw), params["experts"], quantized=quantized)
    moe.validate_expert_layer(_spec(held=4, first=4), params["experts"])  # a sound share
    with pytest.raises(ValueError, match="not a share"):
        moe.validate_expert_layer(_spec(held=4, first=5), params["experts"])
    # gated experts are held to none of the two-matrix rules
    gated = dict(params["experts"], gate_proj=params["experts"]["up_proj"])
    moe.validate_expert_layer(_spec(early_affinity_modulation=True), gated)


@pytest.mark.parametrize("change,error,what", [
    (dict(hybrid_override_pattern="MEMEM*EMEMEM-"), NotImplementedError, "a '-' block"),
    (dict(hybrid_override_pattern="MEMEM*"), ValueError, "hybrid_override_pattern"),
    (dict(mlp_hidden_act="silu"), NotImplementedError, "mlp_hidden_act"),
    (dict(n_group=2), NotImplementedError, "group-limited"),
    (dict(use_bias=True), NotImplementedError, "bias"),
    (dict(time_step_limit=[0.0, 1.0]), NotImplementedError, "time_step_limit"),
    (dict(n_groups=3), NotImplementedError, "n_groups"),
    (dict(n_routed_experts_published=16), ValueError, "expert_share"),
    (dict(expert_share={"first": 2, "of": 2}), ValueError, "expert_share"),
])
def test_what_the_model_does_not_build_is_refused_at_config_time(change, error, what):
    with pytest.raises(error, match=what):
        make_config(dict(ATTRS, **change))


@pytest.mark.parametrize("tpu,what", [
    (dict(is_prefix_caching=True), "is_prefix_caching"),
    (dict(serving_ragged=True), "serving_ragged"),
    (dict(speculation_length=4), "speculation"),
    (dict(kv_cache_dtype="int8"), "quantisation"),
    (dict(tp_degree=2), "degree > 1"),
])
def test_what_a_per_slot_state_cannot_be_served_with_is_refused(tpu, what):
    with pytest.raises(SlotStateServingError, match=what):
        make_config(**tpu)


def test_a_pass_is_counted_under_both_families_and_the_share_is_told():
    """``nxdi_ssm_*`` (the builder's state KIND) and ``nxdi_moe_*``
    (``expert_layers()``) in one session; ``nxdi_moe_experts_hit_total``
    counts the HELD experts a dispatch streams, ``nxdi_moe_experts_held{of}``
    tells the share, ``nxdi_moe_rows_routed_total`` the choices made."""
    app = TpuModelForCausalLM(None, make_config()).load(random_weights=True)
    assert app.builder.expert_layers() == (5, 4, 2)
    assert app.builder.moe_spec().num_experts == 8 and app.builder.moe_spec().first_expert == 0
    with TelemetrySession() as tel:
        s = ServingSession(app, telemetry=tel)
        s.add_request("a", np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
        drain(s)
        snap = tel.registry.snapshot()
    total = lambda name, **labels: sum(
        x["value"] for x in snap[name]["samples"]
        if all(x["labels"].get(k) == v for k, v in labels.items()))
    assert [(x["labels"], x["value"]) for x in snap["nxdi_moe_experts_held"]["samples"]] == [({"of": "8"}, 4.0)]
    # 19 tokens in 2 chunks of 16; 3 tokens = the prefill's and 2 decode steps (and a dispatch ahead)
    decodes = total("nxdi_steps_total", kind="decode")
    assert decodes >= 2
    assert total("nxdi_moe_experts_hit_total", program="chunk") == 2 * 5 * 4
    assert total("nxdi_moe_experts_hit_total", program="decode") == decodes * 5 * 4
    assert total("nxdi_moe_rows_routed_total", program="chunk") == 19 * 5 * 2
    assert total("nxdi_moe_rows_routed_total", program="decode") == decodes * 5 * 2
    assert total("nxdi_ssm_rows_advanced_total", program="decode") == decodes
    assert total("nxdi_ssm_state_resets_total") == 1


# ---------------------------------------------------------------------------
# the reference's parts against what transformers has installed
# ---------------------------------------------------------------------------


def _geo(**kw):
    base = dict(hidden=64, pattern="M", heads=4, kv_heads=2, head_dim=16, vocab=8, rms_eps=1e-5,
                m_heads=16, m_head_dim=8, m_state=32, m_groups=8, m_conv=4, experts=8, held=8,
                first=0, top_k=2, norm_topk=True, scaling=2.5, rope_theta=1e4, degree=1)
    return ref.Geometry(**dict(base, **kw))


def test_the_references_recurrence_and_conv_are_the_installed_mamba2_at_eight_groups():
    """``Mamba2Mixer.torch_forward`` (transformers 4.57.6), ``n_groups`` 8:
    the same in_proj split, conv, dt, A, groups of B/C and D skip. The
    installed gated norm is over ONE group whatever ``n_groups`` (a departure
    of theirs from the nemotron_h modeling), so the reference is given its
    one-group variant here, and the grouped form is held by its equation in
    the next test."""
    torch = pytest.importorskip("torch")
    from transformers.models.mamba2 import Mamba2Config
    from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer

    geo = _geo()
    cfg = Mamba2Config(num_heads=16, head_dim=8, hidden_size=64, expand=2, state_size=32, n_groups=8,
                       conv_kernel=4, use_conv_bias=True, use_bias=False, rms_norm=True,
                       layer_norm_epsilon=1e-5, chunk_size=8, time_step_limit=(0.0, float("inf")))
    torch.manual_seed(0)
    mixer = Mamba2Mixer(cfg, layer_idx=0).float().eval()
    with torch.no_grad():
        for p in (mixer.dt_bias, mixer.D, mixer.norm.weight, mixer.conv1d.bias):
            p.copy_(torch.randn_like(p) * 0.5 + (1.0 if p is not mixer.conv1d.bias else 0.0))
        mixer.dt_bias.sub_(3.0)
        x = torch.randn(1, 21, 64)
        want = mixer.torch_forward(x).numpy()[0]
    t = lambda a: jnp.asarray(a.detach().numpy())
    w = {"w_in": t(mixer.in_proj.weight).T, "conv_w": t(mixer.conv1d.weight)[:, 0, :].T,
         "conv_b": t(mixer.conv1d.bias), "A_log": t(mixer.A_log), "D": t(mixer.D),
         "dt_bias": t(mixer.dt_bias), "gnorm": t(mixer.norm.weight), "w_out": t(mixer.out_proj.weight).T}
    with jax.default_matmul_precision("highest"):
        got, y, z = ref.mamba_mixer(jnp.asarray(x.numpy()[0]), w, geo, fault="gated_norm_one_group")
        grouped, _, _ = ref.mamba_mixer(jnp.asarray(x.numpy()[0]), w, geo)
        shared_bc, _, _ = ref.mamba_mixer(jnp.asarray(x.numpy()[0]), w, geo, fault="groups_read_as_one")
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5 * np.abs(want).max())
    # the groups matter, and so does the norm's grouping
    assert np.abs(np.asarray(shared_bc) - want).max() > 1e-2 * np.abs(want).max()
    assert np.abs(np.asarray(grouped) - want).max() > 1e-2 * np.abs(want).max()


def test_the_references_gated_norm_by_group_is_its_equation():
    rng = np.random.default_rng(3)
    y, z = rng.standard_normal((7, 64)), rng.standard_normal((7, 64))
    w = rng.standard_normal(64)
    got = np.asarray(ref.grouped_gated_norm(jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
                                            jnp.asarray(w, jnp.float32), 8, 1e-5))
    v = (y * z / (1 + np.exp(-z))).reshape(7, 8, 8)
    want = (v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(7, 64) * w
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_the_references_router_is_the_installed_deepseek_v3_gate():
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3TopkRouter

    cfg = DeepseekV3Config(hidden_size=64, n_routed_experts=8, num_experts_per_tok=2, n_group=1,
                           topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5)
    torch.manual_seed(1)
    gate = DeepseekV3TopkRouter(cfg).float().eval()
    with torch.no_grad():
        gate.weight.copy_(torch.randn_like(gate.weight))
        gate.e_score_correction_bias.copy_(torch.randn(8) * 0.3)
        x = torch.randn(30, 64)
        idx, wts = gate(x[None])
    with jax.default_matmul_precision("highest"):
        _, chosen, taken = ref.router(jnp.asarray(x.numpy()), jnp.asarray(gate.weight.detach().numpy()).T,
                                      jnp.asarray(gate.e_score_correction_bias.numpy()), _geo())
    order = np.argsort(np.asarray(chosen), -1)
    theirs = np.argsort(idx.numpy().reshape(30, 2), -1)
    assert np.array_equal(np.take_along_axis(np.asarray(chosen), order, -1),
                          np.take_along_axis(idx.numpy().reshape(30, 2), theirs, -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(taken), order, -1),
                               np.take_along_axis(wts.numpy().reshape(30, 2), theirs, -1), rtol=1e-5, atol=1e-6)


def test_the_checkpoint_names_fill_the_tree():
    """``convert_hf_state_dict`` from the published names (``backbone.layers.
    N.mixer.*``): every leaf of ``param_shapes`` at its shape, the held
    experts taken from ``first_expert`` on, up as published (out, in)."""
    attrs = dict(ATTRS, expert_share={"first": 1, "of": 2})
    b = get_model_builder("nemotron_h")(make_config(attrs))
    s, rng, sd = b.ssm_spec(), np.random.default_rng(0), {}
    n = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    for i, kind in enumerate(attrs["hybrid_override_pattern"]):
        p = f"backbone.layers.{i}."
        sd[p + "norm.weight"] = n(128)
        if kind == "M":
            sd.update({p + "mixer.in_proj.weight": n(2 * s.d_inner + 2 * 4 * 32 + 8, 128),
                       p + "mixer.conv1d.weight": n(s.conv_dim, 1, 4), p + "mixer.conv1d.bias": n(s.conv_dim),
                       p + "mixer.A_log": n(8), p + "mixer.D": n(8), p + "mixer.dt_bias": n(8),
                       p + "mixer.norm.weight": n(s.d_inner), p + "mixer.out_proj.weight": n(128, s.d_inner)})
        elif kind == "*":
            sd.update({p + "mixer.q_proj.weight": n(128, 128), p + "mixer.k_proj.weight": n(64, 128),
                       p + "mixer.v_proj.weight": n(64, 128), p + "mixer.o_proj.weight": n(128, 128)})
        else:
            sd.update({p + "mixer.gate.weight": n(8, 128), p + "mixer.gate.e_score_correction_bias": n(8),
                       p + "mixer.shared_experts.up_proj.weight": n(96, 128),
                       p + "mixer.shared_experts.down_proj.weight": n(128, 96)})
            for e in range(8):
                sd[p + f"mixer.experts.{e}.up_proj.weight"] = n(48, 128)
                sd[p + f"mixer.experts.{e}.down_proj.weight"] = n(128, 48)
    sd.update({"backbone.embeddings.weight": n(512, 128), "backbone.norm_f.weight": n(128),
               "lm_head.weight": n(512, 128)})
    params = b.convert_hf_state_dict(sd, dtype=jnp.float32)
    shapes = jax.tree.map(lambda a: tuple(a.shape), params)
    assert shapes == b.param_shapes()
    experts = params["layers"]["moe"]["mlp"]["experts"]
    np.testing.assert_array_equal(np.asarray(experts["up_proj"]["weight"][0, 0]),
                                  sd["backbone.layers.1.mixer.experts.4.up_proj.weight"])
    np.testing.assert_array_equal(np.asarray(experts["down_proj"]["weight"][0, 3]),
                                  sd["backbone.layers.1.mixer.experts.7.down_proj.weight"].T)


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
def test_a_padded_position_is_routed_to_no_expert(path):
    """``valid``: a padded position of a chunk pass leaves the sort (no group,
    no visit) and gets nothing from the routed experts; the real positions
    get what they got."""
    params, x = _expert_layer(seed=3, tokens=48)
    spec = _spec(held=4, first=0)
    mine = _share_of(params, 0, 4)
    valid = jnp.asarray(np.arange(48) % 3 != 1)
    aff, _ = moe.linear_router(mine, x[0], spec)
    _, se, _, sizes = moe._sorted_dispatch(aff, K, 0, 4, valid)
    assert int(sizes.sum()) == int((np.asarray(se) < 4).sum()) <= int(valid.sum()) * K
    with jax.default_matmul_precision("highest"):
        whole = moe.expert_mlps_grouped(mine["experts"], x[0], aff, spec, kernel=path == "kernel")
        got = moe.expert_mlps_grouped(mine["experts"], x[0], aff, spec, kernel=path == "kernel", valid=valid)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(valid)], np.asarray(whole)[np.asarray(valid)],
                               rtol=0, atol=1e-5)
    assert np.abs(np.asarray(got)[~np.asarray(valid)]).max() == 0
