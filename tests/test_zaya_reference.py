"""ZAYA1 (``model_type: "zaya"``) on the paged, chunked serving path, held to
the benchmark's plain reference (``benchmark/harness/references/zaya.py``:
float32, no cache, no kernel, the convs as explicit shifts) — logits, not
tokens. Small size, CPU, seeded random weights.

What is new in this model and what holds it here: the one-token carry per
slot beside paged K/V (across chunk boundaries, a last chunk with invalid
positions, a reused slot, a row that sits a pass out, scrub, preemption), the
value shift, the q-k mean, the normalised q/k with a key temperature, the
partial rotary, the router's carry from layer to layer, ``p_e``, the choices
the step returns as ``forward``'s third value, and ``moe_layer``'s router as
a function (the default one bit for bit the parent's).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import correct
from benchmark.harness.references import zaya as ref
from neuronx_distributed_inference_tpu.config import (
    ChunkedPrefillConfig,
    SlotStateServingError,
    TpuConfig,
    to_dtype,
)
from neuronx_distributed_inference_tpu.models import get_model_builder
from neuronx_distributed_inference_tpu.modules import moe
from neuronx_distributed_inference_tpu.modules.rope import apply_rope, rope_cos_sin
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector, fill_slot_state
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import LogitSpy, drain

CHUNK = 16
ATTRS = dict(
    model_type="zaya", hidden_size=128, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    num_hidden_layers=3, layer_types=["hybrid"] * 3, vocab_size=512, rms_norm_eps=1e-5,
    hidden_act="silu", cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                                "rope_type": "default"}},
    num_experts=8, num_experts_per_tok=1, moe_intermediate_size=64, router_hidden_size=32,
    tie_word_embeddings=True, attention_bias=False, sliding_window=None,
)
#: the same model at a head the Pallas kernels take (head_dim 64), two layers
KERNEL_ATTRS = dict(ATTRS, head_dim=64, num_hidden_layers=2, layer_types=["hybrid"] * 2)


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
    cls = get_model_builder("zaya").config_cls
    return cls(TpuConfig(**opts), load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])


@pytest.fixture(scope="module")
def app():
    return TpuModelForCausalLM(None, make_config()).load(random_weights=True)


@pytest.fixture(scope="module", params=["native", "kernels"])
def served(request, app):
    """(app, attrs): the module's app on native attention, or one whose
    served programs hold both paged attention kernels (forced: the auto gates
    ask for a TPU; interpret mode here)."""
    if request.param == "native":
        return app, ATTRS
    cfg = make_config(KERNEL_ATTRS, attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)
    return TpuModelForCausalLM(None, cfg).load(random_weights=True), KERNEL_ATTRS


def check_request(app, attrs, spy, slot, prompt, generated, tol=2e-5):
    """Served logits at the last prompt position and after every generated
    token but the last, against the reference's full forward."""
    geo = ref.geometry(attrs, 1)
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    want = ref.reference_logits(app.params, geo, list(prompt) + list(generated[:-1]), positions)
    got = np.stack([spy.at(slot, p) for p in positions])
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(1.0, np.abs(want).max()))


def test_chunked_prefill_then_decode_is_the_reference_and_a_reused_slot_starts_clean(served):
    """A prompt of 2.5 chunks, then 1-ahead decode; a second request is
    admitted into the slot the first has left (its carry is there still: the
    position-0 rule zeroes it), beside a third in another."""
    app, attrs = served
    app.init_kv_cache()
    rng = np.random.default_rng(2)
    first = rng.integers(0, 512, size=int(2.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("first", first, max_new_tokens=6)
        drain(s)
        assert s.requests["first"].slot == -1
        assert np.abs(np.asarray(app.kv_cache.state.last[:, 0])).max() > 0  # left behind
        check_request(app, attrs, spy, 0, first, s.requests["first"].generated)
        spy.rows.clear()
        second, third = rng.integers(0, 512, size=21), rng.integers(0, 512, size=50)
        s.add_request("second", second, max_new_tokens=5)
        s.add_request("third", third, max_new_tokens=5)
        slots = {r: s.requests[r].slot for r in ("second", "third")}
        assert slots["second"] == 0  # the slot "first" held, carry and all
        drain(s)
        check_request(app, attrs, spy, slots["second"], second, s.requests["second"].generated)
        check_request(app, attrs, spy, slots["third"], third, s.requests["third"].generated)


def _prefill(app, prompt, chunk, slot=0, width=128):
    """The prompt through ``app.forward`` in chunks of ``chunk`` (the last
    padded with invalid positions); the logits at its last position."""
    bs = app.config.tpu_config.pa_block_size
    table = (1 + slot * (width // bs) + np.arange(width // bs))[None].astype(np.int32)
    last = None
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        ids, sm = np.zeros((1, chunk), np.int32), np.full((1, chunk), -1, np.int32)
        pos = (start + np.arange(chunk))[None].astype(np.int32)
        ids[0, :n] = prompt[start : start + n]
        sm[0, :n] = table[0, pos[0, :n] // bs] * bs + pos[0, :n] % bs
        mask = (np.arange(width)[None] < start + n).astype(np.int32)
        _, logits, _ = app.forward(ids, pos, np.asarray([slot], np.int32), attention_mask=mask,
                                   slot_mapping=sm, block_table=table, phase="tkg")
        last = logits[0, n - 1]
    return last, table


def _decode(app, token, position, slot, table, width=128):
    B = app.config.tpu_config.batch_size
    ids, pos = np.zeros((B, 1), np.int32), np.zeros((B, 1), np.int32)
    rows, tables = np.full(B, -1, np.int32), np.zeros((B, table.shape[1]), np.int32)
    ids[slot], pos[slot], rows[slot], tables[slot] = token, position, slot, table[0]
    mask = (np.arange(width)[None, :] <= pos).astype(np.int32)
    _, logits, _ = app.forward(ids, pos, rows, attention_mask=mask, block_table=tables, phase="tkg")
    return logits[slot, 0]


def test_a_prompt_in_three_chunks_is_the_prompt_in_one(app):
    """The conv carry (both stages) and the value shift across two chunk
    boundaries, the last chunk 8 valid positions of 16: the logits at the
    last position and after one decode step equal those of the same prompt
    in one chunk of 64 (24 invalid positions), and the carry left behind is
    the same token's, bit for bit."""
    prompt = np.random.default_rng(3).integers(0, 512, size=40)
    runs = []
    for chunk in (CHUNK, 64):
        app.init_kv_cache()
        last, table = _prefill(app, prompt, chunk)
        carry = np.asarray(app.kv_cache.state.last[:, 0])
        runs.append((last, _decode(app, 7, len(prompt), 0, table), carry))
    (a_last, a_next, a_carry), (b_last, b_next, b_carry) = runs
    np.testing.assert_allclose(a_last, b_last, rtol=0, atol=2e-6)
    np.testing.assert_allclose(a_next, b_next, rtol=0, atol=2e-6)
    np.testing.assert_allclose(a_carry, b_carry, rtol=0, atol=2e-6)
    assert np.abs(a_carry).max() > 0.1


def test_the_carry_between_chunks_is_not_small_here(app):
    """The control of the tests above: with the carry zeroed before the
    prompt's last chunk the logits move by many times their tolerance."""
    prompt = np.random.default_rng(3).integers(0, 512, size=40)
    app.init_kv_cache()
    sound, _ = _prefill(app, prompt, CHUNK)
    app.init_kv_cache()
    _prefill(app, prompt[:32], CHUNK)
    app.kv_cache = fill_slot_state(app.kv_cache, [0], 0.0)
    bs = app.config.tpu_config.pa_block_size
    table = (1 + np.arange(128 // bs))[None].astype(np.int32)
    ids, sm = np.zeros((1, CHUNK), np.int32), np.full((1, CHUNK), -1, np.int32)
    pos = (32 + np.arange(CHUNK))[None].astype(np.int32)
    ids[0, :8], sm[0, :8] = prompt[32:], table[0, pos[0, :8] // bs] * bs + pos[0, :8] % bs
    mask = (np.arange(128)[None] < 40).astype(np.int32)
    _, logits, _ = app.forward(ids, pos, np.asarray([0], np.int32), attention_mask=mask,
                               slot_mapping=sm, block_table=table, phase="tkg")
    assert np.abs(logits[0, 7] - sound).max() > 100 * 2e-5 * max(1.0, np.abs(sound).max())


def test_a_row_that_sits_a_pass_out_keeps_its_carry_bit_identical(app):
    """A chunk pass advances the slots of its rows only, a decode pass its
    live rows only: the carry of every other slot is the same bits after."""
    app.init_kv_cache()
    rng = np.random.default_rng(4)
    mark = jnp.asarray(rng.standard_normal(app.kv_cache.state.last.shape), jnp.float32)
    app.kv_cache = type(app.kv_cache)(k=app.kv_cache.k, v=app.kv_cache.v,
                                      state=type(app.kv_cache.state)(last=mark))
    before = np.asarray(mark)
    _, table = _prefill(app, rng.integers(0, 512, size=20), CHUNK, slot=1)  # a chunk pass of slot 1
    after = np.asarray(app.kv_cache.state.last)
    assert np.array_equal(after[:, [0, 2, 3]], before[:, [0, 2, 3]])
    assert not np.array_equal(after[:, 1], before[:, 1])
    _decode(app, 5, 20, 1, table)  # a decode pass: row 1 live, rows 0, 2, 3 sit it out
    again = np.asarray(app.kv_cache.state.last)
    assert np.array_equal(again[:, [0, 2, 3]], before[:, [0, 2, 3]])
    assert not np.array_equal(again[:, 1], after[:, 1])


def test_partial_rotary_is_the_explicit_formula():
    """The first 64 of a head's 128 dimensions rotate, as pairs (i, i + 32)
    by angle position x theta^(-2i/64); the other 64 pass through."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 3, 2, 128)).astype(np.float32)
    positions = np.asarray([[0, 1, 700], [5, 6, 7]], np.int32)
    theta, n = 5e6, 64
    inv_freq = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    cos, sin = rope_cos_sin(jnp.asarray(positions), jnp.asarray(inv_freq, jnp.float32))
    got = np.asarray(apply_rope(jnp.asarray(x), cos, sin))
    want = x.astype(np.float64).copy()
    for b in range(2):
        for s in range(3):
            for i in range(n // 2):
                ang = positions[b, s] * inv_freq[i]
                lo, hi = x[b, s, :, i].astype(np.float64), x[b, s, :, i + n // 2].astype(np.float64)
                want[b, s, :, i] = lo * np.cos(ang) - hi * np.sin(ang)
                want[b, s, :, i + n // 2] = hi * np.cos(ang) + lo * np.sin(ang)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)  # float32 angles at position 700
    assert np.array_equal(got[..., n:], x[..., n:])
    # full rotary (tables as wide as half the head) is what it was
    full_cos, full_sin = rope_cos_sin(jnp.asarray(positions), jnp.asarray(
        theta ** (-np.arange(0, 128, 2) / 128), jnp.float32))
    full = np.asarray(apply_rope(jnp.asarray(x), full_cos, full_sin))
    assert full.shape == x.shape and not np.array_equal(full[..., n:], x[..., n:])


def test_the_steps_third_value_is_the_references_own_choice(app):
    """``forward`` returns the expert of every token and layer under
    ``output_choices``; at float32 they are the reference's own argmax, and
    every expert of a layer is some token's (the test weights spread)."""
    app.init_kv_cache()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, size=n).astype(np.int32) for n in (40, 9)]
    chosen = [[int(t) for t in rng.integers(0, 512, size=5)] for _ in prompts]
    served, choices = correct._forced_pass(app, prompts, chosen, 128)
    geo = ref.geometry(ATTRS, 1)
    for p, c, got, row in zip(prompts, chosen, served, choices):
        tokens, positions = correct.probe_row(p, c)
        want, _, own = ref.forward(app.params, geo, tokens, positions)
        assert row[ref.NAME].shape == (len(tokens), 3, 1) and row[ref.NAME].dtype == np.int32
        assert np.array_equal(row[ref.NAME], np.transpose(own, (1, 0, 2)))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * max(1.0, np.abs(want).max()))
    used = {int(e) for e in choices[0][ref.NAME][:, 0, 0]}
    assert len(used) >= 4, used


def test_without_the_option_forward_returns_two_values_and_a_model_without_choices_refuses_it():
    from tests.conftest import make_tiny_config

    plain = TpuModelForCausalLM(None, make_config(output_choices=False)).load(random_weights=True)
    out = plain.forward(np.zeros((4, 1), np.int32), np.zeros((4, 1), np.int32),
                        np.arange(4, dtype=np.int32), block_table=np.zeros((4, 8), np.int32), phase="tkg")
    assert len(out) == 2
    llama = TpuModelForCausalLM(None, make_tiny_config(tpu=dict(output_choices=True)))
    llama.load(random_weights=True)
    with pytest.raises(NotImplementedError, match="output_choices"):
        llama.generate(np.array([[1, 2, 3]]), np.ones((1, 3), np.int32), max_new_tokens=2)


def _parents_moe_layer(params, hidden, spec):
    """``moe_layer`` with the router as the PARENT of this PR wrote it inside
    the function (frozen copy), the expert strategies shared."""

    def parents_router(params, x, spec):
        rdt = to_dtype(spec.router_dtype)
        router_logits = x.astype(rdt) @ params["router"]["weight"].astype(rdt)
        if spec.router_bias:
            router_logits = router_logits + params["router"]["bias"].astype(rdt)
        correction = params["router"].get("e_score_correction_bias")
        if correction is not None:
            correction = correction.astype(jnp.float32)
        return moe.router_top_k(router_logits.astype(jnp.float32), spec, correction_bias=correction)

    return moe.moe_layer(params, hidden, spec, router=parents_router)


@pytest.mark.parametrize("shape", [(4, 1), (2, 64)], ids=["decode", "chunk"])
@pytest.mark.parametrize("model_type,norm_topk", [("mixtral", True), ("qwen3_moe", False)])
def test_moe_layer_with_the_default_router_is_the_parents(model_type, norm_topk, shape):
    from tests.conftest import make_tiny_config

    cfg = make_tiny_config(model_type=model_type, num_local_experts=16, num_experts=16,
                           num_experts_per_tok=1 if shape[1] > 1 else 2, norm_topk_prob=norm_topk,
                           moe_intermediate_size=64)
    builder = get_model_builder(model_type)(cfg)
    spec = builder.moe_spec()
    params = jax.tree.map(lambda a: a[0], builder.random_params()["layers"]["mlp"])
    hidden = jnp.asarray(np.random.default_rng(7).standard_normal(shape + (cfg.hidden_size,)),
                         params["router"]["weight"].dtype)
    ours, parents = (jax.jit(lambda p, h, fn=fn: fn(p, h, spec))(params, hidden)
                     for fn in (moe.moe_layer, _parents_moe_layer))
    assert np.array_equal(np.asarray(ours), np.asarray(parents))
    assert str(jax.make_jaxpr(lambda p, h: moe.moe_layer(p, h, spec))(params, hidden)) == str(
        jax.make_jaxpr(lambda p, h: _parents_moe_layer(p, h, spec))(params, hidden))


def test_bf16_serving_stays_within_the_twins_noise_and_its_choices_within_the_margin():
    """The benchmark's own rule (``correct.judge``: replay, then margin) at
    the small size: the served bf16 model on the teacher-forced probe path."""
    bapp = TpuModelForCausalLM(None, make_config(dtype="bfloat16")).load(random_weights=True)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 512, size=n).astype(np.int32) for n in (int(2.5 * CHUNK), 9)]
    chosen = [[int(t) for t in rng.integers(0, 512, size=5)] for _ in prompts]
    served, choices = correct._forced_pass(bapp, prompts, chosen, 128)
    cfg = dict(ATTRS, reference="zaya")
    for r in range(2):  # a row at a time: the tokens of a second row would be held to the session's rule
        facts = correct.judge(cfg, bapp.params, 1, prompts[r:r + 1], chosen[r:r + 1],
                              served[r:r + 1], choices[r:r + 1])
        row = facts["rows"][0]
        assert 0.2 < row["ratio"] <= correct.K, facts
        assert len(row["choice_regret"]) == 3
    with pytest.raises(correct.CorrectnessError, match="returned none"):
        correct.judge(cfg, bapp.params, 1, prompts, chosen, served, None)


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(app):
    app.init_kv_cache()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, size=n) for n in (40, 27)]
    geo = ref.geometry(ATTRS, 1)
    with LogitSpy(app) as spy:
        s = ServingSession(app, fault_injector=FaultInjector().exhaust_pool(5))
        for i, p in enumerate(prompts):
            s.add_request(f"r{i}", p, max_new_tokens=8)
        drain(s)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        for i, p in enumerate(prompts):
            req = s.requests[f"r{i}"]
            positions = [len(p) - 1 + k for k in range(8)]
            want = ref.reference_logits(app.params, geo, list(p) + req.generated[:-1], positions)
            ok = False
            for slot in range(s.num_slots):  # re-admission may land in either slot
                try:
                    got = np.stack([spy.at(slot, q) for q in positions])
                except AssertionError:
                    continue
                ok = ok or np.abs(got - want).max() <= 2e-5 * max(1.0, np.abs(want).max())
            assert ok, f"r{i}: no slot served the reference's logits after the preemption"


def test_scrub_zeroes_the_slots_carry_and_only_it(app):
    app.init_kv_cache()
    rng = np.random.default_rng(10)
    s = ServingSession(app)
    s.add_request("victim", rng.integers(0, 512, size=30), max_new_tokens=6)
    s.add_request("bystander", rng.integers(0, 512, size=30), max_new_tokens=6)
    for _ in range(4):
        s.step()
    before = np.asarray(app.kv_cache.state.last[:, 1])
    assert np.abs(np.asarray(app.kv_cache.state.last[:, 0])).max() > 0
    s._finish(s.requests["victim"], reason="test", scrub=True)
    assert not np.asarray(app.kv_cache.state.last[:, 0]).any()
    assert np.array_equal(np.asarray(app.kv_cache.state.last[:, 1]), before)
    drain(s)
    assert len(s.requests["bystander"].generated) == 6


def test_the_pool_spans_every_layer_beside_the_carry(app):
    cache = app.kv_cache
    assert app.paged_layers == 3 and cache.k.shape[:3] == (3, 49, 2)
    assert cache.state.last.shape == (3, 4, 2 * 6 * 32 + 32)  # [u | a | W_v2 x] a slot a layer
    assert ServingSession(app).block_bytes == 3 * 2 * 2 * 32 * 4 * 16


def test_moe_and_carry_counters_count_what_the_step_knows(app):
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    app.init_kv_cache()
    tel = TelemetrySession(enabled=True)
    s = ServingSession(app, telemetry=tel)
    rng = np.random.default_rng(11)
    s.add_request("a", rng.integers(0, 512, size=40), max_new_tokens=3)  # 3 chunk passes
    s.add_request("b", rng.integers(0, 512, size=10), max_new_tokens=3)  # 1
    drain(s)
    snap = tel.registry.snapshot()
    by = lambda name: {x["labels"]["program"]: x["value"] for x in snap[name]["samples"]}
    steps = {x["labels"]["kind"]: x["value"] for x in snap["nxdi_steps_total"]["samples"]}
    carry, rows, experts = (by(n) for n in ("nxdi_latent_carry_rows_advanced_total",
                                            "nxdi_moe_rows_routed_total", "nxdi_moe_experts_hit_total"))
    assert carry["chunk"] == 4 and carry["decode"] >= 4
    assert rows["chunk"] == 50 * 3 * 1  # real prompt tokens x expert layers x top-1
    assert rows["decode"] == carry["decode"] * 3
    assert experts["decode"] == steps["decode"] * 3 * 8  # every held expert of every layer, a dispatch
    assert experts["chunk"] == snap["nxdi_prefill_chunk_dispatches_total"]["samples"][0]["value"] * 3 * 8
    assert "nxdi_ssm_rows_advanced_total" not in snap or not snap["nxdi_ssm_rows_advanced_total"]["samples"]
    # the routed rows again, by the strategy each pass's program holds: off the
    # chip no pass takes the kernel, and chunks of under 64 positions are dense
    grouped = {(x["labels"]["program"], x["labels"]["path"]): x["value"]
               for x in snap["nxdi_moe_grouped_rows_total"]["samples"]}
    assert grouped == {("chunk", "dense"): rows["chunk"], ("decode", "dense"): rows["decode"]}


@pytest.mark.parametrize("tpu,match", [
    (dict(is_prefix_caching=True), "is_prefix_caching"),
    (dict(serving_ragged=True), "serving_ragged"),
    (dict(speculation_length=4), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(tp_degree=2), "degree > 1"),
], ids=["prefix_caching", "ragged", "speculation", "kv_quant", "tp2"])
def test_what_the_carry_does_not_support_yet_is_a_typed_refusal(tpu, match):
    with pytest.raises(SlotStateServingError, match=match) as e:
        make_config(**tpu)
    assert "one-token carry" in str(e.value)


@pytest.mark.parametrize("attrs,match", [
    (dict(num_experts_per_tok=2), "num_experts_per_tok"),
    (dict(cca_time1=3), "cca_time"),
    (dict(layer_types=["hybrid", "hybrid_sliding", "hybrid"]), "layer_types"),
    (dict(sliding_window=4096), "sliding_window"),
])
def test_unwritten_mechanisms_are_refused_not_guessed(attrs, match):
    with pytest.raises(NotImplementedError, match=match):
        make_config({**ATTRS, **attrs})


def test_the_unpaged_paths_are_refused():
    cfg = make_config(is_block_kv_layout=False, is_chunked_prefill=False, chunked_prefill_config=None)
    with pytest.raises(NotImplementedError, match="paged, chunked path"):
        get_model_builder("zaya")(cfg)
