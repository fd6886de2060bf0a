"""Brumby (``model_type: "brumby"``: Qwen3's decoder with power retention of
degree 2 in attention's place) on the paged, chunked serving path, held to the
benchmark's plain reference (``benchmark/harness/references/brumby.py``: the
ATTENTION form, float32, no state, no chunk, no kernel) in logits, and the
three forms of the recurrence held to each other. Small size, CPU, seeded
random weights.

What is new in this model and what holds it here: a state of ``D x d`` a KV
head (``D`` the tiled symmetric square of the key) read by the five query
heads of its group, the chunked form on the LIVE rows of the stacked state in
place, the decode kernel that builds ``phi`` in VMEM, a stack in which NO layer
pages (no pool, admission by slots alone, no block table in either program),
and the typed refusals.

Tolerances, each with its reason: 1e-5 of the logits' scale for the served
path against the reference (float32 both; the two sum the same terms in
another order: the recurrence carries ``phi(k) v^T`` decayed token by token,
the reference sums ``a_tj v_j`` over j; the served path reads 1e-7 - 1e-6 here,
and the same recurrence with its state rounded to bf16 after every token 8e-5:
the tolerance lies between, five times under the bf16 state); 2e-5 relative for one form of the
recurrence against another (float32 products at ``Precision.HIGHEST``, sums of
some thousand terms in another order).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness.references import brumby as ref
from neuronx_distributed_inference_tpu.config import (
    ChunkedPrefillConfig,
    SlotStateServingError,
    TpuConfig,
)
from neuronx_distributed_inference_tpu.models import get_model_builder
from neuronx_distributed_inference_tpu.models.granite_hybrid import POWER
from neuronx_distributed_inference_tpu.modules import power_retention as pr
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    SLOT_STATE,
    HybridBlockCache,
    NoPoolAllocator,
)
from neuronx_distributed_inference_tpu.ops.power_state_update import power_state_update
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector, fill_slot_state
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import LogitSpy, drain

CHUNK = 16
SLOTS = 16
ATTRS = dict(
    model_type="brumby", hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    num_attention_heads=10, num_key_value_heads=2, head_dim=16, vocab_size=256,
    rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, max_position_embeddings=512,
    sliding_window=None, use_sliding_window=False, max_window_layers=2,
    power_degree=2, power_norm_eps=1e-6, power_state_dim=192,
)
TOL = 1e-5


def make_config(attrs=ATTRS, **tpu):
    opts = dict(
        dtype="float32", batch_size=SLOTS, seq_len=256, enable_bucketing=True,
        context_encoding_buckets=[256], token_generation_buckets=[256],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_block_size=16, pa_pool_bytes=1 << 20, is_chunked_prefill=True, output_logits=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
    )
    opts.update(tpu)
    cls = get_model_builder("brumby").config_cls
    return cls(TpuConfig(**opts), load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])


@pytest.fixture(scope="module")
def app():
    return TpuModelForCausalLM(None, make_config()).load(random_weights=True)


def served_is_reference(app, spy, slot, prompt, generated, tol=TOL):
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


def test_no_layer_pages_and_the_application_builds_no_pool(app):
    """``pa_pool_bytes`` is set and no layer pages: nothing is divided by a
    block's bytes (0), the pool has zero layers and no byte, the session's
    allocator holds nothing."""
    b = app.builder
    assert b.cache_layers() == (SLOT_STATE,) * 2 and app.paged_layers == 0
    assert b.layer_fn().layer_types == (POWER, POWER)
    assert app.config.tpu_config.pa_num_blocks == 0
    cache = app.kv_cache
    assert isinstance(cache, HybridBlockCache)
    assert cache.k.shape[0] == 0 and cache.k.size == 0 and cache.v.size == 0
    state = cache.state
    assert isinstance(state, pr.PowerState) and state.KIND == "power"
    assert pr.state_dim(16) == 192 and pr.state_dim(128) == 8704
    assert state.ssm.shape == (2, SLOTS, 2, 192, 16) and state.ssm.dtype == jnp.float32
    assert state.conv.shape == (2, SLOTS, 2, 192) and state.conv.dtype == jnp.float32  # the normaliser z
    assert state.nbytes == 4 * (state.ssm.size + state.conv.size)
    s = ServingSession(app)
    assert isinstance(s.allocator, NoPoolAllocator) and not s.pooled
    assert s.kv_pool_bytes == 0 and s.kv_free_bytes == 0 and s.slot_state_kind == "power"
    shapes = b.param_shapes()["layers"][POWER]["self_attn"]
    assert shapes["g_proj"] == {"weight": (2, 64, 2), "bias": (2, 2)}


def test_chunked_prefill_then_decode_is_the_reference_as_rows_join_and_leave(app):
    """A prompt of 3.5 chunks (the state carried from chunk to chunk, a last
    chunk with invalid positions), then decode through the state kernel; a
    second request is admitted into the slot the first has left (its state is
    there still: the position-0 rule zeroes it) beside a third, which joins
    while the second decodes."""
    app.init_kv_cache()
    rng = np.random.default_rng(2)
    first = rng.integers(0, 256, size=int(3.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("first", first, max_new_tokens=6)
        drain(s)
        assert s.requests["first"].slot == -1
        assert np.abs(np.asarray(app.kv_cache.state.ssm[:, 0])).max() > 0  # left behind
        assert not np.asarray(app.kv_cache.state.ssm[:, 1:]).any()  # no other slot was touched
        assert served_is_reference(app, spy, 0, first, s.requests["first"].generated)
        spy.rows.clear()
        second, third = rng.integers(0, 256, size=21), rng.integers(0, 256, size=70)
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
    prompt = np.random.default_rng(2).integers(0, 256, size=int(2.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        s.add_request("r", prompt, max_new_tokens=1)
        s.step(), s.step()
        app.kv_cache = fill_slot_state(app.kv_cache, [0], 0.0)
        drain(s)
        got = spy.at(0, len(prompt) - 1)
    want = ref.reference_logits(app.params, ref.geometry(ATTRS, 1), prompt, [len(prompt) - 1])[0]
    assert np.abs(got - want).max() > 5 * TOL * max(1.0, np.abs(want).max())


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(app):
    """There is no pool to exhaust, so the preemption is the injected one; the
    request re-prefills prompt and committed tokens from a zero state."""
    app.init_kv_cache()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=n) for n in (40, 27)]
    with LogitSpy(app) as spy:
        s = ServingSession(app, fault_injector=FaultInjector().exhaust_pool(5))
        for i, p in enumerate(prompts):
            s.add_request(f"r{i}", p, max_new_tokens=8)
        drain(s)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        for i, p in enumerate(prompts):
            req = s.requests[f"r{i}"]
            assert len(req.generated) == 8
            assert any(served_is_reference(app, spy, slot, p, req.generated)
                       for slot in range(s.num_slots)), f"r{i}: no slot served the reference's logits"


def test_scrub_zeroes_the_slots_state_and_only_it(app):
    app.init_kv_cache()
    rng = np.random.default_rng(4)
    s = ServingSession(app)
    s.add_request("victim", rng.integers(0, 256, size=30), max_new_tokens=6)
    s.add_request("bystander", rng.integers(0, 256, size=30), max_new_tokens=6)
    for _ in range(4):
        s.step()
    state = app.kv_cache.state
    before = (np.asarray(state.ssm[:, 1]), np.asarray(state.conv[:, 1]))
    assert np.abs(np.asarray(state.ssm[:, 0])).max() > 0 and np.abs(np.asarray(state.conv[:, 0])).max() > 0
    s._finish(s.requests["victim"], reason="test", scrub=True)
    state = app.kv_cache.state
    assert isinstance(state, pr.PowerState)
    assert not np.asarray(state.ssm[:, 0]).any() and not np.asarray(state.conv[:, 0]).any()
    assert np.array_equal(np.asarray(state.ssm[:, 1]), before[0])
    assert np.array_equal(np.asarray(state.conv[:, 1]), before[1])
    with LogitSpy(app) as spy:  # the slot is reused after the scrub and serves the reference
        again = rng.integers(0, 256, size=19)
        s.add_request("again", again, max_new_tokens=3)
        slot = s.requests["again"].slot
        drain(s)
        assert slot == 0 and served_is_reference(app, spy, slot, again, s.requests["again"].generated)
    assert len(s.requests["bystander"].generated) == 6


def test_admission_is_by_free_slots_alone(app):
    """17 requests on 16 slots, each far longer than any pool this
    configuration's ``pa_pool_bytes`` could hold were there one: sixteen are
    admitted, the 17th is refused for a SLOT and admitted when one frees; none
    is refused, preempted or dropped for blocks."""
    app.init_kv_cache()
    rng = np.random.default_rng(5)
    s = ServingSession(app)
    results = [s.add_request(f"r{i}", rng.integers(0, 256, size=200), max_new_tokens=2)
               for i in range(SLOTS + 1)]
    assert all(results[:SLOTS]) and not results[SLOTS] and results[SLOTS].reason == "no_slot"
    assert s.free_slots == []
    drain(s, limit=400)
    assert s.add_request("late", rng.integers(0, 256, size=200), max_new_tokens=2)
    drain(s, limit=400)
    reqs = [s.requests[f"r{i}"] for i in range(SLOTS)] + [s.requests["late"]]
    assert all(r.status == "finished" and len(r.generated) == 2 and r.preemptions == 0 for r in reqs)
    assert s.allocator.seq_blocks == {} and s.allocator.num_blocks == 0


def test_a_pass_is_counted_under_the_states_family_and_no_pool_counter_moves(app):
    app.init_kv_cache()
    with TelemetrySession() as tel:
        s = ServingSession(app, telemetry=tel)
        s.add_request("a", np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
        drain(s)
        snap = tel.registry.snapshot()
    total = lambda name, **labels: sum(
        x["value"] for x in snap.get(name, {"samples": []})["samples"]
        if all(x["labels"].get(k) == v for k, v in labels.items()))
    decodes = total("nxdi_steps_total", kind="decode")
    assert decodes >= 2
    assert total("nxdi_power_rows_advanced_total", program="decode") == decodes
    assert total("nxdi_power_rows_advanced_total", program="chunk") == 2  # 19 tokens in 2 chunks of 16
    assert total("nxdi_power_state_resets_total") == 1
    assert total("nxdi_power_state_bytes") == app.kv_cache.state.nbytes
    assert not total("nxdi_ssm_rows_advanced_total") and not total("nxdi_kda_rows_advanced_total")
    for name in snap:  # nothing of a pool is counted: there is none
        if name.startswith(("nxdi_kv_blocks", "nxdi_kv_write")):
            assert not total(name), name


def test_neither_step_program_reads_a_block_table_or_writes_kv(app):
    """The block table and the kv mask are arguments of the traced function
    that nothing reads: neither is among the lowered program's used inputs,
    and no scatter (a K/V write) is in either program."""
    tkg = app.token_generation_model
    for q_len in (None, CHUNK):
        inputs = tkg.example_inputs(256, q_len=q_len)
        traced = tkg.trace_program(app.params, app.kv_cache, inputs, None)[0]
        used = set()
        for eqn in traced.jaxpr.jaxpr.eqns:
            used.update(id(v) for v in eqn.invars)
        args = jax.tree.leaves((app.params, app.kv_cache, inputs))
        invars = traced.jaxpr.jaxpr.invars
        assert len(args) == len(invars)
        unread = [a.shape for a, v in zip(args, invars) if id(v) not in used]
        assert inputs.block_table.shape in unread and inputs.attention_mask.shape in unread
        assert "scatter" not in {e.primitive.name for e in traced.jaxpr.jaxpr.eqns}


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_references_logits(app, fault):
    """Each fault of the selftest is a different function at this size too
    (a prompt of a chunk and a half: the fault that drops the carry has one)."""
    prompt = np.random.default_rng(6).integers(0, 256, size=3 * ref.FAULT_CHUNK // 2)
    geo = ref.geometry(ATTRS, 1)
    sound = ref.reference_logits(app.params, geo, list(prompt), [len(prompt) - 1])
    faulty = ref.reference_logits(app.params, geo, list(prompt), [len(prompt) - 1], fault=fault)
    assert np.abs(faulty - sound).max() > (1e-4 if fault == "state_bf16" else 1e-3) * np.abs(sound).max()


def test_a_bf16_state_fails_the_served_paths_tolerance(app):
    """The served path's tolerance against the reference is one a state held
    in bf16 does not meet: the reference's own recurrence with its state
    rounded after every token is many times further off."""
    prompt = np.random.default_rng(7).integers(0, 256, size=150)
    geo = ref.geometry(ATTRS, 1)
    sound = ref.reference_logits(app.params, geo, list(prompt), [149])
    rounded = ref.reference_logits(app.params, geo, list(prompt), [149], fault="state_bf16")
    assert np.abs(rounded - sound).max() > 5 * TOL * max(1.0, np.abs(sound).max())


# ---------------------------------------------------------------------------
# the layout and the three forms of the recurrence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [8, 16, 32, 128])
def test_phi_of_the_layout_held_gives_the_squared_scaled_product(d):
    rng = np.random.default_rng(d)
    q, k = (jnp.asarray(rng.standard_normal((7, d)), jnp.float32) for _ in range(2))
    got = jnp.sum(pr.phi(q) * pr.phi(k), axis=-1)
    want = jnp.square(jnp.sum(q * k, axis=-1) / np.sqrt(d))
    assert pr.phi(q).shape == (7, pr.state_dim(d))
    n = d // 8
    assert pr.state_dim(d) == 64 * n * (n + 1) // 2 < d * d or d == 8
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def _inputs(seed, R, Q, H, G, d, lo=0.5, hi=0.999):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    lg = jnp.asarray(np.log(rng.uniform(lo, hi, size=(R, Q, G))), jnp.float32)
    return n(R, Q, H, d), n(R, Q, G, d), n(R, Q, G, d), lg


def _state_of_keys(seed, R, G, d, keys=24):
    """(S, z) that ``keys`` random keys and values made: a state whose read
    has a denominator well above 0, as a served state's is."""
    rng = np.random.default_rng(seed)
    pk = pr.phi(jnp.asarray(rng.standard_normal((R, G, keys, d)), jnp.float32))
    vals = jnp.asarray(rng.standard_normal((R, G, keys, d)), jnp.float32)
    return jnp.einsum("rgjD,rgjc->rgDc", pk, vals), jnp.sum(pk, axis=2)


#: y is a ratio whose denominator, a sum of squared scores, nearly vanishes for
#: a token that scores its few keys near 0: there two forms' roundings show up
#: to 2e-4 relative in y, while S and z (sums, no division) agree to 2e-5
Y_TOL = 1e-3


def _token_by_token(q, k, v, lg, S, z, valid):
    ys = []
    for t in range(q.shape[1]):
        y, S, z = pr.power_step(q[:, t], k[:, t], v[:, t], lg[:, t], S, z, valid[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), S, z


@pytest.mark.parametrize("T", [1, 2, 17, 300])
def test_the_recurrence_is_the_attention_form(T):
    """GQA 5:1, decays from 0.5 to 0.999, the normaliser, 1 to 300 positions:
    the state's read is the reference's sum over j, term by term."""
    H, G, d = 10, 2, 16
    q, k, v, lg = _inputs(T, 1, T, H, G, d)
    zero = lambda *s: jnp.zeros(s, jnp.float32)
    got, _, _ = _token_by_token(q, k, v, lg, zero(1, G, 192, d), zero(1, G, 192), jnp.ones((1, T), bool))
    with jax.default_matmul_precision("highest"):
        want = ref.retention(q[0], k[0], v[0], lg[0], 1e-6)
    np.testing.assert_allclose(got[0], want, rtol=Y_TOL, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("Q,sub", [(37, 16), (48, 16), (16, 8), (5, 16), (33, 128), (64, 7)])
def test_the_chunked_form_is_the_recurrence(Q, sub):
    """Across sub-chunks and odd lengths, rows of different valid lengths (one
    of none), from a non-zero incoming state: outputs at valid positions and
    the state after them; a row with no valid position keeps its state bit
    for bit."""
    R, H, G, d = 4, 10, 2, 16
    q, k, v, lg = _inputs(Q, R, Q, H, G, d)
    S, z = _state_of_keys(Q + 1, R, G, d)
    n_valid = np.array([Q, 0, Q // 2 + 1, 1])
    valid = jnp.asarray(np.arange(Q)[None, :] < n_valid[:, None])
    want_y, want_S, want_z = _token_by_token(q, k, v, lg, S, z, valid)
    got_y, got_S, got_z = pr.power_chunk(q, k, v, lg, S, z, valid, chunk_size=sub)
    m = valid[:, :, None, None]
    scale = float(jnp.abs(jnp.where(m, want_y, 0)).max())
    np.testing.assert_allclose(jnp.where(m, got_y, 0), jnp.where(m, want_y, 0), rtol=Y_TOL, atol=2e-5 * scale)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-5, atol=2e-5 * float(jnp.abs(want_S).max()))
    np.testing.assert_allclose(got_z, want_z, rtol=2e-5, atol=2e-5 * float(jnp.abs(want_z).max()))
    assert np.array_equal(np.asarray(got_S[1]), np.asarray(S[1])) and np.array_equal(np.asarray(got_z[1]), np.asarray(z[1]))


def test_the_state_carried_from_chunk_to_chunk_is_the_recurrence():
    R, H, G, d, Q = 2, 10, 2, 16, 24
    q, k, v, lg = _inputs(11, R, 3 * Q, H, G, d, lo=0.9)
    valid = jnp.ones((R, 3 * Q), bool)
    zero = lambda *s: jnp.zeros(s, jnp.float32)
    want_y, want_S, want_z = _token_by_token(q, k, v, lg, zero(R, G, 192, d), zero(R, G, 192), valid)
    S, z, ys = zero(R, G, 192, d), zero(R, G, 192), []
    for c in range(3):
        part = lambda a: a[:, c * Q : (c + 1) * Q]
        y, S, z = pr.power_chunk(part(q), part(k), part(v), part(lg), S, z, part(valid), chunk_size=16)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, rtol=Y_TOL, atol=2e-5 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-5 * float(jnp.abs(want_S).max()))


def test_the_chunk_pass_touches_the_live_rows_of_the_stack_alone():
    """``advance_rows`` on a stack of 3 layers x 6 slots: the two live rows'
    slots of layer 1 advance (one from zero: ``reset``), every other slot and
    layer keeps its bits, an empty row (a slot past the last) writes nothing."""
    R, H, G, d, Q, L, N = 4, 10, 2, 16, 12, 3, 6
    q, k, v, lg = _inputs(21, R, Q, H, G, d)
    s_stack, z_stack = (a.reshape((L, N) + a.shape[1:]) for a in _state_of_keys(22, L * N, G, d))
    slots = jnp.asarray([4, N + 1, 2, N + 3], jnp.int32)  # rows 1 and 3 are empty
    valid = jnp.asarray(np.arange(Q)[None, :] < np.array([Q, Q, 5, 0])[:, None])
    reset = jnp.asarray([False, False, True, False])
    y, new_s, new_z = jax.jit(pr.advance_rows)(q, k, v, lg, valid, reset, s_stack, z_stack, 1, slots)
    for r, slot in ((0, 4), (2, 2)):
        S0 = jnp.where(reset[r], 0.0, s_stack[1, slot])[None]
        z0 = jnp.where(reset[r], 0.0, z_stack[1, slot])[None]
        want_y, want_S, want_z = _token_by_token(q[r:r + 1], k[r:r + 1], v[r:r + 1], lg[r:r + 1], S0, z0, valid[r:r + 1])
        n = int(valid[r].sum())
        np.testing.assert_allclose(y[r, :n], want_y[0, :n], rtol=Y_TOL, atol=2e-5 * float(jnp.abs(want_y[0, :n]).max()))
        np.testing.assert_allclose(new_s[1, slot], want_S[0], rtol=2e-5, atol=2e-5 * float(jnp.abs(want_S).max()))
        np.testing.assert_allclose(new_z[1, slot], want_z[0], rtol=2e-5, atol=2e-5 * float(jnp.abs(want_z).max()))
    untouched = np.ones((L, N), bool)
    untouched[1, 4] = untouched[1, 2] = False
    assert np.array_equal(np.asarray(new_s)[untouched], np.asarray(s_stack)[untouched])
    assert np.array_equal(np.asarray(new_z)[untouched], np.asarray(z_stack)[untouched])
    assert not np.asarray(y[1]).any() and not np.asarray(y[3]).any()


@pytest.mark.parametrize("live", [(1, 1, 1, 1), (0, 1, 0, 1), (0, 0, 1, 0), (1, 0, 0, 0), (0, 0, 0, 0)])
@pytest.mark.parametrize("d", [16, 32])
def test_the_decode_kernel_is_the_step_on_the_stacked_state_in_place(d, live):
    """Interpret mode: layer 1 of a stack of 3 advances by ``power_step`` for
    the live rows (one from zero: ``reset``), the group's five query heads
    read the one state, and every other layer and every row that is not live
    keeps its bits (leading, trailing and all rows dead: the redirected
    blocks)."""
    R, G, n_rep = 4, 2, 5
    H, D = G * n_rep, pr.state_dim(d)
    q, k, v, lg = (a[:, 0] for a in _inputs(d + sum(live), R, 1, H, G, d))
    # the row that starts from zero reads ONE key: its queries lie near it, so
    # that the denominator (the one squared score) is well above eps
    q = q.at[1].set(jnp.repeat(k[1], n_rep, axis=0) + 0.1 * q[1])
    S, z = _state_of_keys(31, R, G, d)
    assert S.shape == (R, G, D, d)
    valid = jnp.asarray(live, bool)
    reset = jnp.asarray([False, True, False, False])
    fresh = reset & valid
    want_y, want_S, want_z = pr.power_step(
        q, k, v, lg, jnp.where(fresh[:, None, None, None], 0.0, S), jnp.where(fresh[:, None, None], 0.0, z), valid)
    want_S = jnp.where(valid[:, None, None, None], want_S, S)
    want_z = jnp.where(valid[:, None, None], want_z, z)
    s_stack, z_stack = jnp.stack([S * 0 + 7, S, S * 0 + 9]), jnp.stack([z * 0 + 7, z, z * 0 + 9])
    y, new_s, new_z = power_state_update(s_stack, z_stack, jnp.int32(1), q, k, v, lg, valid, reset, interpret=True)
    m = valid[:, None, None]
    np.testing.assert_allclose(y, jnp.where(m, want_y, 0), rtol=Y_TOL, atol=2e-5 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(new_s[1], want_S, rtol=1e-6, atol=1e-6 * float(jnp.abs(want_S).max()))
    np.testing.assert_allclose(new_z[1], want_z, rtol=1e-6, atol=1e-6)
    dead = ~np.asarray(valid)
    assert np.array_equal(np.asarray(new_s[1])[dead], np.asarray(S)[dead])
    assert np.array_equal(np.asarray(new_z[1])[dead], np.asarray(z)[dead])
    assert (np.asarray(new_s[0]) == 7).all() and (np.asarray(new_s[2]) == 9).all()
    assert (np.asarray(new_z[0]) == 7).all() and (np.asarray(new_z[2]) == 9).all()


# ---------------------------------------------------------------------------
# refusals and the checkpoint's names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change,error,what", [
    (dict(power_degree=3), NotImplementedError, "degree other than 2"),
    (dict(tie_word_embeddings=True), NotImplementedError, "tie_word_embeddings"),
    (dict(attention_bias=True), NotImplementedError, "attention_bias"),
    (dict(hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(power_state_dim=136), ValueError, "power_state_dim 136"),
])
def test_what_the_model_does_not_build_is_refused_at_config_time(change, error, what):
    with pytest.raises(error, match=what):
        make_config({**ATTRS, **change})


@pytest.mark.parametrize("tpu,what", [
    (dict(is_prefix_caching=True), "is_prefix_caching"),
    (dict(serving_ragged=True), "serving_ragged"),
    (dict(speculation_length=3), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(tp_degree=2), "degree > 1"),
])
def test_what_a_power_retention_state_cannot_be_served_with_is_refused(tpu, what):
    with pytest.raises(SlotStateServingError, match="power retention layers.*" + what):
        make_config(**tpu)


def test_the_contiguous_path_is_refused_by_the_builder():
    cfg = make_config(is_block_kv_layout=False, is_chunked_prefill=False, pa_pool_bytes=None,
                      chunked_prefill_config=None)
    with pytest.raises(NotImplementedError, match="paged, chunked path only"):
        get_model_builder("brumby")(cfg)


def test_the_checkpoint_names_fill_the_tree(app):
    """Qwen3's names and the gate's two tensors -> the stacked tree under
    ``layers.power``, each leaf of the shape ``param_shapes`` declares and the
    gate transposed to (in, out)."""
    b = app.builder
    rng = np.random.default_rng(8)
    t = lambda *s: rng.standard_normal(s).astype(np.float32)
    Hd, H, G, d, I, V = 64, 10, 2, 16, 96, 256
    sd = {"model.embed_tokens.weight": t(V, Hd), "model.norm.weight": t(Hd), "lm_head.weight": t(V, Hd)}
    for i in range(2):
        p = f"model.layers.{i}."
        sd.update({
            p + "input_layernorm.weight": t(Hd), p + "post_attention_layernorm.weight": t(Hd),
            p + "self_attn.q_proj.weight": t(H * d, Hd), p + "self_attn.k_proj.weight": t(G * d, Hd),
            p + "self_attn.v_proj.weight": t(G * d, Hd), p + "self_attn.o_proj.weight": t(Hd, H * d),
            p + "self_attn.q_norm.weight": t(d), p + "self_attn.k_norm.weight": t(d),
            p + "self_attn.g_proj.weight": t(G, Hd), p + "self_attn.g_proj.bias": t(G),
            p + "mlp.gate_proj.weight": t(I, Hd), p + "mlp.up_proj.weight": t(I, Hd),
            p + "mlp.down_proj.weight": t(Hd, I),
        })
    params = b.convert_hf_state_dict(sd, dtype=jnp.float32)
    shapes = jax.tree.map(lambda s: tuple(s), b.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    assert got == shapes
    gate = params["layers"][POWER]["self_attn"]["g_proj"]
    np.testing.assert_array_equal(gate["weight"][1], sd["model.layers.1.self_attn.g_proj.weight"].T)
    np.testing.assert_array_equal(gate["bias"][0], sd["model.layers.0.self_attn.g_proj.bias"])
    missing = {k: v for k, v in sd.items() if not k.endswith("layers.1.self_attn.g_proj.bias")}
    with pytest.raises(KeyError, match="g_proj.bias"):
        b.convert_hf_state_dict(missing, dtype=jnp.float32)
