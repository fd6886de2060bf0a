"""Serving fault-containment suite (ISSUE 7): admission validation,
poisoned-row quarantine, deadlines, dispatch retry, watchdog, preemption
re-admission fairness — every FaultInjector mode against BOTH session
classes, driven deterministically.

The headline pins:
- an injected NaN row fails ONLY that row: co-batched rows' outputs stay
  byte-identical to a clean run on the legacy split AND the ragged paths,
  and over every kind of cache the cells serve (a per-slot state, a one-token
  carry under an expert layer, a latent pool, a cache of two lifetimes)
  (the ROADMAP-named garbage-block coupling bug, fixed by the non-finite
  token sentinel + the block-0 read scrub + quarantine scrub-on-release);
- injected dispatch faults retry with bounded backoff, then fail only the
  in-flight rows — the session keeps serving;
- a zero-progress livelock becomes a watchdog preemption and then a LOUD
  WatchdogError with a diagnostic snapshot, never an invisible spin;
- repeated pool exhaustion cannot starve a request: evictions re-queue
  AHEAD of new arrivals and resume byte-identically.
"""

import dataclasses

import numpy as np
import pytest

import jax

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.faults import (
    FaultInjector,
    WatchdogError,
    fill_kv_rows,
)
from neuronx_distributed_inference_tpu.runtime.serving import (
    ServingSession,
    SpeculativeServingSession,
)
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

pytestmark = pytest.mark.robustness

PROMPTS = {
    "r1": [5, 17, 92, 41, 8, 3, 77, 21, 60, 14, 2, 90],  # 12 tokens
    "r2": list(range(30, 52)),  # 22 tokens: prefills across several chunks
    "r3": [7, 7, 7],
}


class FakeClock:
    """Deterministic clock whose sleep() advances it — deadlines and
    backoff pin exactly, tests never actually wait."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float):
        self.t += float(s)


def _paged_cfg(ragged=False, **extra):
    tpu = dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=24,
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=2, kernel_q_tile_size=16
        ),
        serving_ragged=ragged, seq_len=64,
    )
    tpu.update(extra)
    return make_tiny_config(tpu=tpu)


@pytest.fixture(scope="module")
def paged_apps():
    """(legacy split, ragged) — the ragged step pipelines when async_mode
    does (the default), so the ragged app here exercises the PIPELINED
    dispatch: every parametrized containment pin below covers it."""
    sd = make_random_hf_state_dict(_paged_cfg(False))
    legacy = TpuModelForCausalLM(None, _paged_cfg(False)).load(state_dict=sd)
    ragged = TpuModelForCausalLM(None, _paged_cfg(True)).load(state_dict=sd)
    return legacy, ragged


@pytest.fixture(scope="module")
def sync_ragged_app(paged_apps):
    """Synchronous-ragged twin of paged_apps[1] (async_mode=False), sharing
    the same weights — the sync/async fault-parity reference."""
    cfg = _paged_cfg(True, async_mode=False)
    sd = make_random_hf_state_dict(_paged_cfg(False))
    return TpuModelForCausalLM(None, cfg).load(state_dict=sd)


def _reference_app(mode):
    """The tiny preset a reference test serves, as that test builds it: the
    cache kinds of the benchmark's cells that are no plain GQA pool."""
    from tests import test_deepseek_reference, test_granite_hybrid
    from tests import test_mellum_reference, test_zaya_reference

    if mode == "ssm_state":  # state-space layers: a recurrent state a slot
        cfg = test_granite_hybrid.make_config()
    elif mode == "experts_carry":  # an expert layer over a one-token carry
        cfg = test_zaya_reference.make_config()
    elif mode == "latent_pool":  # MLA: one latent a token, expert layers
        return test_deepseek_reference.make_app()
    else:  # window and full layers: a ring a slot beside the pool
        return test_mellum_reference.make_app()
    return TpuModelForCausalLM(None, cfg).load(random_weights=True)


#: the containment pins' modes: the split step pipelined and not, the ragged
#: step likewise, and the split step over each cache kind the cells serve
CONTAINMENT_MODES = [
    "legacy", "legacy_sync", "ragged", "ragged_sync",
    "ssm_state", "experts_carry", "latent_pool", "two_lifetimes",
]


@pytest.fixture(scope="module")
def app_of(paged_apps, sync_ragged_app):
    """mode -> application, each built once and only when a case asks."""
    built = {"legacy": paged_apps[0], "ragged": paged_apps[1],
             "ragged_sync": sync_ragged_app}

    def get(mode):
        if mode not in built:
            if mode == "legacy_sync":
                built[mode] = TpuModelForCausalLM(
                    None, _paged_cfg(False, async_mode=False)
                ).load(state_dict=make_random_hf_state_dict(_paged_cfg(False)))
            else:
                built[mode] = _reference_app(mode)
        return built[mode]

    return get


def _nan_outside_garbage(cache) -> bool:
    """Whether a NaN survives where a live row could read it: any stream of
    blocks outside the shared garbage block 0 (which the read path scrubs on
    every gather), or any per-slot state kept beside the pool."""
    slot_fields = getattr(cache, "SLOT_FIELDS", ())
    for f in dataclasses.fields(cache):
        for leaf in jax.tree_util.tree_leaves(getattr(cache, f.name)):
            a = np.asarray(leaf)
            if np.issubdtype(a.dtype, np.floating) and np.isnan(
                a if f.name in slot_fields else a[:, 1:]
            ).any():
                return True
    return False


@pytest.fixture(scope="module")
def plain_app():
    cfg = make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=4, ctx_batch_size=1)
    )
    return TpuModelForCausalLM(None, cfg).load(
        state_dict=make_random_hf_state_dict(cfg)
    )


@pytest.fixture(scope="module")
def spec_apps():
    mk = lambda: make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
    )
    sd = make_random_hf_state_dict(mk(), seed=0)
    target = TpuModelForCausalLM(None, mk()).load(state_dict=sd)
    draft = TpuModelForCausalLM(None, mk()).load(
        state_dict=make_random_hf_state_dict(mk(), seed=7)
    )
    return target, draft


def _drive(sess, max_steps=300):
    """Per-step drain (every fault fires on step() granularity)."""
    for _ in range(max_steps):
        if not (sess.active or sess._readmit):
            break
        sess.step()
    else:
        raise AssertionError("session failed to drain within max_steps")
    return {rid: list(r.generated) for rid, r in sess.requests.items()}


def _fresh_session(app, **kw):
    """A fresh session over a freshly-initialized cache."""
    app.init_kv_cache()
    return ServingSession(app, **kw)


def _mix(app, injector=None, telemetry=None, n_tokens=6):
    """The standard 3-request mix, per-step driven, fresh cache."""
    sess = _fresh_session(app, telemetry=telemetry, fault_injector=injector)
    for rid, prompt in PROMPTS.items():
        assert sess.add_request(rid, prompt, max_new_tokens=n_tokens)
    out = _drive(sess)
    return sess, out


# ---------------------------------------------------------------------------
# admission validation
# ---------------------------------------------------------------------------


def test_admission_validation_rejects_typed(plain_app):
    """Malformed requests get terminal REJECTED verdicts with reasons —
    never a raise, never a NaN row — and healthy co-batched requests are
    byte-identical to a clean run."""
    plain_app.init_kv_cache()
    golden_sess = ServingSession(plain_app)
    assert golden_sess.add_request("g", PROMPTS["r1"], max_new_tokens=6)
    golden = _drive(golden_sess)["g"]

    plain_app.init_kv_cache()
    tel = TelemetrySession()
    sess = ServingSession(plain_app, telemetry=tel)
    bad = {
        "oov_hi": dict(input_ids=[5, 500], reason="token_id_out_of_range"),
        "oov_neg": dict(input_ids=[-3, 5], reason="token_id_out_of_range"),
        "empty": dict(input_ids=[], reason="empty_prompt"),
        "toolong": dict(input_ids=list(range(1, 100)), reason="prompt_too_long"),
        "nobudget": dict(
            input_ids=[5, 6], max_new_tokens=0, reason="invalid_max_new_tokens"
        ),
    }
    assert sess.add_request("good", PROMPTS["r1"], max_new_tokens=6)
    for rid, spec in bad.items():
        res = sess.add_request(
            rid, spec["input_ids"],
            max_new_tokens=spec.get("max_new_tokens", 4),
        )
        assert not res and res.reason == spec["reason"], (rid, res)
        assert sess.rejected[rid].status == "rejected"
        assert sess.rejected[rid].fail_reason == spec["reason"]
        assert rid not in sess.requests  # never admitted, no slot burned
    out = _drive(sess)
    assert out["good"] == golden  # rejects cost co-batched rows nothing
    tel.close()
    rej = {
        s["labels"]["reason"]: s["value"]
        for s in tel.registry.snapshot()["nxdi_requests_rejected_total"]["samples"]
    }
    assert rej == {
        "token_id_out_of_range": 2, "empty_prompt": 1,
        "prompt_too_long": 1, "invalid_max_new_tokens": 1,
    }


def test_admission_validation_off_restores_legacy(plain_app):
    """admission_validation=False: the session admits unvalidated requests
    (legacy raise-late behavior) — the knob is real, not cosmetic."""
    tc = plain_app.config.tpu_config
    plain_app.init_kv_cache()
    tc.admission_validation = False
    try:
        sess = ServingSession(plain_app)
        assert sess.admission_validation is False
        # out-of-vocab id: admitted (embedding lookup clamps; the row runs)
        assert sess.add_request("oov", [5, 500], max_new_tokens=2)
        _drive(sess)
    finally:
        tc.admission_validation = True


# ---------------------------------------------------------------------------
# poisoned-row quarantine: the ROADMAP-named NaN coupling bug
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", CONTAINMENT_MODES)
def test_nan_row_quarantined_cobatch_byte_identical(app_of, mode):
    """A NaN-poisoned row (device KV NaN -> non-finite logits -> sentinel
    token) fails ONLY that row: healthy co-batched rows are byte-identical
    to a clean run on the legacy split and the ragged dispatch paths and
    over every cache kind, the poisoned blocks (and the slot's state) are scrubbed
    before the pool recycles them, and a new request reusing the freed
    capacity decodes byte-identically."""
    app = app_of(mode)
    _, golden = _mix(app)

    inj = FaultInjector(seed=0).poison_kv_row(step=4, slot=1)  # r2's slot
    tel = TelemetrySession()
    sess, out = _mix(app, injector=inj, telemetry=tel)
    assert any(f["kind"] == "poison_kv_row" for f in inj.log)

    victim = sess.requests["r2"]
    assert victim.status == "failed" and victim.fail_reason == "non_finite"
    # the victim kept its pre-poison tokens (a clean-run prefix), no garbage
    assert out["r2"] == golden["r2"][: len(out["r2"])]
    assert len(out["r2"]) < len(golden["r2"])
    # co-batched rows: byte-identical to the clean run
    assert out["r1"] == golden["r1"]
    assert out["r3"] == golden["r3"]
    # quarantine released the victim's blocks back to the pool...
    assert len(sess.allocator.free) == sess.allocator.num_blocks
    # ...and scrubbed them: no NaN survives anywhere outside the shared
    # garbage block 0 (which the read path scrubs on every gather)
    assert not _nan_outside_garbage(sess.app.kv_cache)
    tel.close()
    snap = tel.registry.snapshot()
    assert snap["nxdi_rows_quarantined_total"]["samples"][0]["value"] == 1
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert fin["non_finite"] == 1

    # freed-capacity reuse: a new request over the scrubbed blocks decodes
    # byte-identically to an isolated clean run
    probe = [42, 10, 11]
    iso = _fresh_session(app)
    assert iso.add_request("iso", probe, max_new_tokens=4)
    golden_probe = _drive(iso)["iso"]
    assert sess.add_request("r4", probe, max_new_tokens=4)
    out2 = _drive(sess)
    assert out2["r4"] == golden_probe


@pytest.mark.parametrize("mode", CONTAINMENT_MODES)
def test_poisoned_garbage_block_cannot_couple_rows(app_of, mode):
    """NaN written straight into SHARED garbage block 0 (the
    post-propagation state of the legacy drain's surplus lockstep writes)
    changes NO healthy row by a byte: masked reads of the garbage block are
    scrubbed to exact zeros in the gather (0*NaN=NaN is dead)."""
    app = app_of(mode)
    _, golden = _mix(app)
    inj = FaultInjector().poison_garbage_block(step=2)
    _, out = _mix(app, injector=inj)
    assert any(f["kind"] == "poison_garbage_block" for f in inj.log)
    assert out == golden  # every row byte-identical, nobody quarantined


def test_nan_tokens_host_boundary_quarantine(paged_apps):
    """The nan_logits injector mode corrupts only the HOST-fetched tokens
    (device cache stays clean): quarantine bookkeeping in isolation —
    victim fails, others unaffected, KV released."""
    legacy, _ = paged_apps
    _, golden = _mix(legacy)
    inj = FaultInjector().nan_logits(step=5, slot=0)  # r1's slot
    tel = TelemetrySession()
    sess, out = _mix(legacy, injector=inj, telemetry=tel)
    assert sess.requests["r1"].fail_reason == "non_finite"
    assert out["r1"] == golden["r1"][: len(out["r1"])]
    assert out["r2"] == golden["r2"] and out["r3"] == golden["r3"]
    assert len(sess.allocator.free) == sess.allocator.num_blocks
    tel.close()
    assert (
        tel.registry.snapshot()["nxdi_rows_quarantined_total"]["samples"][0]["value"]
        == 1
    )


# ---------------------------------------------------------------------------
# forced pool exhaustion, preemption re-admission fairness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", CONTAINMENT_MODES)
def test_injected_pool_exhaustion_resumes_byte_identical(app_of, mode):
    """exhaust_pool evicts every allocating row for one step; evictions
    re-queue, re-admit, and the final streams are byte-identical to a
    fault-free run (rollback + greedy re-prefill regenerates exactly)."""
    app = app_of(mode)
    _, golden = _mix(app)
    inj = FaultInjector().exhaust_pool(3)
    tel = TelemetrySession()
    sess, out = _mix(app, injector=inj, telemetry=tel)
    assert any(f["kind"] == "exhaust_pool" for f in inj.log)
    assert out == golden
    preempted = [r for r in sess.requests.values() if r.preemptions > 0]
    assert preempted, "expected at least one injected eviction"
    tel.close()
    snap = tel.registry.snapshot()
    assert snap["nxdi_requests_preempted_total"]["samples"][0]["value"] >= 1
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert "preempted" not in fin  # every eviction resumed and finished
    # re-admission must NOT double-count admissions or first tokens: the
    # admitted counter stays == unique requests and the TTFT conservation
    # law (TTFT count == finished requests) holds under preemption
    assert (
        snap["nxdi_requests_admitted_total"]["samples"][0]["value"]
        == len(sess.requests)
    )
    assert snap["nxdi_ttft_ms"]["samples"][0]["count"] == sum(fin.values())


def test_preempted_readmission_ages_ahead_of_new_arrivals():
    """The fairness pin (ISSUE 7 satellite): against a tiny pool, an
    evicted request is re-admitted BEFORE any new arrival may take its
    capacity — alternating admissions cannot starve it, and it still
    delivers its full budget byte-identically."""
    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=3,
            seq_len=64,
        )
    )
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)

    # golden: each request alone against an unconstrained session
    def golden_for(prompt):
        big = make_tiny_config(
            tpu=dict(
                is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16,
                seq_len=64,
            )
        )
        a = TpuModelForCausalLM(None, big).load(state_dict=sd)
        s = ServingSession(a)
        assert s.add_request("g", prompt, max_new_tokens=8)
        return _drive(s)["g"]

    p1 = list(range(1, 17))
    p2 = [x + 1 for x in p1]
    g1, g2 = golden_for(p1), golden_for(p2)

    app.init_kv_cache()
    sess = ServingSession(app)
    assert sess.add_request("r1", p1, max_new_tokens=8)
    assert sess.add_request("r2", p2, max_new_tokens=8)
    # step until the pool evicts one of them
    for _ in range(20):
        sess.step()
        if sess._readmit:
            break
    assert sess._readmit, "expected a pool eviction"
    waiting = sess._readmit[0].req_id
    # a NEW arrival while an eviction waits is refused as backlog — it may
    # not steal the capacity the aged request is queued for
    res = sess.add_request("r3", [9, 9, 9], max_new_tokens=2)
    assert not res and res.reason == "backlog"
    out = _drive(sess)
    assert out["r1"] == g1 and out["r2"] == g2  # nobody starved, byte-exact
    assert sess.requests[waiting].preemptions >= 1
    assert all(r.status == "finished" for r in sess.requests.values())
    # with the backlog drained, the new arrival admits and completes
    assert sess.add_request("r3", [9, 9, 9], max_new_tokens=2)
    assert len(_drive(sess)["r3"]) == 2


# ---------------------------------------------------------------------------
# per-request deadlines + injected latency
# ---------------------------------------------------------------------------


def test_request_deadline_exceeded(plain_app):
    """A request past its wall-clock TTL is dropped with terminal
    deadline_exceeded (overrun observed in the histogram); co-batched
    requests run to completion untouched."""
    clock = FakeClock()
    plain_app.init_kv_cache()
    tel = TelemetrySession()
    sess = ServingSession(
        plain_app, telemetry=tel, clock=clock, sleep_fn=clock.sleep
    )
    assert sess.add_request("ttl", PROMPTS["r1"], max_new_tokens=30,
                            deadline_s=1.0)
    assert sess.add_request("free", PROMPTS["r3"], max_new_tokens=6)
    sess.step()
    clock.t += 5.0  # blow way past the 1s TTL
    out = _drive(sess)
    ttl = sess.requests["ttl"]
    assert ttl.status == "failed" and ttl.fail_reason == "deadline_exceeded"
    assert len(out["ttl"]) < 30
    assert len(out["free"]) == 6
    assert len(sess.free_slots) == sess.num_slots
    tel.close()
    snap = tel.registry.snapshot()
    h = snap["nxdi_deadline_overrun_ms"]["samples"][0]
    assert h["count"] == 1 and h["sum"] >= 3500.0  # ~4s overrun observed
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert fin["deadline_exceeded"] == 1


def test_injected_latency_trips_deadline(plain_app):
    """FaultInjector latency flows through the session's injectable sleep:
    a slow step pushes a deadlined request past its TTL deterministically."""
    clock = FakeClock()
    plain_app.init_kv_cache()
    inj = FaultInjector().latency(step=2, seconds=3.0)
    sess = ServingSession(
        plain_app, fault_injector=inj, clock=clock, sleep_fn=clock.sleep
    )
    assert sess.add_request("ttl", PROMPTS["r1"], max_new_tokens=30,
                            deadline_s=1.0)
    _drive(sess)
    assert any(f["kind"] == "latency" for f in inj.log)
    assert sess.requests["ttl"].fail_reason == "deadline_exceeded"


# ---------------------------------------------------------------------------
# bounded dispatch retry
# ---------------------------------------------------------------------------


def test_dispatch_retry_recovers_byte_identical(plain_app):
    """Transient dispatch errors under the retry budget: capped exponential
    backoff, then success — outputs byte-identical to a clean run, retries
    counted."""
    _, golden = (lambda s: (s, _drive(s)))(_plain_sess(plain_app))
    inj = FaultInjector().dispatch_error(step=2, attempts=2)  # <= retries(2)
    sleeps = []
    tel = TelemetrySession()
    sess = _plain_sess(
        plain_app, fault_injector=inj, telemetry=tel, sleep_fn=sleeps.append
    )
    out = _drive(sess)
    assert out == golden
    assert sleeps == [0.02, 0.04]  # base * 2**(attempt-1), capped
    assert all(r.status == "finished" for r in sess.requests.values())
    tel.close()
    snap = tel.registry.snapshot()
    assert snap["nxdi_dispatch_retries_total"]["samples"][0]["value"] == 2


def test_dispatch_retry_exhaustion_fails_rows_not_process(plain_app):
    """Past the retry budget only the IN-FLIGHT rows fail
    (dispatch_error); the session survives and keeps admitting + serving
    new requests."""
    inj = FaultInjector().dispatch_error(step=2, attempts=10)
    sleeps = []
    sess = _plain_sess(plain_app, fault_injector=inj, sleep_fn=sleeps.append)
    out = _drive(sess)
    failed = [r for r in sess.requests.values() if r.status == "failed"]
    assert failed and all(r.fail_reason == "dispatch_error" for r in failed)
    assert len(sleeps) == 2  # retried the budget before giving up
    assert len(sess.free_slots) == sess.num_slots  # all resources released
    # the session is alive: a fresh request admits and completes
    probe = [42, 10, 11]
    iso = _plain_sess(plain_app, adds={})
    assert iso.add_request("g", probe, max_new_tokens=4)
    golden = _drive(iso)["g"]
    plain_app.init_kv_cache()
    assert sess.add_request("after", probe, max_new_tokens=4)
    assert _drive(sess)["after"] == golden


def _plain_sess(app, adds=None, **kw):
    app.init_kv_cache()
    sess = ServingSession(app, **kw)
    adds = PROMPTS if adds is None else adds
    for rid, prompt in adds.items():
        assert sess.add_request(rid, prompt, max_new_tokens=6)
    return sess


# ---------------------------------------------------------------------------
# watchdog: zero-progress livelock -> preempt largest -> loud failure
# ---------------------------------------------------------------------------


def test_watchdog_preempts_then_fails_loud(paged_apps):
    """Stalled dispatches (zero committed tokens, zero admissions): after
    one watchdog window the largest request is preempted; after a second
    windowed trip the session raises WatchdogError carrying a diagnostic
    snapshot — a livelock becomes a debuggable, loud failure."""
    legacy, _ = paged_apps
    tc = legacy.config.tpu_config
    legacy.init_kv_cache()
    old = tc.watchdog_no_progress_steps
    tc.watchdog_no_progress_steps = 3
    try:
        inj = FaultInjector().stall(*range(1, 40))
        tel = TelemetrySession()
        sess = ServingSession(legacy, telemetry=tel, fault_injector=inj)
        for rid, prompt in PROMPTS.items():
            assert sess.add_request(rid, prompt, max_new_tokens=6)
        with pytest.raises(WatchdogError) as ei:
            for _ in range(40):
                sess.step()
        snap = ei.value.snapshot
        assert snap["step_index"] >= 6  # two full 3-step windows
        assert snap["active"] or snap["waiting"]
        assert "free_blocks" in snap and "last_dispatch_error" in snap
        tel.close()
        msnap = tel.registry.snapshot()
        assert (
            msnap["nxdi_watchdog_preemptions_total"]["samples"][0]["value"] == 1
        )
        assert msnap["nxdi_watchdog_trips_total"]["samples"][0]["value"] == 1
    finally:
        tc.watchdog_no_progress_steps = old


def test_watchdog_quiet_on_healthy_traffic(paged_apps):
    """A tight watchdog window must never fire on a healthy run (every
    step commits tokens or advances prefill)."""
    legacy, _ = paged_apps
    tc = legacy.config.tpu_config
    old = tc.watchdog_no_progress_steps
    tc.watchdog_no_progress_steps = 2  # hair-trigger
    try:
        tel = TelemetrySession()
        _, out = _mix(legacy, telemetry=tel)
        assert all(len(v) > 0 for v in out.values())
        tel.close()
        snap = tel.registry.snapshot()
        assert snap["nxdi_watchdog_trips_total"]["samples"][0]["value"] == 0
        assert (
            snap["nxdi_watchdog_preemptions_total"]["samples"][0]["value"] == 0
        )
    finally:
        tc.watchdog_no_progress_steps = old


# ---------------------------------------------------------------------------
# SpeculativeServingSession under every fault mode
# ---------------------------------------------------------------------------


def _spec_sess(target, draft, **kw):
    target.init_kv_cache()
    draft.init_kv_cache()
    sess = SpeculativeServingSession(target, draft, speculation_length=4, **kw)
    assert sess.add_request("s1", [5, 17, 92, 41], max_new_tokens=8)
    assert sess.add_request("s2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
    return sess


def test_spec_session_nan_quarantine_and_draft_immunity(spec_apps):
    """Speculative serving: a poisoned TARGET row quarantines (sentinel in
    the verify window) with the co-batched row byte-identical; a poisoned
    DRAFT only costs acceptance length — outputs stay byte-identical
    (greedy verification emits the target's own tokens)."""
    target, draft = spec_apps
    golden = _drive(_spec_sess(target, draft))

    # host-boundary corruption of slot 1 (s2)
    inj = FaultInjector().nan_logits(step=2, slot=1)
    tel = TelemetrySession()
    sess = _spec_sess(target, draft, fault_injector=inj, telemetry=tel)
    out = _drive(sess)
    assert sess.requests["s2"].fail_reason == "non_finite"
    assert out["s2"] == golden["s2"][: len(out["s2"])]
    assert out["s1"] == golden["s1"]
    tel.close()
    assert (
        tel.registry.snapshot()["nxdi_rows_quarantined_total"]["samples"][0]["value"]
        == 1
    )

    # device poisoning of the TARGET's cache line for slot 0 (s1)
    inj2 = FaultInjector().poison_kv_row(step=2, slot=0)
    sess2 = _spec_sess(target, draft, fault_injector=inj2)
    out2 = _drive(sess2)
    assert sess2.requests["s1"].fail_reason == "non_finite"
    assert out2["s1"] == golden["s1"][: len(out2["s1"])]
    assert out2["s2"] == golden["s2"]

    # a poisoned DRAFT cannot corrupt outputs: byte-identical, just slower
    target.init_kv_cache()
    draft.init_kv_cache()
    sess3 = SpeculativeServingSession(target, draft, speculation_length=4)
    assert sess3.add_request("s1", [5, 17, 92, 41], max_new_tokens=8)
    assert sess3.add_request("s2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
    sess3.step()
    draft.kv_cache = fill_kv_rows(draft.kv_cache, [0], float("nan"))
    out3 = _drive(sess3)
    assert out3 == golden
    assert all(r.status == "finished" for r in sess3.requests.values())


def test_spec_session_dispatch_retry_and_deadline(spec_apps):
    """The containment wrapper is shared: speculative sessions retry
    transient dispatch faults (byte-identical recovery), fail in-flight
    rows on exhaustion, and honor per-request deadlines."""
    target, draft = spec_apps
    golden = _drive(_spec_sess(target, draft))

    sleeps = []
    inj = FaultInjector().dispatch_error(step=2, attempts=1)
    sess = _spec_sess(target, draft, fault_injector=inj, sleep_fn=sleeps.append)
    assert _drive(sess) == golden
    assert sleeps == [0.02]

    inj2 = FaultInjector().dispatch_error(step=2, attempts=10)
    sess2 = _spec_sess(target, draft, fault_injector=inj2,
                       sleep_fn=sleeps.append)
    _drive(sess2)
    failed = [r for r in sess2.requests.values() if r.status == "failed"]
    assert failed and all(r.fail_reason == "dispatch_error" for r in failed)

    clock = FakeClock()
    target.init_kv_cache()
    draft.init_kv_cache()
    sess3 = SpeculativeServingSession(
        target, draft, speculation_length=4, clock=clock, sleep_fn=clock.sleep
    )
    assert sess3.add_request("ttl", [5, 17, 92, 41], max_new_tokens=30,
                             deadline_s=1.0)
    sess3.step()
    clock.t += 9.0
    _drive(sess3)
    assert sess3.requests["ttl"].fail_reason == "deadline_exceeded"


def test_spec_session_rejects_overlong_prompt_typed(spec_apps):
    """The speculative session's admission validation converts the
    windowed-prompt NotImplementedError into a typed REJECT at the door."""
    target, draft = spec_apps
    target.init_kv_cache()
    draft.init_kv_cache()
    sess = SpeculativeServingSession(target, draft, speculation_length=4)
    res = sess.add_request("long", list(range(1, 100)), max_new_tokens=4)
    assert not res and res.reason == "prompt_too_long"
    assert sess.rejected["long"].status == "rejected"


# ---------------------------------------------------------------------------
# injector determinism
# ---------------------------------------------------------------------------


def test_fault_injector_seeded_schedules_reproducible():
    """random_schedule is a pure function of the seed: same seed, same
    armed plan; a different seed diverges."""
    def plan(seed):
        inj = FaultInjector(seed=seed).random_schedule(
            n_steps=64, rate=0.3,
            kinds=("exhaust_pool", "dispatch_error", "latency", "stall"),
        )
        return (
            dict(inj._latency), set(inj._stall), set(inj._exhaust_pool),
            dict(inj._dispatch_fail),
        )

    assert plan(11) == plan(11)
    assert plan(11) != plan(12)
    # at rate 0.3 over 64 steps, a schedule actually armed something
    lat, stall, pool, derr = plan(11)
    assert lat or stall or pool or derr


# ---------------------------------------------------------------------------
# quarantine x prefix caching, re-admission progress x watchdog
# ---------------------------------------------------------------------------


def test_quarantine_spares_shared_prefix_blocks():
    """Prefix caching: quarantining a row must NOT zero cached prefix
    blocks a live sharer still attends over (their content is a healthy
    prefill's writes), and the victim's own registered blocks must leave
    the match index before their ids recycle — a later identical prompt
    re-prefills instead of attending scrubbed KV."""
    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=24,
            is_prefix_caching=True, seq_len=64,
        )
    )
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    base = list(range(1, 17))        # two full 8-token shared blocks
    pa = base + [40, 41, 42, 43]
    pb = base + list(range(50, 58))  # full third block: "b" registers it

    sess = ServingSession(app)
    assert sess.add_request("a", pa, max_new_tokens=10)
    assert sess.add_request("b", pb, max_new_tokens=10)
    golden = _drive(sess)

    app.init_kv_cache()
    inj = FaultInjector().nan_logits(step=2, slot=1)  # b's slot
    sess = ServingSession(app, fault_injector=inj)
    assert sess.add_request("a", pa, max_new_tokens=10)
    assert sess.add_request("b", pb, max_new_tokens=10)
    alloc = sess.allocator
    shared = list(alloc.seq_blocks[1][:2])  # b attached a's prefix blocks
    assert shared == alloc.seq_blocks[0][:2]
    b3 = alloc.seq_blocks[1][2]  # b's own full block, commit-registered
    out = _drive(sess)
    assert sess.requests["b"].fail_reason == "non_finite"
    # the sharer is untouched: byte-identical to the clean run
    assert out["a"] == golden["a"]
    # shared prefix blocks survived the scrub: still registered/matchable
    assert all(b in alloc.hash_of_block for b in shared)
    # b's registered block left the match index (content not matchable);
    # a longer same-prefix probe matches ONLY the healthy shared blocks
    assert b3 not in alloc.hash_of_block
    assert alloc.match_prefix(1, np.asarray(pb + [59], np.int32)) == 16


def test_watchdog_quiet_under_preempt_readmit_churn():
    """Pool-exhaustion churn that makes real forward progress — each
    eviction's re-admission commits a token inside step() — must never
    trip the watchdog: the progress baseline is snapped BEFORE
    re-admission. Only a genuinely stuck session (failed re-admissions,
    nothing committed) escalates."""
    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=3,
            seq_len=64, watchdog_no_progress_steps=2,  # hair trigger
        )
    )
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    tel = TelemetrySession()
    sess = ServingSession(app, telemetry=tel)
    p1 = list(range(1, 17))
    assert sess.add_request("r1", p1, max_new_tokens=8)
    assert sess.add_request("r2", [x + 1 for x in p1], max_new_tokens=8)
    out = _drive(sess)
    assert all(len(v) == 8 for v in out.values())
    assert max(r.preemptions for r in sess.requests.values()) >= 1
    tel.close()
    snap = tel.registry.snapshot()
    assert snap["nxdi_watchdog_trips_total"]["samples"][0]["value"] == 0
    assert snap["nxdi_watchdog_preemptions_total"]["samples"][0]["value"] == 0


def test_containment_actions_count_as_watchdog_progress(plain_app):
    """Terminal transitions made at the TOP of step() (deadline expiries,
    re-admission commits) are forward progress: the watchdog baseline is
    snapped before them. With dispatches stalled but one request resolving
    per step, the session is draining work, not livelocked — the watchdog
    must stay quiet instead of spuriously preempting and then raising."""
    clock = FakeClock()
    plain_app.init_kv_cache()
    tc = plain_app.config.tpu_config
    old = tc.watchdog_no_progress_steps
    tc.watchdog_no_progress_steps = 2  # hair trigger
    try:
        inj = FaultInjector().stall(*range(1, 20))
        tel = TelemetrySession()
        sess = ServingSession(
            plain_app, fault_injector=inj, telemetry=tel,
            clock=clock, sleep_fn=clock.sleep,
        )
        prompts = dict(PROMPTS, r4=[11, 12, 13, 14])
        for i, (rid, p) in enumerate(prompts.items()):
            assert sess.add_request(rid, p, max_new_tokens=40,
                                    deadline_s=0.5 + i * 1.0)
        for _ in range(8):
            if not sess.active:
                break
            sess.step()
            clock.t += 1.0  # exactly one TTL expires per step
        assert all(r.fail_reason == "deadline_exceeded"
                   for r in sess.requests.values())
        tel.close()
        snap = tel.registry.snapshot()
        assert snap["nxdi_watchdog_trips_total"]["samples"][0]["value"] == 0
        assert (
            snap["nxdi_watchdog_preemptions_total"]["samples"][0]["value"] == 0
        )
    finally:
        tc.watchdog_no_progress_steps = old


def test_quantized_scale_immune_to_non_finite_writes():
    """The per-(layer, head) running-absmax scale is SHARED across the
    batch and monotone: if a poisoned row's NaN write folded into it, every
    co-batched row (and all future requests) would dequantize to NaN — a
    cross-row coupling the quarantine scrub cannot undo. Non-finite
    elements must not inflate the scale; healthy rows' codes must stay
    byte-identical to an all-healthy write."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.modules.kvcache import (
        QuantizedKV,
        _quantized_update,
    )

    L, B, S, H, D = 2, 3, 4, 2, 8
    rng = np.random.default_rng(0)
    healthy = rng.standard_normal((B, S, H, D)).astype(np.float32)
    healthy[1] *= 0.1  # row 1 never sets the absmax: clean == dirty scale
    valid = jnp.ones((B, S), bool)
    stream = QuantizedKV(
        data=jnp.zeros((L, B, S, H, D), jnp.int8),
        scale=jnp.zeros((L, H), jnp.float32),
    )

    codes_clean, scale_clean = _quantized_update(
        stream, jnp.asarray(healthy), 0, valid
    )

    poisoned = healthy.copy()
    poisoned[1] = np.nan  # row 1's whole write goes non-finite
    codes_dirty, scale_dirty = _quantized_update(
        stream, jnp.asarray(poisoned), 0, valid
    )

    assert bool(jnp.all(jnp.isfinite(scale_dirty)))
    # the scale learned only from the finite rows
    finite_amax = np.abs(np.delete(healthy, 1, axis=0)).max(axis=(0, 1, 3))
    np.testing.assert_allclose(scale_dirty[0], finite_amax, rtol=1e-6)
    assert bool(jnp.array_equal(scale_clean, scale_dirty))
    # healthy rows' codes byte-identical under the co-batched poison
    # (row 1's own codes are garbage — that row is quarantined and scrubbed)
    mask = np.ones(B, bool)
    mask[1] = False
    assert bool(jnp.array_equal(codes_dirty[mask], codes_clean[mask]))


def test_spec_draft_prefill_dispatch_guarded(spec_apps):
    """The DRAFT-side admission prefill rides _guarded_dispatch like every
    other dispatch: past the retry budget a transient draft CTE failure
    terminally FAILs only that request (dispatch_error, slot released)
    instead of escaping add_request with the slot leaked; under the budget
    the admission retries and the run stays byte-identical."""
    from neuronx_distributed_inference_tpu.runtime.faults import (
        TransientDispatchError,
    )

    target, draft = spec_apps
    golden = _drive(_spec_sess(target, draft))

    class FlakyCTE:
        def __init__(self, inner, fail_times):
            self._inner = inner
            self.left = fail_times

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __call__(self, *a, **kw):
            if self.left > 0:
                self.left -= 1
                raise TransientDispatchError("injected draft CTE failure")
            return self._inner(*a, **kw)

    # under the budget (2 retries): admission succeeds, outputs byte-equal
    target.init_kv_cache()
    draft.init_kv_cache()
    sleeps = []
    sess = SpeculativeServingSession(
        target, draft, speculation_length=4, sleep_fn=sleeps.append
    )
    sess.draft.context_encoding_model = FlakyCTE(
        sess.draft.context_encoding_model, 2
    )
    assert sess.add_request("s1", [5, 17, 92, 41], max_new_tokens=8)
    assert sess.add_request("s2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
    assert _drive(sess) == golden
    assert len(sleeps) == 2

    # past the budget: terminal dispatch_error, slot released, no raise
    target.init_kv_cache()
    draft.init_kv_cache()
    sess = SpeculativeServingSession(
        target, draft, speculation_length=4, sleep_fn=lambda s: None
    )
    sess.draft.context_encoding_model = FlakyCTE(
        sess.draft.context_encoding_model, 10
    )
    assert sess.add_request("s1", [5, 17, 92, 41], max_new_tokens=8)
    bad = sess.requests["s1"]
    assert bad.status == "failed" and bad.fail_reason == "dispatch_error"
    assert bad.slot == -1 and len(sess.free_slots) == sess.num_slots
    # the session is alive: the co-batched request serves normally
    sess.draft.context_encoding_model = sess.draft.context_encoding_model._inner
    assert sess.add_request("s2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
    out = _drive(sess)
    assert out["s2"] == golden["s2"]


def test_rejected_history_bounded(plain_app):
    """Rejection volume is attacker-controlled: session.rejected keeps the
    newest REJECTED_HISTORY_MAX records and evicts oldest-first instead of
    growing host memory without bound."""
    from neuronx_distributed_inference_tpu.runtime.serving import (
        REJECTED_HISTORY_MAX,
    )

    plain_app.init_kv_cache()
    sess = ServingSession(plain_app)
    n = REJECTED_HISTORY_MAX + 50
    for i in range(n):
        assert not sess.add_request(f"bad{i}", [], max_new_tokens=4)
    assert len(sess.rejected) == REJECTED_HISTORY_MAX
    assert f"bad{n - 1}" in sess.rejected  # newest kept
    assert "bad0" not in sess.rejected  # oldest evicted


# ---------------------------------------------------------------------------
# pipelined ragged dispatch under faults (ISSUE 8): the epoch-guarded
# one-step-late consume must survive every containment policy
# ---------------------------------------------------------------------------


def test_async_ragged_dispatch_retry_recovers_byte_identical(paged_apps):
    """Transient dispatch errors on the PIPELINED ragged path, within the
    retry budget: backoff + retry, then success — the full mix is
    byte-identical to a clean run (the chained previous-step tokens are
    re-fed to the retried dispatch, nothing is consumed twice)."""
    app = paged_apps[1]
    _, golden = _mix(app)
    inj = FaultInjector().dispatch_error(step=4, attempts=2)  # <= retries(2)
    sleeps = []
    app.init_kv_cache()
    sess = ServingSession(app, fault_injector=inj, sleep_fn=sleeps.append)
    assert sess.ragged_async
    for rid, prompt in PROMPTS.items():
        assert sess.add_request(rid, prompt, max_new_tokens=6)
    out = _drive(sess)
    assert out == golden
    assert sleeps == [0.02, 0.04]
    assert all(r.status == "finished" for r in sess.requests.values())


def test_async_ragged_retry_exhaustion_keeps_pending_tokens(paged_apps):
    """Past the retry budget on the pipelined path: the already-executed
    previous step is consumed BEFORE the in-flight rows fail, so every
    failed request keeps a clean-run PREFIX including its last in-flight
    token (sync commit order); the session survives and serves new work."""
    app = paged_apps[1]
    _, golden = _mix(app)
    inj = FaultInjector().dispatch_error(step=5, attempts=10)
    sleeps = []
    app.init_kv_cache()
    sess = ServingSession(app, fault_injector=inj, sleep_fn=sleeps.append)
    for rid, prompt in PROMPTS.items():
        assert sess.add_request(rid, prompt, max_new_tokens=6)
    out = _drive(sess)
    failed = [r for r in sess.requests.values() if r.status == "failed"]
    assert failed and all(r.fail_reason == "dispatch_error" for r in failed)
    assert len(sleeps) == 2  # retried the budget before giving up
    for rid, toks in out.items():
        assert toks == golden[rid][: len(toks)], rid  # clean-run prefixes
    assert len(sess.free_slots) == sess.num_slots
    # alive: a fresh request admits and completes byte-identically
    probe = [42, 10, 11]
    app.init_kv_cache()
    iso = ServingSession(app)
    assert iso.add_request("iso", probe, max_new_tokens=4)
    golden_probe = _drive(iso)["iso"]
    app.init_kv_cache()
    assert sess.add_request("after", probe, max_new_tokens=4)
    assert _drive(sess)["after"] == golden_probe


def test_async_ragged_deadline_expiry_mid_pipeline(paged_apps):
    """A request expiring while its dispatched step is still in flight:
    terminal deadline_exceeded at the step boundary, its in-flight token is
    discarded (stale entry), and co-batched rows keep their full
    clean-run streams."""
    app = paged_apps[1]
    _, golden = _mix(app, n_tokens=8)
    clock = FakeClock()
    app.init_kv_cache()
    sess = ServingSession(app, clock=clock, sleep_fn=clock.sleep)
    assert sess.ragged_async
    assert sess.add_request("r1", PROMPTS["r1"], max_new_tokens=8,
                            deadline_s=1.0)
    assert sess.add_request("r2", PROMPTS["r2"], max_new_tokens=8)
    assert sess.add_request("r3", PROMPTS["r3"], max_new_tokens=8)
    for _ in range(4):
        sess.step()  # r1's next step is dispatched and UNCONSUMED here
    clock.t += 5.0  # r1 expires with a pending in-flight step
    out = _drive(sess)
    r1 = sess.requests["r1"]
    assert r1.status == "failed" and r1.fail_reason == "deadline_exceeded"
    assert out["r1"] == golden["r1"][: len(out["r1"])]
    assert len(out["r1"]) < 8
    assert out["r2"] == golden["r2"]
    assert out["r3"] == golden["r3"]
    assert len(sess.free_slots) == sess.num_slots


# ---------------------------------------------------------------------------
# disaggregated prefill tier (ISSUE 15): the KV hand-off as a failure domain
# — every handoff_* injector mode x victim-typed containment x co-batched
# byte-identity x retry-exhaust x tier-dead degradation
# ---------------------------------------------------------------------------


DISAGG_REQS = {
    "d1": dict(ids=[5, 17, 92, 41], gen=6),
    "d2": dict(ids=list(range(30, 52)), gen=6),
    "d3": dict(ids=[7, 7, 7], gen=5),
    "d4": dict(ids=[11, 23, 5, 99, 100, 3], gen=6),
}


def _disagg_cfg(stage=None):
    return make_tiny_config(tpu=dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        seq_len=64, is_prefill_stage=stage,
    ))


@pytest.fixture(scope="module")
def disagg_tier_apps():
    """2 contiguous-cache decode apps + 1 prefill-stage app on partitioned
    devices, shared weights — the hand-off containment target."""
    from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
    from neuronx_distributed_inference_tpu.runtime.router import (
        partition_devices,
    )

    sd = make_random_hf_state_dict(_disagg_cfg())
    parts = partition_devices(3)
    apps = []
    for i, stage in enumerate([None, None, True]):
        cfg = _disagg_cfg(stage)
        apps.append(TpuModelForCausalLM(
            None, cfg,
            mesh=mesh_from_config(cfg.tpu_config, devices=parts[i]),
        ).load(state_dict=sd))
    return apps


@pytest.fixture(scope="module")
def disagg_reference(disagg_tier_apps):
    app = disagg_tier_apps[0]
    app.init_kv_cache()
    sess = ServingSession(app)
    for rid, spec in DISAGG_REQS.items():
        assert sess.add_request(rid, spec["ids"], max_new_tokens=spec["gen"])
    sess.run_to_completion()
    return {rid: list(sess.requests[rid].generated) for rid in DISAGG_REQS}


def _disagg_drain(apps, injector=None, retries=2, timeout=None, clock=None,
                  sleep=None, telemetry=None):
    from neuronx_distributed_inference_tpu.runtime.replica import (
        PrefillReplicaHandle,
    )
    from neuronx_distributed_inference_tpu.runtime.router import ServingRouter

    for app in apps:
        app.init_kv_cache()
    sessions = [ServingSession(app, telemetry=telemetry) for app in apps[:2]]
    ph = PrefillReplicaHandle(apps[2], 0, fault_injector=injector)
    with ServingRouter(sessions, prefill_replicas=[ph], telemetry=telemetry,
                       handoff_max_retries=retries, handoff_timeout_s=timeout,
                       clock=clock, sleep_fn=sleep) as router:
        for rid, spec in DISAGG_REQS.items():
            router.add_request(rid, spec["ids"], max_new_tokens=spec["gen"])
        out = router.run_to_completion()
    return router, ph, out


@pytest.mark.parametrize("mode", ["handoff_corrupt", "handoff_truncate"])
def test_handoff_payload_fault_fails_one_request(
    disagg_tier_apps, disagg_reference, mode
):
    """A corrupt/truncated payload that ARRIVES is caught by the decode
    session's inject validation: exactly ONE request dies, typed
    FAILED(handoff), destination line scrubbed — every co-batched request's
    stream is byte-identical to a clean run, and the slot recycles."""
    inj = FaultInjector(0)
    getattr(inj, mode)(0)  # hand-off #0 == the first placed request
    router, ph, out = _disagg_drain(disagg_tier_apps, injector=inj)
    failed = [r for r in router.requests.values() if r.status == "failed"]
    assert len(failed) == 1
    assert failed[0].fail_reason == "handoff"
    assert failed[0].tokens == []  # nothing was decoded from the bad payload
    for rid in DISAGG_REQS:
        if rid != failed[0].req_id:
            assert out[rid] == disagg_reference[rid], (mode, rid)
    assert any(f["kind"] == mode for f in inj.log)
    # the tier member is NOT penalized for transit corruption
    assert ph.health == "healthy"
    # the victim's slot recycled: decode sessions drained empty
    for h in router.replicas:
        assert len(h.session.free_slots) == h.session.num_slots


@pytest.mark.parametrize("mode", ["handoff_drop", "handoff_latency"])
def test_handoff_transit_fault_retries_and_recovers(
    disagg_tier_apps, disagg_reference, mode
):
    """A transit fault within the retry budget is invisible in the output:
    the bounded retry re-extracts and re-sends, the drain stays
    byte-identical, and the member stays HEALTHY."""
    clock = FakeClock()
    inj = FaultInjector(0)
    if mode == "handoff_drop":
        inj.handoff_drop(0, attempts=1)
    else:
        # latency past the 1s timeout: the attempt is observed as timed
        # out (retryable); the retry runs latency-free and succeeds
        inj.handoff_latency(0, 5.0)
    router, ph, out = _disagg_drain(
        disagg_tier_apps, injector=inj, retries=2, timeout=1.0,
        clock=clock, sleep=clock.sleep,
    )
    assert out == disagg_reference
    assert all(r.status == "finished" for r in router.requests.values())
    assert ph.health == "healthy"
    assert any(f["kind"] == mode for f in inj.log)


@pytest.mark.parametrize("mode", ["handoff_drop", "handoff_stall"])
def test_handoff_retry_exhaustion_fails_one_and_degrades_member(
    disagg_tier_apps, disagg_reference, mode
):
    """Exhausting the bounded hand-off retry fails ONLY the in-flight
    request (typed FAILED(handoff)) and degrades the tier member like a
    dispatch give-up — the drain continues through the degraded member,
    co-batched requests byte-identical."""
    inj = FaultInjector(0)
    if mode == "handoff_drop":
        inj.handoff_drop(0, attempts=5)
    else:
        inj.handoff_stall(0)  # stays armed: every attempt of #0 stalls
    router, ph, out = _disagg_drain(disagg_tier_apps, injector=inj, retries=1)
    failed = [r for r in router.requests.values() if r.status == "failed"]
    assert len(failed) == 1 and failed[0].fail_reason == "handoff"
    assert ph.health == "degraded"
    assert ph.give_ups == 1
    for rid in DISAGG_REQS:
        if rid != failed[0].req_id:
            assert out[rid] == disagg_reference[rid], (mode, rid)


def test_handoff_second_exhaustion_kills_member_tier_degrades(
    disagg_tier_apps, disagg_reference
):
    """Two give-ups kill the (only) tier member: its in-flight requests'
    verdicts are typed, the tier reads DEAD, and every later placement
    degrades to LOCAL monolithic prefill — the remaining requests complete
    byte-identically (the tier-wide graceful-degradation pin)."""
    import warnings

    inj = FaultInjector(0).handoff_stall(0).handoff_stall(1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        router, ph, out = _disagg_drain(disagg_tier_apps, injector=inj,
                                        retries=0)
    assert ph.health == "dead" and ph.health_reason == "handoff"
    failed = sorted(
        r.req_id for r in router.requests.values() if r.status == "failed"
    )
    assert len(failed) == 2  # exactly the two stalled hand-offs' victims
    for rid in DISAGG_REQS:
        if rid not in failed:
            assert out[rid] == disagg_reference[rid]
            assert router.requests[rid].status == "finished"


@pytest.mark.parametrize("mode,reason", [
    ("handoff_corrupt", "handoff_corrupt"),
    ("handoff_truncate", "handoff_truncated"),
])
def test_handoff_failure_counter_carries_typed_reason(
    disagg_tier_apps, mode, reason
):
    """The inject validator's TYPED cause labels
    nxdi_handoff_failures_total — an operator can tell a truncated transfer
    from NaN corruption from a format mismatch in the metric stream (retry
    exhaustion labels `handoff_exhausted`, covered above)."""
    inj = FaultInjector(0)
    getattr(inj, mode)(0)
    with TelemetrySession() as tel:
        _disagg_drain(disagg_tier_apps, injector=inj, telemetry=tel)
    snap = tel.registry.snapshot()
    reasons = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_handoff_failures_total"]["samples"]
    }
    assert reasons == {reason: 1}


def test_handoff_wall_time_bills_against_deadline(disagg_tier_apps):
    """The hand-off's own wall time (prefill, retries, backoff) counts
    against the request's TTL — a hand-off that consumes the whole deadline
    yields a typed FAILED(deadline_exceeded), never a request that decodes
    past its SLA on a silently-extended deadline (the local-prefill path
    bills its prefill the same way)."""
    from neuronx_distributed_inference_tpu.runtime.replica import (
        PrefillReplicaHandle,
    )
    from neuronx_distributed_inference_tpu.runtime.router import ServingRouter

    clock = FakeClock()
    # 10s injected hand-off latency with NO transfer timeout armed: the
    # attempt itself succeeds, but the request's 2s TTL is long gone
    inj = FaultInjector(0).handoff_latency(0, 10.0)
    for app in disagg_tier_apps:
        app.init_kv_cache()
    sessions = [
        ServingSession(app, clock=clock, sleep_fn=clock.sleep)
        for app in disagg_tier_apps[:2]
    ]
    ph = PrefillReplicaHandle(disagg_tier_apps[2], 0, fault_injector=inj)
    with ServingRouter(sessions, prefill_replicas=[ph], clock=clock,
                       sleep_fn=clock.sleep) as router:
        assert router.add_request("slow", DISAGG_REQS["d1"]["ids"],
                                  max_new_tokens=6, deadline_s=2.0)
        assert router.add_request("ok", DISAGG_REQS["d3"]["ids"],
                                  max_new_tokens=5)
        out = router.run_to_completion()
    slow = router.requests["slow"]
    assert slow.status == "failed"
    assert slow.fail_reason == "deadline_exceeded"
    assert slow.tokens == []  # never decoded past its SLA
    assert router.requests["ok"].status == "finished"
    assert len(out["ok"]) == 5


def test_total_outage_publishes_dead_gauges(disagg_tier_apps):
    """A step() on a fully-dead fleet still publishes gauges: every
    replica's health gauge must read 0 (dead) and the global queue gauge
    must read the drained (cleared) queue — a dashboard must never show a
    healthy fleet during a total outage."""
    from neuronx_distributed_inference_tpu.runtime.router import ServingRouter

    with TelemetrySession() as tel:
        for app in disagg_tier_apps[:2]:
            app.init_kv_cache()
        sessions = [
            ServingSession(app, telemetry=tel) for app in disagg_tier_apps[:2]
        ]
        with ServingRouter(sessions, telemetry=tel) as router:
            assert router.add_request("x", DISAGG_REQS["d1"]["ids"],
                                      max_new_tokens=6)
            router.step()
            for h in router.replicas:
                h.kill("outage")  # incl. one killed while IDLE
            router.step()  # the early-return path must still publish
            snap = tel.registry.snapshot()
    health = {
        s["labels"]["replica"]: s["value"]
        for s in snap["nxdi_router_replica_health"]["samples"]
    }
    assert health == {"0": 0, "1": 0}
    assert snap["nxdi_router_queue_depth"]["samples"][0]["value"] == 0
    assert router.requests["x"].status == "failed"
