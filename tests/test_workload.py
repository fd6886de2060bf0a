"""Workload engine + SLO goodput subsystem (ISSUE 14; docs/WORKLOADS.md).

The acceptance pins:
- seeded determinism: same seed => byte-identical arrival trace (digest),
  => byte-identical router token streams across two runs, sequential AND
  `router_threading`; the trace JSON round-trips exactly;
- open-loop semantics: a request is admitted no earlier than its arrival
  step (the driver's admission events record both), backlog refusals retry
  and are scored against goodput (TTFT measured from ARRIVAL), and the
  backlog give-up records `nxdi_requests_rejected_total{reason=backlog}` —
  the reason the bench's clean-traffic containment pin excludes;
- SLO scorer arithmetic on hand-built traces: attainment, miss taxonomy,
  goodput accounting, dip/recovery extraction on synthetic series;
- the standing chaos row: a seeded replica kill mid-run shows a nonzero
  goodput dip with finite recovery, byte-identically reproducible;
- per-tenant spec-acceptance profiles (prose-ish vs code-ish) move the
  measured acceptance EWMAs without changing one output byte.
"""

import json

import numpy as np
import pytest

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.replica import ReplicaHandle
from neuronx_distributed_inference_tpu.runtime.router import (
    ServingRouter,
    partition_devices,
)
from neuronx_distributed_inference_tpu.runtime.serving import (
    ServingSession,
    SpeculativeServingSession,
)
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from neuronx_distributed_inference_tpu.telemetry.tracing import RequestTrace
from neuronx_distributed_inference_tpu.workload import (
    Arrival,
    ArrivalSpec,
    ChaosPlan,
    TenantProfile,
    VirtualClock,
    WorkloadDriver,
    WorkloadSpec,
    WorkloadTrace,
    extract_dip,
    generate,
    score,
    standard_spec,
)
from neuronx_distributed_inference_tpu.workload.driver import WorkloadResult

pytestmark = pytest.mark.workload


def _paged_cfg(**extra):
    tpu = dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=48,
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=2, kernel_q_tile_size=16
        ),
        seq_len=64,
    )
    tpu.update(extra)
    return make_tiny_config(tpu=tpu)


@pytest.fixture(scope="module")
def state_dict():
    return make_random_hf_state_dict(_paged_cfg())


@pytest.fixture(scope="module")
def single_app(state_dict):
    return TpuModelForCausalLM(None, _paged_cfg()).load(state_dict=state_dict)


@pytest.fixture(scope="module")
def replica_apps(state_dict):
    parts = partition_devices(2)
    apps = []
    for i in range(2):
        cfg = _paged_cfg()
        apps.append(TpuModelForCausalLM(
            None, cfg, mesh=mesh_from_config(cfg.tpu_config, devices=parts[i])
        ).load(state_dict=state_dict))
    return apps


def _spec(seed=3, n=8, rate=1.5, **kw):
    base = dict(
        seed=seed, n_requests=n, vocab_size=118, rate=rate,
        max_prompt_len=16, min_output_len=4, max_output_len=8,
        shared_prefix_len=8, ttft_slo_s=1e4, itl_slo_s=1e3,
    )
    base.update(kw)
    return standard_spec(**base)


def _run_router(apps, trace, *, threaded=False, chaos=None,
                policy="least_loaded"):
    for app in apps:
        app.init_kv_cache()
    vc = VirtualClock()
    with TelemetrySession(clock=vc.now) as tel:
        sessions = [
            ServingSession(app, telemetry=tel, clock=vc.now) for app in apps
        ]
        handles = [
            ReplicaHandle(s, i, clock=vc.now) for i, s in enumerate(sessions)
        ]
        with ServingRouter(handles, policy=policy, telemetry=tel,
                           clock=vc.now, threaded=threaded) as router:
            drv = WorkloadDriver(router, trace, clock=vc, telemetry=tel,
                                 chaos=chaos)
            result = drv.run()
    return result, tel


# ---------------------------------------------------------------------------
# generator: determinism, serialization, distribution bounds
# ---------------------------------------------------------------------------


def test_trace_determinism_and_digest():
    spec = _spec()
    t1, t2 = generate(spec), generate(spec)
    assert t1.dumps() == t2.dumps()
    assert t1.digest() == t2.digest()
    t3 = generate(_spec(seed=4))
    assert t3.digest() != t1.digest()


def test_trace_json_roundtrip_exact():
    trace = generate(_spec())
    payload = trace.dumps()
    back = WorkloadTrace.loads(payload)
    assert back.dumps() == payload  # byte-identical round trip
    # and through generic json (the replay/archival path)
    back2 = WorkloadTrace.loads(json.loads(payload))
    assert back2.digest() == trace.digest()


def test_arrival_envelopes():
    onoff = ArrivalSpec(kind="onoff", rate=4.0, off_rate=0.0,
                        period_on=2, period_off=3)
    rates = [onoff.rate_at(s) for s in range(10)]
    assert rates[:5] == [4.0, 4.0, 0.0, 0.0, 0.0]  # square wave
    assert rates[5:10] == rates[:5]  # periodic
    di = ArrivalSpec(kind="diurnal", rate=8.0, diurnal_period=16,
                     diurnal_floor=0.25)
    vals = [di.rate_at(s) for s in range(16)]
    assert max(vals) <= 8.0 and min(vals) >= 0.25 * 8.0 - 1e-9
    assert max(vals) > min(vals)  # the envelope actually moves
    with pytest.raises(ValueError, match="unknown arrival kind"):
        ArrivalSpec(kind="bogus")


def test_generate_respects_bounds_and_shared_prefixes():
    tenant = TenantProfile(
        name="t", shared_prefix_len=8, max_prompt_len=16,
        min_output_len=2, max_output_len=6,
    )
    spec = WorkloadSpec(seed=11, n_requests=20, vocab_size=50,
                        arrival=ArrivalSpec(rate=2.0), tenants=(tenant,))
    trace = generate(spec)
    assert len(trace.arrivals) == 20
    prefix = trace.arrivals[0].input_ids[:8]
    steps = [a.step for a in trace.arrivals]
    assert steps == sorted(steps)  # arrival order
    for a in trace.arrivals:
        assert 9 <= len(a.input_ids) <= 16  # prefix + >=1 suffix token
        assert a.input_ids[:8] == prefix  # the pool-shared prefix
        assert 2 <= a.max_new_tokens <= 6
        assert all(0 <= t < 50 for t in a.input_ids)
    with pytest.raises(ValueError, match="suffix"):
        TenantProfile(name="bad", shared_prefix_len=16, max_prompt_len=16)
    # standard_spec clamps the stock prefix below tiny prompt bounds
    # instead of handing TenantProfile a negative length
    tiny = standard_spec(seed=0, n_requests=2, vocab_size=32,
                         max_prompt_len=4, rate=5.0)
    assert all(t.shared_prefix_len == 0 for t in tiny.tenants)
    assert len(generate(tiny).arrivals) == 2


def test_accept_gate_follows_base_id_across_failover_suffix():
    """The sessions call the gate with their OWN request id, which carries
    a ~fN suffix per router-failover incarnation — the tenant profile (and
    the deterministic agreement sequence) must follow the base id."""
    from neuronx_distributed_inference_tpu.workload.generator import (
        base_req_id,
        make_accept_gate,
    )

    assert base_req_id("prose0-0003~f1") == "prose0-0003"
    assert base_req_id("prose0-0003") == "prose0-0003"
    assert base_req_id("odd~fx") == "odd~fx"  # not an incarnation suffix
    trace = generate(_spec(seed=2, n=4, spec_profiles=True))
    profiled = [a.req_id for a in trace.arrivals
                if a.spec_accept_rate is not None]
    rid = profiled[0]
    g1 = make_accept_gate(trace)
    g2 = make_accept_gate(trace)
    # incarnation ids draw the SAME deterministic sequence as the base id
    seq_base = [g1(rid, 3) for _ in range(4)]
    seq_failover = [g2(rid, 3), g2(rid, 3),
                    g2(f"{rid}~f1", 3), g2(f"{rid}~f1", 3)]
    assert seq_failover == seq_base
    assert make_accept_gate(trace)("unknown-req", 3) is None


# ---------------------------------------------------------------------------
# SLO scorer: unit tests on hand-built traces and series
# ---------------------------------------------------------------------------


def _handbuilt_result():
    """Three requests: one meets, one blows TTFT, one fails server-side."""
    tenants = (
        TenantProfile(name="a", ttft_slo_s=5.0, itl_slo_s=10.0,
                      max_prompt_len=8, max_output_len=8),
    )
    spec = WorkloadSpec(seed=0, n_requests=3, vocab_size=16,
                        tenants=tenants, arrival=ArrivalSpec(rate=10.0))
    arrivals = [
        Arrival("a-0000", 0, "a", (1, 2), 4, ttft_slo_s=5.0, itl_slo_s=10.0),
        Arrival("a-0001", 0, "a", (3, 4), 4, ttft_slo_s=5.0, itl_slo_s=10.0),
        Arrival("a-0002", 2, "a", (5, 6), 4, ttft_slo_s=5.0, itl_slo_s=10.0),
    ]
    trace = WorkloadTrace(spec=spec, arrivals=arrivals)
    res = WorkloadResult(trace=trace)
    res.outputs = {"a-0000": [7, 8, 9, 1], "a-0001": [7, 7, 7, 7],
                   "a-0002": [2]}
    res.statuses = {"a-0000": "finished", "a-0001": "finished",
                    "a-0002": "failed"}
    res.step_commits = [{}, {"a-0000": 2}, {"a-0000": 2, "a-0001": 4},
                        {"a-0002": 1}, {}]
    # live_steps is recorded AFTER each step: the step committing the
    # run's LAST tokens reads not-live but must stay in the series; only
    # the genuinely idle trailing step trims
    res.live_steps = [True, True, True, False, False]
    res.steps = 5
    return trace, res


def test_score_attainment_arithmetic():
    trace, res = _handbuilt_result()
    tel = TelemetrySession()
    # a-0000: first token at t=1 (TTFT 1 <= 5), 4 tokens over 2s -> met
    tel.completed.append(RequestTrace(
        req_id="a-0000", t_submit=0.0, t_first_token=1.0, t_last_token=3.0,
        tokens=4, finish_reason="length"))
    # a-0001: first token at t=8 -> TTFT 8 > 5 -> ttft miss
    tel.completed.append(RequestTrace(
        req_id="a-0001", t_submit=0.0, t_first_token=8.0, t_last_token=9.0,
        tokens=4, finish_reason="length"))
    # a-0002: served a token but FAILED server-side -> failed miss
    tel.completed.append(RequestTrace(
        req_id="a-0002", t_submit=2.0, t_first_token=3.0, t_last_token=3.0,
        tokens=1, finish_reason="dispatch_error"))
    rep = score(res, tel, bucket_steps=2)
    assert rep.attainment == pytest.approx(1 / 3, abs=1e-4)
    assert rep.misses_by_kind == {"ttft": 1, "failed": 1}
    assert rep.slo_met_tokens == 4  # only a-0000's tokens are goodput
    assert rep.total_tokens == 9
    by_req = {s.req_id: s for s in rep.per_request}
    assert by_req["a-0000"].met and by_req["a-0000"].ttft_s == 1.0
    assert by_req["a-0001"].miss_kind == "ttft"
    assert by_req["a-0002"].miss_kind == "failed"
    # a-0000's avg ITL: (3-1)/(4-1)s
    assert by_req["a-0000"].avg_itl_s == pytest.approx(2 / 3)
    # the goodput series buckets ONLY met requests' commits
    assert rep.series == [2, 2]
    # the miss census landed in the registry, labelled by kind and tenant
    snap = tel.registry.snapshot()
    missed = {
        (s["labels"]["kind"], s["labels"]["tenant"]): s["value"]
        for s in snap["nxdi_slo_missed_total"]["samples"]
    }
    assert missed == {("ttft", "a"): 1, ("failed", "a"): 1}


def test_extract_dip_on_synthetic_series():
    # steady 20/bucket, kill at bucket 3, dip to 5, recover to 11 (>=
    # 0.8 * 0.5 * 20 = 8 target with one of two replicas surviving)
    series = [12, 20, 20, 8, 5, 9, 11, 10]
    dip = extract_dip(series, 3, bucket_steps=4, alive_frac=0.5,
                      recovery_frac=0.8)
    assert dip.baseline == 20.0
    assert dip.dip_value == 5.0
    assert dip.dip_frac == pytest.approx(0.75)
    assert dip.recovery_target == pytest.approx(8.0)
    # dip bucket is 4; first bucket >= target is 5 -> (5-3)*4 steps
    assert dip.recovery_steps == 8
    # never recovers -> None (finite-recovery assertions must be able to
    # fail honestly)
    assert extract_dip([10, 20, 2, 2, 2], 2, alive_frac=0.5).recovery_steps is None
    # no pre-kill baseline / kill outside the series -> no read
    assert extract_dip([0, 0, 0, 0], 2) is None
    assert extract_dip([5, 5], 7) is None
    # a kill INSIDE the warmup window has no steady baseline: refuse the
    # read rather than compare against the ramp bucket (dip would read ~0)
    assert extract_dip([7, 16, 14, 14], 1) is None
    # the bounded dip window ignores the natural end-of-run drain-down
    tail = [10, 20, 18, 19, 20, 6, 2]
    d2 = extract_dip(tail, 2, dip_window_buckets=3, alive_frac=1.0)
    assert d2.dip_value == 18.0  # NOT the trailing 2


# ---------------------------------------------------------------------------
# open-loop semantics against a live session
# ---------------------------------------------------------------------------


def test_open_loop_admission_and_backlog(single_app):
    """Bursty arrivals overrun the 4 slots: every admission happens at or
    after its arrival step, at least one request waits in the backlog, and
    the wait is scored against goodput (TTFT from arrival) while generous
    SLOs keep attainment at exactly 1.0."""
    trace = generate(_spec(seed=7, n=10, rate=4.0, arrival_kind="onoff"))
    single_app.init_kv_cache()
    vc = VirtualClock()
    with TelemetrySession(clock=vc.now) as tel:
        sess = ServingSession(single_app, telemetry=tel, clock=vc.now)
        drv = WorkloadDriver(sess, trace, clock=vc, telemetry=tel)
        result = drv.run()
    assert set(result.outputs) == {a.req_id for a in trace.arrivals}
    admitted = {ev.req_id: ev for ev in result.admissions}
    assert set(admitted) == set(result.outputs)
    arrival_of = trace.arrival_steps
    for ev in result.admissions:
        assert ev.arrival_step == arrival_of[ev.req_id]
        # the open-loop pin: never admitted before arrival
        assert ev.admitted_step >= ev.arrival_step
    waited = [ev for ev in result.admissions
              if ev.admitted_step > ev.arrival_step]
    assert waited, "the burst never overran capacity — not open-loop"
    assert result.backlog_refusals > 0
    # refusal census recorded (retried, NON-terminal)
    snap = tel.registry.snapshot()
    refused = sum(
        s["value"] for s in snap["nxdi_workload_refusals_total"]["samples"]
    )
    assert refused == result.backlog_refusals
    rep = score(result, tel)
    assert rep.attainment == 1.0
    assert rep.slo_met_tokens == rep.total_tokens > 0
    # backlogged requests' TTFT includes the wait (>= admission delay)
    by_req = {s.req_id: s for s in rep.per_request}
    for ev in waited:
        assert by_req[ev.req_id].ttft_s >= (
            ev.admitted_step - ev.arrival_step
        ) * result.step_dt_s


def test_backlog_giveup_records_rejected_backlog(single_app):
    """Past max_backlog_steps the driver gives up: the arrival is terminal
    never_served(backlog), recorded as rejected{reason=backlog} — and the
    bench-convention rejected count (backlog EXCLUDED) stays 0."""
    trace = generate(_spec(seed=7, n=12, rate=6.0, max_output_len=8,
                           min_output_len=6))
    single_app.init_kv_cache()
    vc = VirtualClock()
    with TelemetrySession(clock=vc.now) as tel:
        sess = ServingSession(single_app, telemetry=tel, clock=vc.now)
        drv = WorkloadDriver(sess, trace, clock=vc, telemetry=tel,
                             max_backlog_steps=1)
        result = drv.run()
    gave_up = [rid for rid, why in result.never_served.items()
               if why == "backlog"]
    assert gave_up, "the tiny backlog budget never tripped"
    snap = tel.registry.snapshot()
    samples = snap["nxdi_requests_rejected_total"]["samples"]
    backlog_rejected = sum(
        s["value"] for s in samples if s["labels"]["reason"] == "backlog"
    )
    other_rejected = sum(
        s["value"] for s in samples if s["labels"]["reason"] != "backlog"
    )
    assert backlog_rejected == len(gave_up)
    assert other_rejected == 0  # the clean-traffic pin stays clean
    rep = score(result, tel)
    assert rep.misses_by_kind.get("never_served") == len(gave_up)
    assert rep.attainment < 1.0


def test_deadline_slo_is_enforced_server_side(single_app):
    """The PR-7 wall-clock deadline rides the trace: on the virtual clock a
    2-virtual-second TTL expires mid-decode, the session terminates the
    request as deadline_exceeded, and the scorer counts it as a failed
    miss."""
    tenants = (TenantProfile(
        name="tight", shared_prefix_len=4, max_prompt_len=12,
        min_output_len=10, max_output_len=12, deadline_s=2.0,
    ),)
    spec = WorkloadSpec(seed=1, n_requests=3, vocab_size=118,
                        arrival=ArrivalSpec(rate=3.0), tenants=tenants)
    trace = generate(spec)
    single_app.init_kv_cache()
    vc = VirtualClock()
    with TelemetrySession(clock=vc.now) as tel:
        sess = ServingSession(single_app, telemetry=tel, clock=vc.now)
        result = WorkloadDriver(sess, trace, clock=vc, telemetry=tel).run()
    assert any(st == "failed" for st in result.statuses.values())
    rep = score(result, tel)
    assert rep.attainment < 1.0
    assert rep.misses_by_kind.get("failed", 0) >= 1


class _StubTarget:
    """Scripted single-session stand-in: refuses capacity until a given
    driver step, then admits — isolates the driver's backlog policy from
    serving timing."""

    def __init__(self, admit_from_step):
        from neuronx_distributed_inference_tpu.runtime.serving import (
            AdmissionResult,
        )

        self._AdmissionResult = AdmissionResult
        self.admit_from = admit_from_step
        self.requests = {}
        self.active = []
        self._readmit = []
        self.offers = []
        self._step_no = 0

    def add_request(self, rid, ids, max_new_tokens=0, deadline_s=None):
        self.offers.append((rid, self._step_no))
        if self._step_no < self.admit_from:
            return self._AdmissionResult(False, "no_slot")
        self.requests[rid] = type(
            "R", (), {"generated": [], "status": "finished"}
        )()
        return self._AdmissionResult(True)

    def step(self):
        self._step_no += 1
        return {}


def _two_arrival_trace():
    tenants = (TenantProfile(name="t", max_prompt_len=8, max_output_len=2),)
    spec = WorkloadSpec(seed=0, n_requests=2, vocab_size=16, tenants=tenants)
    return WorkloadTrace(spec=spec, arrivals=[
        Arrival("t-0000", 0, "t", (1, 2), 2),
        Arrival("t-0001", 0, "t", (3, 4), 2),
    ])


def test_backlog_giveup_requires_refused_offer():
    """An arrival that aged past max_backlog_steps behind a blocked head is
    still OFFERED — if capacity just freed it admits; the give-up may only
    follow a refused offer at the current step (never a pre-offer chain
    rejection)."""
    stub = _StubTarget(admit_from_step=6)
    drv = WorkloadDriver(stub, _two_arrival_trace(), clock=VirtualClock(),
                         max_backlog_steps=5)
    res = drv.run()
    # both waited 6 > 5 while the head was blocked, but capacity freed at
    # step 6 and the offers won
    assert not res.never_served
    assert sorted(e.admitted_step for e in res.admissions) == [6, 6]
    # a target that NEVER admits still gives up — after each arrival's own
    # refused offer, not before it
    stub2 = _StubTarget(admit_from_step=10**9)
    drv2 = WorkloadDriver(stub2, _two_arrival_trace(), clock=VirtualClock(),
                          max_backlog_steps=2)
    res2 = drv2.run()
    assert res2.never_served == {"t-0000": "backlog", "t-0001": "backlog"}
    offered = {rid for rid, _ in stub2.offers}
    assert offered == {"t-0000", "t-0001"}  # every give-up was offered


def test_demo_trace_out_is_standalone(tmp_path):
    """--workload-trace-out needs no --model-path (no model is loaded);
    every other mode still requires it as a clean usage error."""
    from neuronx_distributed_inference_tpu.inference_demo import main

    out = tmp_path / "trace.json"
    rc = main(["run", "--workload-trace-out", str(out),
               "--workload-requests", "4", "--workload-vocab", "64",
               "--workload-max-prompt", "12"])
    assert rc == 0
    t = WorkloadTrace.loads(out.read_text())
    assert len(t.arrivals) == 4
    assert main(["run"]) == 2  # no model, no trace-out: usage error


# ---------------------------------------------------------------------------
# seeded byte-identity: sequential AND threaded router
# ---------------------------------------------------------------------------


def test_seeded_runs_byte_identical_sequential_and_threaded(replica_apps):
    trace = generate(_spec(seed=5, n=12, rate=1.0, min_output_len=6,
                           max_output_len=10))
    r1, _ = _run_router(replica_apps, trace)
    r2, _ = _run_router(replica_apps, trace)
    assert r1.outputs == r2.outputs  # same seed => identical token streams
    assert r1.step_commits == r2.step_commits
    assert [e.admitted_step for e in r1.admissions] == [
        e.admitted_step for e in r2.admissions
    ]
    r3, _ = _run_router(replica_apps, trace, threaded=True)
    assert r3.outputs == r1.outputs  # thread-per-replica stepping too
    assert r3.step_commits == r1.step_commits


# ---------------------------------------------------------------------------
# the standing chaos row: seeded replica kill, dip + recovery
# ---------------------------------------------------------------------------


def test_chaos_kill_goodput_dip_and_recovery(replica_apps):
    trace = generate(_spec(seed=5, n=14, rate=1.0, min_output_len=12,
                           max_output_len=16))
    chaos = ChaosPlan(kill_step=8)
    res, tel = _run_router(replica_apps, trace, chaos=chaos)
    assert res.chaos is not None and res.chaos["step"] == 8
    # every request reached a terminal state; the kill's requests failed
    # over (the PR-10 machinery under the workload layer)
    assert all(st == "finished" for st in res.statuses.values())
    rep = score(res, tel, bucket_steps=4)
    assert rep.attainment == 1.0  # generous SLOs: chaos costs time, not SLOs
    assert rep.dip is not None
    assert rep.dip.dip_frac > 0.0
    assert rep.dip.recovery_steps is not None  # finite recovery
    # reproducible chaos: the same seed replays the same run byte-for-byte
    res2, _ = _run_router(replica_apps, trace, chaos=chaos)
    assert res2.outputs == res.outputs
    assert res2.chaos == res.chaos


# ---------------------------------------------------------------------------
# per-tenant spec-acceptance profiles (the CPU-harness draft model)
# ---------------------------------------------------------------------------


def _contiguous_cfg():
    return make_tiny_config(tpu=dict(
        is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
        seq_len=64,
    ))


@pytest.fixture(scope="module")
def spec_pair(state_dict):
    cfg_t, cfg_d = _contiguous_cfg(), _contiguous_cfg()
    sd = make_random_hf_state_dict(cfg_t)
    target = TpuModelForCausalLM(None, cfg_t).load(state_dict=sd)
    draft = TpuModelForCausalLM(None, cfg_d).load(state_dict=sd)  # SAME weights
    return target, draft


def test_accept_profiles_move_acceptance_not_outputs(spec_pair):
    """Split-path speculative serving with a same-weights draft (true
    acceptance ~1.0): the per-tenant profiles cap the accepted counts —
    code-ish tenants' acceptance EWMAs collapse, prose-ish stay high — and
    the emitted token streams are BYTE-IDENTICAL to the unprofiled run
    (capped tokens are the target's own greedy tokens, regenerated next
    round)."""
    target, draft = spec_pair
    spec = standard_spec(seed=9, n_requests=6, vocab_size=118, rate=1.0,
                         max_prompt_len=12, min_output_len=8,
                         max_output_len=10, shared_prefix_len=4,
                         spec_profiles=True)
    trace = generate(spec)
    rates = {a.req_id: a.spec_accept_rate for a in trace.arrivals}
    assert set(rates.values()) == {0.9, 0.2}  # prose-ish vs code-ish

    def run(profiled):
        t = trace
        if not profiled:
            import dataclasses

            t = WorkloadTrace(spec=trace.spec, arrivals=[
                dataclasses.replace(a, spec_accept_rate=None)
                for a in trace.arrivals
            ])
        target.init_kv_cache()
        draft.init_kv_cache()
        vc = VirtualClock()
        with TelemetrySession(clock=vc.now) as tel:
            sess = SpeculativeServingSession(
                target, draft, speculation_length=3,
                telemetry=tel, clock=vc.now,
            )
            res = WorkloadDriver(sess, t, clock=vc, telemetry=tel).run()
            ewma = {rid: r.accept_ewma for rid, r in sess.requests.items()}
        return res, ewma

    res_prof, ewma = run(True)
    res_plain, ewma_plain = run(False)
    assert res_prof.outputs == res_plain.outputs  # byte-identical streams
    prose = [ewma[r] for r in ewma if rates[r] == 0.9]
    code = [ewma[r] for r in ewma if rates[r] == 0.2]
    assert prose and code
    # the gate separates the tenants; without it everything sits near 1.0
    assert np.mean(code) < 0.5 < np.mean(prose) + 0.3
    assert np.mean(list(ewma_plain.values())) > 0.8
    assert np.mean(code) < np.mean(prose)


# ---------------------------------------------------------------------------
# ChaosPlan schedules (ISSUE 15 satellite): tier targeting + multi-kill
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def disagg_apps(state_dict):
    """2 CONTIGUOUS-cache decode apps + 1 prefill-stage app on partitioned
    devices — the disaggregated-tier workload target (the KV hand-off
    scatters whole cache lines, so the tier forbids the paged layout)."""
    parts = partition_devices(3)
    apps = []
    for i, stage in enumerate([None, None, True]):
        cfg = make_tiny_config(tpu=dict(
            is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
            seq_len=64, is_prefill_stage=stage,
        ))
        apps.append(TpuModelForCausalLM(
            None, cfg, mesh=mesh_from_config(cfg.tpu_config, devices=parts[i])
        ).load(state_dict=state_dict))
    return apps


def _run_disagg(apps, trace, *, chaos=None):
    from neuronx_distributed_inference_tpu.runtime.replica import (
        PrefillReplicaHandle,
    )

    for app in apps:
        app.init_kv_cache()
    vc = VirtualClock()
    with TelemetrySession(clock=vc.now) as tel:
        sessions = [
            ServingSession(app, telemetry=tel, clock=vc.now)
            for app in apps[:2]
        ]
        handles = [
            ReplicaHandle(s, i, clock=vc.now) for i, s in enumerate(sessions)
        ]
        with ServingRouter(
            handles, policy="least_loaded", telemetry=tel, clock=vc.now,
            prefill_replicas=[PrefillReplicaHandle(apps[2], 0)],
        ) as router:
            drv = WorkloadDriver(router, trace, clock=vc, telemetry=tel,
                                 chaos=chaos)
            result = drv.run()
    return result, tel


def test_chaos_tier_validation(replica_apps):
    trace = generate(_spec(seed=6, n=4))
    for app in replica_apps:
        app.init_kv_cache()
    sessions = [ServingSession(app) for app in replica_apps]
    with ServingRouter(sessions) as router:
        with pytest.raises(ValueError, match="prefill tier"):
            WorkloadDriver(router, trace,
                           chaos=ChaosPlan(kill_step=2, tier="prefill"))
        with pytest.raises(ValueError, match="tier"):
            WorkloadDriver(router, trace,
                           chaos=ChaosPlan(kill_step=2, tier="gpu"))
        with pytest.raises(ValueError, match="kills"):
            WorkloadDriver(router, trace,
                           chaos=ChaosPlan(kill_step=2, kills=0))


def test_chaos_multi_kill_schedule_seeded_replay(replica_apps):
    """kills=2 gap_steps=6 on a 2-replica router: both decode replicas die
    in sequence — the first kill fails over, the second is a total outage
    whose remaining requests surface as typed verdicts (never a raise) —
    and the seeded schedule replays byte-identically."""
    trace = generate(_spec(seed=7, n=10, rate=1.0, min_output_len=8,
                           max_output_len=12))
    chaos = ChaosPlan(kill_step=6, kills=2, gap_steps=6, seed=11)
    res, tel = _run_router(replica_apps, trace, chaos=chaos)
    events = res.chaos["events"]
    assert [e["step"] for e in events] == [6, 12]
    killed = {e["replica"] for e in events if "replica" in e}
    assert killed == {0, 1}  # the whole decode fleet died
    assert res.chaos["alive_before"] == 2
    # every request reached a TYPED terminal state (finished before the
    # outage, or failed with a verdict afterwards)
    assert set(res.statuses.values()) <= {"finished", "failed"}
    assert "failed" in set(res.statuses.values())
    # seeded replay: byte-identical outputs, commits, and kill schedule
    res2, _ = _run_router(replica_apps, trace, chaos=chaos)
    assert res2.outputs == res.outputs
    assert res2.step_commits == res.step_commits
    assert res2.chaos == res.chaos


def test_chaos_prefill_tier_kill_degrades_not_dips(disagg_apps):
    """ChaosPlan(tier='prefill') kills the ONLY tier member mid-run: decode
    capacity survives, placements degrade to local monolithic prefill
    (loud counter), EVERY request still finishes, attainment holds, and
    the scorer's capacity adjustment knows no decode replica died
    (alive_frac pinned 1.0). Seeded replay byte-identical."""
    trace = generate(_spec(seed=8, n=10, rate=1.0, min_output_len=8,
                           max_output_len=12))
    chaos = ChaosPlan(kill_step=4, tier="prefill", seed=3)
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("ignore")
        res, tel = _run_disagg(disagg_apps, trace, chaos=chaos)
    assert res.chaos["tier"] == "prefill"
    assert res.chaos["alive_frac"] == 1.0
    assert all(st == "finished" for st in res.statuses.values())
    rep = score(res, tel, bucket_steps=4)
    assert rep.attainment == 1.0
    # the degradation was LOUD: local-prefill fallbacks were counted
    snap = tel.registry.snapshot()
    fallback = snap["nxdi_handoff_local_prefill_total"]["samples"][0]["value"]
    assert fallback > 0
    # finite recovery: decode capacity never left, so the series holds at
    # (or quickly returns to) its baseline under the UNREDUCED target
    if rep.dip is not None:
        assert rep.dip.recovery_steps is not None
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        res2, _ = _run_disagg(disagg_apps, trace, chaos=chaos)
    assert res2.outputs == res.outputs
    assert res2.chaos == res.chaos
