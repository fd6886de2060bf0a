"""Ragged mixed-step serving (ISSUE 6): one dispatch per step, byte-identical
greedy outputs, fetch parity, zero steady-state recompiles, telemetry.

The acceptance pins:
- ONE compiled-program dispatch per step() for a mixed prefill+decode step
  under ``serving_ragged=True`` (vs >= 2 on the legacy split path),
- ``run_to_completion`` byte-identical to the legacy split dispatch on the
  standard mix,
- telemetry fetch-count parity (recording adds zero device round trips) and
  zero steady-state recompiles once the mix is warmed and sealed,
- the mixed-step composition histogram: each label's observation count ==
  the number of mixed dispatches.
"""

import numpy as np
import pytest

import jax

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

PROMPTS = {
    "r1": [5, 17, 92, 41],
    "r2": list(range(30, 52)),  # 22 tokens: chunks across several steps
    "r3": [7, 7, 7],
}


def _cfg(ragged, **extra):
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
def state_dict():
    return make_random_hf_state_dict(_cfg(False))


@pytest.fixture(scope="module")
def apps(state_dict):
    legacy = TpuModelForCausalLM(None, _cfg(False)).load(state_dict=state_dict)
    # the ragged step pipelines when async_mode does (the default): the
    # module's ragged app runs the PIPELINED path
    ragged = TpuModelForCausalLM(None, _cfg(True)).load(state_dict=state_dict)
    return legacy, ragged


@pytest.fixture(scope="module")
def sync_ragged_app(state_dict):
    return TpuModelForCausalLM(
        None, _cfg(True, async_mode=False)
    ).load(state_dict=state_dict)


def _standard_mix(app, telemetry=None):
    """The standard mix: staggered arrivals so chunked prefill of a long
    prompt overlaps live decode of earlier requests."""
    app.init_kv_cache()
    sess = ServingSession(app, telemetry=telemetry)
    assert sess.add_request("r1", PROMPTS["r1"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r2", PROMPTS["r2"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r3", PROMPTS["r3"], max_new_tokens=5)
    return sess.run_to_completion()


def test_ragged_matches_legacy_split_byte_identical(apps):
    """run_to_completion with serving_ragged=True produces byte-identical
    greedy outputs to the legacy split dispatch on the standard mix."""
    legacy, ragged = apps
    out_legacy = _standard_mix(legacy)
    out_ragged = _standard_mix(ragged)
    assert out_ragged == out_legacy
    assert all(len(v) > 0 for v in out_ragged.values())


def test_one_dispatch_per_mixed_step(apps):
    """A step with BOTH prefilling and decoding requests runs as ONE
    compiled-program dispatch under serving_ragged (vs >= 2 legacy)."""
    from neuronx_distributed_inference_tpu.runtime.model_runner import (
        MixedStepRunner,
        SubModelRunner,
    )

    legacy, ragged = apps
    counts = {}
    for name, app in (("legacy", legacy), ("ragged", ragged)):
        app.init_kv_cache()
        sess = ServingSession(app)
        # r1 fully admitted and decoding; r2 still mid-prefill (22 > 16)
        assert sess.add_request("r1", PROMPTS["r1"], max_new_tokens=8)
        sess.step()
        assert sess.add_request("r2", PROMPTS["r2"], max_new_tokens=8)
        sess.step()  # r2 chunk 1 of 2
        assert sess.prefilling and sess.decoding  # genuinely mixed now
        n = {"n": 0}
        orig_sub = SubModelRunner.__call__
        orig_mixed = MixedStepRunner.__call__

        def counting_sub(self, *a, **kw):
            n["n"] += 1
            return orig_sub(self, *a, **kw)

        def counting_mixed(self, *a, **kw):
            n["n"] += 1
            return orig_mixed(self, *a, **kw)

        SubModelRunner.__call__ = counting_sub
        MixedStepRunner.__call__ = counting_mixed
        try:
            sess.step()
        finally:
            SubModelRunner.__call__ = orig_sub
            MixedStepRunner.__call__ = orig_mixed
        counts[name] = n["n"]
    assert counts["ragged"] == 1, counts
    assert counts["legacy"] >= 2, counts


def test_fetch_parity_and_zero_recompiles_sealed(apps):
    """Telemetry on/off performs IDENTICAL device-fetch counts over a full
    ragged drain, and — with the mix warmed and the mixed runner sealed —
    the retrace guard observes zero steady-state recompiles."""
    from neuronx_distributed_inference_tpu.analysis import RetraceGuard

    _, ragged = apps
    golden = _standard_mix(ragged, TelemetrySession(enabled=False))  # warm

    counter = {"n": 0}
    real_asarray = np.asarray
    real_device_get = jax.device_get

    def counting_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            counter["n"] += 1
        return real_asarray(a, *args, **kwargs)

    def counting_device_get(x, *args, **kwargs):
        counter["n"] += 1
        return real_device_get(x, *args, **kwargs)

    np.asarray = counting_asarray
    jax.device_get = counting_device_get
    try:
        counter["n"] = 0
        out_off = _standard_mix(ragged, TelemetrySession(enabled=False))
        fetches_off = counter["n"]
        counter["n"] = 0
        with TelemetrySession() as tel:
            ragged.mixed_step_model.seal()
            try:
                with RetraceGuard() as guard:
                    out_on = _standard_mix(ragged, tel)
            finally:
                ragged.mixed_step_model._sealed = False
        fetches_on = counter["n"]
    finally:
        np.asarray = real_asarray
        jax.device_get = real_device_get

    assert out_on == out_off == golden
    assert fetches_off > 0
    assert fetches_on == fetches_off, (fetches_off, fetches_on)
    assert guard.traces == []  # zero steady-state recompiles, sealed


def test_mixed_step_histogram_pins_dispatch_count(apps):
    """The mixed-step composition histogram: each label's observation COUNT
    equals the number of mixed dispatches, prefill+decode row sums match
    the work actually done, and the padded fraction is well-formed."""
    _, ragged = apps
    with TelemetrySession() as tel:
        out = _standard_mix(ragged, tel)
    snap = tel.registry.snapshot()
    mixed_steps = [
        s for s in snap["nxdi_steps_total"]["samples"]
        if s["labels"]["kind"] == "mixed"
    ]
    n_dispatch = int(mixed_steps[0]["value"])
    assert n_dispatch > 0
    hist = {
        s["labels"]["kind"]: s
        for s in snap["nxdi_mixed_step_rows"]["samples"]
    }
    for kind in ("prefill_rows", "decode_rows", "padded_slots", "query_tokens"):
        assert hist[kind]["count"] == n_dispatch, (kind, hist[kind], n_dispatch)
    # prefill rows observed >= the chunked prompt's chunk count
    assert hist["prefill_rows"]["sum"] >= 2  # r2 takes 2 chunks alone
    total_generated = sum(len(v) for v in out.values())
    # every generated token except each request's first (emitted by its
    # final prefill chunk) came from a decode row observation
    assert hist["decode_rows"]["sum"] == total_generated - len(out)
    assert hist["padded_slots"]["sum"] >= 0
    # the bucket-census label is the mixed runner's tag
    models = {s["labels"]["model"] for s in
              snap["nxdi_bucket_dispatch_total"]["samples"]}
    assert "mixed_step_model" in models


def test_ragged_decode_only_and_slot_reuse(apps):
    """Pure-decode regime (no prefill pending) still runs single mixed
    dispatches; freed slots accept new requests with correct outputs."""
    legacy, ragged = apps
    legacy.init_kv_cache()
    s0 = ServingSession(legacy)
    assert s0.add_request("a", [42, 10, 11], max_new_tokens=4)
    golden = s0.run_to_completion()["a"]

    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    for i in range(4):
        assert sess.add_request(f"x{i}", [1 + i, 2, 3], max_new_tokens=3)
    sess.run_to_completion()
    assert len(sess.free_slots) == 4
    assert sess.add_request("a", [42, 10, 11], max_new_tokens=4)
    assert sess.run_to_completion()["a"] == golden


def test_ragged_eos_stops_early(apps):
    legacy, ragged = apps
    legacy.init_kv_cache()
    s0 = ServingSession(legacy)
    assert s0.add_request("e", [5, 6, 7], max_new_tokens=8)
    golden = s0.run_to_completion()["e"]
    eos = golden[2]

    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    assert sess.add_request("e", [5, 6, 7], max_new_tokens=8, eos_token_id=eos)
    assert sess.run_to_completion()["e"] == golden[:3]
    assert len(sess.free_slots) == 4


def test_ragged_quantized_kv_deterministic():
    """Quantized-KV ragged serving: individually DETERMINISTIC (two
    identical runs byte-match) and every request completes. Cross-mode
    byte-parity is documented as NOT guaranteed for quantized caches — the
    running-absmax scale couples whatever one dispatch co-writes, and the
    ragged step groups writes differently than the split path
    (docs/SERVING.md; same semantics class as docs/KV_QUANT.md)."""
    cfg = _cfg(True, kv_cache_dtype="int8")
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    runs = []
    for _ in range(2):
        app.init_kv_cache()  # fresh codes AND scales: restores exactly
        runs.append(_standard_mix(app))
    assert runs[0] == runs[1]
    assert all(len(v) > 0 for v in runs[0].values())


def test_serving_ragged_config_validation():
    with pytest.raises(ValueError, match="paged cache"):
        make_tiny_config(tpu=dict(
            is_continuous_batching=True, serving_ragged=True,
        ))
    with pytest.raises(ValueError, match="is_continuous_batching"):
        make_tiny_config(tpu=dict(
            is_block_kv_layout=True, serving_ragged=True,
        ))
    with pytest.raises(NotImplementedError, match="plain causal"):
        make_tiny_config(tpu=dict(
            is_continuous_batching=True, is_block_kv_layout=True,
            serving_ragged=True, sliding_window=16,
        ))


def test_session_requires_mixed_family():
    """A session asked for ragged dispatch on an app built WITHOUT the
    mixed_step family fails loudly at construction."""
    cfg = _cfg(True)
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg).load(state_dict=sd)
    app.mixed_step_model = None
    with pytest.raises(ValueError, match="mixed_step"):
        ServingSession(app)


# ---------------------------------------------------------------------------
# async 1-ahead pipelining (ISSUE 8): chained dispatch, one-step-late consume
# ---------------------------------------------------------------------------


def test_ragged_step_pipelines_iff_async_mode(apps, sync_ragged_app):
    """The ragged step pipelines exactly when async_mode does (the config
    default is pipelining ON, as the split path's 1-ahead decode);
    async_mode=False is the synchronous dispatch+fetch-per-step form, and
    the split path's own 1-ahead machinery stays off either way."""
    _, ragged = apps
    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    assert sess.ragged_async is True and sess.async_decode is False
    sync_ragged_app.init_kv_cache()
    sess = ServingSession(sync_ragged_app)
    assert sess.ragged_async is False and sess.async_decode is False


def test_async_vs_sync_vs_legacy_byte_identical(apps, sync_ragged_app):
    """Tentpole acceptance pin: async-ragged, sync-ragged and the legacy
    split dispatch produce byte-identical greedy streams on the standard
    staggered mix."""
    legacy, ragged_async = apps
    out_legacy = _standard_mix(legacy)
    out_sync = _standard_mix(sync_ragged_app)
    out_async = _standard_mix(ragged_async)
    assert out_async == out_sync == out_legacy
    assert all(len(v) > 0 for v in out_async.values())


def test_async_exactly_one_consumed_fetch_per_step(apps):
    """Pipelining ON: a steady-state decode step() performs EXACTLY one
    consumed host fetch (np.asarray on the previous step's tokens — started
    non-blocking at dispatch) and one mixed dispatch."""
    from neuronx_distributed_inference_tpu.runtime.model_runner import (
        MixedStepRunner,
    )

    _, ragged = apps
    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    assert sess.ragged_async
    assert sess.add_request("a", PROMPTS["r1"], max_new_tokens=12)
    assert sess.add_request("b", PROMPTS["r3"], max_new_tokens=12)
    for _ in range(4):  # past prefill, into the pipelined decode regime
        sess.step()
    assert sess._pending is not None

    fetches = {"n": 0}
    dispatches = {"n": 0}
    real_asarray = np.asarray
    orig_call = MixedStepRunner.__call__

    def counting_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            fetches["n"] += 1
        return real_asarray(a, *args, **kwargs)

    def counting_call(self, *a, **kw):
        dispatches["n"] += 1
        return orig_call(self, *a, **kw)

    np.asarray = counting_asarray
    MixedStepRunner.__call__ = counting_call
    try:
        for _ in range(3):
            before = (fetches["n"], dispatches["n"])
            out = sess.step()
            assert out, "steady-state step must deliver tokens"
            assert fetches["n"] == before[0] + 1, "exactly one consumed fetch"
            assert dispatches["n"] == before[1] + 1, "exactly one dispatch"
    finally:
        np.asarray = real_asarray
        MixedStepRunner.__call__ = orig_call
    sess.run_to_completion()


def test_async_tokens_consumed_one_step_late(apps):
    """The pipelined contract made visible: the step() that dispatches a
    row's first decode work returns no token for it; the NEXT step() does —
    and the final stream matches the synchronous path's."""
    _, ragged = apps
    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    assert sess.add_request("solo", PROMPTS["r1"], max_new_tokens=4)
    first = sess.step()   # dispatches the first decode step; nothing consumed
    assert first == {}
    second = sess.step()  # consumes step 1 while step 2 runs on device
    assert "solo" in second
    sess.run_to_completion()
    assert len(sess.requests["solo"].generated) == 4


def test_vectorized_descriptor_build_matches_reference(apps):
    """The vectorized descriptor build is element-for-element identical to
    the straightforward per-row reference build (the pre-ISSUE-8 loop),
    on a genuinely mixed prefill+decode schedule."""
    _, ragged = apps
    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    assert sess.add_request("d1", PROMPTS["r1"], max_new_tokens=8)
    sess.step()
    sess.step()
    assert sess.add_request("p1", PROMPTS["r2"], max_new_tokens=8)
    sess.step()
    rows = sess._schedule_mixed({})  # idempotent allocs: blocks already cover
    kinds = {t[1] for t in rows}
    assert kinds == {"prefill", "decode"}, rows  # genuinely mixed
    d = sess._build_mixed_descriptors(rows)

    # --- reference build: per-row python loops over the allocator ---------
    from neuronx_distributed_inference_tpu.modules.autobucketing import (
        get_target_bucket,
    )

    tq = sess.mixed_runner.q_tile
    R = sess.num_slots
    row_start = np.zeros(R, np.int32)
    row_len = np.zeros(R, np.int32)
    ctx_len = np.zeros(R, np.int32)
    cursor = 0
    for req, _kind, n, _p0, _c in rows:
        row_start[req.slot] = cursor
        row_len[req.slot] = n
        cursor += -(-n // tq) * tq
    T = cursor
    ids = np.zeros(T, np.int32)
    positions = np.full(T, -1, np.int32)
    slot_mapping = np.full(T, -1, np.int32)
    max_ctx = 0
    for req, kind, n, p0, _c in rows:
        s = row_start[req.slot]
        if kind == "prefill":
            ids[s : s + n] = req.input_ids[p0 : p0 + n]
        else:
            ids[s] = req.last_token
        positions[s : s + n] = np.arange(p0, p0 + n, dtype=np.int32)
        slot_mapping[s : s + n] = sess.allocator.slot_mapping(
            req.slot, np.arange(p0, p0 + n)
        )
        ctx_len[req.slot] = p0 + n
        max_ctx = max(max_ctx, p0 + n)
    width = get_target_bucket(
        ragged.token_generation_model.buckets, max_ctx
    )

    assert d["T"] == T
    assert d["width"] == width
    np.testing.assert_array_equal(d["row_start"], row_start)
    np.testing.assert_array_equal(d["row_len"], row_len)
    np.testing.assert_array_equal(d["ctx_len"], ctx_len)
    np.testing.assert_array_equal(d["ids"], ids)
    np.testing.assert_array_equal(d["positions"], positions)
    np.testing.assert_array_equal(d["slot_mapping"], slot_mapping)
    # block table: scheduled rows match the allocator's view exactly
    mb = d["block_table"].shape[1]
    for req, *_ in rows:
        np.testing.assert_array_equal(
            d["block_table"][req.slot],
            sess.allocator.block_table(req.slot, mb),
        )
    assert not d["chained"] and (d["chain_src"] == -1).all()
    sess.run_to_completion()


def test_async_slot_reuse_after_finish(apps):
    """Freed slots accept new requests mid-pipeline: the dangling
    speculative pending step for finished rows is discarded, and the new
    request's stream matches an isolated run byte-for-byte."""
    legacy, ragged = apps
    legacy.init_kv_cache()
    s0 = ServingSession(legacy)
    assert s0.add_request("probe", [42, 10, 11], max_new_tokens=4)
    golden = s0.run_to_completion()["probe"]

    ragged.init_kv_cache()
    sess = ServingSession(ragged)
    for i in range(4):
        assert sess.add_request(f"w{i}", [1 + i, 2, 3], max_new_tokens=3)
    sess.run_to_completion()
    # NOTE: budget terminations are host-predictable, so no speculative tail
    # step dangles here (the scheduler skips rows whose pending token
    # predictably finishes them) — _pending may legitimately be None
    assert sess.add_request("probe", [42, 10, 11], max_new_tokens=4)
    assert sess.run_to_completion()["probe"] == golden
