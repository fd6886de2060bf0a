"""Runtime telemetry (ISSUE 4): serving metrics, request-lifecycle tracing,
step spans, and the zero-device-round-trip recording contract.

The load-bearing assertions:
- TTFT/ITL/queue-wait are monotone per request and conserve token counts;
- drop/preemption counters fire on KV pool exhaustion;
- the bucket-dispatch census only ever names buckets the app compiled;
- the speculation acceptance histogram sums EXACTLY to committed decode
  tokens;
- a fetch-counting shim proves telemetry-on performs the identical number
  of device fetches as telemetry-off, and the retrace guard still observes
  zero steady-state recompiles (the acceptance criterion);
- the retrace-guard bridge surfaces traces/sealed-retraces as counters.
"""

import functools
import importlib.util
import json
import pathlib
import time

import jax
import numpy as np
import pytest

from tests.conftest import make_random_hf_state_dict, make_tiny_config

from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import (
    ServingSession,
    SpeculativeServingSession,
)
from neuronx_distributed_inference_tpu.telemetry import (
    MetricsRegistry,
    SloMonitor,
    TelemetrySession,
    load_events,
)
from neuronx_distributed_inference_tpu.telemetry import tracing as tel_tracing

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# metrics registry + exposition
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    r = MetricsRegistry()
    c = r.counter("nxdi_x_total", "things")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotone

    fam = r.counter("nxdi_labelled_total", "by reason", labels=("reason",))
    fam.child(("a",)).inc()
    fam.child(("a",)).inc()
    fam.child(("b",)).inc()
    assert fam.child(("a",)).value == 2

    g = r.gauge("nxdi_g", "level")
    g.set(7.5)
    assert g.value == 7.5

    h = r.histogram("nxdi_h_ms", "lat", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 5000):
        h.observe(v)
    assert h.count == 4 and h.sum == 5055.5
    assert h.cumulative() == [1, 2, 3, 4]  # le=1, le=10, le=100, +Inf

    # idempotent re-registration returns the SAME instrument; a kind
    # mismatch is a loud programming error
    assert r.counter("nxdi_x_total") is c
    with pytest.raises(ValueError):
        r.gauge("nxdi_x_total")

    text = r.prometheus_text()
    assert "# TYPE nxdi_x_total counter" in text
    assert "nxdi_x_total 3" in text
    assert 'nxdi_labelled_total{reason="a"} 2' in text
    assert 'nxdi_h_ms_bucket{le="+Inf"} 4' in text
    assert "nxdi_h_ms_count 4" in text

    snap = r.snapshot()
    assert snap["nxdi_x_total"]["samples"][0]["value"] == 3
    assert snap["nxdi_h_ms"]["samples"][0]["buckets"]["+Inf"] == 4
    json.dumps(snap)  # JSON-able by construction


def test_metrics_report_renders_snapshot():
    """scripts/metrics_report.render is the reference consumer of the
    snapshot format — it must digest a real registry dump."""
    path = pathlib.Path(__file__).parents[1] / "scripts" / "metrics_report.py"
    spec = importlib.util.spec_from_file_location("metrics_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    r = MetricsRegistry()
    r.counter("nxdi_tokens_generated_total", "tokens").inc(42)
    r.gauge("nxdi_kv_free_bytes", "free").set(1024)
    h = r.histogram("nxdi_ttft_ms", "ttft", buckets=(10, 100))
    h.observe(5)
    h.observe(50)
    out = mod.render(r.snapshot())
    assert "nxdi_tokens_generated_total" in out and "42" in out
    assert "nxdi_kv_free_bytes" in out
    assert "n=2" in out and "p50<=" in out


def test_event_log_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with TelemetrySession(jsonl_path=path) as s:
        s.request_submitted("r1")
        s.request_admitted("r1")
        with s.span("unit.span"):
            pass
        s.event("custom", detail=3)
    events = load_events(path)
    kinds = [e["type"] for e in events]
    assert kinds == ["request_submitted", "request_admitted", "span", "custom"]
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)  # the offline-replay ordering contract
    assert events[2]["name"] == "unit.span" and events[2]["dur_ms"] >= 0


# ---------------------------------------------------------------------------
# serving lifecycle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cb_app():
    cfg = make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=4, ctx_batch_size=1)
    )
    a = TpuModelForCausalLM(None, cfg)
    a.load(state_dict=make_random_hf_state_dict(cfg))
    return a


def test_serving_ttft_itl_monotone_and_conserving(cb_app):
    tel = TelemetrySession()
    sess = ServingSession(cb_app, telemetry=tel)
    assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=5)
    sess.step()
    assert sess.add_request("r3", [7, 7, 7], max_new_tokens=4)
    out = sess.run_to_completion()
    tel.close()

    total = sum(len(v) for v in out.values())
    assert total == 6 + 5 + 4

    # every request completed with a monotone lifecycle
    assert not tel.traces and len(tel.completed) == 3
    for tr in tel.completed:
        assert tr.finish_reason == "length"
        assert tr.t_submit <= tr.t_admit <= tr.t_first_dispatch
        assert tr.t_first_dispatch <= tr.t_first_token <= tr.t_last_token
        assert tr.t_last_token <= tr.t_finish
        assert tr.ttft_s >= 0 and tr.queue_wait_s >= 0
        assert all(d >= 0 for d in tr.itl_s)

    snap = tel.registry.snapshot()
    assert snap["nxdi_requests_submitted_total"]["samples"][0]["value"] == 3
    assert snap["nxdi_requests_admitted_total"]["samples"][0]["value"] == 3
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert fin == {"length": 3}
    # conservation: TTFT once per request, ITL for every later token
    assert snap["nxdi_ttft_ms"]["samples"][0]["count"] == 3
    assert snap["nxdi_itl_ms"]["samples"][0]["count"] == total - 3
    assert snap["nxdi_tokens_generated_total"]["samples"][0]["value"] == total
    steps = {
        s["labels"]["kind"]: s["value"]
        for s in snap["nxdi_steps_total"]["samples"]
    }
    assert steps.get("prefill", 0) >= 3 and steps.get("decode", 0) >= 1


def test_bucket_census_matches_compiled_buckets(cb_app):
    tel = TelemetrySession()
    sess = ServingSession(cb_app, telemetry=tel)
    sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=8)
    sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=8)
    sess.run_to_completion()
    tel.close()
    census = tel.registry.snapshot()["nxdi_bucket_dispatch_total"]["samples"]
    assert census, "no bucket dispatches recorded"
    compiled = {
        cb_app.context_encoding_model.tag: set(cb_app.context_encoding_model.buckets),
        cb_app.token_generation_model.tag: set(cb_app.token_generation_model.buckets),
    }
    for s in census:
        model = s["labels"]["model"]
        bucket = int(s["labels"]["bucket"])
        assert bucket in compiled[model], (
            f"census names bucket {bucket} for {model}, which was never "
            f"compiled ({sorted(compiled[model])})"
        )
        assert s["value"] > 0
    # both sub-models actually appear
    assert {s["labels"]["model"] for s in census} == set(compiled)


def test_slot_exhaustion_drops_are_counted(cb_app):
    tel = TelemetrySession()
    sess = ServingSession(cb_app, telemetry=tel)
    for i in range(4):
        assert sess.add_request(f"a{i}", [1 + i, 2, 3], max_new_tokens=2)
    assert not sess.add_request("overflow", [9], max_new_tokens=2)
    sess.run_to_completion()
    tel.close()
    snap = tel.registry.snapshot()
    drops = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_dropped_total"]["samples"]
    }
    assert drops == {"no_slot": 1}
    dropped = [t for t in tel.completed if t.finish_reason == "dropped"]
    assert len(dropped) == 1 and dropped[0].req_id == "overflow"


def test_pool_exhaustion_preemption_and_admission_drop():
    """Paged pool of 3 usable blocks, block_size=16: two 16-token prompts
    take one block each; the first decode step needs a second block per row
    — one row gets the last free block, the other is preempted (vLLM-style)
    and, since ISSUE 7, RE-ADMITTED once the first request frees its blocks:
    preemption is an eviction event, not a terminal state, and the resumed
    request still delivers its full budget. A third admission finds no
    blocks and is dropped as kv_blocks."""
    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=3,
            seq_len=64,
        )
    )
    app = TpuModelForCausalLM(None, cfg).load(
        state_dict=make_random_hf_state_dict(cfg)
    )
    tel = TelemetrySession()
    sess = ServingSession(app, telemetry=tel)
    pool_bytes = sess.kv_pool_bytes
    assert pool_bytes > 0 and sess.kv_free_bytes == pool_bytes

    p = list(range(1, 17))  # exactly one block of prompt
    assert sess.add_request("r1", p, max_new_tokens=8)
    assert sess.add_request("r2", [x + 1 for x in p], max_new_tokens=8)
    while sess.active or sess._readmit:
        sess.step()
    # one of the two was evicted when the pool ran dry mid-decode ...
    preempted = [r for r in sess.requests.values() if r.preemptions > 0]
    assert len(preempted) == 1
    # ... and re-admitted (aging): BOTH requests complete their full budget
    assert all(len(r.generated) == 8 for r in sess.requests.values())
    assert all(r.status == "finished" for r in sess.requests.values())

    # admission-time exhaustion: a 2-block prompt admits (2 of 3 blocks),
    # a second 2-block prompt cannot get its blocks -> dropped as kv_blocks
    # (a free SLOT exists; the POOL is what ran out)
    sess2 = ServingSession(app, telemetry=tel)
    p32 = list(range(1, 33))
    assert sess2.add_request("r3", p32, max_new_tokens=2)
    assert not sess2.add_request("r4", [x + 2 for x in p32], max_new_tokens=2)
    tel.close()

    snap = tel.registry.snapshot()
    # the preemption counter records the EVICTION; the finished census shows
    # no terminal "preempted" (the request resumed and finished by length)
    assert snap["nxdi_requests_preempted_total"]["samples"][0]["value"] == 1
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert "preempted" not in fin
    assert fin["length"] == 2
    drops = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_dropped_total"]["samples"]
    }
    assert drops == {"kv_blocks": 1}
    # the free-bytes gauge tracked the pool under pressure
    assert snap["nxdi_kv_pool_bytes"]["samples"][0]["value"] == pool_bytes
    assert snap["nxdi_kv_free_bytes"]["samples"][0]["value"] < pool_bytes


def test_chunked_prefill_queue_wait_and_chunk_count():
    """Chunked prefill: queue wait is observed at the FIRST prefill chunk
    (not admission), and the per-request chunk histogram records the chunk
    ladder the prompt actually consumed."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    cfg = make_tiny_config(
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16,
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=16
            ),
            seq_len=64,
        )
    )
    app = TpuModelForCausalLM(None, cfg).load(
        state_dict=make_random_hf_state_dict(cfg)
    )
    tel = TelemetrySession()
    sess = ServingSession(app, telemetry=tel)
    prompt = list(range(1, 41))  # 40 tokens -> 3 chunks of 16
    assert sess.add_request("r1", prompt, max_new_tokens=4)
    sess.run_to_completion()
    tel.close()
    (tr,) = tel.completed
    assert tr.prefill_chunks == 3
    assert tr.queue_wait_s >= 0 and tr.ttft_s >= tr.queue_wait_s
    h = tel.registry.snapshot()["nxdi_prefill_chunks_per_request"]["samples"][0]
    assert h["count"] == 1 and h["sum"] == 3
    prefilled = tel.registry.snapshot()["nxdi_tokens_prefilled_total"]
    assert prefilled["samples"][0]["value"] == 40


@pytest.mark.parametrize("head_dim", [128, 64])
def test_chunk_kv_block_counter_reads_what_the_host_knows(head_dim):
    """``nxdi_chunk_kv_blocks_total``: per chunk pass the pool blocks the
    prefilling rows' causal contexts hold with the chunk in (``live``) and
    the block-table entries the paged prefill kernel attends for them
    (``walked``: whole groups of ``blocks_per_group`` blocks up to a row's
    frontier at head_dim 128; rows x table width where the kernel keeps a
    block a grid step). Rows of KNOWN contexts: prompts of 600 and 6 tokens
    in chunks of 16 and blocks of 8, so the long row's passes end at
    16, 32, ..., 592, 600 tokens. Nothing is counted with telemetry off."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
        blocks_per_group,
    )

    cfg = make_tiny_config(
        hidden_size=2 * head_dim, num_attention_heads=2, num_key_value_heads=1,
        tpu=dict(
            is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
            is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=120,
            is_chunked_prefill=True, seq_len=1024, token_generation_buckets=[1024],
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=16
            ),
        ),
    )
    app = TpuModelForCausalLM(None, cfg).load(
        state_dict=make_random_hf_state_dict(cfg)
    )
    long_prompt = [(i * 37) % 100 + 2 for i in range(600)]

    def drive(tel):
        app.init_kv_cache()
        sess = ServingSession(app, telemetry=tel)
        assert sess.add_request("long", long_prompt, max_new_tokens=2)
        assert sess.add_request("short", [5, 17, 92, 41, 33, 88], max_new_tokens=2)
        while sess.active:
            sess.step()

    off = TelemetrySession(enabled=False)
    drive(off)
    assert off.registry.snapshot() == {}

    tel = TelemetrySession(enabled=True)
    drive(tel)
    bs, MB = 8, 1024 // 8
    contexts = [16 * k for k in range(1, 38)] + [600, 6]
    live = [-(-n // bs) for n in contexts]
    P = blocks_per_group(1, bs, head_dim, cfg.tpu_config.kv_dtype, MB)
    assert P == (64 if head_dim == 128 else 1)  # 512 tokens a group
    walked = [-(-n // P) * P if head_dim == 128 else MB for n in live]
    snap = tel.registry.snapshot()["nxdi_chunk_kv_blocks_total"]["samples"]
    got = {x["labels"]["kind"]: x["value"] for x in snap}
    assert got == {"live": sum(live), "walked": sum(walked)}
    assert got["live"] == 2 * sum(range(1, 38)) + 75 + 1
    # one group up to 512 tokens, two past it; the table's 128 a row-pass off the lanes
    assert got["walked"] == (64 * 32 + 128 * 6 + 64 if head_dim == 128 else 128 * 39)
    tel.close()


def test_chunk_kv_write_block_counter_counts_whole_and_merged_blocks():
    """``nxdi_chunk_kv_write_blocks_total``: per chunk pass whose paged KV
    write moves whole blocks (chunk widths over ``TKG_MAX_Q_LEN``, head_dim
    on the 128 lanes), the blocks a row's tokens cover whole and the edge
    blocks the write reads and merges, from each row's ``(start, n)``. A
    hand-made pass at blocks of 32: positions 0-127 are 4 whole blocks;
    40-139 are 2 whole (64-127) and 2 merged (40-63, 128-139); ``n`` = 0 is
    nothing. Then a served prompt of 100 tokens in chunks of 64: passes
    (0, 64) and (64, 36) are 3 whole blocks and 1 merged; at head_dim 64 the
    write goes token by token and nothing is counted."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.modules.block_kvcache import chunk_write_blocks

    count = functools.partial(chunk_write_blocks, q_len=128, block_size=32, head_dim=128)
    assert count([(0, 128)]) == (4, 0)
    assert count([(40, 100)]) == (2, 2)
    assert count([(64, 0)]) == (0, 0)
    assert count([(0, 128)], head_dim=64) == count([(0, 16)], q_len=16) == (0, 0)
    tel = TelemetrySession(enabled=True)
    tel.kv_write_blocks(*count([(0, 128), (40, 100), (64, 0)]))
    snap = tel.registry.snapshot()["nxdi_chunk_kv_write_blocks_total"]["samples"]
    assert {x["labels"]["kind"]: x["value"] for x in snap} == {"whole": 6, "merged": 2}
    tel.close()

    for head_dim, want in ((128, {"whole": 3, "merged": 1}), (64, {"whole": 0, "merged": 0})):
        cfg = make_tiny_config(
            hidden_size=2 * head_dim, num_attention_heads=2, num_key_value_heads=1,
            tpu=dict(
                is_continuous_batching=True, batch_size=2, ctx_batch_size=1,
                is_block_kv_layout=True, pa_block_size=32, pa_num_blocks=8,
                is_chunked_prefill=True, seq_len=128, token_generation_buckets=[128],
                chunked_prefill_config=ChunkedPrefillConfig(
                    max_num_seqs=2, kernel_q_tile_size=64
                ),
            ),
        )
        app = TpuModelForCausalLM(None, cfg).load(
            state_dict=make_random_hf_state_dict(cfg)
        )
        tel = TelemetrySession(enabled=True)
        sess = ServingSession(app, telemetry=tel)
        assert sess.add_request("r", [(i * 37) % 100 + 2 for i in range(100)], max_new_tokens=2)
        while sess.active:
            sess.step()
        snap = tel.registry.snapshot()["nxdi_chunk_kv_write_blocks_total"]
        got = {x["labels"]["kind"]: x["value"] for x in snap["samples"]}
        assert got == want
        tel.close()


def test_double_finish_counts_once(cb_app):
    """_finish and _preempt can both legitimately run twice for one request
    (an already-dispatched row's token is consumed a step later and may hit
    a termination condition again) — counters must record the FIRST
    transition only."""
    tel = TelemetrySession()
    sess = ServingSession(cb_app, telemetry=tel)
    assert sess.add_request("r", [1, 2, 3], max_new_tokens=4)
    req = sess.requests["r"]
    sess._preempt(req)
    sess._preempt(req)  # idempotent: one eviction event
    sess._readmit.remove(req)
    sess._finish(req, "preempted")
    sess._finish(req, "preempted")
    tel.close()
    snap = tel.registry.snapshot()
    assert snap["nxdi_requests_preempted_total"]["samples"][0]["value"] == 1
    fin = {
        s["labels"]["reason"]: s["value"]
        for s in snap["nxdi_requests_finished_total"]["samples"]
    }
    assert fin == {"preempted": 1}
    assert req.status == "failed" and req.fail_reason == "preempted"


# ---------------------------------------------------------------------------
# speculation acceptance
# ---------------------------------------------------------------------------


def test_spec_acceptance_histogram_sums_to_committed_tokens():
    """Speculative session: the acceptance histogram's SUM equals the decode
    tokens speculation committed (total generated minus the per-request
    first token, which prefill produced). The plain session records no
    acceptance observations — same registry contract, empty histogram."""
    mk = lambda: make_tiny_config(
        tpu=dict(is_continuous_batching=True, batch_size=2, ctx_batch_size=1)
    )
    sd = make_random_hf_state_dict(mk(), seed=0)

    tel_plain = TelemetrySession()
    plain = TpuModelForCausalLM(None, mk()).load(state_dict=sd)
    s_plain = ServingSession(plain, telemetry=tel_plain)
    assert s_plain.add_request("r1", [5, 17, 92, 41], max_new_tokens=7)
    assert s_plain.add_request("r2", [64, 3, 27, 9], max_new_tokens=6)
    plain_out = s_plain.run_to_completion()
    tel_plain.close()
    snap = tel_plain.registry.snapshot()
    assert snap["nxdi_spec_accept_len"]["samples"][0]["count"] == 0
    assert snap["nxdi_tokens_generated_total"]["samples"][0]["value"] == sum(
        len(v) for v in plain_out.values()
    )

    target = TpuModelForCausalLM(None, mk()).load(state_dict=sd)
    draft = TpuModelForCausalLM(None, mk()).load(state_dict=sd)  # full accept
    tel = TelemetrySession()
    sess = SpeculativeServingSession(target, draft, speculation_length=4,
                                     telemetry=tel)
    assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=7)
    assert sess.add_request("r2", [64, 3, 27, 9], max_new_tokens=6)
    out = sess.run_to_completion()
    tel.close()
    assert out == plain_out  # greedy verification is byte-equal

    total = sum(len(v) for v in out.values())
    h = tel.registry.snapshot()["nxdi_spec_accept_len"]["samples"][0]
    assert h["sum"] == total - 2, (
        "acceptance histogram must sum to committed decode tokens "
        f"(got {h['sum']}, committed {total - 2})"
    )
    assert h["count"] >= 2  # at least one round per request
    assert (
        tel.registry.snapshot()["nxdi_tokens_generated_total"]["samples"][0]["value"]
        == total
    )


def test_fused_spec_acceptance_telemetry():
    """The fused-speculation host loop records acceptance into the default
    session: with B=1 and no EOS the committed sum is exactly
    max_new_tokens - 1 (the CTE token is not a speculation product)."""
    from neuronx_distributed_inference_tpu.config import FusedSpecConfig
    from neuronx_distributed_inference_tpu.runtime.fused_spec import (
        TpuFusedSpecModelForCausalLM,
    )

    spec_cfg = make_tiny_config(tpu=dict(batch_size=1))
    spec_cfg.tpu_config.speculation_length = 4
    spec_cfg.tpu_config.enable_fused_speculation = True
    spec_cfg.fused_spec_config = FusedSpecConfig(
        draft_model_name="tiny-draft", draft_config=make_tiny_config()
    )
    app = TpuFusedSpecModelForCausalLM(None, spec_cfg)
    app.load(
        target_state_dict=make_random_hf_state_dict(spec_cfg, seed=0),
        draft_state_dict=make_random_hf_state_dict(spec_cfg, seed=7),
    )

    prev = tel_tracing.default_session()
    tel = TelemetrySession()
    tel_tracing.set_default_session(tel)
    try:
        prompt = np.array([[5, 17, 92, 41, 33, 88, 2, 11]])
        out = app.generate(prompt, np.ones_like(prompt), max_new_tokens=9)
    finally:
        tel_tracing.set_default_session(prev)
        tel.close()
    assert out.num_generated == 9
    snap = tel.registry.snapshot()
    h = snap["nxdi_spec_accept_len"]["samples"][0]
    assert h["sum"] == 9 - 1
    assert snap["nxdi_tokens_generated_total"]["samples"][0]["value"] == 9
    census = {s["labels"]["model"] for s in
              snap["nxdi_bucket_dispatch_total"]["samples"]}
    assert census == {"fused_spec_cte", "fused_spec_tkg"}


# ---------------------------------------------------------------------------
# the acceptance criterion: fetch parity + zero steady-state recompiles
# ---------------------------------------------------------------------------


def _run_workload(app, telemetry):
    app.init_kv_cache()
    sess = ServingSession(app, telemetry=telemetry)
    assert sess.add_request("r1", [5, 17, 92, 41], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r2", [64, 3, 27, 9, 14, 33], max_new_tokens=5)
    return sess.run_to_completion()


def test_fetch_parity_and_zero_recompiles_with_telemetry(cb_app, monkeypatch):
    """The tentpole's hard constraint: telemetry recording piggybacks on the
    device fetches the runtime already performs. A fetch-counting shim
    (np.asarray / jax.device_get over jax.Array values) must count the SAME
    number of fetches with telemetry enabled and disabled, and the retrace
    guard must still observe zero steady-state recompiles."""
    from neuronx_distributed_inference_tpu.analysis import RetraceGuard

    golden = _run_workload(cb_app, TelemetrySession(enabled=False))  # compile

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

    monkeypatch.setattr(np, "asarray", counting_asarray)
    monkeypatch.setattr(jax, "device_get", counting_device_get)

    counter["n"] = 0
    out_off = _run_workload(cb_app, TelemetrySession(enabled=False))
    fetches_off = counter["n"]

    counter["n"] = 0
    with TelemetrySession() as tel:
        # ISSUE 19: span recording + live SLO monitor active — both are
        # host-side consumers of the same records and must stay fetch-neutral
        tel.attach_slo_monitor(SloMonitor())
        with RetraceGuard() as guard:
            out_on = _run_workload(cb_app, tel)
        trace_doc = tel.export_chrome_trace()
    fetches_on = counter["n"]

    assert out_on == out_off == golden
    assert fetches_off > 0
    assert fetches_on == fetches_off, (
        f"telemetry changed the per-run device fetch count: "
        f"{fetches_off} -> {fetches_on}"
    )
    assert guard.traces == []  # zero steady-state recompiles
    # and it actually recorded something while staying fetch-neutral
    snap = tel.registry.snapshot()
    assert snap["nxdi_tokens_generated_total"]["samples"][0]["value"] == sum(
        len(v) for v in out_on.values()
    )
    # the span timeline landed too, without costing a single extra fetch
    assert any(
        ev["ph"] == "X" for ev in trace_doc["traceEvents"]
    )


def test_disabled_session_records_nothing(cb_app):
    tel = TelemetrySession(enabled=False)
    _run_workload(cb_app, tel)
    assert tel.registry.snapshot() == {}
    assert not tel.traces and not tel.completed and not tel.events


# ---------------------------------------------------------------------------
# retrace-guard bridge
# ---------------------------------------------------------------------------


def test_retrace_counter_bridge():
    """Every jit trace increments nxdi_jit_traces_total; a forbidden
    post-seal retrace increments nxdi_sealed_retrace_total BEFORE the
    RetraceError raises — the counter is the operable signal, the exception
    stays the hard stop."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.retrace_guard import (
        RetraceError,
        trace_marker,
    )

    class Owner:
        _sealed = False

    owner = Owner()
    fn = jax.jit(trace_marker("toy_tel", lambda x: x * 2, owner=owner))
    with TelemetrySession() as tel:
        fn(jnp.ones((2,)))  # compile no. 1
        fn(jnp.ones((2,)))  # cache hit: no trace
        fn(jnp.ones((3,)))  # compile no. 2
        owner._sealed = True
        with pytest.raises(RetraceError):
            fn(jnp.ones((4,)))  # forbidden steady-state recompile
        snap = tel.registry.snapshot()
    traces = {
        s["labels"]["tag"]: s["value"]
        for s in snap["nxdi_jit_traces_total"]["samples"]
    }
    sealed = {
        s["labels"]["tag"]: s["value"]
        for s in snap["nxdi_sealed_retrace_total"]["samples"]
    }
    assert traces["toy_tel"] == 3
    assert sealed["toy_tel"] == 1
    assert any(e["type"] == "sealed_retrace" for e in tel.events)


def test_span_annotations_nest_without_device_sync(cb_app):
    """Spans bound host dispatch; they must compose with generation and
    leave ordered span events behind."""
    with TelemetrySession() as tel:
        prev = tel_tracing.default_session()
        tel_tracing.set_default_session(tel)
        try:
            prompt = np.array([[5, 17, 92, 41]])
            cb_app.generate(prompt, np.ones_like(prompt), max_new_tokens=4)
        finally:
            tel_tracing.set_default_session(prev)
    spans = [e for e in tel.events if e["type"] == "span"]
    assert any(e["name"] == "app.cte" for e in spans)
    assert any(e["name"] == "app.decode_chunk" for e in spans)
    assert all(e["dur_ms"] >= 0 for e in spans)


# ---------------------------------------------------------------------------
# serving host-gap telemetry (ISSUE 8): per-step host/fetch split + gauge
# ---------------------------------------------------------------------------


def test_step_timing_unit():
    """step_timing feeds the host/fetch histograms and the cumulative
    nxdi_serving_host_frac gauge; the disabled session is a no-op."""
    tel = TelemetrySession()
    tel.step_timing(3.0, 1.0)
    tel.step_timing(1.0, 1.0)
    tel.close()
    snap = tel.registry.snapshot()
    host = snap["nxdi_step_host_ms"]["samples"][0]
    wait = snap["nxdi_step_fetch_wait_ms"]["samples"][0]
    assert host["count"] == 2 and host["sum"] == 4.0
    assert wait["count"] == 2 and wait["sum"] == 2.0
    frac = snap["nxdi_serving_host_frac"]["samples"][0]["value"]
    assert frac == pytest.approx(4.0 / 6.0)
    off = TelemetrySession(enabled=False)
    off.step_timing(1.0, 1.0)  # must not raise, must record nothing


def test_serving_host_frac_recorded_on_ragged_drain():
    """A pipelined ragged drain records one step-timing observation per
    ragged step and a host-frac gauge in (0, 1]; with telemetry DISABLED
    the session records nothing (and still drains identically)."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    cfg = make_tiny_config(tpu=dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=24,
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=2, kernel_q_tile_size=16
        ),
        serving_ragged=True, seq_len=64,
    ))
    app = TpuModelForCausalLM(None, cfg).load(
        state_dict=make_random_hf_state_dict(cfg)
    )

    def drain(tel):
        app.init_kv_cache()
        sess = ServingSession(app, telemetry=tel)
        assert sess.ragged_async
        assert sess.add_request("a", [5, 17, 92, 41], max_new_tokens=6)
        assert sess.add_request("b", list(range(30, 52)), max_new_tokens=6)
        return sess.run_to_completion()

    golden = drain(TelemetrySession(enabled=False))
    with TelemetrySession() as tel:
        out = drain(tel)
    assert out == golden
    snap = tel.registry.snapshot()
    steps = {
        s["labels"]["kind"]: s["value"]
        for s in snap["nxdi_steps_total"]["samples"]
    }
    host = snap["nxdi_step_host_ms"]["samples"][0]
    wait = snap["nxdi_step_fetch_wait_ms"]["samples"][0]
    # one timing observation per _ragged_step entered (dispatching or not —
    # a consume-only tail step still times its host work)
    assert host["count"] >= steps["mixed"]
    assert wait["count"] == host["count"]
    frac = snap["nxdi_serving_host_frac"]["samples"][0]["value"]
    assert 0.0 < frac <= 1.0


def test_metrics_registry_thread_safe_exact_counts():
    """ISSUE 13 satellite: concurrent labels() calls cannot mint duplicate
    children (the check-then-act race), and inc/observe from N threads lose
    nothing — counts and histogram sum/count conservation stay EXACT (a bare
    `+=` would lose updates under interleaving)."""
    import threading

    from neuronx_distributed_inference_tpu.telemetry.metrics import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    ctr_fam = reg.counter("t_ctr", "x", labels=("who",))
    hist_fam = reg.histogram("t_hist", "x", buckets=(1.0, 10.0),
                             labels=("who",))
    gauge = reg.gauge("t_gauge", "x")

    N_THREADS, N_OPS = 8, 2000
    barrier = threading.Barrier(N_THREADS)
    minted = []
    minted_lock = threading.Lock()

    def worker(i):
        barrier.wait()  # maximize contention on the first-mint race
        # every thread asks for the SAME new labels concurrently
        c = ctr_fam.child(("shared",))
        h = hist_fam.child(("shared",))
        with minted_lock:
            minted.append((id(c), id(h)))
        for k in range(N_OPS):
            c.inc()
            h.observe(float(k % 20))
            gauge.set(i)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(N_THREADS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # one child object per label tuple, no orphans
    assert len({m[0] for m in minted}) == 1
    assert len({m[1] for m in minted}) == 1
    assert set(ctr_fam.children) == {("shared",)}
    c = ctr_fam.child(("shared",))
    h = hist_fam.child(("shared",))
    assert c.value == N_THREADS * N_OPS  # exact: no lost increments
    assert h.count == N_THREADS * N_OPS
    # conservation: sum equals the deterministic per-thread contribution
    per_thread = sum(float(k % 20) for k in range(N_OPS))
    assert h.sum == pytest.approx(N_THREADS * per_thread)
    # bucket totals equal count (cumulative +Inf bucket catches all)
    assert h.cumulative()[-1] == h.count


def test_telemetry_session_thread_safe_token_accounting():
    """Concurrent per-replica record paths into ONE TelemetrySession (the
    router_threading sharing shape): token totals stay exact and the
    trace table stays consistent."""
    import threading

    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    with TelemetrySession() as tel:
        N_THREADS, N_TOK = 6, 500
        for i in range(N_THREADS):
            tel.request_submitted(f"rq{i}")
            tel.request_admitted(f"rq{i}")
        barrier = threading.Barrier(N_THREADS)

        def worker(i):
            barrier.wait()
            tel.request_first_token(f"rq{i}")
            for _ in range(N_TOK - 1):
                tel.request_tokens(f"rq{i}", 1)
            tel.step_timing(1.0, 1.0)
            tel.request_finished(f"rq{i}", "length")

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(N_THREADS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = tel.registry.snapshot()
        total = snap["nxdi_tokens_generated_total"]["samples"][0]["value"]
        assert total == N_THREADS * N_TOK  # exact under contention
        fin = sum(
            s["value"]
            for s in snap["nxdi_requests_finished_total"]["samples"]
        )
        assert fin == N_THREADS
        assert len(tel.completed) == N_THREADS
        assert not tel.traces  # every trace moved to completed exactly once
        # host-frac sums: N threads x (1.0 + 1.0) ms, no lost updates
        assert tel._host_ms_sum == pytest.approx(N_THREADS * 1.0)
        assert tel._fetch_wait_ms_sum == pytest.approx(N_THREADS * 1.0)


def test_metrics_exposition_safe_during_concurrent_minting():
    """Review-found race: snapshot()/prometheus_text() iterate a family's
    children while replica threads mint NEW label children under the
    family lock — exposition must copy under that same lock or a scrape
    dies mid-iteration with 'dictionary changed size'."""
    import threading

    from neuronx_distributed_inference_tpu.telemetry.metrics import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    fam = reg.counter("t_mint", "x", labels=("who",))
    stop = threading.Event()
    errors = []

    def minter():
        i = 0
        while not stop.is_set():
            fam.child((f"label{i}",)).inc()
            i += 1

    def scraper(render):
        try:
            while not stop.is_set():
                render()
        except RuntimeError as e:  # "dictionary changed size ..."
            errors.append(e)

    threads = [threading.Thread(target=minter)] + [
        threading.Thread(target=scraper, args=(fn,))
        for fn in (reg.snapshot, reg.prometheus_text)
    ]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join()
    assert errors == [], errors
    # everything minted is visible to a final scrape
    snap = reg.snapshot()
    assert len(snap["t_mint"]["samples"]) == len(fam.children) > 0


# ---------------------------------------------------------------------------
# bounded buffers, corrupt-tail tolerance, export-during-drain (ISSUE 19)
# ---------------------------------------------------------------------------


def test_event_buffer_bounded_with_dropped_counter(monkeypatch):
    """The in-memory event ring evicts oldest past TELEMETRY_EVENT_MAX and
    counts every eviction — a long-lived serving process cannot grow event
    memory linearly with traffic."""
    monkeypatch.setenv(tel_tracing.TELEMETRY_EVENT_MAX_ENV, "8")
    with TelemetrySession() as s:
        for i in range(20):
            s.event("tick", i=i)
        assert len(s.events) == 8
        assert [e["i"] for e in s.events] == list(range(12, 20))
        sample = next(
            x
            for x in s.registry.snapshot()[
                "nxdi_telemetry_dropped_total"]["samples"]
            if x["labels"] == {"kind": "events"}
        )
        assert sample["value"] == 12
        # the span store is bounded by the same knob
        assert s.spans.max_spans == 8


def test_load_events_skips_corrupt_trailing_line(tmp_path):
    """A crash mid-flush leaves a truncated last line; offline replay keeps
    every intact record and warns instead of raising."""
    path = str(tmp_path / "events.jsonl")
    with TelemetrySession(jsonl_path=path) as s:
        s.event("a")
        s.event("b")
    with open(path, "a") as f:
        f.write('{"type": "c", "ts":')  # truncated mid-write
    with pytest.warns(UserWarning, match="skipping corrupt JSONL line"):
        events = load_events(path)
    assert [e["type"] for e in events] == ["a", "b"]


def test_export_chrome_trace_safe_during_active_drain():
    """The ISSUE-19 bugfix pin: export snapshots span/trace state under the
    session lock, so exporting WHILE worker threads record produces a
    consistent, serializable trace every time (no dict-changed-size, no
    half-written span)."""
    import threading

    with TelemetrySession() as s:
        stop = threading.Event()
        errors = []

        def hammer(k):
            i = 0
            try:
                while not stop.is_set():
                    rid = f"t{k}-{i:04d}"
                    s.request_submitted(rid)
                    s.request_first_token(rid)
                    s.request_tokens(rid, 2)
                    s.request_finished(rid, "eos")
                    i += 1
            except Exception as e:  # pragma: no cover - the failure signal
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(k,)) for k in range(4)
        ]
        for t in threads:
            t.start()
        try:
            docs = [s.export_chrome_trace() for _ in range(20)]
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert errors == [], errors
        for doc in docs:
            json.dumps(doc)  # every snapshot serializes cleanly
        final = s.export_chrome_trace()
    assert any(ev["ph"] == "X" for ev in final["traceEvents"])


# ---------------------------------------------------------------------------
# metric-catalog drift: docs/OBSERVABILITY.md vs the registered families
# ---------------------------------------------------------------------------


def test_catalog_drift_detects_both_directions():
    """The checker FIRES both ways on a fixture: a registered family the
    doc never mentions, and a documented name no session registers."""
    from neuronx_distributed_inference_tpu.telemetry.metrics import (
        catalog_drift,
    )

    doc = """
    | `nxdi_requests_total` | counter | per-status census |
    | `nxdi_step_ms` | histogram | `nxdi_step_ms_bucket` rides along |
    | `nxdi_ghost_metric_total` | counter | removed in a refactor |
    """
    families = ["nxdi_requests_total", "nxdi_step_ms", "nxdi_secret_gauge"]
    undocumented, unregistered = catalog_drift(doc, families)
    assert undocumented == ["nxdi_secret_gauge"]
    assert unregistered == ["nxdi_ghost_metric_total"]
    # exposition suffixes of a documented histogram are NOT drift
    assert "nxdi_step_ms_bucket" not in unregistered


def test_catalog_drift_clean_fixture():
    from neuronx_distributed_inference_tpu.telemetry.metrics import (
        catalog_drift,
    )

    doc = "`nxdi_a_total` and `nxdi_b_ms` (with `nxdi_b_ms_sum`)."
    assert catalog_drift(doc, ["nxdi_a_total", "nxdi_b_ms"]) == ([], [])


def test_observability_doc_matches_registered_families():
    """The REAL contract: every family a fresh TelemetrySession registers
    (SLO monitor bound, eager registration) appears in
    docs/OBSERVABILITY.md, and every `nxdi_*` name the doc mentions exists.
    A metric added without its doc row — or a doc row that outlived its
    metric — fails here, in both directions."""
    from neuronx_distributed_inference_tpu.telemetry.metrics import (
        catalog_drift,
    )

    doc = (
        pathlib.Path(__file__).resolve().parents[1]
        / "docs" / "OBSERVABILITY.md"
    ).read_text()
    with TelemetrySession() as tel:
        SloMonitor().bind(tel.registry)
        families = tel.registry.family_names()
    assert len(families) >= 50
    undocumented, unregistered = catalog_drift(doc, families)
    assert undocumented == [], (
        "registered families missing from docs/OBSERVABILITY.md: "
        f"{undocumented}"
    )
    assert unregistered == [], (
        "docs/OBSERVABILITY.md names families nothing registers: "
        f"{unregistered}"
    )


# ---------------------------------------------------------------------------
# ISSUE 23: start/stop in a running process, spans and counters of the split
# serving step, named step programs and kernels
# ---------------------------------------------------------------------------


def _paged_config(**model):
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    tpu = dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=24,
        is_chunked_prefill=True, seq_len=64,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=4, kernel_q_tile_size=16),
    )
    tpu.update(model.pop("tpu", {}))
    return make_tiny_config(tpu=tpu, **model)


@pytest.fixture(scope="module")
def paged_app():
    """The default split serving path: paged cache, chunked prefill, 1-ahead
    decode, four slots."""
    cfg = _paged_config()
    return TpuModelForCausalLM(None, cfg).load(state_dict=make_random_hf_state_dict(cfg))


SPLIT_PROMPTS = (list(range(1, 41)), [5, 17, 92, 41], list(range(60, 82)))


def _drive_split(app, tel, switch=None, steps=40):
    """Three requests through ``step()`` (never ``run_to_completion``: the
    split step is what is instrumented), the second and third arriving while
    the first is in flight. ``switch`` maps a step number to "start"/"stop",
    applied before that step."""
    app.init_kv_cache()
    sess = ServingSession(app, telemetry=tel)
    arrivals = {0: 0, 2: 1, 5: 2}
    for k in range(steps):
        if switch and k in switch:
            getattr(tel, switch[k])()
        if k in arrivals:
            i = arrivals[k]
            assert sess.add_request(f"r{i}", SPLIT_PROMPTS[i], max_new_tokens=6 + i)
        if k > max(arrivals) and not sess.active:
            break
        sess.step()
    assert not sess.active
    return {rid: list(r.generated) for rid, r in sess.requests.items()}


def test_start_and_stop_mid_run_change_nothing(paged_app, monkeypatch):
    """A session that is started and stopped (twice) while requests are in
    flight produces the tokens of one that never was, performs the same
    number of device fetches, compiles nothing, and leaves no span open and
    no request trace behind after ``stop()``."""
    from neuronx_distributed_inference_tpu.analysis import RetraceGuard

    golden = _drive_split(paged_app, TelemetrySession(enabled=False))  # compiles
    counter = {"n": 0}
    real_asarray, real_device_get = np.asarray, jax.device_get

    def counting_asarray(a, *args, **kwargs):
        counter["n"] += isinstance(a, jax.Array)
        return real_asarray(a, *args, **kwargs)

    def counting_device_get(x, *args, **kwargs):
        counter["n"] += 1
        return real_device_get(x, *args, **kwargs)

    monkeypatch.setattr(np, "asarray", counting_asarray)
    monkeypatch.setattr(jax, "device_get", counting_device_get)
    never = TelemetrySession(enabled=False)
    out_never = _drive_split(paged_app, never)
    fetches_never, counter["n"] = counter["n"], 0
    tel = TelemetrySession(enabled=False)
    with RetraceGuard() as guard:
        out = _drive_split(paged_app, tel, switch={1: "start", 4: "stop", 7: "start", 11: "stop"})
    assert out == out_never == golden and sum(map(len, out.values())) == 6 + 7 + 8
    assert counter["n"] == fetches_never > 0
    assert guard.traces == []
    assert never.registry.snapshot() == {} and not never.events
    # r0 was admitted while stopped: counted, never traced, nothing dangling
    assert not tel.enabled and not tel.traces
    assert all(t_end is not None for *_, t_end in tel.span_tree().values())
    snap = tel.registry.snapshot()
    assert snap["nxdi_tokens_generated_total"]["samples"][0]["value"] > 0
    assert "r0" not in {t.req_id for t in tel.completed}
    names = {e["name"] for e in tel.events if e["type"] == "span"}
    assert {"serving.step", "serving.fetch_wait", "serving.prefill_chunk"} <= names
    tel.close()


def _span_events(tel):
    return [e for e in tel.events if e["type"] == "span"]


@pytest.fixture(scope="module")
def block_app():
    """A block-step model (sdar: block 4) on the split serving path."""
    from tests import test_sdar_reference as sdar

    return sdar.make_app()[0]


def _drive_block(app, tel):
    """Three requests through the block step, all admitted before the first
    step: one chunk pass feeds the three, the block passes follow."""
    sess = ServingSession(app, telemetry=tel)
    rng = np.random.default_rng(0)
    for i, n in enumerate((40, 7, 33)):
        assert sess.add_request(f"r{i}", rng.integers(1, 500, n).tolist(), max_new_tokens=8)
    for _ in range(200):
        if not sess.active:
            break
        sess.step()
    assert not sess.active


#: parent of every span of the split step; a session that never prefills in
#: a step (dense: contiguous cache, whole prompts at admission) has no chunk
#: pass, and prefills under its admission
SPLIT_PARENTS = {
    "serving.housekeeping": "serving.step",
    "serving.schedule": "serving.step",
    "serving.prefill_chunk": "serving.step",
    "serving.prefill_chunk.prepare": "serving.prefill_chunk",
    "serving.prefill_chunk.dispatch": "serving.prefill_chunk",
    "serving.prefill_chunk.fetch_start": "serving.prefill_chunk",
    # the committing half runs after the step's decode pass is dispatched
    "serving.prefill_chunk.fetch_wait": "serving.step",
    "serving.prefill_chunk.commit": "serving.step",
    "serving.decode": "serving.step",
    "serving.decode.prepare": "serving.decode",
    "serving.decode.dispatch": "serving.decode",
    "serving.fetch_wait": "serving.step",
    "serving.commit": "serving.step",
    "serving.step": None,
    "serving.admit": None,
}
#: the three spans of ISSUE 56, each under the one or two parents it may have
NEW_PARENTS = {
    "serving.h2d": {"serving.decode.prepare", "serving.prefill_chunk.prepare"},
    "serving.account": {"serving.decode", "serving.prefill_chunk"},
}


@pytest.mark.parametrize("kind", ["chunked", "dense", "block"])
def test_span_tree_of_the_split_step(kind, request):
    """Every span of a split step names its parent and carries the step's
    index; the fetch waits lie inside the step; the host-to-device copies lie
    inside the pass's preparation and what a pass records of itself inside
    the pass, after its dispatch; ``nxdi_step_host_ms`` and
    ``nxdi_step_fetch_wait_ms`` are the step span minus / the fetch-wait
    spans, one observation per step. On a chunked, a dense and a block-step
    session."""
    app = request.getfixturevalue({"chunked": "paged_app", "dense": "cb_app", "block": "block_app"}[kind])
    with TelemetrySession() as tel:
        # dense: the contiguous cache prefills at admission, every step is a decode pass alone
        {"chunked": _drive_split, "dense": _run_workload, "block": _drive_block}[kind](app, tel)
    spans = _span_events(tel)
    parents = dict(SPLIT_PARENTS)
    if kind == "dense":
        parents = {k: v for k, v in parents.items() if "prefill_chunk" not in k}
        parents["serving.prefill"] = "serving.admit"
    assert {e["name"] for e in spans} == set(parents) | set(NEW_PARENTS)
    for e in spans:
        if e["name"] in NEW_PARENTS:
            assert e["parent"] in NEW_PARENTS[e["name"]], e
        else:
            assert e["parent"] == parents[e["name"]], e
        assert e["t0"] <= e["t1"]
    steps = {e["step"]: e for e in spans if e["name"] == "serving.step"}
    assert sorted(steps) == list(range(1, len(steps) + 1))
    for e in spans:
        if e["name"] not in ("serving.step", "serving.admit", "serving.prefill"):
            outer = steps[e["step"]]
            assert outer["t0"] <= e["t0"] and e["t1"] <= outer["t1"], e
    # one copy span a dispatch, inside its preparation; one account span a
    # pass, after the pass's last dispatch returned
    by_name = lambda name: [e for e in spans if e["name"] == name]
    launches = by_name("serving.decode.dispatch") + by_name("serving.prefill_chunk.dispatch")
    assert len(by_name("serving.h2d")) == len(launches)
    assert len(by_name("serving.account")) == len(by_name("serving.decode")) + len(
        by_name("serving.prefill_chunk"))
    for e in by_name("serving.h2d"):
        prep = next(p for p in by_name(e["parent"]) if p["t0"] <= e["t0"] and e["t1"] <= p["t1"])
        assert prep["step"] == e["step"]
        assert e["arrays"] >= 5 and e["bytes"] >= 4 * e["arrays"]
    for e in by_name("serving.account"):
        owner = next(p for p in by_name(e["parent"]) if p["t0"] <= e["t0"] and e["t1"] <= p["t1"])
        inside = [d for d in launches if owner["t0"] <= d["t0"] and d["t1"] <= owner["t1"]]
        assert inside and max(d["t1"] for d in inside) <= e["t0"], e
    # the planning of a decode pass lies before it, a chunk pass's before its first dispatch
    for e in by_name("serving.decode"):
        assert any(p["step"] == e["step"] and p["t1"] <= e["t0"] for p in by_name("serving.schedule"))
    if kind != "chunked":
        return
    both = [k for k in steps
            if {"serving.prefill_chunk", "serving.decode", "serving.fetch_wait"}
            <= {e["name"] for e in spans if e.get("step") == k}]
    assert both, "no step held a chunk pass, a decode dispatch and a consume"
    admits = [e for e in spans if e["name"] == "serving.admit"]
    assert [(e["req_id"], e["verdict"]) for e in admits] == [
        ("r0", "admitted"), ("r1", "admitted"), ("r2", "admitted")]
    decode = next(e for e in spans if e["name"] == "serving.decode")
    assert decode["rows"] >= 1
    launch = next(e for e in spans if e["name"] == "serving.decode.dispatch")
    assert launch["program"] == "decode" and launch["q"] == 1
    assert launch["kv"] in app.token_generation_model.buckets
    snap = tel.registry.snapshot()
    host, wait = (snap[n]["samples"][0] for n in ("nxdi_step_host_ms", "nxdi_step_fetch_wait_ms"))
    assert host["count"] == wait["count"] == len(steps)
    waited = sum(e["dur_ms"] for e in spans if e["name"].endswith("fetch_wait"))
    assert wait["sum"] == pytest.approx(waited, rel=1e-6)
    assert host["sum"] + wait["sum"] == pytest.approx(
        sum(e["dur_ms"] for e in steps.values()), rel=1e-6)


def test_step_span_says_what_the_scheduler_decided(paged_app):
    """``chunk_rows``, ``chunk_dispatches`` and ``decode_rows`` of every
    ``serving.step`` against the schedule counted by hand: prompts of 40, 4
    and 22 tokens arriving before steps 1, 3 and 6, a chunk of 16 tokens, a
    request decoding from the step after its prompt's last chunk."""
    with TelemetrySession() as tel:
        _drive_split(paged_app, tel)
    spans = _span_events(tel)
    steps = sorted((e for e in spans if e["name"] == "serving.step"), key=lambda e: e["step"])
    decided = [(e["chunk_rows"], e["chunk_dispatches"], e["decode_rows"]) for e in steps]
    assert decided[:9] == [
        (1, 1, 0),  # r0: 16 of 40
        (1, 1, 0),  # r0: 32 of 40
        (2, 1, 0),  # r0: its last 8; r1: its 4
        (0, 0, 2),  # both decode
        (0, 0, 2),
        (1, 1, 2),  # r2: 16 of 22
        (1, 1, 2),  # r2: its last 6
        (0, 0, 3),
        (0, 0, 3),
    ]
    rows_of = lambda name, k: sum(e["rows"] for e in spans if e["name"] == name and e["step"] == k)
    for e in steps:
        assert e["chunk_rows"] == rows_of("serving.prefill_chunk", e["step"])
        assert e["decode_rows"] == rows_of("serving.decode", e["step"])
        assert e["chunk_dispatches"] == -(-e["chunk_rows"] // 4)  # four slots: a 4-row program
    assert sum(e["chunk_rows"] for e in steps) == 3 + 1 + 2  # the chunks of 40, 4 and 22 tokens


def test_chunk_rows_counter_counts_live_and_empty_rows():
    """``nxdi_chunk_rows_total{kind}`` against a schedule: 3 requests
    prefilling at once are 3 live rows of the 8-row program and 5 empty in
    one dispatch; 11 at once are 11 live of 16 in two. The step's span says
    the same."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    cfg = _paged_config(tpu=dict(
        batch_size=16, pa_num_blocks=40,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=16, kernel_q_tile_size=16)))
    app = TpuModelForCausalLM(None, cfg).load(state_dict=make_random_hf_state_dict(cfg))
    assert app.token_generation_model.chunk_rows == 8
    tel = TelemetrySession()
    sess = ServingSession(app, telemetry=tel)

    def rows():
        family = tel.registry.snapshot()["nxdi_chunk_rows_total"]["samples"]
        return {s["labels"]["kind"]: s["value"] for s in family}

    for i in range(3):
        assert sess.add_request(f"a{i}", [7 + i, 3, 11, 2], max_new_tokens=12)
    sess.step()
    assert rows() == {"live": 3, "empty": 5}
    for i in range(11):
        assert sess.add_request(f"b{i}", [20 + i, 5, 9], max_new_tokens=2)
    sess.step()
    assert rows() == {"live": 3 + 11, "empty": 5 + 5}
    sess.run_to_completion()
    tel.close()
    assert rows() == {"live": 14, "empty": 10}  # every prompt took one chunk
    steps = sorted((e for e in _span_events(tel) if e["name"] == "serving.step"),
                   key=lambda e: e["step"])
    assert [(e["chunk_rows"], e["chunk_dispatches"]) for e in steps[:3]] == [(3, 1), (11, 2), (0, 0)]
    assert steps[1]["decode_rows"] == 3
    value = lambda name: tel.registry.snapshot()[name]["samples"][0]["value"]
    assert value("nxdi_prefill_chunk_dispatches_total") == 3
    assert value("nxdi_chunk_steps_total") == 2


def test_padding_counters_of_the_split_step(paged_app):
    """Per chunk pass real + padded == dispatches x chunk rows x q_bucket,
    and the counters are the sums over the passes; a decode dispatch runs
    over every slot whatever its rows."""
    with TelemetrySession() as tel:
        _drive_split(paged_app, tel)
    spans = _span_events(tel)
    slots = paged_app.config.tpu_config.chunked_prefill_config.max_num_seqs
    chunks = [e for e in spans if e["name"] == "serving.prefill_chunk"]
    assert len(chunks) >= 4
    chunk_rows = paged_app.token_generation_model.chunk_rows
    assert chunk_rows == min(8, slots)
    for e in chunks:
        assert e["dispatches"] == 1  # never more rows than the program is wide
        assert e["real_tokens"] + e["padded_tokens"] == chunk_rows * e["q_bucket"]
        assert 0 < e["real_tokens"] <= e["rows"] * e["q_bucket"]

    def value(name):
        return tel.registry.snapshot()[name]["samples"][0]["value"]

    assert value("nxdi_prefill_real_tokens_total") == sum(map(len, SPLIT_PROMPTS))
    assert value("nxdi_prefill_real_tokens_total") == sum(e["real_tokens"] for e in chunks)
    assert value("nxdi_prefill_padded_tokens_total") == sum(e["padded_tokens"] for e in chunks)
    assert value("nxdi_prefill_chunk_dispatches_total") == len(chunks)
    decodes = [e for e in spans if e["name"] == "serving.decode"]
    assert value("nxdi_decode_slots_total") == slots * len(decodes)
    assert value("nxdi_decode_rows_total") == sum(e["rows"] for e in decodes)
    assert 0 < value("nxdi_decode_rows_total") <= value("nxdi_decode_slots_total")


def test_stopped_session_span_is_the_shared_null_context():
    """A session that is stopped (never started, or stopped again) hands out
    ONE null context: no clock read, nothing recorded, no instrument made."""

    def no_clock():
        raise AssertionError("a stopped session read the clock")

    tel = TelemetrySession(enabled=False, clock=no_clock)
    assert tel.span("a", rows=1) is tel.span("b") is tel_tracing.NULL_SPAN
    with tel.span("a") as sp:
        assert sp.dur_s == 0.0
    tel.prefill_pass(3, 5, rows=(1, 7))
    tel.decode_pass(1, 4)
    tel.step_timing(1.0, 1.0)
    assert tel.registry.snapshot() == {} and not tel.events and tel.spans is None
    assert tel.stop() is None  # stopping what never started is a no-op
    tel.clock = time.perf_counter
    tel.start()
    assert tel.span("a") is not tel_tracing.NULL_SPAN
    with tel.span("outer", step=7):
        with tel.span("inner"):
            pass
    tel.stop()
    assert tel.span("a") is tel_tracing.NULL_SPAN
    inner, outer = (e for e in tel.events if e["type"] == "span")
    assert (inner["name"], inner["parent"], inner["step"]) == ("inner", "outer", 7)
    assert (outer["name"], outer["parent"], outer["step"]) == ("outer", None, 7)
    tel.start()  # instruments are created once: the same families, no error
    tel.close()


@pytest.mark.parametrize("q_len,module,kernel", [
    (None, "jit_token_generation_model_decode", "paged_tkg_decode_attention"),
    (128, "jit_token_generation_model_chunk", "paged_flash_attention"),
])
def test_step_programs_and_kernels_carry_stable_names(q_len, module, kernel):
    """Lowered for the TPU target, the decode step and the chunk pass of
    the one token-generation runner are two modules named by tag and kind,
    and each holds its Pallas kernel under the name the benchmark's trace
    readers search for (``pallas_call(name=...)``, not the accident of a
    jitted wrapper's name)."""
    from jax import export

    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.ops.kernel_mode import force_compiled_kernels

    cfg = _paged_config(
        hidden_size=256, intermediate_size=256, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=1024,
        tpu=dict(
            seq_len=512, dtype="bfloat16", pa_block_size=32, pa_num_blocks=40,
            token_generation_buckets=[512], attn_kernel_enabled=True,
            attn_block_tkg_kernel_enabled=True,
            chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=4, kernel_q_tile_size=128),
        ),
    )
    app = TpuModelForCausalLM(None, cfg).load(random_weights=True)
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(512, q_len=q_len)
    program = tkg.program_for(inputs)
    with jax.set_mesh(app.mesh), force_compiled_kernels():
        text = export.export(program, platforms=["tpu"])(
            app.params, app.kv_cache, inputs, None).mlir_module()
    assert f"module @{module} " in text
    assert f'kernel_name = "{kernel}"' in text
    other = "paged_flash_attention" if q_len is None else "paged_tkg_decode_attention"
    assert f'kernel_name = "{other}"' not in text


# ---------------------------------------------------------------------------
# ISSUE 56: the readers of the three spans and the row counter, held in
# tier-1 (benchmark/selftest is run by hand): the stack nesting equal to
# program_span's pairwise one, the phases adding up to the host's time and
# the three idle metrics to the slice's idle time, on the recorded traces
# ---------------------------------------------------------------------------

from benchmark.selftest.test_span_phase import (  # noqa: E402,F401
    fake,
    test_counter_share,
    test_idle_under_the_waits_between_the_steps_and_under_the_host,
    test_recorded_traces_nest_equally_and_add_up,
    test_self_time_by_phase_and_what_no_leaf_names,
    test_span_and_module_counts_that_differ_fail_loudly,
    test_the_recorded_trace_holds_the_three_spans_under_their_parents,
    test_the_stack_nests_as_the_pairwise_comparison_does,
    test_what_a_trace_does_not_hold_reads_as_nothing,
)
