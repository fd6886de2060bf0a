#!/usr/bin/env python
"""Benchmark driver entry: prints ONE JSON line with the headline metric.

Measures steady-state decode throughput (tokens/sec) on the available chip for
full-size random-weight models, mirroring the reference's benchmark_sampling
metric definitions (reference: utils/benchmark.py:479-499 —
throughput = runs·tokens·batch/total).

Points (VERDICT r3 #1/#3, r4 #1/#2/#3):
- llama-3.2-1B bf16: bs=1 decode (headline), TTFT, 512-token prefill, bs=4 decode
- llama-3.2-1B int8: bs=1 decode + TTFT (HBM-bound decode ⇒ int8 halves traffic)
- serving-under-load: 8 concurrent 1B int8 requests through ServingSession
  (chunked prefill + paged cache): aggregate decode tok/s + p50/p99 TTFT
- the SAME serving mix through the ragged mixed-step dispatch
  (serving_ragged=True, ISSUE 6): one ragged paged-attention dispatch per
  step instead of the CTE/TKG pair — the ragged_* summary keys (incl. the
  padded-token fraction) are the split-vs-ragged comparison
- llama-3.1-8B int8: bs=1 decode + TTFT (the closest single-chip proxy for the
  BASELINE.json 8B north star; int8 8B fits one 16G v5e chip)
- llama-3.2-1B bf16 16k long-context (VERDICT r5 weak #5): 16384-token
  prefill TTFT + decode at 16k context (~1 GB KV) — the budgeted, skippable
  last point that validates the retuned + head-packed prefill tiles where
  attention dominates

vs_baseline anchors against the reference's Llama3.2-1B-class integration
throughput gate (~1057 tok/s on 32 trainium cores,
test_llama3_2_1b_4layer_context_parallel.py:36-44). We run on ONE v5e chip,
so >1.0 means one TPU chip beats the 32-core trn gate.

Robustness contract (VERDICT r4 #1): the machine-readable summary line is
printed (stdout, flushed) IMMEDIATELY after the headline point and RE-printed,
updated, after every later point — so a driver-side kill anywhere mid-suite
still leaves a parseable last line. A total wall-clock budget
(``BENCH_BUDGET_S``, default 1200 s) skips not-yet-started points as
``skipped_budget`` so the suite finishes inside any sane driver timeout
instead of being killed by it; the exit code is non-zero whenever a point
errored or was skipped — a suite that did not measure everything it lists
has not passed.

Quantize-once (VERDICT r4 #2): quantized points persist a presharded int8
artifact under ``BENCH_CACHE_DIR`` (default ``.bench_cache/``, gitignored);
warm runs restore the sharded arrays directly — no host quantize walk, no
full-precision staging (reference quantize-at-prep posture,
application_base.py:744-797).

The whole measurement path (build → load → warmup → measure) is importable and
size-parameterized so the test suite smoke-runs the EXACT code path on CPU
(tests/test_bench_smoke.py) — two of three rounds shipped a bench-only crash
the suite missed (VERDICT r3 weak #2), and r4's artifact was voided by a
driver timeout the old all-or-nothing output format could not survive.
"""

import json
import os
import sys
import time
import warnings

# model shapes live in the device/cost model (the single source of truth the
# static roofline projections are computed from — ISSUE 11); bench rows and
# projections can therefore never disagree about the shape they describe
from neuronx_distributed_inference_tpu.analysis.device_model import (  # noqa: E402
    LLAMA_1B,
    LLAMA_1B_DRAFT4,
    LLAMA_8B,
)

TINY = dict(  # smoke-test model (CPU suite)
    model_type="llama",
    hidden_size=64,
    intermediate_size=128,
    num_attention_heads=4,
    num_key_value_heads=2,
    num_hidden_layers=2,
    vocab_size=128,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
    max_position_embeddings=256,
    hidden_act="silu",
    tie_word_embeddings=False,
)

# reference gates (BASELINE.md): 1B-class 32-core integration throughput, and
# the 8B bf16 trn1-32-core gate (1665 * 0.8)
BASELINE_1B = 1057.0
BASELINE_8B_GATE = 1332.0


def _budget_s() -> float:
    return float(os.environ.get("BENCH_BUDGET_S", "1200"))


def _cache_dir() -> str:
    return os.environ.get(
        "BENCH_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache"),
    )


def _require_chip():
    """One check, no retry: a measuring run that finds no TPU, or a TPU the
    device registry does not know, fails at once. Returns the DeviceSpec."""
    import jax

    from neuronx_distributed_inference_tpu.analysis import device_model

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench measures on a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}). The CPU smoke path is --tiny --cpu and "
            f"reports counts, not device metrics."
        )
    spec = device_model.resolve_device(dev.device_kind)
    if spec is None:
        raise RuntimeError(
            f"device_kind {dev.device_kind!r} is not in "
            f"device_model.DEVICE_REGISTRY; add its peaks before measuring"
        )
    return spec


def build_app(
    hf_attrs,
    *,
    batch,
    seq_len,
    ce_buckets,
    tkg_buckets,
    dtype="bfloat16",
    quantized=False,
    cache_key=None,
    block_kv=False,
    extra_tpu=None,
    devices=None,
    load=True,
):
    """Build + load a random-weight app — the exact production code path.

    ``load=False`` returns the app built but unloaded: the caller brings the
    weights (chip_smoke.py shares one set across apps, or loads one state
    dict at two tp degrees).

    ``cache_key``: when set and ``quantized``, the final sharded params are
    persisted as a presharded artifact under BENCH_CACHE_DIR/<cache_key> and
    restored on later runs — quantize once, not per load (VERDICT r4 #2).
    """
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    def load_cfg(c):
        for k, v in hf_attrs.items():
            setattr(c, k, v)

    from neuronx_distributed_inference_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    # bench points re-run across processes and rounds: compiles are paid
    # once, wherever the environment (or the fixed default) puts the cache
    configure_compile_cache()
    kw = {}
    if block_kv:
        from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

        kw = dict(
            is_continuous_batching=True,
            ctx_batch_size=1,
            is_block_kv_layout=True,
            pa_num_blocks=block_kv["num_blocks"],
            pa_block_size=block_kv["block_size"],
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=block_kv["max_seqs"],
                kernel_q_tile_size=block_kv.get("q_tile", 128),
            ),
        )
    tc = TpuConfig(
        batch_size=batch,
        seq_len=seq_len,
        dtype=dtype,
        enable_bucketing=True,
        context_encoding_buckets=list(ce_buckets),
        token_generation_buckets=list(tkg_buckets),
        quantized=quantized,
        # fused decode-layer kernels need the fused QKV weight layout; with it
        # they auto-enable on TPU (quantized configs fall back structurally)
        fused_qkv=not quantized,
        **kw,
        **(extra_tpu or {}),
    )
    mesh = None
    if devices is not None:
        # multi-replica router point: each replica's mesh over its own
        # device partition (on a 1-chip host the replicas share the chip —
        # correct but serialized; scale-out needs chips)
        from neuronx_distributed_inference_tpu.parallel.mesh import (
            mesh_from_config,
        )

        mesh = mesh_from_config(tc, devices=devices)
    app = TpuModelForCausalLM(
        None, LlamaInferenceConfig(tc, load_config=load_cfg), mesh=mesh
    )
    if not load:
        return app
    artifact = None
    if cache_key:
        artifact = os.path.join(_cache_dir(), cache_key)
    loaded = False
    if artifact and os.path.exists(os.path.join(artifact, "manifest.pkl")):
        from neuronx_distributed_inference_tpu.utils.presharded import (
            config_fingerprint,
            load_presharded,
        )

        t0 = time.time()
        try:
            restored = load_presharded(
                artifact, app.mesh, fingerprint=config_fingerprint(app.config)
            )
        except Exception as e:
            # corrupt/stale artifact (killed mid-write, recipe change):
            # degrade to a cold load + rewrite rather than failing the point
            print(f"presharded cache unusable ({e}); cold load", file=sys.stderr)
            import shutil

            shutil.rmtree(artifact, ignore_errors=True)
            restored = None
        if restored is not None:
            app.params, app._pspecs = restored
            app.init_kv_cache()
            loaded = True
            print(
                f"presharded cache hit {artifact} ({time.time() - t0:.1f}s)",
                file=sys.stderr,
            )
    if not loaded:
        t0 = time.time()
        app.load(random_weights=True)
        print(f"load (cold) {time.time() - t0:.1f}s", file=sys.stderr)
        if artifact:
            from neuronx_distributed_inference_tpu.utils.presharded import (
                config_fingerprint,
                save_presharded,
            )

            t0 = time.time()
            save_presharded(
                app.params, app._pspecs, artifact,
                fingerprint=config_fingerprint(app.config),
            )
            print(
                f"presharded cache write {artifact} ({time.time() - t0:.1f}s)",
                file=sys.stderr,
            )
    return app


def measure_point(app, *, batch, prompt_len, gen_len, long_prompt=None):
    """Warmup-compile then measure TTFT / decode throughput (+ optional
    long-prompt prefill throughput). Returns a dict of metrics including
    ``kv_bytes``, the cache's true HBM cost (codes + scales for quantized
    caches) — the quantity the kv-quant rows halve."""
    import numpy as np

    from neuronx_distributed_inference_tpu.modules.kvcache import cache_nbytes

    rng = np.random.RandomState(0)
    vocab = app.config.vocab_size - 10
    ids = rng.randint(0, vocab, size=(batch, prompt_len))
    mask = np.ones_like(ids)

    # warmup / compile — run the SAME programs the measured runs use
    # (gen_len-sized decode chunk and the 1-token TTFT path)
    t0 = time.time()
    app.generate(ids, mask, max_new_tokens=gen_len)
    app.generate(ids, mask, max_new_tokens=1)
    compile_s = time.time() - t0

    t0 = time.time()
    app.generate(ids, mask, max_new_tokens=1)
    ttft_ms = (time.time() - t0) * 1e3

    t0 = time.time()
    out = app.generate(ids, mask, max_new_tokens=gen_len)
    decode_tok_s = out.num_generated * batch / (time.time() - t0)

    res = {
        "ttft_ms": round(ttft_ms, 1),
        "decode_tok_s": round(decode_tok_s, 2),
        "compile_s": round(compile_s, 1),
        "kv_bytes": cache_nbytes(app.kv_cache),
    }
    if long_prompt:
        ids_l = rng.randint(0, vocab, size=(batch, long_prompt))
        mask_l = np.ones_like(ids_l)
        app.generate(ids_l, mask_l, max_new_tokens=1)  # compile
        t0 = time.time()
        app.generate(ids_l, mask_l, max_new_tokens=1)
        res["prefill_tok_s"] = round(long_prompt / (time.time() - t0), 1)
    return res


def _counter_delta(snap, base_snap, name, exclude_reasons=()):
    """Per-run counter delta between two registry snapshots (the PR-7
    containment-census convention). ``exclude_reasons`` drops samples whose
    ``reason`` label matches — the clean-traffic 0/0/0 pin excludes
    ``reason=backlog`` from the rejected count, because an open-loop
    goodput run INTENDS backlog refusals (ISSUE 14 satellite): they are
    workload pressure, not containment events, and they are reported under
    their own ``backlog_*`` keys."""

    def total(s):
        fam = s.get(name)
        if not fam:
            return 0
        return int(sum(
            smp["value"]
            for smp in fam["samples"]
            if smp.get("labels", {}).get("reason") not in exclude_reasons
        ))

    return total(snap) - total(base_snap)


def measure_serving(app, *, n_requests, prompt_len, gen_len):
    """Serving-under-load: concurrent requests with staggered arrivals through
    ServingSession (continuous batching + chunked prefill + paged cache).
    Aggregate decode throughput + per-request TTFT/ITL — the product metric
    for a serving framework (VERDICT r4 #3; reference serving hot path
    model_wrapper.py:582-751, async_execution.py:190).

    TTFT/ITL come from the runtime telemetry layer's per-request traces
    (telemetry/tracing.py) — the same instrumentation production serving
    exposes — not from bench-local stopwatch bookkeeping; the session's
    registry rides the process-default registry so ``--metrics-out`` dumps
    the full serving metric set for this point."""
    import numpy as np

    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import (
        TelemetrySession,
        default_registry,
    )

    rng = np.random.RandomState(0)
    vocab = app.config.vocab_size - 10
    prompts = [
        rng.randint(0, vocab, size=(prompt_len,)).tolist() for _ in range(n_requests)
    ]

    def run_once(registry=None):
        # registry=None -> the session's own throwaway registry: the warmup
        # pass compiles every (q, kv) chunk program and its compile-dominated
        # TTFT/ITL observations must not pollute the --metrics-out dump
        app.init_kv_cache()  # fresh block pool between runs
        with TelemetrySession(registry=registry) as tel:
            session = ServingSession(app, telemetry=tel)
            produced = set()
            t_start = time.time()
            # staggered arrivals: 2 up-front, then one more every scheduler
            # step until all n_requests have arrived — prefill chunks
            # interleave with live decode (the continuous-batching regime,
            # not a static batch)
            next_idx = 0
            for _ in range(2):
                session.add_request(str(next_idx), prompts[next_idx],
                                    max_new_tokens=gen_len)
                next_idx += 1
            while True:
                produced.update(session.step())
                if next_idx < n_requests and session.free_slots:
                    session.add_request(str(next_idx), prompts[next_idx],
                                        max_new_tokens=gen_len)
                    next_idx += 1
                if next_idx >= n_requests:
                    if not session.active:
                        break
                    if len(produced) >= n_requests:
                        # every request admitted + producing: drain the decode
                        # tail in multi-step chunks (one host sync per chunk —
                        # vLLM-style multi-step scheduling)
                        session.run_to_completion(decode_chunk_size=16)
                        break
            total_s = time.time() - t_start
            counts = {rid: len(r.generated) for rid, r in session.requests.items()}
        return tel, counts, total_s

    run_once()  # warmup / compile pass over all (q, kv) chunk programs
    base_snap = default_registry().snapshot()  # census delta baseline
    tel, counts, total_s = run_once(default_registry())
    ttfts = [t * 1e3 for t in tel.ttft_values_s()]
    itls = [t * 1e3 for t in tel.itl_values_s()]
    total_tokens = sum(counts.values())

    def pct(vals, p):
        # one percentile implementation: the telemetry session's
        v = tel.percentile(vals, p / 100)
        return round(v, 1) if v is not None else None

    res = {
        "decode_tok_s": round(total_tokens / total_s, 2),
        "ttft_ms": pct(ttfts, 50),
        "ttft_p99_ms": pct(ttfts, 99),
        "itl_ms": pct(itls, 50),
        "itl_p99_ms": pct(itls, 99),
        "n_requests": n_requests,
        "total_tokens": total_tokens,
    }
    # fault-containment census (ISSUE 7): rejected/quarantined/preempted
    # counts sourced from the telemetry registry — on clean traffic all
    # three MUST be 0 (the containment layer's overhead proof; the first
    # hardware session compares these rows against pre-containment numbers).
    # The registry is the PROCESS-default (shared across bench points), so
    # each point reports the delta over its own measured run, not the
    # cumulative process totals.
    snap = tel.registry.snapshot()

    res["rejected"] = _counter_delta(
        snap, base_snap, "nxdi_requests_rejected_total",
        exclude_reasons=("backlog",),
    )
    res["quarantined"] = _counter_delta(
        snap, base_snap, "nxdi_rows_quarantined_total")
    res["preempted"] = _counter_delta(
        snap, base_snap, "nxdi_requests_preempted_total")
    # ragged mixed-step dispatch (serving_ragged): padded-token fraction of
    # the packed total-token buckets, from the mixed-step composition
    # histogram the session records per dispatch
    # host-gap telemetry (ISSUE 8): host-time fraction of serving step wall
    # time over THIS measured run — ~1.0 means the host loop, not the chip,
    # bounds throughput; the async-pipelined row should push it (and
    # absolute host ms/step) down vs the synchronous row. Computed as a
    # per-run DELTA over the step-timing histograms (like the containment
    # counters above), NOT from the process-shared cumulative gauge — the
    # registry spans bench points, and a row that never recorded step
    # timing must not inherit another row's value (or a default 0.0).
    def _hist_delta(name):
        def sc(s):
            fam = s.get(name)
            if not fam or not fam.get("samples"):
                return 0.0, 0
            smp = fam["samples"][0]
            return float(smp["sum"]), int(smp["count"])

        s1, c1 = sc(snap)
        s0, c0 = sc(base_snap)
        return s1 - s0, c1 - c0

    host_ms, host_n = _hist_delta("nxdi_step_host_ms")
    wait_ms, _ = _hist_delta("nxdi_step_fetch_wait_ms")
    if host_n > 0 and host_ms + wait_ms > 0:
        res["host_frac"] = round(host_ms / (host_ms + wait_ms), 4)
    mixed = snap.get("nxdi_mixed_step_rows")
    if mixed:
        base_mixed = base_snap.get("nxdi_mixed_step_rows")
        base_sums = (
            {s["labels"]["kind"]: s["sum"] for s in base_mixed["samples"]}
            if base_mixed
            else {}
        )
        sums = {
            s["labels"]["kind"]: s["sum"] - base_sums.get(s["labels"]["kind"], 0)
            for s in mixed["samples"]
        }
        denom = sums.get("padded_slots", 0) + sums.get("query_tokens", 0)
        if denom:
            res["padded_token_frac"] = round(
                sums.get("padded_slots", 0) / denom, 4
            )
    return res


def measure_serving_spec(target, draft, *, n_requests, prompt_len, gen_len, k):
    """Spec-ragged serving (ISSUE 12): the SAME staggered mix through
    SpeculativeServingSession with verification packed into the ragged
    mixed dispatch (serving_spec_ragged) — prefill chunks + decode rows +
    spec-verify rows in ONE program launch per step, draft proposals and
    the accepted-token frontier chained device-side, draft length adaptive
    per request. Beside the usual serving metrics the row reports
    ``spec_acceptance``: the measured per-draft acceptance RATE
    ((committed - rounds) / drafted, from the registry's acceptance and
    draft-length histograms) — the parameter the acceptance-parameterized
    projection is re-evaluated at, so the recorded ceiling tracks the
    workload the row actually saw (random weights ⇒ near-zero acceptance:
    this row's CPU/clean-bench number is the WORST-case overhead bound;
    spec-friendly acceptance comes from real checkpoints)."""
    import numpy as np

    from neuronx_distributed_inference_tpu.runtime.serving import (
        SpeculativeServingSession,
    )
    from neuronx_distributed_inference_tpu.telemetry import (
        TelemetrySession,
        default_registry,
    )

    rng = np.random.RandomState(0)
    vocab = target.config.vocab_size - 10
    prompts = [
        rng.randint(0, vocab, size=(prompt_len,)).tolist() for _ in range(n_requests)
    ]

    def run_once(registry=None):
        target.init_kv_cache()
        draft.init_kv_cache()
        with TelemetrySession(registry=registry) as tel:
            session = SpeculativeServingSession(
                target, draft, speculation_length=k, telemetry=tel
            )
            t_start = time.time()
            next_idx = 0
            for _ in range(2):
                session.add_request(str(next_idx), prompts[next_idx],
                                    max_new_tokens=gen_len)
                next_idx += 1
            while True:
                session.step()
                if next_idx < n_requests and session.free_slots:
                    session.add_request(str(next_idx), prompts[next_idx],
                                        max_new_tokens=gen_len)
                    next_idx += 1
                    continue
                if next_idx >= n_requests and not (
                    session.active or session._readmit
                ):
                    break
            total_s = time.time() - t_start
            counts = {rid: len(r.generated) for rid, r in session.requests.items()}
        return tel, counts, total_s

    run_once()  # warmup / compile pass (mixed_spec buckets + chain programs)
    base_snap = default_registry().snapshot()
    tel, counts, total_s = run_once(default_registry())
    ttfts = [t * 1e3 for t in tel.ttft_values_s()]
    itls = [t * 1e3 for t in tel.itl_values_s()]
    total_tokens = sum(counts.values())

    def pct(vals, p):
        v = tel.percentile(vals, p / 100)
        return round(v, 1) if v is not None else None

    snap = tel.registry.snapshot()

    def _hist(which, name):
        fam = which.get(name)
        if not fam or not fam.get("samples"):
            return 0.0, 0
        smp = fam["samples"][0]
        return float(smp["sum"]), int(smp["count"])

    acc_s1, acc_c1 = _hist(snap, "nxdi_spec_accept_len")
    acc_s0, acc_c0 = _hist(base_snap, "nxdi_spec_accept_len")
    dl_s1, _ = _hist(snap, "nxdi_spec_draft_len")
    dl_s0, _ = _hist(base_snap, "nxdi_spec_draft_len")
    committed, rounds = acc_s1 - acc_s0, acc_c1 - acc_c0
    drafted = dl_s1 - dl_s0
    acceptance = (
        round(max(0.0, committed - rounds) / drafted, 4) if drafted > 0 else None
    )
    res = {
        "decode_tok_s": round(total_tokens / total_s, 2),
        "ttft_ms": pct(ttfts, 50),
        "ttft_p99_ms": pct(ttfts, 99),
        "itl_ms": pct(itls, 50),
        "itl_p99_ms": pct(itls, 99),
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "spec_acceptance": acceptance,
        "spec_rounds": int(rounds),
    }

    res["rejected"] = _counter_delta(
        snap, base_snap, "nxdi_requests_rejected_total",
        exclude_reasons=("backlog",),
    )
    res["quarantined"] = _counter_delta(
        snap, base_snap, "nxdi_rows_quarantined_total")
    res["preempted"] = _counter_delta(
        snap, base_snap, "nxdi_requests_preempted_total")
    return res


def measure_router(apps, *, n_requests, prompt_len, gen_len, policy,
                   prefill_apps=None, elastic=None):
    """Scale-out serving: the SAME staggered request mix routed over N
    single-chip replica sessions by ServingRouter (ISSUE 10;
    docs/SERVING.md "Multi-replica front-end"). Aggregate tok/s across
    replicas plus the router's own product metrics: failover count (MUST be
    0 on clean traffic — the router layer's zero-overhead proof) and
    ``balance_frac`` = min-replica tokens / even share (1.0 == the
    placement policy spread the mix perfectly).

    ``prefill_apps`` (ISSUE 15): prefill-stage apps forming a disaggregated
    PREFILL tier — every placement context-encodes there and hands KV over
    to a decode replica. The row then additionally reports the hand-off
    census: ``handoffs`` (MUST equal the request count on clean traffic),
    ``handoff_failures`` and ``handoff_local_prefill`` (both MUST be 0 —
    the tier's zero-containment-events proof).

    ``elastic`` (ISSUE 20): ``dict(retire_step=N)`` exercises the elastic
    fleet primitives mid-drain — at step N the highest-id replica is
    retired (``retire_replica``, graceful drain), and the moment its drain
    finalizes a FRESH session over the same warmed app re-joins via
    ``add_replica`` (zero recompiles: the jit cache is per-app). The row
    then reports the ``elastic_*`` census: retire/add counts, attainment
    (finished / submitted — MUST be 1.0), and the leak pins (zero leaked
    KV blocks across every session incl. the retired one, zero leaked
    threads across the run).

    Containment census matches PR 7's convention: rejected / failover /
    re-admitted are PER-RUN deltas against a pre-run registry snapshot."""
    import numpy as np

    from neuronx_distributed_inference_tpu.runtime.replica import (
        PrefillReplicaHandle,
    )
    from neuronx_distributed_inference_tpu.runtime.router import ServingRouter
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import (
        TelemetrySession,
        default_registry,
    )

    rng = np.random.RandomState(0)
    vocab = apps[0].config.vocab_size - 10
    prompts = [
        rng.randint(0, vocab, size=(prompt_len,)).tolist() for _ in range(n_requests)
    ]

    def run_once(registry=None):
        import threading as _threading

        threads_before = _threading.active_count()
        for app in apps:
            app.init_kv_cache()  # fresh block pool per replica between runs
        tier = []
        for i, papp in enumerate(prefill_apps or ()):
            papp.init_kv_cache()
            tier.append(PrefillReplicaHandle(papp, i))
        sessions = []
        elastic_info = None
        with TelemetrySession(registry=registry) as tel:
            # threaded stepping follows TpuConfig.router_threading on the
            # replica apps (the *_router_threaded row sets it); the context
            # manager joins the worker pool even if the drain raises
            # (no-op when sequential)
            with ServingRouter(
                [ServingSession(app, telemetry=tel) for app in apps],
                policy=policy, telemetry=tel, prefill_replicas=tier,
            ) as router:
                sessions = [h.session for h in router.replicas]
                retire_step = (elastic or {}).get("retire_step")
                retired_id = None
                added = False
                step_i = 0
                t_start = time.time()
                next_idx = 0
                for _ in range(2):
                    router.add_request(str(next_idx), prompts[next_idx],
                                       max_new_tokens=gen_len)
                    next_idx += 1
                while True:
                    router.step()
                    step_i += 1
                    if retire_step is not None:
                        if retired_id is None and step_i >= retire_step:
                            victim = max(
                                router.replicas, key=lambda h: h.replica_id
                            )
                            retired_id = victim.replica_id
                            router.retire_replica(retired_id, drain=True)
                        elif retired_id is not None and not added and all(
                            h.replica_id != retired_id
                            for h in router.replicas
                        ):
                            # drain finalized: re-join a FRESH session over
                            # the same warmed app (shared jit cache — zero
                            # recompiles)
                            sess = ServingSession(apps[-1], telemetry=tel)
                            sessions.append(sess)
                            router.add_replica(sess)
                            added = True
                    if next_idx < n_requests:
                        router.add_request(str(next_idx), prompts[next_idx],
                                           max_new_tokens=gen_len)
                        next_idx += 1
                        continue
                    if not router.has_live_work:
                        break
                total_s = time.time() - t_start
                counts = {
                    rid: len(r.tokens)
                    for rid, r in router.requests.items()
                }
                per_replica = [h.tokens_served for h in router.replicas]
                threaded = router.threaded
                handoffs = sum(p.handoffs for p in router.prefill_replicas)
                if retire_step is not None:
                    elastic_info = {
                        "elastic_retired": int(retired_id is not None),
                        "elastic_added": int(added),
                        "elastic_attainment": round(
                            sum(
                                1 for r in router.requests.values()
                                if r.status == "finished"
                            ) / n_requests, 4,
                        ),
                        # every session's allocator drained (the retired
                        # one included): nothing a retired replica owned
                        # leaks a KV block
                        "elastic_leaked_blocks": sum(
                            len(getattr(s.allocator, "seq_blocks", ()) or ())
                            for s in sessions
                        ),
                    }
        if elastic_info is not None:
            elastic_info["elastic_leaked_threads"] = (
                _threading.active_count() - threads_before
            )
        return (tel, counts, per_replica, total_s, threaded, handoffs,
                elastic_info)

    run_once()  # warmup / compile pass over every replica's programs
    base_snap = default_registry().snapshot()
    (tel, counts, per_replica, total_s, threaded, handoffs,
     elastic_info) = run_once(default_registry())
    total_tokens = sum(counts.values())
    snap = tel.registry.snapshot()

    def _ctr(name, exclude_reasons=()):
        return _counter_delta(snap, base_snap, name,
                              exclude_reasons=exclude_reasons)

    def _hist_sum(name):
        def total(s):
            fam = s.get(name)
            if not fam:
                return 0.0
            return float(sum(smp["sum"] for smp in fam["samples"]))

        return total(snap) - total(base_snap)

    # per-step overlap (ISSUE 13): 1 - stepping-phase wall / sum of the
    # per-replica step walls, per-run deltas over the nxdi_replica_step_ms
    # histograms + the router-step span — ~0 when replicas host-serialize
    # (sequential stepping), up to (N-1)/N when the thread-per-replica
    # pool overlaps them fully
    replica_ms = _hist_sum("nxdi_replica_step_ms")
    phase_ms = _hist_sum("nxdi_router_step_ms")
    overlap = (
        round(max(0.0, 1.0 - phase_ms / replica_ms), 4)
        if replica_ms > 0 else None
    )

    n = len(apps)
    even_share = total_tokens / n if n else 0
    res = {
        "decode_tok_s": round(total_tokens / total_s, 2),
        "n_requests": n_requests,
        "n_replicas": n,
        "total_tokens": total_tokens,
        "tokens_per_replica": per_replica,
        "balance_frac": (
            round(min(per_replica) / even_share, 4) if even_share else None
        ),
        "router_threading": threaded,
        "overlap_frac": overlap,
        # containment deltas (PR 7 convention): clean traffic MUST report
        # 0 failovers — the pre-flip check for any failover-policy knob
        "rejected": _ctr("nxdi_router_rejected_total")
        + _ctr("nxdi_requests_rejected_total", exclude_reasons=("backlog",)),
        "failover": _ctr("nxdi_router_failovers_total"),
        # re-admissions = pool-exhaustion evictions that re-queued inside a
        # replica (aging); also exposed under PR 7's "preempted" name so
        # every serving row carries the same containment key set
        "readmitted": _ctr("nxdi_requests_preempted_total"),
        "preempted": _ctr("nxdi_requests_preempted_total"),
        "quarantined": _ctr("nxdi_rows_quarantined_total"),
    }
    if prefill_apps:
        # disaggregated-tier census (ISSUE 15): on clean traffic every
        # prompt hands off (handoffs == n_requests) with ZERO typed
        # hand-off failures and ZERO local-prefill fallbacks
        res["n_prefill_replicas"] = len(prefill_apps)
        res["handoffs"] = handoffs
        res["handoff_failures"] = _ctr("nxdi_handoff_failures_total")
        res["handoff_local_prefill"] = _ctr("nxdi_handoff_local_prefill_total")
        res["handoff_retries"] = _ctr("nxdi_handoff_retries_total")
    if elastic_info is not None:
        # elastic-fleet census (ISSUE 20): retire + add both happened,
        # every submitted request finished (attainment 1.0) and nothing
        # leaked — blocks or threads
        res.update(elastic_info)
        res["elastic_events"] = _ctr("nxdi_router_elastic_total")
    return res


def measure_goodput(apps, *, workload, chaos_kill_step=None,
                    policy="least_loaded", bucket_steps=4,
                    prefill_apps=None, chaos_tier="decode"):
    """Open-loop SLO goodput (ISSUE 14; docs/WORKLOADS.md): a seeded
    workload trace (arrival process × heavy-tailed lengths × shared-prefix
    tenant pools) drives the serving stack through the open-loop
    WorkloadDriver on a VIRTUAL clock — requests are admitted no earlier
    than their arrival step, refused arrivals retry from the backlog, and
    every latency policy in the stack (deadlines, EWMAs, telemetry traces)
    runs on deterministic virtual time. The scored number is **goodput**:
    tokens from requests that met their TTFT/ITL SLOs (measured from
    ARRIVAL, so backlog wait counts) per wall second, beside the raw
    ``decode_tok_s`` the closed-loop rows report.

    ``apps``: one app = single ServingSession; N apps = a ServingRouter
    over N replica sessions. ``chaos_kill_step``: arm the standing chaos
    row — a seeded replica kill mid-run, scored as goodput-dip depth +
    recovery time off the time-bucketed goodput series (workload/slo.py
    extract_dip). ``prefill_apps`` (ISSUE 15): a disaggregated PREFILL
    tier in front of the decode replicas; ``chaos_tier="prefill"`` aims
    the kill at a tier member instead of a decode replica — decode
    capacity survives, so the scorer's recovery target stays at the FULL
    baseline (alive_frac 1.0) and the row's claim is containment (local-
    prefill fallback, no wedge), not a capacity dip. Containment deltas follow the PR-7 convention with
    ``reason=backlog`` EXCLUDED from the rejected count: open-loop backlog
    refusals are intended workload pressure, reported under
    ``backlog_refusals`` instead."""
    from neuronx_distributed_inference_tpu.runtime.replica import ReplicaHandle
    from neuronx_distributed_inference_tpu.runtime.router import ServingRouter
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import (
        SloMonitor,
        TelemetrySession,
        default_registry,
    )
    from neuronx_distributed_inference_tpu.workload import (
        ChaosPlan,
        VirtualClock,
        WorkloadDriver,
        generate,
        score,
        standard_spec,
    )

    from neuronx_distributed_inference_tpu.runtime.replica import (
        PrefillReplicaHandle,
    )

    trace = generate(standard_spec(
        vocab_size=apps[0].config.vocab_size - 10, **workload
    ))
    chaos = (
        ChaosPlan(kill_step=chaos_kill_step, tier=chaos_tier)
        if chaos_kill_step is not None else None
    )

    def run_once(registry=None):
        for app in apps:
            app.init_kv_cache()
        tier = []
        for i, papp in enumerate(prefill_apps or ()):
            papp.init_kv_cache()
            tier.append(PrefillReplicaHandle(papp, i))
        vc = VirtualClock()
        with TelemetrySession(registry=registry, clock=vc.now) as tel:
            # live windowed SLO attainment / burn rate rides every goodput
            # run (ISSUE 19) — the nxdi_slo_burn_rate gauges land in the
            # --metrics-out dump beside the offline scorer's numbers
            tel.attach_slo_monitor(SloMonitor())
            sessions = [
                ServingSession(app, telemetry=tel, clock=vc.now)
                for app in apps
            ]
            t_start = time.time()
            if len(apps) > 1:
                handles = [
                    ReplicaHandle(s, i, clock=vc.now)
                    for i, s in enumerate(sessions)
                ]
                with ServingRouter(handles, policy=policy, telemetry=tel,
                                   clock=vc.now,
                                   prefill_replicas=tier) as router:
                    drv = WorkloadDriver(router, trace, clock=vc,
                                         telemetry=tel, chaos=chaos)
                    with warnings.catch_warnings():
                        # a chaos prefill-tier kill degrades to local
                        # prefill LOUDLY (that one warning is the product
                        # behavior under test, not an error); anything else
                        # stays visible
                        warnings.filterwarnings(
                            "ignore",
                            message="disaggregated prefill tier is DEAD",
                        )
                        result = drv.run()
            else:
                drv = WorkloadDriver(sessions[0], trace, clock=vc,
                                     telemetry=tel)
                result = drv.run()
            total_s = time.time() - t_start
            report = score(result, tel, bucket_steps=bucket_steps)
            trace_out = _trace_out_path()
            if trace_out and registry is not None:
                # measured pass only (the warmup pass would overwrite the
                # real timeline with compile-dominated spans)
                tel.export_chrome_trace(trace_out)
                print(f"chrome trace -> {trace_out}", file=sys.stderr)
        return result, report, total_s

    run_once()  # warmup / compile pass over every program the trace touches
    base_snap = default_registry().snapshot()
    result, report, total_s = run_once(default_registry())
    snap = default_registry().snapshot()
    res = {
        "decode_tok_s": round(report.total_tokens / total_s, 2),
        "goodput_tok_s": round(report.slo_met_tokens / total_s, 2),
        "slo_attainment": report.attainment,
        "slo_attainment_by_tenant": report.attainment_by_tenant,
        "slo_misses": report.misses_by_kind,
        "slo_met_tokens": report.slo_met_tokens,
        "total_tokens": report.total_tokens,
        "n_requests": len(trace.arrivals),
        "n_replicas": len(apps),
        "virtual_steps": result.steps,
        "backlog_refusals": result.backlog_refusals,
        "goodput_series": report.series,
        "workload_digest": trace.digest(),
        # containment deltas (PR 7 convention), backlog EXCLUDED from
        # rejected — the open-loop rows intend backlog refusals
        "rejected": _counter_delta(
            snap, base_snap, "nxdi_requests_rejected_total",
            exclude_reasons=("backlog",),
        ) + _counter_delta(snap, base_snap, "nxdi_router_rejected_total"),
        "backlog_rejected": _counter_delta(
            snap, base_snap, "nxdi_requests_rejected_total",
        ) - _counter_delta(
            snap, base_snap, "nxdi_requests_rejected_total",
            exclude_reasons=("backlog",),
        ),
        "quarantined": _counter_delta(
            snap, base_snap, "nxdi_rows_quarantined_total"),
        "preempted": _counter_delta(
            snap, base_snap, "nxdi_requests_preempted_total"),
    }
    if prefill_apps:
        res["n_prefill_replicas"] = len(prefill_apps)
        res["handoff_failures"] = _counter_delta(
            snap, base_snap, "nxdi_handoff_failures_total")
        res["handoff_local_prefill"] = _counter_delta(
            snap, base_snap, "nxdi_handoff_local_prefill_total")
    if chaos is not None:
        res["chaos"] = result.chaos
        res["failover"] = _counter_delta(
            snap, base_snap, "nxdi_router_failovers_total")
        dip = report.dip
        res["goodput_dip_frac"] = dip.dip_frac if dip else None
        res["goodput_recovery_steps"] = (
            dip.recovery_steps if dip else None
        )
    return res


def _suite_params(tiny):
    if tiny:
        attrs_1b = attrs_8b = TINY
        prompt, gen, long_prompt = 16, 8, 32
        seq, ce, tkg = 64, [16, 32], [32, 64]
        ce4, tkg4 = [16], [32]
        serving = dict(n_requests=3, prompt=12, gen=6, seq=64,
                       blocks=24, block_size=16, max_seqs=4, q_tile=16)
        lc = dict(prompt=48, gen=8, seq=64, ce=[48], tkg=[64])
        mc = dict(prompt=32, gen=8, seq=64, ce=[32], tkg=[64])
        # open-loop goodput workloads (ISSUE 14): generous SLOs on the CPU
        # harness — the clean row must pin slo_attainment == 1.0; the burst
        # row's on/off arrivals overrun the 4 slots so backlog refusals
        # actually happen; the chaos row needs sustained decode so the
        # seeded replica kill lands mid-stream
        wl = dict(seed=14, n_requests=8, rate=1.5, arrival_kind="poisson",
                  shared_prefix_len=8, max_prompt_len=16,
                  min_output_len=4, max_output_len=8,
                  ttft_slo_s=1e4, itl_slo_s=1e3)
        wl_burst = dict(seed=14, n_requests=10, rate=4.0,
                        arrival_kind="onoff", shared_prefix_len=8,
                        max_prompt_len=16, min_output_len=4,
                        max_output_len=8, ttft_slo_s=1e4, itl_slo_s=1e3)
        wl_chaos = dict(seed=14, n_requests=14, rate=1.0,
                        arrival_kind="poisson", shared_prefix_len=8,
                        max_prompt_len=16, min_output_len=12,
                        max_output_len=16, ttft_slo_s=1e4, itl_slo_s=1e3)
        chaos_kill = 8
    else:
        attrs_1b, attrs_8b = LLAMA_1B, LLAMA_8B
        prompt, gen, long_prompt = 128, 256, 512
        seq, ce, tkg = 1024, [128, 512], [512, 1024]
        ce4, tkg4 = [128], [512]
        serving = dict(n_requests=8, prompt=128, gen=128, seq=1024,
                       blocks=512, block_size=32, max_seqs=8)
        # 16k long-context point (VERDICT r5 weak #5): 1B shape, ~1 GB KV
        # ((B+1)=2 cache rows x 16896 x 8 kv heads x 64 x k+v x 16 layers
        # x bf16) — validates the retuned + head-packed prefill tiles at the
        # length where attention dominates. The 8k point pairs with it so the
        # bf16 vs *_kvq8 rows isolate the KV DMA term at both depths.
        # TKG buckets are 512-ALIGNED (8704 = 17*512, 16896 = 33*512) so the
        # TKG decode kernel is shape-eligible (use_tkg_kernel requires
        # kv_width % 512 == 0 — the old 16448 bucket silently pinned the
        # native gather path for long-context decode).
        lc = dict(prompt=16384, gen=32, seq=16896, ce=[16384], tkg=[16896])
        mc = dict(prompt=8192, gen=32, seq=8704, ce=[8192], tkg=[8704])
        # open-loop goodput workloads (ISSUE 14): hardware-scale traces.
        # SLOs stay generous for the clean row's attainment==1.0 contract;
        # SLO-sweep exploration (tight TTFT under burst) is an operator
        # exercise over the same seeded traces (docs/WORKLOADS.md)
        wl = dict(seed=14, n_requests=24, rate=2.0, arrival_kind="poisson",
                  shared_prefix_len=32, max_prompt_len=128,
                  min_output_len=32, max_output_len=128,
                  ttft_slo_s=1e4, itl_slo_s=1e3)
        wl_burst = dict(seed=14, n_requests=32, rate=8.0,
                        arrival_kind="onoff", shared_prefix_len=32,
                        max_prompt_len=128, min_output_len=32,
                        max_output_len=128, ttft_slo_s=1e4, itl_slo_s=1e3)
        wl_chaos = dict(seed=14, n_requests=32, rate=2.0,
                        arrival_kind="poisson", shared_prefix_len=32,
                        max_prompt_len=128, min_output_len=64,
                        max_output_len=128, ttft_slo_s=1e4, itl_slo_s=1e3)
        chaos_kill = 16
    return {
        # ORDER = budget priority: the headline first (its number is the
        # contract), then cheap points, the serving point, and the expensive
        # 8B transfer-bound point last.
        "bf16_1b_bs1": dict(
            attrs=attrs_1b, batch=1, seq=seq, ce=ce, tkg=tkg,
            prompt=prompt, gen=gen, long_prompt=long_prompt, quantized=False,
            cache_key="bf16_1b" if not tiny else None,
        ),
        "bf16_1b_bs4": dict(
            attrs=attrs_1b, batch=4, seq=seq, ce=ce4, tkg=tkg4,
            prompt=prompt, gen=gen, long_prompt=None, quantized=False,
            cache_key="bf16_1b" if not tiny else None,
        ),
        "int8_1b_bs1": dict(
            attrs=attrs_1b, batch=1, seq=seq, ce=ce[:1], tkg=tkg[:1],
            prompt=prompt, gen=gen, long_prompt=None, quantized=True,
            cache_key="int8_1b" if not tiny else None,
        ),
        # shares the int8_1b presharded artifact: same model/dtype/recipe —
        # only the KV layout differs, which is not part of the artifact
        "serving_1b_int8": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            cache_key="int8_1b" if not tiny else None,
        ),
        # SAME request mix through the ragged mixed-step dispatch (ISSUE 6):
        # one ragged dispatch per step replaces the CTE/TKG pair — the pair
        # of rows is the split-vs-ragged serving comparison for the next
        # hardware session. Own artifact key: serving_ragged is part of the
        # config fingerprint, so sharing int8_1b's would thrash it.
        # serving_ragged_async pinned OFF here: this is the SYNCHRONOUS
        # ragged row the *_ragged_async row below is measured against.
        "serving_1b_int8_ragged": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            extra_tpu=dict(serving_ragged=True, serving_ragged_async=False),
            cache_key="int8_1b_ragged" if not tiny else None,
        ),
        # SAME ragged mix with grouped-int4 weights (ISSUE 17): the serving
        # side of the weight-streaming pair — decode slots stream packed
        # int4 projections while prefill rides the same ragged dispatch.
        # Beside serving_1b_int8_ragged this isolates the weight-bandwidth
        # term under a mixed CE+TKG serving load. Own artifact key:
        # weight_dtype is part of the config fingerprint.
        "serving_1b_int4_ragged": dict(
            attrs=attrs_1b, quantized=False, serving=serving,
            extra_tpu=dict(weight_dtype="int4", serving_ragged=True,
                           serving_ragged_async=False),
            cache_key="int4_1b_ragged" if not tiny else None,
        ),
        # SAME mix again with async 1-ahead pipelining on the ragged path
        # (ISSUE 8): step k+1 chains on step k's on-device tokens, the fetch
        # is non-blocking, host bookkeeping overlaps the device — the
        # ragged_async_* keys vs ragged_* quantify the overlap win and
        # serving_host_frac localizes what host gap remains.
        "serving_1b_int8_ragged_async": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            extra_tpu=dict(serving_ragged=True, serving_ragged_async=True),
            cache_key="int8_1b_ragged_async" if not tiny else None,
        ),
        # SAME mix with speculative verification packed INTO the ragged
        # mixed dispatch (ISSUE 12, serving_spec_ragged): one
        # mixed_step_spec launch per step serves prefill + decode +
        # spec-verify rows; draft proposals and the accepted-token frontier
        # chain device-side; draft length adapts per request. The 4-layer
        # 1B-width draft shape is shared with the acceptance-parameterized
        # projection (device_model.LLAMA_1B_DRAFT4). Own artifact key:
        # serving_spec_ragged + speculation_length are in the fingerprint.
        "serving_1b_int8_spec_ragged": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            spec=dict(
                speculation_length=4,
                draft_attrs=TINY if tiny else LLAMA_1B_DRAFT4,
                draft_cache_key="int8_1b_draft4" if not tiny else None,
            ),
            extra_tpu=dict(serving_ragged=True, serving_ragged_async=True,
                           serving_spec_ragged=True, speculation_length=4),
            cache_key="int8_1b_spec_ragged" if not tiny else None,
        ),
        # SAME mix routed over 2 single-chip replicas by ServingRouter
        # (ISSUE 10): the scale-out row. On a 1-chip host both replicas
        # share the chip (correct, serialized — the row then measures the
        # router layer's overhead); with 2+ chips each replica gets its own
        # device partition and router_tok_s is the data-parallel scale-out
        # number. Shares the int8_1b serving artifact (identical model
        # config; the router is a layer above the session).
        "serving_1b_int8_router": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            router=dict(replicas=2, policy="least_loaded",
                        n_requests=4 if tiny else 8),
            cache_key="int8_1b" if not tiny else None,
        ),
        # SAME routed mix with THREAD-PER-REPLICA stepping (ISSUE 13,
        # TpuConfig.router_threading): every alive replica's step()
        # dispatches from a persistent worker pool behind a per-step
        # barrier, so replica device steps overlap instead of
        # host-serializing. Beside the sequential router row this pair is
        # the threading win: router_threaded_tok_s vs router_tok_s, and
        # router_step_overlap_frac (from the nxdi_replica_step_ms
        # histograms + the router-step span) measures how much of the
        # per-replica step wall actually overlapped (0 = serialized,
        # 0.5 = two replicas fully concurrent). Own artifact key:
        # router_threading is part of the config fingerprint.
        "serving_1b_int8_router_threaded": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            router=dict(replicas=2, policy="least_loaded",
                        n_requests=4 if tiny else 8),
            extra_tpu=dict(router_threading=True),
            cache_key="int8_1b_router_threaded" if not tiny else None,
        ),
        # SAME routed mix with a DISAGGREGATED PREFILL TIER (ISSUE 15,
        # TpuConfig.router_prefill_replicas): one dedicated prefill replica
        # context-encodes every prompt and hands the populated KV over to
        # the 2 decode replicas — no decode replica ever runs a prefill, so
        # long-prompt bursts cannot stall co-located decode ITL. The
        # hand-off needs the CONTIGUOUS cache (whole-line scatter), so this
        # row runs the contiguous serving config; its containment deltas
        # must be 0/0/0 on clean traffic AND handoffs == requests with
        # ZERO hand-off failures / local-prefill fallbacks (the tier's
        # zero-containment-events proof). Own artifact keys: the stage
        # split is part of the config fingerprint.
        "serving_1b_int8_disagg": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            router=dict(replicas=2, policy="least_loaded",
                        n_requests=4 if tiny else 8),
            disagg=dict(prefill_replicas=1),
            cache_key="int8_1b_disagg" if not tiny else None,
        ),
        # SAME routed mix under an ELASTIC fleet (ISSUE 20): at a seeded
        # step mid-drain one replica is RETIRED (placement stops, its owned
        # requests drain in place, worker joined on finalize) and a fresh
        # session over the same warmed app re-joins via add_replica (the
        # jit cache is per-app — zero recompiles). The elastic_* census
        # pins attainment == 1.0 with ZERO leaked KV blocks/threads — the
        # scale-in/scale-out path is free under clean traffic, exactly
        # what the lifecycle audit (LIFE801/804/805) licenses statically.
        # Shares the int8_1b serving artifact (identical model config; the
        # elastic machinery is router bookkeeping above the session).
        "serving_1b_int8_elastic": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            router=dict(replicas=2, policy="least_loaded",
                        n_requests=4 if tiny else 8),
            elastic=dict(retire_step=2),
            cache_key="int8_1b" if not tiny else None,
        ),
        # Open-loop SLO goodput rows (ISSUE 14, docs/WORKLOADS.md): a seeded
        # workload trace (Poisson / bursty arrivals, heavy-tailed lengths,
        # shared-prefix tenants) drives the SAME serving config through the
        # WorkloadDriver on a virtual clock, scored as goodput-under-SLO
        # (tokens from TTFT/ITL-met requests) instead of drain tok/s. The
        # clean row pins slo_attainment == 1.0 under generous SLOs; the
        # burst row's on/off arrival bursts overrun the slot count, so the
        # driver backlog (and its refusal census) actually engages; the
        # chaos row routes over 2 replicas and kills one mid-run (seeded),
        # scored as goodput-dip depth + recovery time off the time-bucketed
        # goodput series. Shares the int8_1b serving artifact (identical
        # model config — the workload layer sits above the session).
        "serving_1b_int8_goodput": dict(
            attrs=attrs_1b, quantized=True, serving=serving, workload=wl,
            cache_key="int8_1b" if not tiny else None,
        ),
        "serving_1b_int8_goodput_burst": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            workload=wl_burst,
            cache_key="int8_1b" if not tiny else None,
        ),
        "serving_1b_int8_goodput_chaos": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            workload=wl_chaos,
            chaos=dict(replicas=2, kill_step=chaos_kill),
            cache_key="int8_1b" if not tiny else None,
        ),
        # the standing DISAGGREGATED chaos row (ISSUE 15): the same seeded
        # open-loop trace over 2 decode replicas + 1 prefill replica, with
        # the chaos kill aimed at the PREFILL TIER mid-run. Decode capacity
        # survives — placements degrade to local monolithic prefill (the
        # loud nxdi_handoff_local_prefill_total census) — so the pinned
        # claim is containment: attainment holds, goodput recovers finitely
        # against the FULL baseline (alive_frac 1.0), nothing wedges.
        "serving_1b_int8_disagg_chaos": dict(
            attrs=attrs_1b, quantized=True, serving=serving,
            workload=wl_chaos,
            chaos=dict(replicas=2, kill_step=chaos_kill, tier="prefill"),
            disagg=dict(prefill_replicas=1),
            cache_key="int8_1b_disagg" if not tiny else None,
        ),
        # single-chip proxy for the BASELINE 8B north star: int8 8B fits 16G
        "int8_8b_bs1": dict(
            attrs=attrs_8b, batch=1, seq=seq, ce=ce[:1], tkg=tkg[:1],
            prompt=prompt, gen=gen, long_prompt=None, quantized=True,
            cache_key="int8_8b" if not tiny else None,
        ),
        # int4 weight-streaming flagship (ISSUE 17): the SAME 8B shape with
        # grouped-int4 packed weights (weight_dtype="int4") — decode streams
        # ~0.53 byte/param (codes + group scales) through the fused-dequant
        # quant_matmul kernel, vs int8's 1 byte. Beside int8_8b_bs1 this
        # pair is the weight-bandwidth halving measured where decode is
        # weight-bound. Own artifact key: weight_dtype joins the config
        # fingerprint, so sharing int8_8b's would thrash it.
        "bf16_8b_int4": dict(
            attrs=attrs_8b, batch=1, seq=seq, ce=ce[:1], tkg=tkg[:1],
            prompt=prompt, gen=gen, long_prompt=None, quantized=False,
            extra_tpu=dict(weight_dtype="int4"),
            cache_key="bf16_8b_int4" if not tiny else None,
        ),
        # LAST in budget priority: the expensive long-context points are the
        # first casualties of a tight BENCH_BUDGET_S (skippable by design).
        # The 8k/16k bf16 vs *_kvq8 pairs report kv_bytes + decode tok/s so
        # the KV-quant bandwidth win is measured where KV DMA dominates.
        "bf16_1b_8k": dict(
            attrs=attrs_1b, batch=1, seq=mc["seq"], ce=mc["ce"],
            tkg=mc["tkg"], prompt=mc["prompt"], gen=mc["gen"],
            long_prompt=None, quantized=False,
            cache_key="bf16_1b" if not tiny else None,
        ),
        "bf16_1b_8k_kvq8": dict(
            attrs=attrs_1b, batch=1, seq=mc["seq"], ce=mc["ce"],
            tkg=mc["tkg"], prompt=mc["prompt"], gen=mc["gen"],
            long_prompt=None, quantized=False,
            extra_tpu=dict(kv_cache_dtype="int8"),
            cache_key="bf16_1b" if not tiny else None,
        ),
        "bf16_1b_16k": dict(
            attrs=attrs_1b, batch=1, seq=lc["seq"], ce=lc["ce"],
            tkg=lc["tkg"], prompt=lc["prompt"], gen=lc["gen"],
            long_prompt=None, quantized=False,
            cache_key="bf16_1b" if not tiny else None,
        ),
        "bf16_1b_16k_kvq8": dict(
            attrs=attrs_1b, batch=1, seq=lc["seq"], ce=lc["ce"],
            tkg=lc["tkg"], prompt=lc["prompt"], gen=lc["gen"],
            long_prompt=None, quantized=False,
            extra_tpu=dict(kv_cache_dtype="int8"),
            cache_key="bf16_1b" if not tiny else None,
        ),
    }


def _device_spec(tiny):
    """The DeviceSpec of the chip this process runs on. On a measuring run
    an unknown device is an error; the ``tiny`` CPU path (tests) gets None —
    its rows are counts, projected against the registry default and carrying
    no model error."""
    if not tiny:
        return _require_chip()
    import jax

    from neuronx_distributed_inference_tpu.analysis import device_model

    return device_model.resolve_device(jax.devices()[0].device_kind)


def _attach_projection(res, attrs, *, batch, kv_width, quantized, extra_tpu,
                       tiny, scale=1):
    """Static roofline projection beside the measured row (ISSUE 11):
    ``projected_tok_s`` is the device-model lower-bound ceiling for this
    row's shape on the chip the run is on, and ``model_error_frac`` =
    measured/projected - 1. Only the ``tiny`` CPU path may run on a device
    the registry does not know: it projects against the registry default
    and reports a null error, since an error against a chip the run never
    touched means nothing.

    ``scale``: aggregate multiplier for multi-mesh rows (the router point
    passes the count of NON-overlapping replica meshes — replicas sharing
    one chip split its HBM stream and add no ceiling). Applied only when
    the device RESOLVES to a registry chip: the CPU harness's virtual
    partitions share one host, so its projection stays the committed
    single-chip number (`device_model.BENCH_ROW_MODELS` / --compare)."""
    from neuronx_distributed_inference_tpu.analysis import device_model

    spec = _device_spec(tiny)
    proj = device_model.decode_projection(
        attrs,
        batch=batch,
        kv_width=kv_width,
        # explicit weight_dtype (the int4 rows) wins over the quantized flag
        weight_dtype=(extra_tpu or {}).get(
            "weight_dtype", "int8" if quantized else "bfloat16"
        ),
        kv_dtype=(extra_tpu or {}).get("kv_cache_dtype", "bfloat16"),
        device=spec,  # None (tiny CPU path only) -> DEFAULT_DEVICE inside
    )
    projected = proj["tok_s"] * (scale if spec is not None else 1)
    res["projected_tok_s"] = round(projected, 2)
    res["model_error_frac"] = (
        round(res["decode_tok_s"] / projected - 1.0, 4)
        if spec is not None and res.get("decode_tok_s")
        else None
    )
    return res


def run_point(name, tiny=False):
    """Build + measure one benchmark point in THIS process."""
    import jax

    p = _suite_params(tiny)[name]

    def _disagg_fleet(s, n_decode):
        """(decode apps, prefill apps) for a disaggregated-tier row: the
        hand-off scatters whole cache lines, so BOTH stages run the
        CONTIGUOUS cache (no block_kv); each replica gets its own device
        partition, prefill replicas after the decode ones."""
        from neuronx_distributed_inference_tpu.runtime.router import (
            partition_devices,
        )

        n_pre = p["disagg"]["prefill_replicas"]
        parts = partition_devices(n_decode + n_pre)
        contiguous = dict(is_continuous_batching=True, ctx_batch_size=1)
        ck = p.get("cache_key")
        decode = [
            build_app(
                p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
                ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
                quantized=p["quantized"], cache_key=ck,
                extra_tpu={**contiguous, **(p.get("extra_tpu") or {})},
                devices=parts[i],
            )
            for i in range(n_decode)
        ]
        prefill = [
            build_app(
                p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
                ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
                quantized=p["quantized"],
                cache_key=f"{ck}_pre" if ck else None,
                extra_tpu={**contiguous, "is_prefill_stage": True,
                           **(p.get("extra_tpu") or {})},
                devices=parts[n_decode + i],
            )
            for i in range(n_pre)
        ]
        return decode, prefill

    if "workload" in p:
        from neuronx_distributed_inference_tpu.runtime.router import (
            partition_devices,
        )

        s = p["serving"]
        ch = p.get("chaos")
        n_apps = ch["replicas"] if ch else 1
        if "disagg" in p:
            apps, prefill_apps = _disagg_fleet(s, n_apps)
        else:
            prefill_apps = None
            parts = partition_devices(n_apps) if n_apps > 1 else [None]
            apps = [
                build_app(
                    p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
                    ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
                    quantized=p["quantized"], cache_key=p.get("cache_key"),
                    block_kv=dict(num_blocks=s["blocks"],
                                  block_size=s["block_size"],
                                  max_seqs=s["max_seqs"]),
                    extra_tpu=p.get("extra_tpu"), devices=parts[i],
                )
                for i in range(n_apps)
            ]
        res = measure_goodput(
            apps, workload=p["workload"],
            chaos_kill_step=ch["kill_step"] if ch else None,
            chaos_tier=(ch or {}).get("tier", "decode"),
            prefill_apps=prefill_apps,
        )
        # same aggregate decode ceiling as the closed-loop serving rows:
        # goodput <= throughput <= the device projection
        _attach_projection(
            res, p["attrs"], tiny=tiny, batch=s["max_seqs"], kv_width=s["seq"],
            quantized=p["quantized"], extra_tpu=p.get("extra_tpu"),
        )
    elif "router" in p:
        from neuronx_distributed_inference_tpu.runtime.router import (
            partition_devices,
        )

        s, r = p["serving"], p["router"]
        if "disagg" in p:
            apps, prefill_apps = _disagg_fleet(s, r["replicas"])
            parts = partition_devices(
                r["replicas"] + p["disagg"]["prefill_replicas"]
            )[: r["replicas"]]
        else:
            prefill_apps = None
            parts = partition_devices(r["replicas"])
            apps = [
                build_app(
                    p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
                    ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
                    quantized=p["quantized"], cache_key=p.get("cache_key"),
                    block_kv=dict(num_blocks=s["blocks"],
                                  block_size=s["block_size"],
                                  max_seqs=s["max_seqs"]),
                    extra_tpu=p.get("extra_tpu"), devices=parts[i],
                )
                for i in range(r["replicas"])
            ]
        res = measure_router(
            apps, n_requests=r["n_requests"], prompt_len=s["prompt"],
            gen_len=s["gen"], policy=r["policy"],
            prefill_apps=prefill_apps, elastic=p.get("elastic"),
        )
        # router ceiling: each replica serves its share of the mix and
        # streams its OWN weight copy, so the aggregate scales with the
        # number of non-overlapping replica meshes (1 on a shared chip,
        # = replicas when each replica has its own chip/partition)
        distinct = len({d.id for part in parts for d in part})
        meshes = max(1, distinct // max(1, len(parts[0])))
        rows_per_replica = max(1, r["n_requests"] // r["replicas"])
        _attach_projection(
            res, p["attrs"], tiny=tiny, batch=rows_per_replica, kv_width=s["seq"],
            quantized=p["quantized"], extra_tpu=p.get("extra_tpu"),
            scale=min(meshes, r["replicas"]),
        )
    elif "spec" in p:
        from neuronx_distributed_inference_tpu.analysis import device_model

        s, sp = p["serving"], p["spec"]
        k = sp["speculation_length"]
        target = build_app(
            p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
            ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
            quantized=p["quantized"], cache_key=p.get("cache_key"),
            block_kv=dict(num_blocks=s["blocks"], block_size=s["block_size"],
                          max_seqs=s["max_seqs"], q_tile=s.get("q_tile", 128)),
            extra_tpu=p.get("extra_tpu"),
        )
        # the DRAFT app: contiguous cache, same slot count / decode reach
        # (the spec session's construction contract)
        draft = build_app(
            sp["draft_attrs"], batch=s["max_seqs"], seq_len=s["seq"],
            ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
            quantized=p["quantized"], cache_key=sp.get("draft_cache_key"),
            extra_tpu=dict(is_continuous_batching=True, ctx_batch_size=1),
        )
        res = measure_serving_spec(
            target, draft, n_requests=s["n_requests"], prompt_len=s["prompt"],
            gen_len=s["gen"], k=k,
        )
        # acceptance-parameterized ceiling (ISSUE 12): re-projected at the
        # MEASURED acceptance rate so the recorded ceiling describes the
        # workload this run actually saw (falls back to the committed 0.8
        # operating point when no spec round ran)
        spec_dev = _device_spec(tiny)
        proj = device_model.spec_decode_projection(
            p["attrs"], batch=s["max_seqs"], kv_width=s["seq"],
            acceptance=(
                res["spec_acceptance"] if res.get("spec_acceptance") is not None
                else 0.8
            ),
            draft_len=k - 1, draft_attrs=sp["draft_attrs"],
            weight_dtype="int8" if p["quantized"] else "bfloat16",
            kv_dtype=(p.get("extra_tpu") or {}).get("kv_cache_dtype", "bfloat16"),
            device=spec_dev,
        )
        res["projected_tok_s"] = round(proj["tok_s"], 2)
        res["model_error_frac"] = (
            round(res["decode_tok_s"] / proj["tok_s"] - 1.0, 4)
            if spec_dev is not None and res.get("decode_tok_s")
            else None
        )
    elif "serving" in p:
        s = p["serving"]
        app = build_app(
            p["attrs"], batch=s["max_seqs"], seq_len=s["seq"],
            ce_buckets=[s["seq"]], tkg_buckets=[s["seq"]],
            quantized=p["quantized"], cache_key=p.get("cache_key"),
            block_kv=dict(num_blocks=s["blocks"], block_size=s["block_size"],
                          max_seqs=s["max_seqs"]),
            extra_tpu=p.get("extra_tpu"),
        )
        res = measure_serving(
            app, n_requests=s["n_requests"], prompt_len=s["prompt"],
            gen_len=s["gen"],
        )
        # aggregate decode ceiling at the full slot count / serving bucket
        _attach_projection(
            res, p["attrs"], tiny=tiny, batch=s["max_seqs"], kv_width=s["seq"],
            quantized=p["quantized"], extra_tpu=p.get("extra_tpu"),
        )
    else:
        app = build_app(
            p["attrs"], batch=p["batch"], seq_len=p["seq"], ce_buckets=p["ce"],
            tkg_buckets=p["tkg"], quantized=p["quantized"],
            cache_key=p.get("cache_key"), extra_tpu=p.get("extra_tpu"),
        )
        res = measure_point(
            app, batch=p["batch"], prompt_len=p["prompt"], gen_len=p["gen"],
            long_prompt=p["long_prompt"],
        )
        # the measured decode runs at the bucket covering prompt+gen
        ctx = p["prompt"] + p["gen"]
        kv_w = min([b for b in p["tkg"] if b >= ctx] or [max(p["tkg"])])
        _attach_projection(
            res, p["attrs"], tiny=tiny, batch=p["batch"], kv_width=kv_w,
            quantized=p["quantized"], extra_tpu=p.get("extra_tpu"),
        )
    res["device"] = str(jax.devices()[0])
    return res


def summary_line(points):
    """The machine-readable summary over whatever points exist so far.
    Keys are stable; not-yet-run points contribute null fields."""

    def g(name, key):
        return points.get(name, {}).get(key)

    headline = g("bf16_1b_bs1", "decode_tok_s")
    return {
        "metric": "llama3.2-1b-bf16 decode throughput (bs=1, 1 chip)",
        "value": headline,
        "unit": "tokens/sec",
        "vs_baseline": (
            round(headline / BASELINE_1B, 4) if headline else None
        ),
        # static roofline projection (ISSUE 11): the device-model ceiling
        # for the headline row and its measured error — model_error_frac is
        # null on a host whose device doesn't resolve to a registry spec
        # (the CPU harness) and populated on hardware
        "projected_tok_s": g("bf16_1b_bs1", "projected_tok_s"),
        "model_error_frac": g("bf16_1b_bs1", "model_error_frac"),
        "ttft_ms": g("bf16_1b_bs1", "ttft_ms"),
        "prefill_tok_s": g("bf16_1b_bs1", "prefill_tok_s"),
        "decode_bs4_tok_s": g("bf16_1b_bs4", "decode_tok_s"),
        "int8_1b_tok_s": g("int8_1b_bs1", "decode_tok_s"),
        "int8_1b_ttft_ms": g("int8_1b_bs1", "ttft_ms"),
        "serving_tok_s": g("serving_1b_int8", "decode_tok_s"),
        # the serving rows' aggregate device ceiling + measured error: the
        # measured-vs-predicted pair hardware session zero closes on (the
        # CPU harness carries the projection with a null error)
        "serving_projected_tok_s": g("serving_1b_int8", "projected_tok_s"),
        "serving_model_error_frac": g("serving_1b_int8", "model_error_frac"),
        # TTFT/ITL sourced from the runtime telemetry traces (not bench
        # stopwatches): the numbers production serving would report
        "serving_ttft_p50_ms": g("serving_1b_int8", "ttft_ms"),
        "serving_ttft_p99_ms": g("serving_1b_int8", "ttft_p99_ms"),
        "serving_itl_p50_ms": g("serving_1b_int8", "itl_ms"),
        "serving_itl_p99_ms": g("serving_1b_int8", "itl_p99_ms"),
        # ragged mixed-step serving row (ISSUE 6): same request mix, ONE
        # ragged dispatch per step — compare against serving_* above; the
        # padded-token fraction quantifies the packing efficiency the
        # per-phase split was throwing away
        "ragged_tok_s": g("serving_1b_int8_ragged", "decode_tok_s"),
        "ragged_ttft_p50_ms": g("serving_1b_int8_ragged", "ttft_ms"),
        "ragged_ttft_p99_ms": g("serving_1b_int8_ragged", "ttft_p99_ms"),
        "ragged_itl_p50_ms": g("serving_1b_int8_ragged", "itl_ms"),
        "ragged_itl_p99_ms": g("serving_1b_int8_ragged", "itl_p99_ms"),
        "ragged_padded_frac": g("serving_1b_int8_ragged", "padded_token_frac"),
        # async-pipelined ragged serving row (ISSUE 8): same mix, 1-ahead
        # chained dispatch + non-blocking fetch — compare against the
        # ragged_* (sync) row; serving_host_frac is the measured host-gap
        # share of step wall time on the pipelined path
        "ragged_async_tok_s": g("serving_1b_int8_ragged_async", "decode_tok_s"),
        "ragged_async_itl_p50_ms": g("serving_1b_int8_ragged_async", "itl_ms"),
        "ragged_async_ttft_p50_ms": g("serving_1b_int8_ragged_async", "ttft_ms"),
        "serving_host_frac": g("serving_1b_int8_ragged_async", "host_frac"),
        # spec-ragged serving row (ISSUE 12): verification inside the mixed
        # dispatch. spec_ragged_acceptance is the MEASURED per-draft
        # acceptance rate (random weights => ~0: the worst-case overhead
        # bound); spec_ragged_projected_tok_s is the acceptance-
        # parameterized ceiling re-projected at that measured rate, which
        # --compare prefers over the static 0.8-acceptance table row
        "spec_ragged_tok_s": g("serving_1b_int8_spec_ragged", "decode_tok_s"),
        "spec_ragged_acceptance": g("serving_1b_int8_spec_ragged",
                                    "spec_acceptance"),
        "spec_ragged_itl_p50_ms": g("serving_1b_int8_spec_ragged", "itl_ms"),
        "spec_ragged_projected_tok_s": g("serving_1b_int8_spec_ragged",
                                         "projected_tok_s"),
        # fault-containment census (ISSUE 7), sourced from the telemetry
        # registry over the measured serving run: clean traffic MUST report
        # 0/0/0 — the containment layer's ~0-overhead proof the first
        # hardware session checks before flipping any policy knob
        "serving_rejected": g("serving_1b_int8", "rejected"),
        "serving_quarantined": g("serving_1b_int8", "quarantined"),
        "serving_preempted": g("serving_1b_int8", "preempted"),
        # multi-replica router row (ISSUE 10): same mix over 2 replica
        # sessions via ServingRouter — router_failover MUST be 0 on clean
        # traffic (per-run delta, PR 7 convention) and router_balance_frac
        # (min-replica tokens / even share) is the placement-policy quality
        # number the first multi-chip session compares policies by
        "router_tok_s": g("serving_1b_int8_router", "decode_tok_s"),
        # the router row's projection carries its mesh-count scaling, which
        # the static --compare table cannot know — recorded here so the
        # offline report uses the run's own ceiling
        "router_projected_tok_s": g("serving_1b_int8_router", "projected_tok_s"),
        "router_failover": g("serving_1b_int8_router", "failover"),
        "router_balance_frac": g("serving_1b_int8_router", "balance_frac"),
        # thread-per-replica router row (ISSUE 13): same routed mix with
        # router_threading on — compare router_threaded_tok_s against
        # router_tok_s for the threading win, and router_step_overlap_frac
        # (replica-step histograms vs the router-step span) for how much of
        # the per-replica step wall actually ran concurrently. On a 1-chip
        # host both replicas share the device, so the overlap a chip-per-
        # replica deployment would convert to tok/s is the hardware
        # session's number to confirm.
        # disaggregated prefill tier (ISSUE 15): the routed mix with every
        # prompt context-encoded on a dedicated prefill replica and handed
        # over; clean traffic pins handoffs == requests and ZERO hand-off
        # failures / local-prefill fallbacks, and the chaos row pins
        # containment under a prefill-tier kill
        "disagg_tok_s": g("serving_1b_int8_disagg", "decode_tok_s"),
        "disagg_handoffs": g("serving_1b_int8_disagg", "handoffs"),
        "disagg_handoff_failures": g("serving_1b_int8_disagg",
                                     "handoff_failures"),
        "disagg_local_prefill": g("serving_1b_int8_disagg",
                                  "handoff_local_prefill"),
        "disagg_chaos_goodput_tok_s": g("serving_1b_int8_disagg_chaos",
                                        "goodput_tok_s"),
        "disagg_chaos_attainment": g("serving_1b_int8_disagg_chaos",
                                     "slo_attainment"),
        "disagg_chaos_local_prefill": g("serving_1b_int8_disagg_chaos",
                                        "handoff_local_prefill"),
        "disagg_chaos_dip_frac": g("serving_1b_int8_disagg_chaos",
                                   "goodput_dip_frac"),
        "disagg_chaos_recovery_steps": g("serving_1b_int8_disagg_chaos",
                                         "goodput_recovery_steps"),
        "router_threaded_tok_s": g("serving_1b_int8_router_threaded",
                                   "decode_tok_s"),
        "router_step_overlap_frac": g("serving_1b_int8_router_threaded",
                                      "overlap_frac"),
        # elastic fleet row (ISSUE 20): seeded retire + add mid-drain —
        # attainment MUST be 1.0 with ZERO leaked KV blocks/threads (the
        # lifecycle audit's leak-freedom contract, measured)
        "elastic_tok_s": g("serving_1b_int8_elastic", "decode_tok_s"),
        "elastic_attainment": g("serving_1b_int8_elastic",
                                "elastic_attainment"),
        "elastic_leaked_blocks": g("serving_1b_int8_elastic",
                                   "elastic_leaked_blocks"),
        "elastic_leaked_threads": g("serving_1b_int8_elastic",
                                    "elastic_leaked_threads"),
        # open-loop SLO goodput rows (ISSUE 14, docs/WORKLOADS.md):
        # goodput_tok_s counts ONLY tokens from requests that met their
        # TTFT/ITL SLOs (measured from arrival — backlog wait counts);
        # slo_attainment pins 1.0 on the clean generous-SLO row; the chaos
        # row reads the seeded replica kill off the time-bucketed goodput
        # series as dip depth + recovery steps
        "goodput_tok_s": g("serving_1b_int8_goodput", "goodput_tok_s"),
        "slo_attainment": g("serving_1b_int8_goodput", "slo_attainment"),
        "goodput_burst_tok_s": g("serving_1b_int8_goodput_burst",
                                 "goodput_tok_s"),
        "goodput_burst_attainment": g("serving_1b_int8_goodput_burst",
                                      "slo_attainment"),
        "goodput_backlog_refusals": g("serving_1b_int8_goodput_burst",
                                      "backlog_refusals"),
        "goodput_chaos_tok_s": g("serving_1b_int8_goodput_chaos",
                                 "goodput_tok_s"),
        "goodput_dip_frac": g("serving_1b_int8_goodput_chaos",
                              "goodput_dip_frac"),
        "goodput_recovery_steps": g("serving_1b_int8_goodput_chaos",
                                    "goodput_recovery_steps"),
        "int8_8b_tok_s": g("int8_8b_bs1", "decode_tok_s"),
        "int8_8b_ttft_ms": g("int8_8b_bs1", "ttft_ms"),
        # grouped-int4 weight-streaming rows (ISSUE 17): the 8B decode pair
        # against int8_8b_tok_s quantifies the weight-bandwidth halving
        # (~0.53 vs 1 byte/param), and the int4 ragged serving row sits
        # beside ragged_tok_s for the mixed-load version. Projections ride
        # the device model's int4 itemsize (codes + group scales).
        "w4_tok_s": g("bf16_8b_int4", "decode_tok_s"),
        "w4_projected_tok_s": g("bf16_8b_int4", "projected_tok_s"),
        "w4_ttft_ms": g("bf16_8b_int4", "ttft_ms"),
        "w4_serving_tok_s": g("serving_1b_int4_ragged", "decode_tok_s"),
        "w4_serving_projected_tok_s": g("serving_1b_int4_ragged",
                                        "projected_tok_s"),
        "w4_serving_itl_p50_ms": g("serving_1b_int4_ragged", "itl_ms"),
        # 16k long-context row: TTFT ~= the 16k prefill wall time
        "long_ctx_ttft_ms": g("bf16_1b_16k", "ttft_ms"),
        "long_ctx_tok_s": g("bf16_1b_16k", "decode_tok_s"),
        # 8k/16k bf16 vs kv-int8 pairs: decode tok/s + true cache bytes
        # (codes + scales) — the *_kvq8 rows must show kv_bytes ~halved
        "ctx8k_tok_s": g("bf16_1b_8k", "decode_tok_s"),
        "ctx8k_kv_bytes": g("bf16_1b_8k", "kv_bytes"),
        "kvq8_8k_tok_s": g("bf16_1b_8k_kvq8", "decode_tok_s"),
        "kvq8_8k_kv_bytes": g("bf16_1b_8k_kvq8", "kv_bytes"),
        "long_ctx_kv_bytes": g("bf16_1b_16k", "kv_bytes"),
        "kvq8_16k_tok_s": g("bf16_1b_16k_kvq8", "decode_tok_s"),
        "kvq8_16k_ttft_ms": g("bf16_1b_16k_kvq8", "ttft_ms"),
        "kvq8_16k_kv_bytes": g("bf16_1b_16k_kvq8", "kv_bytes"),
        "int8_8b_vs_8b_gate": (
            round(g("int8_8b_bs1", "decode_tok_s") / BASELINE_8B_GATE, 4)
            if g("int8_8b_bs1", "decode_tok_s")
            else None
        ),
        "points": {
            n: ("ok" if "decode_tok_s" in p else
                "skipped_budget" if p.get("skipped_budget") else "error")
            for n, p in points.items()
        },
        "device": g("bf16_1b_bs1", "device"),
    }


def _emit(points):
    print(json.dumps(summary_line(points)), flush=True)


def run_suite(tiny=False, emit=None):
    """The full benchmark point set. ``tiny=True`` runs in-process (the CPU
    test suite exercises the identical code path in seconds); otherwise each
    point runs in its own subprocess, so HBM is fully reclaimed between
    points (an int8 8B point cannot share a 16G chip with an earlier resident
    1B model). A chip belongs to ONE process at a time: this parent imports
    jax (through the package) but must never initialise a backend, on any
    route — tests/test_chip_smoke.py pins that for ``_emit``,
    ``--metrics-out`` and ``--ops-port``.

    A later point that crashes or is skipped for budget is recorded in the
    summary AND fails the suite: :func:`suite_failed` drives the exit code.

    ``emit``: callback invoked with the points dict after every point — suite
    mode uses it to re-print the summary line so a driver-side kill at ANY
    moment still leaves a parseable last line (VERDICT r4 #1).
    """
    points = {}
    names = list(_suite_params(tiny))
    budget = _budget_s()
    t_start = time.monotonic()
    if tiny:
        for name in names:
            if name != names[0] and time.monotonic() - t_start > budget:
                points[name] = {"skipped_budget": True}
            else:
                points[name] = run_point(name, tiny=True)
            if emit:
                emit(points)
        return points
    import subprocess

    for name in names:
        elapsed = time.monotonic() - t_start
        if name != names[0] and elapsed > budget:
            points[name] = {"skipped_budget": True, "elapsed_s": round(elapsed, 1)}
            print(f"{name}: skipped (budget {budget:.0f}s)", file=sys.stderr)
            if emit:
                emit(points)
            continue
        # the headline point always gets the full budget; later points get
        # what remains (+ grace — a point that STARTED may finish slightly
        # over budget rather than be killed uselessly)
        remaining = budget if name == names[0] else budget - elapsed
        try:
            proc = subprocess.run(
                [sys.executable, __file__, "--point", name],
                capture_output=True, text=True,
                timeout=max(120.0, remaining + 180.0),
            )
            if proc.returncode != 0:
                print(proc.stderr[-4000:], file=sys.stderr)
                raise RuntimeError(f"bench point {name} failed rc={proc.returncode}")
            points[name] = json.loads(proc.stdout.strip().splitlines()[-1])
        except Exception as e:  # timeout / crash / bad output
            # a timed-out child's partial stderr is the only diagnostic left
            partial = getattr(e, "stderr", None)
            if partial:
                if isinstance(partial, bytes):
                    partial = partial.decode(errors="replace")
                print(partial[-4000:], file=sys.stderr)
            if name == names[0]:
                raise  # no headline -> the suite IS failed
            # recorded so the remaining points still run; main() exits
            # non-zero on it (suite_failed)
            points[name] = {"error": str(e)[:200]}
        print(f"{name}: {points[name]}", file=sys.stderr)
        if emit:
            emit(points)
    return points


def suite_failed(points) -> bool:
    """True when any point errored or was skipped for budget."""
    return any(
        "error" in p or p.get("skipped_budget") for p in points.values()
    )


def _trace_out_path():
    """--trace-out PATH: Chrome trace-event JSON (Perfetto-loadable) of the
    goodput rows' span timeline, written by the measured pass of each
    ``measure_goodput`` call in THIS process (pass it to a --point
    invocation of a goodput row; docs/OBSERVABILITY.md walks the file)."""
    if "--trace-out" in sys.argv:
        i = sys.argv.index("--trace-out")
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return None


def _metrics_out_path():
    """--metrics-out PATH: dump THIS process's telemetry registry snapshot
    at exit (tiny/--point runs carry the serving metrics; the non-tiny suite
    driver itself runs no model, so point subprocesses are where the data
    lives — pass --metrics-out to a --point invocation for a full dump)."""
    if "--metrics-out" in sys.argv:
        i = sys.argv.index("--metrics-out")
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return None


def _dump_metrics(path):
    from neuronx_distributed_inference_tpu.telemetry import default_registry

    with open(path, "w") as f:
        json.dump(default_registry().snapshot(), f, indent=2)
    print(f"metrics snapshot -> {path}", file=sys.stderr)


def _ops_server():
    """--ops-port N: serve this process's live ops surface (/metrics,
    /healthz, /slo — docs/OBSERVABILITY.md) off the process-default registry
    for the duration of the run. Returned as a context manager so the serve
    thread is JOINED even when the run raises mid-drain (the LIFE804
    thread-lifecycle contract); without the flag it is a no-op context."""
    import contextlib

    if "--ops-port" in sys.argv:
        i = sys.argv.index("--ops-port")
        if i + 1 < len(sys.argv):
            from neuronx_distributed_inference_tpu.telemetry import default_registry
            from neuronx_distributed_inference_tpu.telemetry.ops_server import (
                OpsServer,
            )

            return OpsServer(default_registry(), port=int(sys.argv[i + 1]))
    return contextlib.nullcontext()


def main():
    if "--cpu" in sys.argv:
        # the tiny smoke path on a host with no chip (tests): rows are counts
        import jax

        jax.config.update("jax_platforms", "cpu")
    metrics_out = _metrics_out_path()
    with _ops_server() as ops:
        if ops is not None:
            print(f"ops server -> {ops.url}", file=sys.stderr)
        if len(sys.argv) >= 3 and sys.argv[1] == "--point":
            _require_chip()
            print(json.dumps(run_point(sys.argv[2], tiny=False)))
            if metrics_out:
                _dump_metrics(metrics_out)
            return 0
        tiny = "--tiny" in sys.argv
        # suite mode (non-tiny): this parent stays off the chip — each
        # point's subprocess needs it (see run_suite)
        points = run_suite(tiny=tiny, emit=_emit)
        if metrics_out:
            _dump_metrics(metrics_out)
        return 1 if suite_failed(points) else 0


if __name__ == "__main__":
    sys.exit(main())
