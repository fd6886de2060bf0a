"""Latency/throughput benchmarking with the reference's report schema.

Reference: utils/benchmark.py — ``benchmark_sampling`` (:21), per-submodel
latency collectors via pre/post hooks (:380-430), ``Benchmark`` warmup+N runs
(:432), ``generate_report`` p50/p90/p95/p99/p100/avg + throughput (:479-499),
written to benchmark_report.json.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCHMARK_REPORT_FILENAME = "benchmark_report.json"


def percentile_report(latencies_s: List[float]) -> Dict[str, float]:
    """Latency percentile block (reference generate_report, benchmark.py:479-499)."""
    lat_ms = np.asarray(latencies_s) * 1e3
    return {
        "latency_ms_p50": float(np.percentile(lat_ms, 50)),
        "latency_ms_p90": float(np.percentile(lat_ms, 90)),
        "latency_ms_p95": float(np.percentile(lat_ms, 95)),
        "latency_ms_p99": float(np.percentile(lat_ms, 99)),
        "latency_ms_p100": float(np.percentile(lat_ms, 100)),
        "latency_ms_avg": float(np.mean(lat_ms)),
    }


class Benchmark:
    """Warmup-then-N-runs timer (reference Benchmark, benchmark.py:432-477)."""

    def __init__(self, benchmark_func: Callable, num_runs: int = 20, warmup_runs: int = 3):
        self.benchmark_func = benchmark_func
        self.num_runs = num_runs
        self.warmup_runs = warmup_runs
        self.latencies: List[float] = []

    def run(self) -> List[float]:
        for _ in range(self.warmup_runs):
            self.benchmark_func()
        self.latencies = []
        for _ in range(self.num_runs):
            t0 = time.perf_counter()
            self.benchmark_func()
            self.latencies.append(time.perf_counter() - t0)
        return self.latencies


class SubmodelTimer:
    """Per-sub-model latency collector — wraps SubModelRunner.__call__
    (reference forward pre/post hooks, benchmark.py:380-430). On TPU, device
    work is async; we block on the output to get true step latency."""

    def __init__(self, runner):
        self.runner = runner
        self.latencies: List[float] = []
        self._orig = runner._fn

    def __enter__(self):
        timer = self

        def timed(params, cache, inputs, rng=None):
            import jax

            t0 = time.perf_counter()
            out = timer._orig(params, cache, inputs, rng)
            jax.block_until_ready(out.tokens)
            timer.latencies.append(time.perf_counter() - t0)
            return out

        # wrap the instance-level jitted fn (called as self._fn(...), so an
        # instance attribute intercepts it; __call__ would be looked up on the
        # type and cannot be patched per-instance)
        self.runner._fn = timed
        return self

    def __exit__(self, *exc):
        self.runner._fn = self._orig


class DecodeChunkTimer:
    """Per-token decode latency from real decode_chunk dispatches (replaces
    the generate(2)-generate(1) subtraction proxy; reference hooks each
    submodel forward, benchmark.py:380-430)."""

    def __init__(self, runner):
        self.runner = runner
        self.per_token_latencies: List[float] = []
        self._orig = runner.decode_chunk

    def __enter__(self):
        timer = self

        def timed(params, cache, last, pos, seq_ids, sampling_params, rng,
                  num_steps, bucket, adapter_ids=None):
            import jax

            t0 = time.perf_counter()
            tokens, logits, new_cache = timer._orig(
                params, cache, last, pos, seq_ids, sampling_params, rng,
                num_steps=num_steps, bucket=bucket, adapter_ids=adapter_ids,
            )
            jax.block_until_ready(tokens)
            dt = time.perf_counter() - t0
            timer.per_token_latencies.extend([dt / num_steps] * num_steps)
            return tokens, logits, new_cache

        self.runner.decode_chunk = timed
        return self

    def __exit__(self, *exc):
        self.runner.decode_chunk = self._orig


def benchmark_sampling(
    app,
    input_ids: np.ndarray,
    attention_mask: np.ndarray,
    max_new_tokens: int = 64,
    num_runs: int = 10,
    warmup_runs: int = 2,
    report_path: Optional[str] = None,
) -> Dict:
    """End-to-end + per-submodel benchmark (reference benchmark_sampling,
    benchmark.py:21-120). Returns the report dict; optionally writes
    benchmark_report.json."""
    batch = input_ids.shape[0]
    last_out = {}

    def e2e():
        out = app.generate(input_ids, attention_mask, max_new_tokens=max_new_tokens)
        last_out["out"] = out
        return out

    bench = Benchmark(e2e, num_runs=num_runs, warmup_runs=warmup_runs)
    latencies = bench.run()

    n_tokens = last_out["out"].num_generated * batch
    total = float(np.sum(latencies))
    report = {
        "e2e_model": {
            **percentile_report(latencies),
            "throughput_tokens_per_s": num_runs * n_tokens / total,
        }
    }

    # per-submodel: REAL dispatch hooks on each runner (reference pre/post
    # forward hooks, benchmark.py:380-430) — CTE latency ≈ TTFT, TKG
    # per-token latency ≈ ITL. Hooked runs sync per dispatch, so they are
    # measured separately from the e2e (async-chained) runs above.
    with SubmodelTimer(app.context_encoding_model) as cte_t, DecodeChunkTimer(
        app.token_generation_model
    ) as tkg_t:
        for _ in range(num_runs):
            app.generate(input_ids, attention_mask, max_new_tokens=max_new_tokens)
    report["context_encoding_model"] = percentile_report(cte_t.latencies)
    report["token_generation_model"] = (
        percentile_report(tkg_t.per_token_latencies)
        if tkg_t.per_token_latencies
        # key always present (schema parity); max_new_tokens=1 runs CTE only
        else {"note": "no token-generation steps ran"}
    )

    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2)
    return report
