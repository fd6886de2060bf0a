"""Device profiling utilities.

TPU-native replacement for the reference's ``neuron-profile`` shellout
(reference: utils/profiling.py:33-66 — capture 2 execs on a NEFF, emit a JSON
summary). On TPU the profiler is in-process: ``jax.profiler`` captures an
xplane trace viewable in XProf/TensorBoard, and we post-process the xplane
protobuf into the same kind of per-op summary JSON the reference emits.
"""

from __future__ import annotations

import gzip
import json
from contextlib import contextmanager
from typing import Callable, Dict, Optional

import jax

from neuronx_distributed_inference_tpu.telemetry import device_scopes
from neuronx_distributed_inference_tpu.telemetry.tracing import (
    TelemetrySession,
    default_session,
    newest_xplane,
)


@contextmanager
def profile_capture(out_dir: str, telemetry: Optional[TelemetrySession] = None):
    """Capture a device trace for the enclosed block, through the telemetry
    session's one start/stop control (``telemetry``, default the process
    session): the profiler and the program's own spans are on together, so
    the trace carries ``app.*`` / ``serving.*`` host spans next to the
    device ops they launched.

    Usage::

        with profile_capture("/tmp/profile"):
            run_model()

    The trace lands in ``out_dir/plugins/profile/<ts>/`` and is viewable with
    ``tensorboard --logdir out_dir`` (XProf). A session that was recording
    before keeps recording after.
    """
    tel = telemetry if telemetry is not None else default_session()
    was_recording = tel.enabled
    tel.start(profile_dir=out_dir)
    try:
        yield
    finally:
        tel.stop()
        if was_recording:
            tel.start()


def profile_fn(fn: Callable, out_dir: str, n_warmup: int = 1, n_profile: int = 2):
    """Profile ``fn()`` the way the reference profiles a NEFF: warm up, then
    capture ``n_profile`` executions (reference utils/profiling.py:33 —
    "capture 2 execs, profile the 2nd")."""
    if n_profile < 1:
        raise ValueError(f"n_profile must be >= 1, got {n_profile}")
    for _ in range(n_warmup):
        jax.block_until_ready(fn())
    with profile_capture(out_dir):
        for _ in range(n_profile):
            jax.block_until_ready(fn())
    return summarize_trace(out_dir)


def summarize_trace(out_dir: str, top: int = 25) -> Dict:
    """Per-op device time of the newest trace under ``out_dir``, read with
    ``jax.profiler.ProfileData`` (what the benchmark's reduction trusts):
    {"ops": [{"name", "total_us", "count"}...], "total_us": N} over the
    ``XLA Ops`` lines of the device planes; {"trace_dir", "ops": []} when
    no trace is there. Where the session that recorded the trace wrote its
    device scope tables beside it (``device_scopes.json``: a serving session
    recorded), also ``"by_scope"``: {module: {scope: {"seconds", "share"}}}
    for the step programs on the first device plane, the ops under the
    program's own names (telemetry/device_scopes.py; "" = under no scope)."""
    path = newest_xplane(out_dir)
    if path is None:
        return {"trace_dir": out_dir, "ops": []}
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, Dict] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = device_scopes.short_name(e.name)
                rec = ops.setdefault(name, {"name": name, "total_us": 0.0, "count": 0})
                rec["total_us"] += e.duration_ns / 1e3
                rec["count"] += 1
    total_us = sum(r["total_us"] for r in ops.values())
    ranked = sorted(ops.values(), key=lambda r: -r["total_us"])[:top]
    for r in ranked:
        r["total_us"] = round(r["total_us"], 1)
    summary = {"total_us": round(total_us, 1), "ops": ranked}
    tables = device_scopes.read_tables(out_dir)
    if tables:
        summary["by_scope"] = {
            module: {
                scope: {"seconds": s, "share": s / (sum(sums.values()) or 1.0)}
                for scope, s in sorted(sums.items(), key=lambda kv: -kv[1])
            }
            for module, sums in device_scopes.time_by_scope(data, tables).items()
        }
    return summary


def save_summary(summary: Dict, out_path: str):
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2)
