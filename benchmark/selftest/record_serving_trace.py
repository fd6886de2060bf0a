#!/usr/bin/env python3
"""Record the small trace ``test_program_span.py`` reduces: a few split
serving steps of the configuration's tiny ``rehearsal`` preset on the chip,
with the program's telemetry started through its own control, so that the
trace holds the ``serving.*`` spans next to the device's ``XLA Ops`` and
``XLA Modules``. Run once on the chip

    python3 benchmark/selftest/record_serving_trace.py <out.xplane.pb>

the file it writes is checked in, gzipped, as
``data/serving_small.xplane.pb.gz`` (the names of the ``XLA Ops`` events are
whole HLO instructions: 1.5 MB of text that packs to a tenth). The
device is drained before the profiler starts and before it stops, and every
traced step sits in a ``bench.step`` span, as in ``run.py``."""

import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TRACED_STEPS = 8


def main(out_path: str) -> int:
    import jax
    import numpy as np

    from benchmark.harness import catalog, system
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    cell = catalog.load_cell("qwen3-1p7b.chat")
    cfg = system.resolve_config(cell.config, rehearsal=True)
    devices = jax.devices()[:1]
    # the tiny preset's heads are below the kernels' shape guards: native attention
    app = system.build_app(cfg, devices, 7, tpu_overrides=dict(
        attn_kernel_enabled=False, attn_block_tkg_kernel_enabled=False))
    params, pspecs = system.make_weights(app, 7)
    system.give_weights(app, params, pspecs)
    system.warm_up(app, system.reachable_shapes(app, max_prompt=400, max_context=480))
    telemetry = TelemetrySession(enabled=False)
    session = ServingSession(app, telemetry=telemetry)
    rng = np.random.default_rng(7)
    vocab = system.model_attrs(cfg)["vocab_size"]
    for i, n in enumerate((300, 40, 150)):
        assert session.add_request(f"r{i}", rng.integers(0, vocab, size=n), max_new_tokens=24)
    for _ in range(2):  # untraced: the 1-ahead pipeline is running when the trace starts
        session.step()
    bump = jax.jit(lambda x: x + 1)
    mark = jax.device_put(np.int32(0), devices[0])
    jax.block_until_ready(bump(mark))
    tmp = tempfile.mkdtemp(prefix="trace", dir=os.environ.get("TMPDIR"))
    telemetry.start(profile_dir=tmp)
    for k in range(TRACED_STEPS):
        if k == 3:  # an admission and its chunk passes inside the trace
            with jax.profiler.TraceAnnotation("bench.admit"):
                assert session.add_request("r3", rng.integers(0, vocab, size=200), max_new_tokens=24)
        with jax.profiler.TraceAnnotation("bench.step"):
            session.step()
    with jax.profiler.TraceAnnotation("bench.trace_drain"):
        jax.block_until_ready(bump(mark))
    src = telemetry.stop()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    shutil.copy(src, out_path)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out_path}: {os.path.getsize(out_path)} bytes, {TRACED_STEPS} traced steps on "
          f"{devices[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
