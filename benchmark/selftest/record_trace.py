#!/usr/bin/env python3
"""Record the small trace the selftest reduces: three annotated steps of a
sharded matmul followed by an all-reduce, on every chip JAX finds. Run once
on the chip (``python3 benchmark/selftest/record_trace.py <out.xplane.pb>``);
the file it writes is checked in as ``data/tp4_small.xplane.pb``."""

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_path: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("x",))
    x = jax.device_put(jnp.ones((len(devices) * 256, 512), jnp.bfloat16), NamedSharding(mesh, P("x", None)))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16), NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return jax.lax.with_sharding_constraint(y.T @ y, NamedSharding(mesh, P()))  # all-reduce over x

    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="trace", dir=os.environ.get("TMPDIR"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            step(x, w).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    shutil.copy(src, out_path)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out_path}: {os.path.getsize(out_path)} bytes from {len(devices)} device(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
