#!/usr/bin/env python
"""Kernel tile sweeps for the decode path, for a session on the chip: the
TKG decode-attention kernel across its legal kv tiles (``bs``) and the int4
fused-dequant matmul across its legal output tiles (``bn``), each at a
committed registry shape. Candidates come from the kernel audit's
``legal_tiles``; a winner measured on hardware is what gets promoted into
``analysis/tuning_table.json`` with provenance ``measured``.

Run on hardware: python scripts/decode_scaling.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def sweep_tkg_tiles(bucket=512, dtype="bfloat16", B=1, n=20):
    """Standalone TKG-decode kernel timing across the LEGAL kv-tile sizes
    (``bs``) at the 1B decode shape. Candidates come from the kernel
    audit's ``legal_tiles`` — the same KERN701/702 arithmetic the gate
    runs — so this sweep can only ever measure tilings the gate would
    accept, and its winner is what a hardware session promotes into
    ``analysis/tuning_table.json`` (provenance ``measured``)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.kernel_audit import legal_tiles
    from neuronx_distributed_inference_tpu.ops.decode_attention import (
        tkg_decode_attention,
    )

    L, Hq, Hkv, D = 16, 32, 8, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, 1, Hq, D), jnp.bfloat16)
    cache = jnp.asarray(
        rng.randn(L, B, bucket, Hkv, D), jnp.dtype(dtype)
    )
    li = jnp.int32(0)
    mask = jnp.ones((B, 1, 1, bucket), bool)
    rows = {}
    for tiles in legal_tiles("tkg_decode_attention", f"kv{bucket}", dtype):
        bs = tiles["bs"]
        try:
            out = tkg_decode_attention(
                q, cache, cache, li, mask, scale=D**-0.5, n_kv=Hkv, bs=bs
            )
            jax.device_get(out[0, 0, 0, 0])
            t0 = time.time()
            for _ in range(n):
                out = tkg_decode_attention(
                    q, cache, cache, li, mask, scale=D**-0.5, n_kv=Hkv, bs=bs
                )
            jax.device_get(out[0, 0, 0, 0])
            rows[f"bs{bs}"] = {"us": round((time.time() - t0) / n * 1e6, 1)}
        except Exception as e:  # a tiling the backend rejects
            rows[f"bs{bs}"] = {"error": str(e)[:80]}
    return rows


def sweep_quant_matmul_tiles(shape_class="k2048_n8192", B=8, n=20,
                             interpret=False):
    """Standalone int4 fused-dequant matmul timing across the LEGAL output
    tiles (``bn``) at a committed registry shape (ISSUE 17). Same contract
    as :func:`sweep_tkg_tiles`: candidates come from the kernel audit's
    ``legal_tiles`` so only gate-acceptable tilings are measured, and a
    hardware winner is what gets promoted into
    ``analysis/tuning_table.json`` (provenance ``measured``)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.kernel_audit import legal_tiles
    from neuronx_distributed_inference_tpu.ops.quant_matmul import (
        quant_matmul,
        quantize_tensor_int4,
    )

    K, N = (int(p[1:]) for p in shape_class.split("_"))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, K), jnp.bfloat16)
    packed = quantize_tensor_int4(rng.randn(K, N).astype(np.float32))
    w = jnp.asarray(packed["weight"])
    s = jnp.asarray(packed["scale"])
    rows = {}
    for tiles in legal_tiles("quant_matmul", shape_class, "bfloat16"):
        bn = tiles["bn"]
        try:
            out = quant_matmul(x, w, s, bn=bn, interpret=interpret)
            jax.device_get(out[0, 0])
            t0 = time.time()
            for _ in range(n):
                out = quant_matmul(x, w, s, bn=bn, interpret=interpret)
            jax.device_get(out[0, 0])
            rows[f"bn{bn}"] = {"us": round((time.time() - t0) / n * 1e6, 1)}
        except Exception as e:  # a tiling the backend rejects
            rows[f"bn{bn}"] = {"error": str(e)[:80]}
    return rows


def main():
    print(json.dumps({
        "tkg_tile_sweep_kv512": sweep_tkg_tiles(bucket=512),
        "quant_matmul_tile_sweep_1b": sweep_quant_matmul_tiles(),
    }), flush=True)


if __name__ == "__main__":
    main()
