#!/usr/bin/env python
"""Kernel tile sweeps for the decode path, for a session on the chip: the
TKG decode-attention kernel across its legal kv tiles (``bs``) and the int4
fused-dequant matmul across its legal output tiles (``bn``) and the paged
decode kernel across its legal group sizes (``pages``), each at a committed
registry shape. Candidates come from the kernel audit's
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


def sweep_paged_pages(n_kv=8, n_q=16, layers=28, B=48, bs=32, MB=32, D=128, n=10):
    """The paged decode kernel across the LEGAL group sizes (``pages``: pool
    blocks a step) at a served decode shape, timed as the model runs it: once
    a layer over the stacked pool, B slots whose contexts are drawn as the
    benchmark's decode mix holds them (a prompt of 64-256 tokens and a uniform
    share of an output of 256-768: ~420 tokens, a third of the rows past 512),
    the block table a permutation.
    Defaults: Qwen3-1.7B on one chip; ``n_kv=2, n_q=8, layers=20`` is the
    two-KV-head shape (ZAYA1-8B; Qwen3-14B a chip at tp = 4). The winner is
    what ``analysis/tuning_table.json`` holds as ``measured`` (PERF.md, PR 36)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.kernel_audit import legal_tiles
    from neuronx_distributed_inference_tpu.ops.decode_attention import (
        paged_tkg_decode_attention,
    )
    from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_overrides

    rng = np.random.RandomState(0)
    lens = rng.randint(64, 257, size=B) + (rng.rand(B) * rng.randint(256, 769, size=B)).astype(int)
    blocks = -(-lens // bs)
    NB = int(blocks.sum())
    key = jax.random.PRNGKey(0)
    k_pool, v_pool = (
        jax.random.normal(jax.random.fold_in(key, i), (layers, NB + 1, n_kv, bs, D), jnp.bfloat16)
        for i in (1, 2)
    )
    q = jax.random.normal(key, (B, 1, n_q, D), jnp.bfloat16)
    table = np.zeros((B, MB), np.int32)
    pages = iter(rng.permutation(np.arange(1, NB + 1)))
    for b in range(B):
        table[b, : blocks[b]] = [next(pages) for _ in range(blocks[b])]
    table = jnp.asarray(table)
    mask = jnp.asarray(np.arange(MB * bs)[None, :] < lens[:, None])[:, None, None, :]
    kernel = paged_tkg_decode_attention.__wrapped__  # the override is no jit cache key

    def make_dispatch():  # a new function a candidate: jit caches traces by function
        def dispatch(q, k_pool, v_pool, table, mask):
            def layer(li, acc):
                out = kernel(q, k_pool, v_pool, li, table, mask, scale=D**-0.5, n_kv=n_kv)
                return acc + out.astype(jnp.float32)

            return jax.lax.fori_loop(0, layers, layer, jnp.zeros(q.shape, jnp.float32))

        return jax.jit(dispatch)

    rows = {"live_kv_ms_at_peak": round(
        float(lens.sum()) * n_kv * D * 2 * 2 * layers / 819e9 * 1e3, 3)}
    for tiles in legal_tiles("paged_tkg_decode_attention", f"blk{n_kv}x{bs}x{D}", "bfloat16"):
        with tile_overrides("paged_tkg_decode_attention", tiles):
            try:
                fn = make_dispatch()
                fn(q, k_pool, v_pool, table, mask).block_until_ready()
                t0 = time.time()
                for _ in range(n):
                    out = fn(q, k_pool, v_pool, table, mask)
                out.block_until_ready()
                rows[f"pages{tiles['pages']}"] = {"ms": round((time.time() - t0) / n * 1e3, 3)}
            except Exception as e:  # a group the backend rejects
                rows[f"pages{tiles['pages']}"] = {"error": str(e)[:80]}
    return rows


def main():
    print(json.dumps({
        "tkg_tile_sweep_kv512": sweep_tkg_tiles(bucket=512),
        "quant_matmul_tile_sweep_1b": sweep_quant_matmul_tiles(),
        "paged_pages_sweep_qwen3_1p7b": sweep_paged_pages(),
        "paged_pages_sweep_2kv": sweep_paged_pages(n_kv=2, n_q=8, layers=20),
    }), flush=True)


if __name__ == "__main__":
    main()
