#!/usr/bin/env python
"""Prefill efficiency study: measured MFU for the context-encoding pass and
a flash-kernel block-size sweep (VERDICT r4 next #4 — "give prefill the
decode treatment"; reference CTE kernels sliding_window/attention.py:234,
chunked_prefill/flash_pa_with_schedule.py:157).

Two measurements per sequence length:
- whole-model CTE wall time AND device time (xplane trace): the wall clock
  includes host->device transfer + dispatch, so device time is the honest
  MFU denominator;
- standalone flash-kernel timing across (bq, bkv) tile sizes — the tuning
  surface the whole-model number motivates.

MFU model (bf16 peak 197 TFLOP/s on v5e):
  matmul FLOPs/token = 2 * P_matmul  (P_matmul = params touched by matmuls)
  attention FLOPs    = 4 * L * S^2 * hidden * causal_factor(0.5)
Run on hardware: python scripts/prefill_profile.py
CPU smoke:       python scripts/prefill_profile.py --tiny --cpu
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

V5E_BF16_PEAK = 197e12


def _model_matmul_params(hf):
    H = hf["hidden_size"]
    I = hf["intermediate_size"]
    L = hf["num_hidden_layers"]
    V = hf["vocab_size"]
    Hq = hf["num_attention_heads"]
    Hkv = hf["num_key_value_heads"]
    D = hf.get("head_dim", H // Hq)
    attn = H * (Hq * D) + 2 * H * (Hkv * D) + (Hq * D) * H
    mlp = 3 * H * I
    # embedding lookup is a gather (no matmul); lm_head applies to ONE
    # position per row in prefill — negligible at large S
    return L * (attn + mlp)


def prefill_flops(hf, S):
    L = hf["num_hidden_layers"]
    H = hf["hidden_size"]
    matmul = 2 * _model_matmul_params(hf) * S
    attn = 4 * L * S * S * H * 0.5  # causal
    return matmul + attn


def measure_cte(app, S, hf, n=5, profile_dir=None):
    """Time the raw CTE runner at bucket S as a BURST: n dispatches chained
    on the donated cache, ONE sync at the end — the host round trip
    amortizes over n instead of riding every run."""
    import jax

    rng = np.random.RandomState(0)
    ids = rng.randint(0, hf["vocab_size"] - 10, size=(1, S))
    mask = np.ones_like(ids)
    pos = np.tile(np.arange(S, dtype=np.int32), (1, 1))
    runner = app.context_encoding_model
    inputs, _ = runner.prepare(ids, mask, pos, np.arange(1, dtype=np.int32))
    app.init_kv_cache()  # fresh buffers: earlier measurements donated them
    cache = [app.kv_cache]

    def dispatch():
        # the runner DONATES its cache argument; thread the returned cache
        # back as the next input (same buffers, device-resident)
        out = runner(app.params, cache[0], inputs, None)
        cache[0] = out.cache
        return out

    out = dispatch()  # compile
    jax.device_get(out.tokens)  # a VALUE fetch — block_until_ready has been
    # observed to return early on this experimental backend
    t0 = time.time()
    for _ in range(n):
        out = dispatch()
    jax.device_get(out.tokens)  # the chain serializes on the donated cache
    wall = (time.time() - t0) / n

    device_s = None
    ops = None
    if profile_dir:
        from neuronx_distributed_inference_tpu.utils.profiling import profile_fn

        def profiled():
            out = dispatch()
            jax.device_get(out.tokens)

        summary = profile_fn(profiled, profile_dir, n_warmup=1, n_profile=2)
        ops = (summary.get("ops") or [])[:12]
        total_us = summary.get("total_us")
        if total_us:
            device_s = total_us / 1e6 / 2  # n_profile=2 runs in the trace
    fl = prefill_flops(hf, S)
    res = {
        "S": S,
        "wall_ms": round(wall * 1e3, 2),
        "wall_tok_s": round(S / wall, 1),
        "mfu_wall": round(fl / wall / V5E_BF16_PEAK, 4),
    }
    if device_s:
        res["device_ms"] = round(device_s * 1e3, 2)
        res["mfu_device"] = round(fl / device_s / V5E_BF16_PEAK, 4)
    if ops:
        res["top_ops"] = ops[:6]
    return res


def flash_tile_candidates(shape_class="plain", dtype="bfloat16"):
    """The sweepable (bq, bkv) candidates, from the kernel audit's
    :func:`legal_tiles` — the SAME KERN701/702 arithmetic the gate runs, so
    the sweep and the gate can never disagree about what is sweepable."""
    from neuronx_distributed_inference_tpu.analysis.kernel_audit import legal_tiles

    return [(t["bq"], t["bkv"]) for t in
            legal_tiles("flash_attention", shape_class, dtype)]


def sweep_flash_blocks(S, D=64, H=32, dtype="bfloat16", n=10, packed=False,
                       softmax_bf16=None):
    """Standalone flash-kernel timing across the LEGAL tile sizes at the 1B
    attention shape — the actual tuning surface (candidates come from
    ``legal_tiles``; anything VMEM-over-budget or Mosaic-illegal is never
    timed). ``packed`` sweeps the head-pair packed kernel (round 6): the
    same (bq, bkv) grid at the new arithmetic intensity — packing halves
    head-grid steps and doubles per-tile lanes, so the winning tile must be
    re-measured, not assumed. ``softmax_bf16`` pins the packed softmax mode:
    sweep BOTH, because the shipping default (attention_softmax_fp32=True)
    runs fp32 exp/PV and its winning tile can differ from the bf16 mix."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.ops.flash_attention import (
        flash_attention_bhsd,
    )

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, H, S, D), jnp.bfloat16)
    kv_valid = jnp.ones((1, S), jnp.int32)
    rows = {}
    flops = 4 * S * S * H * D * 0.5
    for bq, bkv in flash_tile_candidates("plain", dtype):
        if bq > S or bkv > S:
            continue
        try:
            out, _, _ = flash_attention_bhsd(
                q, q, q, kv_valid, scale=D**-0.5, causal=True,
                bq=bq, bkv=bkv, packed=packed, softmax_bf16=softmax_bf16,
            )
            jax.device_get(out[0, 0, 0])
            # burst: dispatch n, sync once — a per-iteration sync would
            # add one host round trip per call to the kernel time
            t0 = time.time()
            for _ in range(n):
                out, _, _ = flash_attention_bhsd(
                    out, q, q, kv_valid, scale=D**-0.5, causal=True,
                    bq=bq, bkv=bkv, packed=packed, softmax_bf16=softmax_bf16,
                )
            jax.device_get(out[0, 0, 0])
            dt = (time.time() - t0) / n
            rows[f"bq{bq}_bkv{bkv}"] = {
                "ms": round(dt * 1e3, 2),
                "mfu": round(flops / dt / V5E_BF16_PEAK, 4),
            }
        except Exception as e:  # a tiling the backend rejects
            rows[f"bq{bq}_bkv{bkv}"] = {"error": str(e)[:80]}
    return rows


def run(tiny=False, profile=False):
    import bench

    if tiny:
        hf = dict(bench.TINY)
        lengths = (32, 64)
        seq = 64
        ce = [32, 64]
    else:
        hf = dict(bench.LLAMA_1B)
        lengths = (512, 2048, 8192)
        seq = 8192
        ce = [512, 2048, 8192]
    app = bench.build_app(
        hf, batch=1, seq_len=seq, ce_buckets=ce, tkg_buckets=[seq],
        quantized=False,
    )
    out = {"cte": []}
    for S in lengths:
        pdir = f"/tmp/prefill_prof_{S}" if profile else None
        out["cte"].append(measure_cte(app, S, hf, profile_dir=pdir))
    del app
    if not tiny:
        # unpacked vs head-packed at every tile: the packed winner becomes
        # the default, the unpacked column quantifies the packing win itself
        out["flash_sweep_8k"] = sweep_flash_blocks(8192)
        # packed in BOTH softmax modes: fp32 is the shipping default
        # (attention_softmax_fp32=True); bf16 is the opt-in fast mix — each
        # gets its own winning tile
        out["flash_sweep_8k_packed_fp32"] = sweep_flash_blocks(
            8192, packed=True, softmax_bf16=False
        )
        out["flash_sweep_8k_packed_bf16"] = sweep_flash_blocks(
            8192, packed=True, softmax_bf16=True
        )
    return out


def main():
    if "--cpu" in sys.argv:
        import jax

        jax.config.update("jax_platforms", "cpu")
    res = run(tiny="--tiny" in sys.argv, profile="--profile" in sys.argv)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
