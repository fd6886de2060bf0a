"""inference_demo-style CLI: compile / load / generate / accuracy.

TPU-native re-design of the reference CLI
(reference: src/neuronx_distributed_inference/inference_demo.py — argparse
flags map 1:1 onto config fields :94-389; orchestration run_inference :458).

Usage:
    python -m neuronx_distributed_inference_tpu.inference_demo \
        --model-type llama --task-type causal-lm run \
        --model-path /path/to/hf/checkpoint \
        --compiled-model-path /tmp/compiled \
        --batch-size 1 --seq-len 1024 --tp-degree 1 \
        --prompt "I believe the meaning of life is" \
        --check-accuracy-mode token-matching
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    OnDeviceSamplingConfig,
    TpuConfig,
)
from neuronx_distributed_inference_tpu.models.registry import MODEL_REGISTRY
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.utils.hf_adapter import load_pretrained_config


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="inference_demo", description=__doc__)
    p.add_argument("--model-type", default="llama", choices=sorted(MODEL_REGISTRY))
    p.add_argument("--task-type", default="causal-lm",
               choices=["causal-lm", "image-gen"])
    sub = p.add_subparsers(dest="action", required=True)
    run = sub.add_parser("run", help="compile, load, and generate")

    def onoff(name, default, dest=None, help=None):
        """--name / --no-name boolean pair (reference on/off flag pairs)."""
        dest = dest or name.replace("-", "_")
        run.add_argument(f"--{name}", dest=dest, action="store_true",
                         default=default, help=help)
        run.add_argument(f"--no-{name}", dest=dest, action="store_false")

    # paths
    # required for every mode except --workload-trace-out (which loads no
    # model) — enforced in main() so the trace generator runs standalone
    run.add_argument("--model-path", default=None)
    run.add_argument("--compiled-model-path", default=None)
    run.add_argument("--compilation-cache-dir", default=None,
                     help="persistent XLA cache directory (JAX_COMPILATION_CACHE_DIR, "
                          "when set, wins; default <checkout>/.bench_cache/xla)")
    run.add_argument("--random-weights", action="store_true",
                     help="skip checkpoint load; random weights (perf/testing)")

    # core shapes (reference inference_demo.py:94-180)
    run.add_argument("--batch-size", type=int, default=1)
    run.add_argument("--max-batch-size", type=int, default=None)
    run.add_argument("--ctx-batch-size", type=int, default=None)
    run.add_argument("--tkg-batch-size", type=int, default=None)
    run.add_argument("--seq-len", type=int, default=1024)
    run.add_argument("--max-context-length", type=int, default=None)
    run.add_argument("--max-length", type=int, default=None)
    run.add_argument("--n-active-tokens", type=int, default=None)
    run.add_argument("--dtype", default="bfloat16",
                     choices=["bfloat16", "float32", "float16"])
    run.add_argument("--padding-side", default="right", choices=["right", "left"])
    onoff("cast-logits-fp32", True)
    onoff("attention-softmax-fp32", True)
    run.add_argument("--seed", type=int, default=0)
    onoff("async-mode", True, help="chained decode chunks, one sync per call")

    # parallelism (reference config.py:333-361)
    run.add_argument("--tp-degree", type=int, default=1)
    run.add_argument("--cp-degree", type=int, default=1)
    run.add_argument("--ep-degree", type=int, default=1)
    run.add_argument("--pp-degree", type=int, default=1)
    run.add_argument("--attention-dp-degree", type=int, default=1)
    run.add_argument("--data-parallel-degree", type=int, default=1,
                     help="whole-model DP over the leading ddp mesh axis")
    run.add_argument("--moe-tp-degree", type=int, default=None)
    run.add_argument("--moe-ep-degree", type=int, default=None)
    run.add_argument("--start-rank-id", type=int, default=0)
    run.add_argument("--local-ranks-size", type=int, default=None)
    run.add_argument("--sequence-parallel-enabled", action="store_true")
    run.add_argument("--vocab-parallel", action="store_true")
    run.add_argument("--flash-decoding-enabled", action="store_true")
    run.add_argument("--num-cores-per-group", type=int, default=1)

    # attention / kernels (reference ~25 kernel enable flags)
    run.add_argument("--fused-qkv", action="store_true")
    run.add_argument("--qk-norm", action="store_true")
    run.add_argument("--sliding-window", type=int, default=None)
    run.add_argument("--attention-chunk-size", type=int, default=None)
    run.add_argument("--attn-kernel-enabled", default=None,
                     type=lambda s: s.lower() in ("1", "true", "yes"),
                     help="flash prefill kernel: true/false (default: auto on TPU)")
    run.add_argument("--attn-block-tkg-kernel-enabled", default=None,
                     type=lambda s: s.lower() in ("1", "true", "yes"),
                     help="decode (TKG) attention kernel: true/false (default: auto)")
    run.add_argument("--attn-packed-kernel-enabled", default=None,
                     type=lambda s: s.lower() in ("1", "true", "yes"),
                     help="head-pair packed flash prefill for head_dim<=64: "
                          "true/false (default: auto-on on the flash path)")

    # bucketing
    onoff("enable-bucketing", True)
    run.add_argument("--context-encoding-buckets", type=int, nargs="+", default=None)
    run.add_argument("--token-generation-buckets", type=int, nargs="+", default=None)

    # KV cache / paged / serving (reference block-KV + chunked-prefill flags)
    from neuronx_distributed_inference_tpu.config import KV_CACHE_DTYPES

    run.add_argument(
        "--kv-cache-dtype", default=None, choices=list(KV_CACHE_DTYPES),
        help="KV cache storage dtype; int8/fp8 build the quantized cache "
        "(codes + per-(layer, head) scales, fused in-kernel dequant)",
    )
    run.add_argument("--kv-cache-batch-size", type=int, default=None)
    run.add_argument("--is-continuous-batching", action="store_true")
    run.add_argument("--is-block-kv-layout", action="store_true")
    run.add_argument("--pa-num-blocks", type=int, default=None)
    run.add_argument("--pa-block-size", type=int, default=16)
    run.add_argument(
        "--pa-pool-bytes", type=int, default=None,
        help="size the paged block pool by HBM bytes (block count derived "
        "from the cache dtype's true per-block cost; excludes pa-num-blocks)",
    )
    run.add_argument("--is-prefix-caching", action="store_true")
    run.add_argument("--is-chunked-prefill", action="store_true")
    run.add_argument(
        "--serving-ragged", action="store_true",
        help="ragged mixed-step serving dispatch: pack prefill chunks AND "
        "decode rows into ONE ragged paged-attention launch per step "
        "(requires --is-block-kv-layout under continuous batching; "
        "docs/SERVING.md)",
    )
    from neuronx_distributed_inference_tpu.config import ROUTER_POLICIES

    run.add_argument(
        "--serving-replicas", type=int, default=1,
        help="multi-replica router config (runtime/router.py — the demo "
        "itself runs one generate() session): how many single-chip replica "
        "sessions ServingRouter routes over; 1 = no router layer",
    )
    run.add_argument(
        "--router-policy", default="least_loaded",
        choices=list(ROUTER_POLICIES),
        help="replica placement policy for the router config above: "
        "least_loaded scores replicas from live telemetry (backlog, "
        "occupancy, kv_free_bytes, step/queue-wait EWMAs); cache_aware "
        "follows each replica's real prefix-cache match index (longest "
        "cached prefix wins, load order breaks ties)",
    )
    run.add_argument(
        "--router-prefill-replicas", type=int, default=0,
        help="disaggregated prefill tier (router config): carve this many of "
        "--serving-replicas out as dedicated prefill replicas feeding "
        "decode replicas over the contained KV hand-off; 0 = no tier "
        "(requires the contiguous cache; docs/SERVING.md)",
    )
    run.add_argument(
        "--handoff-max-retries", type=int, default=2,
        help="transient KV hand-off failures retried with capped backoff "
        "this many times; exhaustion fails only the in-flight request "
        "(FAILED(handoff)) and degrades the prefill replica",
    )
    run.add_argument(
        "--handoff-timeout-s", type=float, default=None,
        help="wall-clock bound for ONE hand-off attempt; an attempt past it "
        "counts as a failed attempt and retries (None disables)",
    )
    onoff("router-threading", False, dest="router_threading",
          help="thread-per-replica router stepping (router config): every alive "
          "replica's step() dispatches from a persistent worker pool and "
          "joins at a per-step barrier, so replica device steps overlap "
          "instead of host-serializing; placement/failover/telemetry stay "
          "on the router thread (docs/SERVING.md)")
    run.add_argument("--cp-max-num-seqs", type=int, default=8,
                     help="chunked prefill: max sequences per chunk batch")
    run.add_argument("--cp-kernel-q-tile-size", type=int, default=128)
    run.add_argument("--cp-kernel-kv-tile-size", type=int, default=512)

    # serving fault containment (runtime/serving.py; docs/SERVING.md)
    onoff("admission-validation", True, dest="admission_validation",
          help="typed REJECTED verdicts for malformed requests at admission "
          "(out-of-vocab ids, empty/over-long prompts, bad budgets) instead "
          "of raising mid-batch")
    run.add_argument(
        "--request-deadline-s", type=float, default=None,
        help="wall-clock TTL per request in seconds; past it the request is "
        "dropped with terminal reason deadline_exceeded",
    )
    run.add_argument(
        "--dispatch-max-retries", type=int, default=2,
        help="transient dispatch errors retried with capped backoff this "
        "many times; then only the in-flight rows fail",
    )
    run.add_argument(
        "--watchdog-no-progress-steps", type=int, default=256,
        help="serving steps with zero progress before the watchdog preempts "
        "the largest request (second window: loud WatchdogError); 0 disables",
    )

    # workload engine (workload/generator.py; docs/WORKLOADS.md): seeded
    # open-loop traffic generation. --workload-trace-out materializes the
    # reproducible arrival trace as JSON and exits WITHOUT loading a model
    # — the artifact replays through the WorkloadDriver (same seed =>
    # byte-identical trace, pinned).
    run.add_argument("--workload-seed", type=int, default=0,
                     help="workload trace seed (same seed => byte-identical "
                          "arrival trace)")
    run.add_argument("--workload-requests", type=int, default=32,
                     help="total arrivals in the generated trace")
    run.add_argument("--workload-arrival", default="poisson",
                     choices=["poisson", "onoff", "diurnal"],
                     help="arrival process: steady Poisson, bursty on/off, "
                          "or a diurnal rate envelope")
    run.add_argument("--workload-rate", type=float, default=1.0,
                     help="mean arrivals per virtual step (on-phase / peak "
                          "rate for onoff / diurnal)")
    run.add_argument("--workload-tenants", type=int, default=2,
                     help="tenant pools (alternating prose-ish/code-ish "
                          "spec-acceptance profiles, each with its own "
                          "shared prompt prefix)")
    run.add_argument("--workload-vocab", type=int, default=32000,
                     help="token-id range for the generated prompts (match "
                          "the serving model's vocab)")
    run.add_argument("--workload-max-prompt", type=int, default=128,
                     help="prompt-length upper bound (lognormal body is "
                          "clipped here — keep within the serving buckets)")
    run.add_argument("--workload-max-new-tokens", type=int, default=64,
                     help="output-budget upper bound (Zipf tail clipped)")
    run.add_argument("--workload-ttft-slo", type=float, default=None,
                     help="per-request TTFT SLO in virtual seconds (None "
                          "disables the TTFT term in goodput scoring)")
    run.add_argument("--workload-itl-slo", type=float, default=None,
                     help="per-request average-ITL SLO in virtual seconds")
    run.add_argument("--workload-trace-out", default=None,
                     help="write the generated arrival trace JSON here and "
                          "exit (no model load; replay via "
                          "workload.WorkloadTrace.loads + WorkloadDriver)")

    # sampling (reference on-device sampling flags)
    run.add_argument("--on-device-sampling", action="store_true")
    run.add_argument("--do-sample", action="store_true")
    run.add_argument("--top-k", type=int, default=1)
    run.add_argument("--top-p", type=float, default=1.0)
    run.add_argument("--temperature", type=float, default=1.0)
    run.add_argument("--global-topk", type=int, default=256)
    run.add_argument("--max-topk", type=int, default=256)
    run.add_argument("--deterministic", action="store_true")
    onoff("dynamic-sampling", True, dest="dynamic_sampling",
          help="per-request (top_k, top_p, temperature) tensors")
    run.add_argument("--output-logits", action="store_true")

    # quantization (reference --quantized*)
    run.add_argument("--quantized", action="store_true")
    run.add_argument("--quantization-type", default="per_channel_symmetric",
                     choices=["per_channel_symmetric", "per_tensor_symmetric",
                              "blockwise"])
    run.add_argument("--quantization-dtype", default="int8")
    run.add_argument("--quantized-checkpoints-path", default=None)
    # presharded weight artifact under <compiled_model_path>/presharded:
    # later runs restore sharded (possibly quantized) arrays directly — no
    # HF conversion, no quantize-at-load (reference save_sharded_checkpoint,
    # application_base.py:240-265; VERDICT r4 next #2 quantize-once)
    run.add_argument("--save-sharded-checkpoint", action="store_true")
    run.add_argument("--blockwise-matmul-block-size", type=int, default=128)
    run.add_argument("--modules-to-not-convert", nargs="+", default=None)

    # MoE (reference MoENeuronConfig flags)
    run.add_argument("--router-dtype", default="float32")
    run.add_argument("--early-expert-affinity-modulation", action="store_true")
    onoff("normalize-top-k-affinities", True)
    run.add_argument("--hidden-act-scaling-factor", type=float, default=1.0)
    run.add_argument("--hidden-act-bias", type=float, default=0.0)
    onoff("glu-mlp", True)
    run.add_argument("--glu-type", default="glu")

    # LoRA multi-adapter serving (reference lora_serving flags)
    run.add_argument("--enable-lora", action="store_true")
    run.add_argument("--max-loras", type=int, default=1)
    run.add_argument("--max-lora-rank", type=int, default=16)
    run.add_argument("--max-loras-on-cpu", type=int, default=2)
    run.add_argument("--lora-ckpt-path", action="append", dest="lora_ckpt_paths",
                     default=None, metavar="NAME=PATH",
                     help="adapter checkpoint, repeatable: name=path")
    run.add_argument("--lora-dtype", default="bfloat16")
    run.add_argument("--lora-target-modules", nargs="+",
                     default=["q_proj", "k_proj", "v_proj", "o_proj"])
    run.add_argument("--adapter-id", action="append", dest="adapter_ids",
                     default=None, help="adapter name per prompt (repeatable)")

    # speculation (vanilla / fused / EAGLE / EAGLE3 / Medusa / token trees)
    run.add_argument("--draft-model-path", default=None)
    run.add_argument("--draft-model-type", default=None,
                     help="model_type of the draft (default: same as target; "
                          "llama-eagle / llama-eagle3 for EAGLE drafts)")
    run.add_argument("--speculation-length", type=int, default=0)
    run.add_argument("--enable-fused-speculation", action="store_true")
    run.add_argument("--enable-eagle-speculation", action="store_true")
    run.add_argument("--enable-eagle-draft-input-norm", action="store_true")
    run.add_argument("--is-eagle3", action="store_true",
                     help="EAGLE3: multi-layer target capture + 2H-qkv draft")
    run.add_argument("--token-tree-config", default=None,
                     help="token-tree JSON (inline or @file): adjacency dict "
                          "for static trees, or {step, branching_factor, "
                          "num_inputs} for dynamic trees")
    run.add_argument("--assisted-decoding", action="store_true",
                     help="vanilla (unfused) draft-assisted decoding: draft "
                          "and target compiled independently")
    run.add_argument("--is-medusa", action="store_true")
    run.add_argument("--medusa-speculation-length", type=int, default=0)
    run.add_argument("--num-medusa-heads", type=int, default=0)

    # generation
    run.add_argument("--prompt", action="append", dest="prompts", default=None)
    run.add_argument("--max-new-tokens", type=int, default=64)

    # eval
    run.add_argument("--check-accuracy-mode", default="skip",
                     choices=["skip", "token-matching", "logit-matching"])
    run.add_argument("--divergence-difference-tol", type=float, default=0.001)
    run.add_argument("--skip-warmup", action="store_true")

    # observability (reference inference_demo.py:329-334 + profiling)
    run.add_argument("--input-capture-save-dir", default=None,
                     help="directory for input snapshots / divergence capture")
    run.add_argument("--capture-indices", nargs="+", default=None,
                     help="dispatch indices to snapshot, or 'auto' to capture "
                          "only when the accuracy check diverges")
    run.add_argument("--profile-dir", default=None,
                     help="capture a jax.profiler device trace of generation "
                          "into this directory (view with tensorboard/XProf)")
    run.add_argument("--debug-io", action="store_true",
                     help="log every dispatch's input shapes and output tokens")
    run.add_argument("--capture-points", nargs="+", default=None,
                     help="tensor-capture tap points (modules/tensor_taps)")
    run.add_argument("--tensor-replacement-points", nargs="+", default=None,
                     help="tap points eligible for teacher forcing")
    run.add_argument("--metrics-out", default=None,
                     help="enable runtime telemetry and dump the JSON metrics "
                          "snapshot (bucket census, step counters, token "
                          "counts) to this path at exit; pretty-print with "
                          "scripts/metrics_report.py")
    run.add_argument("--trace-out", default=None,
                     help="enable runtime telemetry and export the run's "
                          "span timeline as Chrome trace-event JSON to this "
                          "path at exit (load in Perfetto / chrome://tracing; "
                          "docs/OBSERVABILITY.md)")
    run.add_argument("--ops-port", type=int, default=None,
                     help="serve the live ops surface (/metrics, /healthz, "
                          "/slo; docs/OBSERVABILITY.md) on this port for the "
                          "duration of the run (0 = ephemeral); the server "
                          "thread is joined on exit even if the run raises")
    return p


def _parse_token_tree(arg):
    if arg is None:
        return None
    if arg.startswith("@"):
        with open(arg[1:]) as f:
            return json.load(f)
    return json.loads(arg)


def create_tpu_config(args) -> TpuConfig:
    """CLI flags -> TpuConfig / MoETpuConfig (reference create_neuron_config,
    inference_demo.py:416-422)."""
    from neuronx_distributed_inference_tpu.config import (
        ChunkedPrefillConfig,
        LoraServingConfig,
        MoETpuConfig,
        TensorCaptureConfig,
        TensorReplacementConfig,
    )

    ods = None
    if args.on_device_sampling or args.do_sample:
        ods = OnDeviceSamplingConfig(
            do_sample=args.do_sample,
            top_k=args.top_k,
            top_p=args.top_p,
            temperature=args.temperature,
            dynamic=args.dynamic_sampling,
            global_topk=args.global_topk,
            deterministic=args.deterministic,
        )
    lora = None
    if args.enable_lora or args.lora_ckpt_paths:
        paths = dict(s.split("=", 1) for s in (args.lora_ckpt_paths or []))
        lora = LoraServingConfig(
            max_loras=args.max_loras,
            max_lora_rank=args.max_lora_rank,
            max_loras_on_cpu=args.max_loras_on_cpu,
            lora_ckpt_paths=paths or None,
            lora_dtype=args.lora_dtype,
            target_modules=tuple(args.lora_target_modules),
        )
    cpc = None
    if args.is_chunked_prefill:
        cpc = ChunkedPrefillConfig(
            max_num_seqs=args.cp_max_num_seqs,
            kernel_q_tile_size=args.cp_kernel_q_tile_size,
            kernel_kv_tile_size=args.cp_kernel_kv_tile_size,
        )
    kwargs = dict(
        batch_size=args.batch_size,
        max_batch_size=args.max_batch_size,
        ctx_batch_size=args.ctx_batch_size,
        tkg_batch_size=args.tkg_batch_size,
        seq_len=args.seq_len,
        max_context_length=args.max_context_length,
        max_length=args.max_length,
        n_active_tokens=args.n_active_tokens,
        dtype=args.dtype,
        padding_side=args.padding_side,
        cast_logits_fp32=args.cast_logits_fp32,
        attention_softmax_fp32=args.attention_softmax_fp32,
        seed=args.seed,
        async_mode=args.async_mode,
        compilation_cache_dir=args.compilation_cache_dir,
        save_sharded_checkpoint=args.save_sharded_checkpoint,
        tp_degree=args.tp_degree,
        cp_degree=args.cp_degree,
        ep_degree=args.ep_degree,
        pp_degree=args.pp_degree,
        attention_dp_degree=args.attention_dp_degree,
        data_parallel_degree=args.data_parallel_degree,
        moe_tp_degree=args.moe_tp_degree,
        moe_ep_degree=args.moe_ep_degree,
        start_rank_id=args.start_rank_id,
        local_ranks_size=args.local_ranks_size,
        sequence_parallel_enabled=args.sequence_parallel_enabled,
        vocab_parallel=args.vocab_parallel,
        flash_decoding_enabled=args.flash_decoding_enabled,
        num_cores_per_group=args.num_cores_per_group,
        fused_qkv=args.fused_qkv,
        qk_norm=args.qk_norm,
        sliding_window=args.sliding_window,
        attention_chunk_size=args.attention_chunk_size,
        attn_kernel_enabled=args.attn_kernel_enabled,
        attn_packed_kernel_enabled=args.attn_packed_kernel_enabled,
        attn_block_tkg_kernel_enabled=args.attn_block_tkg_kernel_enabled,
        enable_bucketing=args.enable_bucketing,
        context_encoding_buckets=args.context_encoding_buckets,
        token_generation_buckets=args.token_generation_buckets,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_cache_batch_size=args.kv_cache_batch_size,
        is_continuous_batching=args.is_continuous_batching,
        is_block_kv_layout=args.is_block_kv_layout,
        pa_num_blocks=args.pa_num_blocks,
        pa_block_size=args.pa_block_size,
        pa_pool_bytes=args.pa_pool_bytes,
        is_prefix_caching=args.is_prefix_caching,
        is_chunked_prefill=args.is_chunked_prefill,
        chunked_prefill_config=cpc,
        serving_ragged=args.serving_ragged,
        serving_replicas=args.serving_replicas,
        router_policy=args.router_policy,
        router_threading=args.router_threading,
        router_prefill_replicas=args.router_prefill_replicas,
        handoff_max_retries=args.handoff_max_retries,
        handoff_timeout_s=args.handoff_timeout_s,
        admission_validation=args.admission_validation,
        request_deadline_s=args.request_deadline_s,
        dispatch_max_retries=args.dispatch_max_retries,
        watchdog_no_progress_steps=args.watchdog_no_progress_steps,
        on_device_sampling_config=ods,
        max_topk=args.max_topk,
        output_logits=args.output_logits
        or args.check_accuracy_mode == "logit-matching",
        quantized=args.quantized,
        quantization_type=args.quantization_type,
        quantization_dtype=args.quantization_dtype,
        quantized_checkpoints_path=args.quantized_checkpoints_path,
        blockwise_matmul_block_size=args.blockwise_matmul_block_size,
        modules_to_not_convert=args.modules_to_not_convert,
        lora_config=lora,
        speculation_length=args.speculation_length,
        enable_fused_speculation=args.enable_fused_speculation,
        enable_eagle_speculation=args.enable_eagle_speculation,
        enable_eagle_draft_input_norm=args.enable_eagle_draft_input_norm,
        is_eagle3=args.is_eagle3,
        token_tree_config=_parse_token_tree(args.token_tree_config),
        medusa_speculation_length=args.medusa_speculation_length,
        num_medusa_heads=args.num_medusa_heads,
        skip_warmup=args.skip_warmup,
        tensor_capture_config=(
            TensorCaptureConfig(points=args.capture_points)
            if args.capture_points else None
        ),
        tensor_replacement_config=(
            TensorReplacementConfig(points=args.tensor_replacement_points)
            if args.tensor_replacement_points else None
        ),
    )
    moe = (
        args.early_expert_affinity_modulation
        or args.router_dtype != "float32"
        or args.hidden_act_scaling_factor != 1.0
        or args.hidden_act_bias != 0.0
        or not args.normalize_top_k_affinities
        or not args.glu_mlp
        or args.glu_type != "glu"
    )
    if moe:
        return MoETpuConfig(
            router_dtype=args.router_dtype,
            early_expert_affinity_modulation=args.early_expert_affinity_modulation,
            normalize_top_k_affinities=args.normalize_top_k_affinities,
            hidden_act_scaling_factor=args.hidden_act_scaling_factor,
            hidden_act_bias=args.hidden_act_bias,
            glu_mlp=args.glu_mlp,
            glu_type=args.glu_type,
            **kwargs,
        )
    return TpuConfig(**kwargs)


def run_inference(args) -> int:
    """Orchestration (reference run_inference, inference_demo.py:458)."""
    from neuronx_distributed_inference_tpu.models.registry import get_model_builder

    tpu_config = create_tpu_config(args)
    builder_cls = get_model_builder(args.model_type)
    config_cls = getattr(builder_cls, "config_cls", InferenceConfig)
    load_config = load_pretrained_config(args.model_path)
    config = config_cls(tpu_config, load_config=load_config)

    if args.assisted_decoding and (
        args.enable_fused_speculation or args.enable_eagle_speculation
    ):
        raise ValueError(
            "--assisted-decoding is the unfused path; it conflicts with "
            "--enable-fused-speculation/--enable-eagle-speculation"
        )
    if args.assisted_decoding and args.do_sample:
        # sampled assisted decoding exists (runtime.assisted requires BOTH
        # apps loaded with do_sample on-device sampling + output_logits);
        # the demo doesn't build the draft app that way, so keep the gate
        raise NotImplementedError(
            "assisted decoding is greedy-only in inference_demo; sampled "
            "speculation runs through --enable-fused-speculation "
            "(multinomial accept/reject) or runtime.assisted directly"
        )
    fused_spec = args.enable_fused_speculation or args.enable_eagle_speculation or (
        args.draft_model_path and args.speculation_length >= 2
        and not args.assisted_decoding
    )
    assisted = args.assisted_decoding
    print(f"[inference_demo] building {args.model_type} app "
          f"(tp={args.tp_degree} ep={args.ep_degree} fused_spec={bool(fused_spec)} "
          f"eagle={args.enable_eagle_speculation} assisted={assisted})",
          file=sys.stderr)
    t0 = time.time()
    draft_app = None
    if args.is_medusa or args.medusa_speculation_length:
        from neuronx_distributed_inference_tpu.runtime.medusa import (
            TpuMedusaModelForCausalLM,
        )

        app = TpuMedusaModelForCausalLM(args.model_path, config)
        app.load(random_weights=args.random_weights)
    elif fused_spec:
        from neuronx_distributed_inference_tpu.config import FusedSpecConfig
        from neuronx_distributed_inference_tpu.runtime.fused_spec import (
            TpuEagleSpecModelForCausalLM,
            TpuFusedSpecModelForCausalLM,
        )

        if not args.draft_model_path:
            raise ValueError("fused/eagle speculation requires --draft-model-path")
        tpu_config.enable_fused_speculation = True
        tpu_config.enable_eagle_speculation = args.enable_eagle_speculation
        draft_type = args.draft_model_type or (
            ("llama-eagle3" if args.is_eagle3 else "llama-eagle")
            if args.enable_eagle_speculation
            else args.model_type
        )
        draft_builder_cls = get_model_builder(draft_type)
        draft_config_cls = getattr(draft_builder_cls, "config_cls", InferenceConfig)
        draft_config = draft_config_cls(
            create_tpu_config(args), load_config=load_pretrained_config(args.draft_model_path)
        )
        draft_config.model_type = draft_type
        config.fused_spec_config = FusedSpecConfig(
            draft_model_name=args.draft_model_path, draft_config=draft_config
        )
        app_cls = (
            TpuEagleSpecModelForCausalLM
            if args.enable_eagle_speculation
            else TpuFusedSpecModelForCausalLM
        )
        app = app_cls(args.model_path, config, draft_model_path=args.draft_model_path)
        app.load(random_weights=args.random_weights)
    else:
        app = TpuModelForCausalLM(args.model_path, config)
        # a presharded artifact makes the eager load redundant: compile()
        # restores the sharded (possibly quantized) arrays directly — no HF
        # conversion, no quantize-at-load (VERDICT r4 next #2; reference
        # save_sharded_checkpoint reload, application_base.py:240-265)
        # only skip the eager load for an artifact saved under THIS model +
        # quantization recipe — a stale or corrupt artifact must not
        # silently override the CLI flags (and must not crash: a kill
        # mid-write degrades to a normal load). One shared gate with
        # compile() so the checks cannot drift (utils/presharded.py).
        from neuronx_distributed_inference_tpu.utils.presharded import (
            artifact_ready,
        )

        artifact_ok = not args.random_weights and artifact_ready(
            config, args.compiled_model_path, args.model_path
        )
        if not artifact_ok:
            app.load(random_weights=args.random_weights)
        if args.lora_ckpt_paths:
            from neuronx_distributed_inference_tpu.utils.hf_checkpoint import (
                load_state_dict,
            )

            adapters = {}
            for entry in args.lora_ckpt_paths:
                name, path = entry.split("=", 1)
                adapters[name] = load_state_dict(path)
            app.load_lora_adapters(adapters)
        if assisted:
            if not args.draft_model_path:
                raise ValueError("--assisted-decoding requires --draft-model-path")
            draft_type = args.draft_model_type or args.model_type
            draft_builder_cls = get_model_builder(draft_type)
            draft_config_cls = getattr(draft_builder_cls, "config_cls", InferenceConfig)
            draft_config = draft_config_cls(
                create_tpu_config(args),
                load_config=load_pretrained_config(args.draft_model_path),
            )
            draft_config.model_type = draft_type
            draft_app = TpuModelForCausalLM(args.draft_model_path, draft_config)
            draft_app.load(random_weights=args.random_weights)
    print(f"[inference_demo] load: {time.time()-t0:.1f}s", file=sys.stderr)
    if not fused_spec:
        t0 = time.time()
        app.compile(args.compiled_model_path)
        print(f"[inference_demo] compile+warmup: {time.time()-t0:.1f}s", file=sys.stderr)

    # tokenize prompts
    prompts = args.prompts or ["I believe the meaning of life is"]
    try:
        from transformers import AutoTokenizer

        tok = AutoTokenizer.from_pretrained(args.model_path)
        enc = tok(prompts, return_tensors="np", padding=True, padding_side="right")
        input_ids = enc["input_ids"]
        attention_mask = enc["attention_mask"]
    except Exception as e:
        print(f"[inference_demo] tokenizer unavailable ({e}); using raw ids",
              file=sys.stderr)
        input_ids = np.array([[1] + [i % 100 + 2 for i in range(15)]] * len(prompts))
        attention_mask = np.ones_like(input_ids)
        tok = None

    eos_token_id = getattr(tok, "eos_token_id", None) if tok else None
    gen_kwargs = dict(max_new_tokens=args.max_new_tokens, eos_token_id=eos_token_id)
    if args.adapter_ids:
        gen_kwargs["lora_adapter_names"] = args.adapter_ids
    if args.do_sample:
        gen_kwargs.update(
            top_k=args.top_k, top_p=args.top_p, temperature=args.temperature
        )
    if args.debug_io:
        from neuronx_distributed_inference_tpu.utils.snapshot import enable_debug_logging

        enable_debug_logging()
    metrics_session = metrics_prev = None
    if args.metrics_out or args.trace_out:
        # a RUN-scoped session over a fresh registry (not the cumulative
        # process-default): the snapshot must describe THIS invocation, not
        # whatever else the embedding process ran earlier
        from neuronx_distributed_inference_tpu.telemetry import (
            TelemetrySession,
            tracing as _tel_tracing,
        )

        metrics_prev = _tel_tracing.default_session()
        metrics_session = _tel_tracing.set_default_session(TelemetrySession())
    capture_hook = None
    if args.input_capture_save_dir and args.capture_indices and args.capture_indices != ["auto"]:
        from neuronx_distributed_inference_tpu.utils.snapshot import install_input_capture

        capture_hook = install_input_capture(
            app, args.input_capture_save_dir,
            capture_indices=[int(i) for i in args.capture_indices],
        )

    import contextlib

    if args.profile_dir:
        from neuronx_distributed_inference_tpu.utils.profiling import profile_capture

        profile_ctx = profile_capture(args.profile_dir)
    else:
        profile_ctx = contextlib.nullcontext()

    # the ops HTTP surface rides the run as a CONTEXT MANAGER so its serve
    # thread is joined even when generation raises (LIFE804)
    if args.ops_port is not None:
        from neuronx_distributed_inference_tpu.telemetry import default_registry
        from neuronx_distributed_inference_tpu.telemetry.ops_server import OpsServer

        ops_ctx = OpsServer(
            (metrics_session.registry if metrics_session is not None
             else default_registry()),
            port=args.ops_port,
        )
    else:
        ops_ctx = contextlib.nullcontext()

    with ops_ctx as ops:
        if ops is not None:
            print(f"[inference_demo] ops server -> {ops.url}", file=sys.stderr)
        with profile_ctx:
            if draft_app is not None:
                from neuronx_distributed_inference_tpu.runtime.assisted import assisted_generate

                out = assisted_generate(
                    app, draft_app, input_ids, attention_mask,
                    max_new_tokens=args.max_new_tokens, eos_token_id=eos_token_id,
                    speculation_length=max(args.speculation_length, 2),
                )
            else:
                out = app.generate(input_ids, attention_mask, **gen_kwargs)
    if args.profile_dir:
        from neuronx_distributed_inference_tpu.utils.profiling import summarize_trace

        # device time under the program's own names, where the run wrote the
        # tables beside its trace (a serving session's step programs)
        for module, scopes in summarize_trace(args.profile_dir).get("by_scope", {}).items():
            for scope, t in scopes.items():
                print(f"[inference_demo] {module} {scope or '(no scope)'}: "
                      f"{t['seconds'] * 1e3:.3f} ms ({t['share']:.1%})", file=sys.stderr)
    if capture_hook is not None:
        print(f"[inference_demo] captured {len(capture_hook.saved)} input snapshots",
              file=sys.stderr)
    if metrics_session is not None:
        from neuronx_distributed_inference_tpu.telemetry import (
            tracing as _tel_tracing,
        )

        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(metrics_session.registry.snapshot(), f, indent=2)
            print(f"[inference_demo] metrics snapshot -> {args.metrics_out}",
                  file=sys.stderr)
        if args.trace_out:
            metrics_session.export_chrome_trace(args.trace_out)
            print(f"[inference_demo] chrome trace -> {args.trace_out}",
                  file=sys.stderr)
        _tel_tracing.set_default_session(metrics_prev)
        metrics_session.close()
    for i, seq in enumerate(out.sequences):
        text = tok.decode(seq, skip_special_tokens=True) if tok else seq.tolist()
        print(f"--- output {i} ---\n{text}")

    if args.check_accuracy_mode != "skip":
        from neuronx_distributed_inference_tpu.utils.accuracy import check_accuracy

        import transformers

        hf = transformers.AutoModelForCausalLM.from_pretrained(args.model_path).eval().float()
        capture_dir = None
        if args.input_capture_save_dir and (
            args.capture_indices == ["auto"] or not args.capture_indices
        ):
            # capture-on-divergence (reference --capture-indices auto,
            # inference_demo.py:600-614)
            capture_dir = args.input_capture_save_dir
        report = check_accuracy(
            app, input_ids, attention_mask, hf,
            max_new_tokens=args.max_new_tokens,
            divergence_tol=args.divergence_difference_tol,
            capture_dir=capture_dir,
        )
        print(f"[accuracy] passed={report.passed} {report.message}")
        if not report.passed:
            return 1

    return 0


def run_image_gen(args) -> int:
    """FLUX text-to-image (reference NeuronFluxApplication demo path,
    models/diffusers/flux/application.py): random-weight smoke or checkpoint
    generation with the four-sub-model pipeline."""
    import numpy as np

    from neuronx_distributed_inference_tpu.models.flux import FluxSpec
    from neuronx_distributed_inference_tpu.models.flux_text import (
        ClipTextSpec,
        T5EncoderSpec,
    )
    from neuronx_distributed_inference_tpu.models.flux_vae import VaeDecoderSpec
    from neuronx_distributed_inference_tpu.runtime.flux import (
        FluxPipelineConfig,
        TpuFluxPipeline,
    )

    if not args.random_weights:
        raise NotImplementedError(
            "image-gen demo currently drives random-weight pipelines; load "
            "checkpoints through runtime.flux.TpuFluxPipeline.load(...)"
        )
    cfg = FluxPipelineConfig(
        backbone=FluxSpec(
            dim=128, num_heads=4, head_dim=32, num_dual=2, num_single=2,
            in_channels=64, joint_dim=64, pooled_dim=48,
            axes_dims_rope=(8, 12, 12),
        ),
        clip=ClipTextSpec(
            hidden_size=48, num_heads=4, num_layers=2, intermediate_size=96,
            vocab_size=1024, max_positions=77,
        ),
        t5=T5EncoderSpec(
            d_model=64, num_heads=4, d_kv=16, num_layers=2, d_ff=128,
            vocab_size=1024,
        ),
        vae=VaeDecoderSpec(latent_channels=16, block_out_channels=(32, 32, 32, 32)),
        height=128, width=128, dtype=args.dtype,
    )
    pipe = TpuFluxPipeline(cfg).load(random_weights=True, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    clip_ids = rng.randint(1, 1000, size=(1, 8))
    t5_ids = rng.randint(1, 1000, size=(1, 16))
    img = pipe.generate(clip_ids, t5_ids, num_inference_steps=4, seed=args.seed)
    print(f"generated image batch: shape={img.shape}, "
          f"range=[{img.min():.3f}, {img.max():.3f}]")
    return 0


def run_workload_trace(args) -> int:
    """--workload-trace-out: materialize the seeded arrival trace and write
    it as JSON (no model load — trace generation is pure host data). The
    artifact is the reproducibility handle: archive it beside a goodput
    run and replay it bit-exactly later."""
    from neuronx_distributed_inference_tpu.workload import (
        generate,
        standard_spec,
    )

    trace = generate(standard_spec(
        seed=args.workload_seed,
        n_requests=args.workload_requests,
        vocab_size=args.workload_vocab,
        arrival_kind=args.workload_arrival,
        rate=args.workload_rate,
        n_tenants=args.workload_tenants,
        max_prompt_len=args.workload_max_prompt,
        max_output_len=args.workload_max_new_tokens,
        ttft_slo_s=args.workload_ttft_slo,
        itl_slo_s=args.workload_itl_slo,
        spec_profiles=True,
    ))
    with open(args.workload_trace_out, "w") as f:
        f.write(trace.dumps())
    print(
        f"workload trace -> {args.workload_trace_out} "
        f"({len(trace.arrivals)} arrivals, digest {trace.digest()[:16]})"
    )
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.workload_trace_out:
        return run_workload_trace(args)
    if args.model_path is None:
        print(
            "inference_demo: error: --model-path is required "
            "(it may be omitted only with --workload-trace-out, which "
            "loads no model)",
            file=sys.stderr,
        )
        return 2
    if args.task_type == "image-gen":
        return run_image_gen(args)
    return run_inference(args)


if __name__ == "__main__":
    sys.exit(main())
