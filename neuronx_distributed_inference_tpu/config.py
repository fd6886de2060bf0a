"""Configuration system.

TPU-native re-design of the reference config stack
(reference: src/neuronx_distributed_inference/models/config.py:81-1064):

- :class:`TpuConfig` — the flat runtime/feature config (reference ``NeuronConfig``,
  config.py:81-652). Every serving feature is a field here; validation of feature
  interactions happens in ``__post_init__`` (reference scatters it through
  ``NeuronConfig.__init__``).
- :class:`InferenceConfig` — wraps a ``TpuConfig`` plus the HF model attributes,
  with ``attribute_map`` aliasing and JSON round-trip
  (reference config.py:716-909).
- Sub-configs: :class:`OnDeviceSamplingConfig` (config.py:931),
  :class:`FusedSpecConfig` (config.py:912), :class:`ChunkedPrefillConfig`
  (config.py:944), :class:`MoETpuConfig` (config.py:665-713),
  :class:`LoraServingConfig` (modules/lora_serving/config.py).

Differences by design (TPU-first):
- dtypes are jnp dtypes serialized as strings.
- Parallel degrees map onto named ``jax.sharding.Mesh`` axes instead of process
  groups; ``world_size`` is derived identically (config.py:353-355).
- No compiler-flag strings: XLA options are set via jit/compilation-cache.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtype handling
# ---------------------------------------------------------------------------

def to_dtype(name_or_dtype) -> Any:
    """Resolve a dtype name (or dtype) to a jnp dtype."""
    if isinstance(name_or_dtype, str):
        key = name_or_dtype.replace("torch.", "")
        table = {
            "float32": jnp.float32,
            "fp32": jnp.float32,
            "bfloat16": jnp.bfloat16,
            "bf16": jnp.bfloat16,
            "float16": jnp.float16,
            "fp16": jnp.float16,
            "int8": jnp.int8,
            "fp8": jnp.float8_e4m3fn,
            "float8_e4m3": jnp.float8_e4m3fn,
            "float8_e5m2": jnp.float8_e5m2,
        }
        if key not in table:
            raise ValueError(f"Unknown dtype name: {name_or_dtype}")
        return table[key]
    return name_or_dtype


def dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


#: accepted kv_cache_dtype names: plain storage dtypes plus the quantized
#: (codes + per-(layer, head) scales) cache formats. Anything else fails
#: validation loudly — an unknown string must not silently serve bf16.
KV_CACHE_DTYPES = (
    "bfloat16", "bf16", "float16", "fp16", "float32", "fp32",
    "int8", "fp8", "float8_e4m3", "float8_e5m2",
)
KV_QUANT_DTYPE_NAMES = ("int8", "fp8", "float8_e4m3", "float8_e5m2")

#: multi-replica router placement policies (runtime/router.py consumes this
#: as its PLACEMENT_POLICIES registry — defined here so config validation
#: needs no runtime import)
ROUTER_POLICIES = ("round_robin", "least_loaded", "cache_aware")


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass
class OnDeviceSamplingConfig:
    """On-device sampler settings (reference config.py:931-941)."""

    do_sample: bool = False
    top_k: int = 1
    top_p: float = 1.0
    temperature: float = 1.0
    dynamic: bool = True  # per-request (top_k, top_p, temperature) tensor
    global_topk: int = 256  # stage-1 topk width for distributed sampling
    deterministic: bool = False
    on_device_sampling: bool = True

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(**_strict_kwargs(cls, dict(d)))


@dataclass
class FusedSpecConfig:
    """Fused speculation: draft + target compiled into one graph
    (reference config.py:912-928, model_base.py:1656)."""

    draft_model_name: str = ""
    draft_config: Optional["InferenceConfig"] = None
    worker_cls_name: str = ""

    def to_dict(self):
        d = {"draft_model_name": self.draft_model_name, "worker_cls_name": self.worker_cls_name}
        if self.draft_config is not None:
            d["draft_config"] = self.draft_config.to_dict()
        return d

    @classmethod
    def from_dict(cls, d):
        draft = d.get("draft_config")
        return cls(
            draft_model_name=d.get("draft_model_name", ""),
            draft_config=InferenceConfig.from_dict(draft) if draft else None,
            worker_cls_name=d.get("worker_cls_name", ""),
        )


@dataclass
class ChunkedPrefillConfig:
    """Chunked prefill settings (reference config.py:944-959)."""

    max_num_seqs: int = 8
    tkg_model_enabled: bool = True
    kernel_q_tile_size: int = 128
    kernel_kv_tile_size: int = 512

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(**_strict_kwargs(cls, dict(d)))


@dataclass
class _TapPointsConfig:
    """Shared base: a validated list of tensor-tap point names
    (modules/tensor_taps.TAP_POINTS)."""

    points: List[str] = field(default_factory=list)

    def __post_init__(self):
        from neuronx_distributed_inference_tpu.modules.tensor_taps import TAP_POINTS

        unknown = set(self.points) - set(TAP_POINTS)
        if unknown:
            raise ValueError(
                f"unknown tap point(s) {sorted(unknown)} for "
                f"{type(self).__name__}; available: {TAP_POINTS}"
            )

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(**_strict_kwargs(cls, dict(d)))


@dataclass
class TensorCaptureConfig(_TapPointsConfig):
    """Capture named intermediate tensors from the traced forward
    (reference TensorCaptureConfig, config.py:987; capture plumbing
    model_base.py:1120-1226)."""


@dataclass
class TensorReplacementConfig(_TapPointsConfig):
    """Teacher-force named intermediate tensors with host-provided goldens
    (reference TensorReplacementConfig, config.py:1038 +
    utils/tensor_replacement/registry.py). The golden arrays are supplied
    per call (application.capture_forward replacements=...)."""


@dataclass
class LoraServingConfig:
    """Multi-adapter LoRA serving (reference modules/lora_serving/config.py)."""

    max_loras: int = 1
    max_lora_rank: int = 16
    max_loras_on_cpu: int = 2
    lora_ckpt_paths: Optional[Dict[str, str]] = None
    lora_dtype: str = "bfloat16"
    target_modules: Tuple[str, ...] = ("q_proj", "k_proj", "v_proj", "o_proj")

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["target_modules"] = list(self.target_modules)
        return d

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        if "target_modules" in d:
            d["target_modules"] = tuple(d["target_modules"])
        return cls(**_strict_kwargs(cls, d))


def _field_names(cls) -> set:
    return {f.name for f in dataclasses.fields(cls)}


def _strict_kwargs(cls, d: dict) -> dict:
    """Reject unknown keys when deserializing a config.

    A typo'd feature flag in a saved ``tpu_config.json`` must fail loudly, not
    round-trip to silently-off (the dataclass constructor refuses an unknown
    keyword the same way for a live config).
    """
    unknown = sorted(set(d) - _field_names(cls))
    if unknown:
        raise ValueError(
            f"Unknown {cls.__name__} key(s) in serialized config: {unknown}. "
            "Refusing to silently drop them — this usually means the artifact "
            "was saved by a DIFFERENT framework version. Migration: re-save "
            "the compiled artifact with this version (compile() writes a "
            "fresh tpu_config.json), or delete the stale key(s) from "
            "tpu_config.json if their features are no longer configured."
        )
    return d


# ---------------------------------------------------------------------------
# TpuConfig (reference NeuronConfig)
# ---------------------------------------------------------------------------


@dataclass
class TpuConfig:
    """Flat runtime/feature config (reference NeuronConfig, config.py:81-652).

    One instance per compiled sub-model; the application deep-copies and
    specializes it per sub-model tag (reference model_base.py:3099-3222).
    """

    # --- core shapes -----------------------------------------------------
    batch_size: int = 1
    max_batch_size: Optional[int] = None  # defaults to batch_size
    ctx_batch_size: Optional[int] = None
    tkg_batch_size: Optional[int] = None
    seq_len: int = 128
    max_context_length: Optional[int] = None  # defaults to seq_len
    n_active_tokens: Optional[int] = None  # tokens processed per step (CTE: bucket len)
    max_new_tokens: Optional[int] = None
    max_length: Optional[int] = None

    # --- dtypes ----------------------------------------------------------
    dtype: str = "bfloat16"  # compute/weight dtype
    cast_logits_fp32: bool = True
    attention_softmax_fp32: bool = True

    # --- bucketing (reference modules/autobucketing.py) ------------------
    enable_bucketing: bool = True
    buckets: Optional[List[int]] = None  # resolved at build
    context_encoding_buckets: Optional[List[int]] = None
    token_generation_buckets: Optional[List[int]] = None

    # --- batching --------------------------------------------------------
    is_continuous_batching: bool = False
    padding_side: str = "right"

    # --- sampling --------------------------------------------------------
    on_device_sampling_config: Optional[OnDeviceSamplingConfig] = None
    max_topk: int = 256
    output_logits: bool = False
    # the step also returns the discrete choices its layers made (an expert
    # layer's selection: name -> int (B, S, L_moe, k)) as ``forward``'s third
    # value; for a correctness probe that replays them. A model whose stack
    # returns none refuses it at trace time (models/base.forward)
    output_choices: bool = False

    # --- KV cache --------------------------------------------------------
    # None = store in `dtype`; "int8"/"fp8" build the quantized cache
    # (codes + per-(layer, head) running-absmax scales, modules/kvcache.py)
    # with fused in-kernel dequant on the decode/paged kernels. Validated
    # against KV_CACHE_DTYPES — unknown names fail loudly.
    kv_cache_dtype: Optional[str] = None
    is_block_kv_layout: bool = False  # paged KV cache
    pa_num_blocks: Optional[int] = None
    pa_block_size: int = 16
    # size the paged block pool by HBM BYTES instead of a block count: the
    # application derives pa_num_blocks = pa_pool_bytes // true-per-block
    # byte cost in the CACHE dtype (block_kvcache.kv_block_bytes) — a
    # quantized cache admits ~2x the blocks for the same budget
    pa_pool_bytes: Optional[int] = None
    is_prefix_caching: bool = False
    is_chunked_prefill: bool = False
    chunked_prefill_config: Optional[ChunkedPrefillConfig] = None
    kv_cache_batch_size: Optional[int] = None
    # ragged mixed-step serving dispatch (runtime/serving.py): pack admitted
    # prefill chunks AND active decode rows into ONE ragged paged-attention
    # dispatch per step() (ops/ragged_paged_attention.py), collapsing the
    # CTE/TKG split on the serving path. Requires the paged cache
    # (is_block_kv_layout) under continuous batching; plain full-length
    # attention only. Default OFF until hardware-validated — the legacy
    # split dispatch stays byte-identical (pinned by test; quantized KV
    # caches agree within the kv-quant tolerance instead: the running
    # absmax couples whatever one dispatch co-writes, and the ragged step
    # groups writes differently — docs/SERVING.md).
    serving_ragged: bool = False
    # multi-replica serving front-end (runtime/router.py): how many
    # single-chip replica sessions the ServingRouter runs the demo/bench
    # serving traffic over (1 = no router layer), and the placement policy
    # that binds requests to replicas. `least_loaded` scores replicas from
    # live telemetry signals (re-admission backlog, occupancy, kv_free_bytes
    # headroom, EWMAs of step-host/queue-wait ms); `round_robin` cycles the
    # healthy set; `cache_aware` ranks candidates by each replica's REAL
    # prefix-cache match index (longest cached block-chain of the prompt),
    # load order breaking ties.
    serving_replicas: int = 1
    router_policy: str = "least_loaded"
    # disaggregated prefill tier (runtime/router.py + runtime/disaggregated
    # .py): carve this many of `serving_replicas` out as DEDICATED prefill
    # replicas — they run context encoding + extract_request_kv only, and
    # the remaining (serving_replicas - router_prefill_replicas) decode
    # replicas inject the handed-over KV and serve decode. A 16k-prompt
    # burst then never stalls a co-located decode row's ITL. The KV hand-off
    # is a CONTAINED failure domain: payload validation at inject (a corrupt
    # or truncated hand-off terminally fails ONE request with typed
    # FAILED(handoff), destination KV scrubbed), bounded hand-off retry with
    # capped backoff, and tier-wide graceful degradation (every prefill
    # replica dead => decode replicas fall back to local monolithic prefill,
    # loudly — nxdi_handoff_local_prefill_total). Requires the contiguous
    # cache (the hand-off scatters whole cache lines; paged decode caches
    # are not supported) under continuous batching. 0 = no tier (every
    # replica prefills locally). See docs/SERVING.md "Disaggregated prefill
    # tier".
    router_prefill_replicas: int = 0
    # hand-off containment knobs: transient hand-off failures (transit loss,
    # timeout, a transient prefill dispatch error) retry up to
    # handoff_max_retries times with capped backoff — exhaustion terminally
    # fails ONLY the in-flight request (FAILED(handoff)) and degrades the
    # prefill replica like a dispatch give-up. handoff_timeout_s bounds one
    # hand-off attempt's wall clock (None = no timeout; an attempt observed
    # past it counts as a failed attempt and retries).
    handoff_max_retries: int = 2
    handoff_timeout_s: Optional[float] = None
    # thread-per-replica router stepping (runtime/router.py): ServingRouter
    # dispatches every alive replica's step() from a persistent pool of one
    # worker thread per replica and waits on a per-step barrier — dispatch
    # and the non-blocking token fetches release the GIL, so N replicas'
    # device steps overlap instead of host-serializing behind one Python
    # loop. Placement, admission, failover harvesting, terminal sync and
    # every telemetry gauge stay on the router thread; ONLY
    # ReplicaHandle.step() runs on workers — the confinement model the
    # concurrency audit (CONC601-604, analysis/concurrency_audit.py) proves
    # statically. Default OFF until hardware-validated; threaded drains are
    # pinned byte-identical to sequential stepping (tests/
    # test_router_threaded.py). See docs/SERVING.md "Threaded replica
    # stepping".
    router_threading: bool = False

    # --- attention -------------------------------------------------------
    fused_qkv: bool = False
    sliding_window: Optional[int] = None
    attention_chunk_size: Optional[int] = None  # chunked attention (llama4)
    flash_decoding_enabled: bool = False
    num_cores_per_group: int = 1
    attn_kernel_enabled: Optional[bool] = None  # None = auto (pallas flash attn on TPU)
    # head-pair packed flash prefill (ops/flash_attention.py packed path):
    # D<=64 models run attention with two heads per 128-lane tile at full
    # MXU contraction depth. None = auto-on for causal D<=64 shapes
    # whenever the flash kernel runs, True = force (still honors shape
    # guards), False = keep the unpacked kernel. The packed softmax
    # intermediates follow attention_softmax_fp32: the default (True) keeps
    # fp32 exp/PV like the unpacked kernel; set it False to add the bf16
    # VPU/MXU win on top of the packing.
    attn_packed_kernel_enabled: Optional[bool] = None
    # decode (TKG) attention kernel, contiguous + paged (ops/decode_attention.py):
    # None = auto on TPU, True = force, False = native gather path.
    # NOTE: artifacts saved before this feature landed serialized the then-
    # inert default `false`, which now pins the native path — re-save the
    # artifact (or edit tpu_config.json to null) to restore auto.
    attn_block_tkg_kernel_enabled: Optional[bool] = None
    qk_norm: bool = False

    # --- speculation -----------------------------------------------------
    speculation_length: int = 0
    enable_fused_speculation: bool = False
    enable_eagle_speculation: bool = False
    enable_eagle_draft_input_norm: bool = False
    # EAGLE3: multi-layer target hidden capture + fused 2H-qkv draft layer
    # (reference is_eagle3, model_base.py:1444-1479)
    is_eagle3: bool = False
    medusa_speculation_length: int = 0
    num_medusa_heads: int = 0
    token_tree_config: Optional[dict] = None

    # --- parallelism (mesh axes; reference config.py:333-361) ------------
    tp_degree: int = 1
    cp_degree: int = 1  # context parallel (prefill attention)
    attention_dp_degree: int = 1  # data parallel decode attention
    # whole-model data parallel (leading ddp mesh axis; rides DCN multi-host:
    # weights replicate, the batch shards). TPU-native extension — the
    # reference runs whole-model DP as separate vLLM replicas.
    data_parallel_degree: int = 1
    pp_degree: int = 1
    ep_degree: int = 1
    moe_tp_degree: Optional[int] = None
    moe_ep_degree: Optional[int] = None
    start_rank_id: int = 0
    local_ranks_size: Optional[int] = None
    sequence_parallel_enabled: bool = False
    vocab_parallel: bool = False
    is_prefill_stage: Optional[bool] = None

    # --- quantization ----------------------------------------------------
    quantized: bool = False
    quantization_type: str = "per_channel_symmetric"  # or per_tensor_symmetric, blockwise
    quantization_dtype: str = "int8"
    modules_to_not_convert: Optional[List[str]] = None
    # pre-quantized checkpoint dir: loaded when present, written after the
    # first quantize-at-load (reference quantized_checkpoints_path,
    # application_base.py:636-797)
    quantized_checkpoints_path: Optional[str] = None
    # input-axis block size for quantization_type="blockwise" (reference
    # blockwise_matmul_block_size, config.py:665-713)
    blockwise_matmul_block_size: int = 128
    # decode weight-storage dtype (docs/WEIGHT_QUANT.md): "bfloat16" keeps
    # weights in compute dtype; "int8" aliases the established quantize-at-
    # load path (quantized=True, per-channel int8); "int4" packs grouped
    # sub-byte codes streamed by the ops/quant_matmul fused-dequant kernel.
    weight_dtype: str = "bfloat16"

    # --- LoRA ------------------------------------------------------------
    lora_config: Optional[LoraServingConfig] = None

    # --- debug taps (reference config.py:987/:1038) -----------------------
    tensor_capture_config: Optional[TensorCaptureConfig] = None
    tensor_replacement_config: Optional[TensorReplacementConfig] = None

    # --- serving fault containment (runtime/serving.py, runtime/faults.py;
    # docs/SERVING.md "Failure containment") ------------------------------
    # validate requests at admission (token-id range vs vocab, empty/over-
    # long prompts, non-positive budgets): malformed requests get a typed
    # terminal REJECTED verdict instead of raising (or NaN-ing) mid-batch.
    # False restores the legacy raise-late behavior.
    admission_validation: bool = True
    # wall-clock TTL per request in seconds (None = no deadline): requests
    # past it are dropped with terminal reason `deadline_exceeded`, checked
    # at step boundaries. Per-request override: add_request(deadline_s=...).
    request_deadline_s: Optional[float] = None
    # transient dispatch errors retry with capped exponential backoff up to
    # this many times; after that only the in-flight rows fail
    # (FAILED(dispatch_error)) — never the process.
    dispatch_max_retries: int = 2
    # no-forward-progress watchdog: after this many consecutive steps with
    # zero committed tokens / prefill advance / admissions (while work is
    # live), preempt the largest request; a second full window raises
    # WatchdogError with a diagnostic snapshot. 0 disables.
    watchdog_no_progress_steps: int = 256

    # --- misc ------------------------------------------------------------
    seed: int = 0
    # True (default): generate() chains CTE -> decode chunks with
    # device-resident tokens, one sync per call (runtime/application.py).
    # False: block at every chunk boundary (step-accurate debugging).
    async_mode: bool = True
    # seal the jit caches after warmup(): any steady-state retrace/recompile
    # raises instead of silently blowing the latency model (analysis/
    # retrace_guard.py). Env override: NXDI_TPU_RETRACE_GUARD=1.
    retrace_guard: bool = False
    skip_warmup: bool = False
    save_sharded_checkpoint: bool = False
    # persistent XLA cache directory; JAX_COMPILATION_CACHE_DIR, when set,
    # wins over it (utils/compile_cache.py)
    compilation_cache_dir: Optional[str] = None

    def __post_init__(self):
        if self.max_batch_size is None:
            self.max_batch_size = self.batch_size
        if self.ctx_batch_size is None:
            self.ctx_batch_size = self.max_batch_size
        if self.tkg_batch_size is None:
            self.tkg_batch_size = self.max_batch_size
        if self.max_context_length is None:
            self.max_context_length = self.seq_len
        if self.max_length is None:
            self.max_length = self.seq_len
        if self.n_active_tokens is None:
            self.n_active_tokens = self.seq_len
        if self.moe_tp_degree is None:
            self.moe_tp_degree = self.tp_degree // self.ep_degree if self.ep_degree > 1 else self.tp_degree
        if self.moe_ep_degree is None:
            self.moe_ep_degree = self.ep_degree
        if self.local_ranks_size is None:
            self.local_ranks_size = self.world_size
        if self.weight_dtype == "bf16":
            self.weight_dtype = "bfloat16"
        if self.weight_dtype == "int8" and not self.quantized:
            # int8 weights already have a first-class path (quantized=True);
            # the weight_dtype spelling is an alias onto it so the knob is
            # one dial across {bfloat16, int8, int4}
            self.quantized = True
        self.validate()

    # world size identical to reference config.py:353-355
    @property
    def world_size(self) -> int:
        return self.tp_degree * self.pp_degree * self.ep_degree * self.data_parallel_degree

    @property
    def torch_dtype(self):  # name kept for API familiarity; returns jnp dtype
        return to_dtype(self.dtype)

    @property
    def jax_dtype(self):
        return to_dtype(self.dtype)

    @property
    def kv_dtype(self):
        return to_dtype(self.kv_cache_dtype) if self.kv_cache_dtype else to_dtype(self.dtype)

    @property
    def kv_quantized(self) -> bool:
        """True when the KV cache stores int8/fp8 codes + scales."""
        return self.kv_cache_dtype in KV_QUANT_DTYPE_NAMES

    @property
    def weight_int4(self) -> bool:
        """True when weights pack to grouped int4 at load (ops/quant_matmul)."""
        return self.weight_dtype == "int4"

    def validate(self):
        """Feature-interaction validation (reference config.py:567-594)."""
        if self.kv_cache_dtype is not None and self.kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r}; supported: "
                f"{KV_CACHE_DTYPES} (int8/fp8 build the quantized cache)"
            )
        if self.pa_pool_bytes is not None:
            if not self.is_block_kv_layout:
                raise ValueError("pa_pool_bytes requires is_block_kv_layout")
            if self.pa_num_blocks is not None:
                raise ValueError(
                    "set pa_num_blocks OR pa_pool_bytes, not both (the pool "
                    "byte budget derives the block count from the cache dtype)"
                )
        if self.request_deadline_s is not None and not self.request_deadline_s > 0:
            raise ValueError(
                "request_deadline_s must be > 0 seconds (None disables "
                "per-request deadlines)"
            )
        if self.dispatch_max_retries < 0:
            raise ValueError(
                "dispatch_max_retries must be >= 0 (0 = fail in-flight rows "
                "on the first transient dispatch error)"
            )
        if self.watchdog_no_progress_steps < 0:
            raise ValueError(
                "watchdog_no_progress_steps must be >= 0 (0 disables the "
                "no-progress watchdog)"
            )
        if self.serving_replicas < 1:
            raise ValueError(
                "serving_replicas must be >= 1 (1 = a single session, no "
                "router layer)"
            )
        if self.router_policy not in ROUTER_POLICIES:
            raise ValueError(
                f"unknown router_policy {self.router_policy!r}; known "
                f"placement policies: {ROUTER_POLICIES}"
            )
        if self.serving_replicas > 1 and not self.is_continuous_batching:
            raise ValueError(
                "serving_replicas > 1 routes over serving sessions: set "
                "is_continuous_batching=True"
            )
        if self.router_prefill_replicas < 0:
            raise ValueError(
                "router_prefill_replicas must be >= 0 (0 = no disaggregated "
                "prefill tier; every replica prefills locally)"
            )
        if self.router_prefill_replicas > 0:
            if self.router_prefill_replicas >= self.serving_replicas:
                raise ValueError(
                    "router_prefill_replicas is carved OUT OF "
                    "serving_replicas: at least one decode replica must "
                    f"remain ({self.router_prefill_replicas} prefill of "
                    f"{self.serving_replicas} total leaves none)"
                )
            if self.is_block_kv_layout:
                raise ValueError(
                    "the disaggregated prefill tier hands KV over into "
                    "contiguous cache lines: router_prefill_replicas > 0 "
                    "does not support is_block_kv_layout (decode replicas "
                    "need the plain contiguous cache)"
                )
        if self.handoff_max_retries < 0:
            raise ValueError(
                "handoff_max_retries must be >= 0 (0 = a hand-off fails its "
                "in-flight request on the first transient failure)"
            )
        if self.handoff_timeout_s is not None and not self.handoff_timeout_s > 0:
            raise ValueError(
                "handoff_timeout_s must be > 0 seconds (None disables the "
                "per-attempt hand-off timeout)"
            )
        if self.attention_dp_degree > 1 and not self.is_continuous_batching:
            raise ValueError("attention_dp_degree > 1 requires is_continuous_batching")
        if self.attention_dp_degree > 1 and self.max_batch_size % self.attention_dp_degree != 0:
            raise ValueError("batch size must divide evenly across attention DP ranks")
        # attention-DP + paged cache: the block pool replicates over the dp
        # axis (batch-parallel attention reads any block); the contiguous
        # cache dp-shards its batch dim instead — see parallel/attention_dp.py
        if self.data_parallel_degree > 1:
            shards = self.attention_dp_degree * self.data_parallel_degree
            if (self.kv_cache_batch_size or self.max_batch_size) % shards != 0:
                raise ValueError(
                    "batch size must be divisible by attention_dp_degree * "
                    "data_parallel_degree"
                )
            if self.enable_fused_speculation:
                raise NotImplementedError(
                    "whole-model DP with fused speculation is not implemented"
                )
            if self.is_block_kv_layout:
                raise NotImplementedError(
                    "whole-model DP with the paged cache is not implemented"
                )
        if self.attention_dp_degree > 1 and self.enable_fused_speculation:
            raise NotImplementedError(
                "attention-DP with fused/EAGLE speculation is not implemented "
                "(the speculation caches are not DP-sharded)"
            )
        if self.attention_dp_degree > 1 and (
            self.kv_cache_batch_size or self.max_batch_size
        ) % self.attention_dp_degree != 0:
            raise ValueError("kv_cache_batch_size must divide across attention DP ranks")
        if self.cp_degree > 1 and self.tp_degree % self.cp_degree != 0:
            raise ValueError("cp_degree must divide tp_degree (cp splits the tp group)")
        if self.tp_degree % (self.cp_degree * self.attention_dp_degree) != 0:
            raise ValueError(
                "cp_degree * attention_dp_degree must divide tp_degree "
                "(both subdivide the TP group)"
            )
        if self.is_chunked_prefill and not self.is_block_kv_layout:
            raise ValueError("chunked prefill requires block KV layout")
        if self.is_chunked_prefill and self.chunked_prefill_config is None:
            self.chunked_prefill_config = ChunkedPrefillConfig()
        if self.is_chunked_prefill and not self.is_continuous_batching:
            raise ValueError("chunked prefill runs through the serving session: "
                             "set is_continuous_batching=True")
        if self.is_prefix_caching and not self.is_block_kv_layout:
            raise ValueError("prefix caching requires block KV layout")
        if self.serving_ragged:
            if not self.is_block_kv_layout:
                raise ValueError(
                    "serving_ragged requires the paged cache "
                    "(is_block_kv_layout=True): the ragged kernel addresses "
                    "rows through block tables"
                )
            if not self.is_continuous_batching:
                raise ValueError(
                    "serving_ragged runs through the serving session: set "
                    "is_continuous_batching=True"
                )
            if self.sliding_window or self.attention_chunk_size:
                raise NotImplementedError(
                    "serving_ragged: the ragged paged kernel "
                    "(ops/ragged_paged_attention.py) implements the plain "
                    "causal+prefix mask, with no lower frontier and no chunk "
                    "rule; a window rides the split step's two paged kernels "
                    "(models/base.paged_attend)"
                )
            if (
                self.attention_dp_degree > 1
                or self.cp_degree > 1
                or self.data_parallel_degree > 1
            ):
                raise NotImplementedError(
                    "serving_ragged is single-shard-parallel (tp only)"
                )
        if (
            self.is_block_kv_layout
            and self.pa_num_blocks is None
            and self.pa_pool_bytes is None
        ):
            self.pa_num_blocks = max(
                1, (self.max_batch_size * self.seq_len + self.pa_block_size - 1) // self.pa_block_size
            )
        if self.enable_eagle_speculation and not self.enable_fused_speculation:
            raise ValueError("EAGLE speculation requires fused speculation")
        if self.is_eagle3 and not self.enable_eagle_speculation:
            raise ValueError("is_eagle3 requires enable_eagle_speculation")
        if self.token_tree_config is not None:
            if not self.enable_eagle_speculation:
                raise ValueError(
                    "token_tree_config requires enable_eagle_speculation "
                    "(trees expand the EAGLE draft; reference eagle/token_tree.py)"
                )
        if self.medusa_speculation_length and self.num_medusa_heads <= 0:
            raise ValueError("medusa requires num_medusa_heads > 0")
        if self.padding_side not in ("right", "left"):
            raise ValueError("padding_side must be 'right' or 'left'")
        if self.quantization_type not in (
            "per_channel_symmetric",
            "per_tensor_symmetric",
            "blockwise",
        ):
            raise ValueError(f"unknown quantization_type {self.quantization_type}")
        if self.weight_dtype not in ("bfloat16", "int8", "int4"):
            raise ValueError(
                f"unknown weight_dtype {self.weight_dtype!r}; supported: "
                "bfloat16 (no conversion), int8 (per-channel quantize-at-"
                "load), int4 (grouped fused-dequant streaming)"
            )
        if self.weight_dtype == "int4":
            if self.quantized:
                raise ValueError(
                    "weight_dtype='int4' and quantized=True are two different "
                    "weight-conversion recipes applied to the same leaves; "
                    "pick one (int8 via weight_dtype='int8' IS quantized=True)"
                )
            if self.quantized_checkpoints_path:
                raise NotImplementedError(
                    "pre-quantized checkpoint artifacts are int8-only; "
                    "weight_dtype='int4' packs at load (refusing to silently "
                    "ignore quantized_checkpoints_path)"
                )
        if self.flash_decoding_enabled and self.cp_degree <= 1:
            raise ValueError(
                "flash decoding on TPU rides the cp mesh axis (S-sharded KV "
                "cache, kvcache.py): set cp_degree > 1 to distribute the "
                "decode softmax (reference num_cores_per_group grouping)"
            )
        if self.num_cores_per_group != 1 and self.num_cores_per_group != self.cp_degree:
            raise ValueError(
                "num_cores_per_group maps onto the cp mesh axis on TPU; it "
                "must equal cp_degree (or 1)"
            )
        expected_moe_tp = (
            self.tp_degree // self.ep_degree if self.ep_degree > 1 else self.tp_degree
        )
        if self.moe_tp_degree != expected_moe_tp or self.moe_ep_degree != self.ep_degree:
            raise NotImplementedError(
                "custom moe_tp/moe_ep degrees are not implemented: experts "
                "shard over the ep mesh axis and expert ffn over (cp, tp) "
                "(parallel/mesh.py); moe degrees follow tp/ep"
            )
        if self.fused_qkv and self.lora_config is not None:
            raise NotImplementedError(
                "fused_qkv with LoRA serving is not supported: adapters "
                "target q/k/v projections individually"
            )

    # --- serialization ---------------------------------------------------

    def to_dict(self) -> dict:
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                d[f.name] = None
            elif hasattr(v, "to_dict"):
                d[f.name] = v.to_dict()
            else:
                d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TpuConfig":
        d = dict(d)
        if d.get("on_device_sampling_config"):
            d["on_device_sampling_config"] = OnDeviceSamplingConfig.from_dict(
                d["on_device_sampling_config"]
            )
        if d.get("chunked_prefill_config"):
            d["chunked_prefill_config"] = ChunkedPrefillConfig.from_dict(d["chunked_prefill_config"])
        if d.get("lora_config"):
            d["lora_config"] = LoraServingConfig.from_dict(d["lora_config"])
        if d.get("tensor_capture_config"):
            d["tensor_capture_config"] = TensorCaptureConfig.from_dict(
                d["tensor_capture_config"]
            )
        if d.get("tensor_replacement_config"):
            d["tensor_replacement_config"] = TensorReplacementConfig.from_dict(
                d["tensor_replacement_config"]
            )
        return cls(**_strict_kwargs(cls, d))


@dataclass
class MoETpuConfig(TpuConfig):
    """MoE extras (reference MoENeuronConfig, config.py:665-713)."""

    glu_mlp: bool = True
    glu_type: str = "glu"
    hidden_act_scaling_factor: float = 1.0
    hidden_act_bias: float = 0.0
    normalize_top_k_affinities: bool = True
    early_expert_affinity_modulation: bool = False
    fused_shared_experts: bool = False
    router_dtype: str = "float32"
    hybrid_sharding_config: Optional[dict] = None

    def validate(self):
        super().validate()
        if not self.glu_mlp or self.glu_type != "glu":
            raise NotImplementedError(
                "non-GLU expert MLPs are not implemented (experts are "
                "gate/up/down GLU, modules/moe.py)"
            )
        if self.hybrid_sharding_config is not None:
            h = dict(self.hybrid_sharding_config)
            total = self.tp_degree * self.ep_degree
            cte_tp = int(h.get("moe_cte_tp_degree", total))
            cte_ep = int(h.get("moe_cte_ep_degree", 1))
            tkg_tp = int(h.get("moe_tkg_tp_degree", self.tp_degree))
            tkg_ep = int(h.get("moe_tkg_ep_degree", self.ep_degree))
            if tkg_tp * tkg_ep != total or cte_tp * cte_ep != total:
                raise ValueError(
                    "hybrid_sharding_config degrees must multiply to "
                    f"tp_degree*ep_degree={total}: got cte {cte_tp}x{cte_ep}, "
                    f"tkg {tkg_tp}x{tkg_ep}"
                )
            if tkg_tp != self.tp_degree or tkg_ep != self.ep_degree:
                raise NotImplementedError(
                    "the PERSISTENT (decode) expert layout is the mesh's "
                    "tp_degree x ep_degree — set moe_tkg_tp/ep to match and "
                    "express the prefill preference via moe_cte_tp/ep"
                )
            if cte_ep != 1:
                raise NotImplementedError(
                    "hybrid prefill sharding supports moe_cte_ep_degree=1 "
                    "(full-TP prefill experts, GSPMD-resharded in the CTE "
                    "program); other factorings need a second weight copy"
                )


# ---------------------------------------------------------------------------
# InferenceConfig
# ---------------------------------------------------------------------------

CONFIG_FILE = "tpu_config.json"  # reference: neuron_config.json (config.py:22)


def speculation_requested(tc: "TpuConfig") -> bool:
    """Whether any speculation option is set: what the validators below
    refuse for a cache no draft's width is held to a reference over."""
    return bool(
        tc.speculation_length or tc.medusa_speculation_length or tc.enable_fused_speculation
        or tc.enable_eagle_speculation
    )


class SlotStateServingError(NotImplementedError):
    """An option that cannot serve a model whose layers keep a constant-size
    per-slot state (state-space layers; a one-token carry) was set for one."""


def validate_slot_state_serving(tc: "TpuConfig", what: str = "state-space layers",
                                state: str = "recurrent state") -> None:
    """Refuse, for a model whose builder declares per-slot state
    (``init_slot_state()``: ``what`` keeps a ``state`` per slot), every
    option that would serve it wrongly rather than not at all. One line
    each: none is a silent wrong answer."""
    refusals = (
        (tc.is_prefix_caching, f"is_prefix_caching: a {state} cannot be shared block by block"),
        (tc.serving_ragged, f"serving_ragged: the ragged mixed step advances no {state}"),
        (speculation_requested(tc), f"speculation: rejected drafts would need a snapshot of the {state} to roll back to"),
        (tc.kv_quantized, f"kv_cache_dtype quantisation: the {state} is kept unquantised beside the pool "
                          "and the two are not held to a reference together"),
        (tc.tp_degree * tc.ep_degree * tc.cp_degree * tc.attention_dp_degree
         * tc.data_parallel_degree > 1,
         f"tp/ep/cp/dp degree > 1: the {state}'s update is not partitioned"),
    )
    for flag, why in refusals:
        if flag:
            raise SlotStateServingError(f"a model with {what} cannot be served with {why}")


class LoopedStackError(NotImplementedError):
    """An option that cannot run a model whose layer stack runs several times
    over one set of weights (models/ouro.py: ``total_ut_steps`` > 1) was set
    for one, or its config asks for an exit the stack does not take."""


def validate_looped_stack(tc: "TpuConfig", loop_steps: int, early_exit_threshold: float) -> None:
    """Refuse, for a model whose builder declares a looped stack, what the
    loop does not do yet, each by name: none is a silent wrong answer. Every
    site listed reads ONE cache entry or ONE pass a layer; the pool of a
    looped stack has ``loop_steps`` x layers entries."""
    refusals = (
        (loop_steps < 1, f"total_ut_steps {loop_steps}: the stack runs at least once"),
        (early_exit_threshold < 1,
         f"early_exit_threshold {early_exit_threshold} < 1: a depth that differs by row (a position "
         "leaving the stack at an earlier loop) is not built; every position runs every loop"),
        (tc.lora_config is not None,
         "lora_config: the adapters are attached by layer (runtime/application.py passes "
         "spec.num_layers), not by layer pass"),
        (speculation_requested(tc), "speculation (speculation_length, medusa, fused, EAGLE and its "
                      "capture_layers): a draft's cache has one entry a layer"),
        (tc.serving_ragged, "serving_ragged: the ragged mixed step scans the layers once "
                            "(models/base.py mixed_forward has its own scan)"),
        (tc.kv_quantized, "kv_cache_dtype quantisation: one scale a (layer, head) is not held to a "
                          "reference over loop_steps streams a layer"),
        (tc.tp_degree * tc.ep_degree * tc.cp_degree * tc.attention_dp_degree
         * tc.data_parallel_degree > 1,
         "tp/ep/cp/dp degree > 1: the looped scan is not held to a reference on a mesh"),
    )
    for flag, why in refusals:
        if flag:
            raise LoopedStackError(f"a model with a looped layer stack cannot run with {why}")


class LatentAttentionError(NotImplementedError):
    """An option that cannot run a model whose attention caches one
    compressed latent and one rotary key a token (MLA: models/deepseek.py)
    was set for one."""


def validate_latent_attention(tc: "TpuConfig") -> None:
    """Refuse, for a model whose builder declares a latent cache stream,
    every option that would run it wrongly rather than not at all: on any
    path what the layer does not write, and on the paged serving path what
    the latent pool cannot do yet. One line each."""
    paged = tc.is_block_kv_layout
    refusals = (
        (tc.cp_degree > 1, "cp_degree > 1: the latent cache is not sequence-sharded"),
        (tc.attention_dp_degree > 1, "attention_dp_degree > 1: the latent cache is not batch-sharded"),
        (tc.data_parallel_degree > 1, "data_parallel_degree > 1: the latent cache is not batch-sharded"),
        (tc.fused_qkv, "fused_qkv: the layer has no q, k and v of one width to fuse"),
        (tc.lora_config is not None, "lora_config: no adapter reaches the latent projections"),
        (paged and tc.is_prefix_caching,
         "is_prefix_caching on the paged cache: shared latent blocks are held to no reference"),
        (paged and tc.serving_ragged,
         "serving_ragged: the ragged mixed step attends (H_kv, D) keys and values only"),
        (paged and speculation_requested(tc),
         "speculation on the paged cache: a draft's width is not held to a reference over latents"),
        (paged and tc.kv_quantized,
         "kv_cache_dtype quantisation on the paged cache: one scale a head cannot serve "
         "a latent every head reads"),
        (paged and tc.tp_degree * tc.ep_degree > 1,
         "tp/ep degree > 1 on the paged cache: the latent pool would be replicated and its "
         "kernels launched per shard, which nothing measures"),
    )
    for flag, why in refusals:
        if flag:
            raise LatentAttentionError(f"a model with latent attention (MLA) cannot run with {why}")


class SparseAttentionError(LatentAttentionError):
    """An option that cannot run a model whose latent attention attends the
    keys a learned indexer selects (models/glm_moe_dsa.py) was set for one."""


def validate_sparse_attention(tc: "TpuConfig") -> None:
    """Refuse, for a model whose layers keep an indexer's key beside the
    latent, what the selection does not do yet and no test holds to a
    reference. It is called after :func:`validate_latent_attention`, whose
    refusals on the paged path (prefix caching: a chosen block is held to
    no reference; speculation widths; the ragged step; a quantised stream,
    the index key's included; every degree > 1) stand for it too."""
    refusals = (
        (not tc.is_block_kv_layout,
         "the contiguous cache (generate() without is_block_kv_layout): it keeps two "
         "streams a token and no indexer key"),
        (tc.is_prefill_stage,
         "is_prefill_stage: the hand-off carries two streams a token"),
    )
    for flag, why in refusals:
        if flag:
            raise SparseAttentionError(
                f"a model with learned sparse attention (an indexer's top-k) cannot run with {why}"
            )


class TwoLifetimeCacheError(NotImplementedError):
    """An option that cannot serve a model whose paged cache has two
    lifetimes (layers that attend a window keep a ring of blocks a slot,
    layers that attend the whole context keep the context: models/mellum.py)
    was set for one."""


def validate_two_lifetime_cache(tc: "TpuConfig") -> None:
    """Refuse, for a model whose builder declares ``WINDOW_KV`` layers beside
    ``PAGED_KV`` ones (``cache_layers()``), what is not built for a cache of
    two lifetimes, each naming its site: none is a silent wrong answer."""
    refusals = (
        (not (tc.is_block_kv_layout and tc.is_chunked_prefill and tc.is_continuous_batching),
         "a contiguous cache or whole-prompt prefill (generate(), is_block_kv_layout / "
         "is_chunked_prefill / is_continuous_batching unset): a window layer's ring holds "
         "window + one prefill chunk and no whole prompt; the contiguous cache's own "
         "interleaved full + ring form (ModelSpec.ring_window, models/gpt_oss.py) is a third "
         "cache and is not wired to this stack"),
        (tc.is_prefix_caching,
         "is_prefix_caching: a cached prefix has the full layers' blocks "
         "(modules/block_kvcache.PrefixCachingAllocator) and nothing of a window layer's ring, "
         "which another request's slot has overwritten since"),
        (speculation_requested(tc),
         "speculation (speculation_length, medusa, fused, EAGLE): a ring "
         "sized for one prefill chunk is not held to a reference at a draft's width, and a "
         "rejected draft's writes may have wrapped over keys the row still attends"),
        (tc.serving_ragged,
         "serving_ragged: the ragged mixed step (models/base.py mixed_forward, "
         "ops/ragged_paged_attention.py) has one table a row and no lower frontier"),
        (tc.kv_quantized,
         "kv_cache_dtype quantisation: the ring is kept unquantised beside the pool "
         "(modules/block_kvcache.WindowRing) and the decode kernel's in-kernel write serves "
         "an unquantised pool alone"),
        (tc.tp_degree * tc.ep_degree * tc.cp_degree * tc.attention_dp_degree
         * tc.data_parallel_degree > 1,
         "tp/ep/cp/dp degree > 1: the ring is replicated (window_ring_pspecs) and its "
         "kernels are not launched per head shard"),
        (tc.is_prefill_stage,
         "is_prefill_stage: the prefill hand-off (runtime/disaggregated.py) carries one "
         "contiguous line a layer and no ring"),
        (tc.sliding_window or tc.attention_chunk_size,
         "TpuConfig.sliding_window / attention_chunk_size: the layers' windows are the "
         "model's own (layer_types), one mask rule a layer"),
    )
    for flag, why in refusals:
        if flag:
            raise TwoLifetimeCacheError(
                f"a model with window and full attention layers (a paged cache of two "
                f"lifetimes) cannot be served with {why}"
            )


class BlockStepServingError(NotImplementedError):
    """An option that cannot serve a model whose decode step fills a block of
    positions (models/sdar.py) was set for one, or its block does not fit
    the programs the serving path compiles."""


def validate_block_step_serving(tc: "TpuConfig", block_length: int, denoise_steps: int,
                                mask_token_id: int, vocab_size: int) -> None:
    """Refuse, for a model whose builder declares a block step, every option
    that is not built and tested for it. One line each: none is a silent
    wrong answer."""
    ods = tc.on_device_sampling_config
    cpc = tc.chunked_prefill_config
    chunk = cpc.kernel_q_tile_size if cpc else 128
    refusals = (
        (not (tc.is_block_kv_layout and tc.is_chunked_prefill and tc.is_continuous_batching),
         "a contiguous cache or whole-prompt prefill: it is served on the paged, chunked path "
         "only (is_block_kv_layout, is_chunked_prefill, is_continuous_batching)"),
        (speculation_requested(tc), "speculation: a draft proposes one position after another"),
        (tc.serving_ragged, "serving_ragged: the ragged mixed step has no block-causal mask"),
        (tc.is_prefix_caching, "is_prefix_caching: a cached prefix would have to end on a block's "
                               "edge and the match does not know blocks"),
        (tc.kv_quantized, "kv_cache_dtype quantisation: not held to a reference under this mask"),
        (bool(ods and ods.do_sample), "do_sample: the reveal ranks the confidence of the argmax"),
        (tc.tp_degree * tc.ep_degree * tc.cp_degree * tc.attention_dp_degree
         * tc.data_parallel_degree > 1, "tp/ep/cp/dp degree > 1: not built or tested for it"),
        (tc.sliding_window or tc.attention_chunk_size,
         "sliding_window / attention_chunk_size: one mask rule at a time"),
        # the chunk program's q ladder starts at 8: a step under that width
        # collides with no chunk program, and chunks of whole blocks keep a
        # block's K and V in one pass
        (block_length < 2 or block_length >= 8 or block_length & (block_length - 1),
         f"block_length {block_length}: a power of two under 8, the first rung of the chunk "
         "program's q ladder"),
        (chunk % max(block_length, 1), f"a prefill chunk ({chunk}) that is no whole number of blocks"),
        (not 1 <= denoise_steps <= block_length, f"denoise_steps {denoise_steps} outside 1..block_length"),
        (not 0 <= mask_token_id < vocab_size, f"mask_token_id {mask_token_id} outside the vocabulary"),
    )
    for flag, why in refusals:
        if flag:
            raise BlockStepServingError(f"a block-step model cannot be served with {why}")


class InferenceConfig:
    """TpuConfig + HF model attributes (reference config.py:716-909).

    Model attributes (hidden_size, num_attention_heads, ...) live as instance
    attributes; ``attribute_map`` aliases alternate names onto canonical ones
    (reference config.py:736-758). JSON round-trip embeds the class path so a
    saved artifact reloads the right subclass (reference config.py:823-905).
    """

    # subclasses may list attrs that must exist post-init
    _REQUIRED_ATTRS: Tuple[str, ...] = ()

    def __init__(self, tpu_config: TpuConfig, load_config=None, metadata: dict = None, **kwargs):
        self.tpu_config = tpu_config
        self.attribute_map: Dict[str, str] = {}
        self.metadata = metadata or {}
        if load_config is not None:
            load_config(self)
        for k, v in kwargs.items():
            setattr(self, k, v)
        self.add_derived_config()
        self.validate_config()

    # alias for reference-API familiarity
    @property
    def neuron_config(self) -> TpuConfig:
        return self.tpu_config

    def __getattr__(self, name):
        # only called when normal lookup fails
        amap = self.__dict__.get("attribute_map", {})
        if name in amap:
            return getattr(self, amap[name])
        raise AttributeError(f"{type(self).__name__} has no attribute {name!r}")

    def __setattr__(self, name, value):
        amap = self.__dict__.get("attribute_map", {})
        if name in amap:
            super().__setattr__(amap[name], value)
        else:
            super().__setattr__(name, value)

    def add_derived_config(self):
        """Hook for model plugins to derive attrs (reference modeling_llama.py:311)."""

    def get_required_attributes(self) -> Tuple[str, ...]:
        return self._REQUIRED_ATTRS

    def validate_config(self):
        missing = [a for a in self.get_required_attributes() if not hasattr(self, a)]
        if missing:
            raise ValueError(f"Config missing required attributes: {missing}")

    # --- serialization ---------------------------------------------------

    def to_dict(self) -> dict:
        d = {}
        for k, v in self.__dict__.items():
            if k in ("tpu_config", "attribute_map", "metadata"):
                continue
            if hasattr(v, "to_dict"):
                d[k] = v.to_dict()
            elif _json_safe(v):
                d[k] = v
        d["tpu_config"] = self.tpu_config.to_dict()
        d["_config_class"] = {"module": type(self).__module__, "name": type(self).__name__}
        if isinstance(self.tpu_config, MoETpuConfig):
            d["tpu_config"]["_moe"] = True
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "InferenceConfig":
        d = dict(d)
        cls_info = d.pop("_config_class", None)
        config_cls = cls
        # only resolve config classes from inside this package: a JSON artifact
        # is untrusted input and must not trigger arbitrary module imports
        if isinstance(cls_info, dict) and str(cls_info.get("module", "")).startswith(
            "neuronx_distributed_inference_tpu."
        ):
            try:
                import importlib

                mod = importlib.import_module(cls_info["module"])
                candidate = getattr(mod, cls_info["name"])
                if isinstance(candidate, type) and issubclass(candidate, InferenceConfig):
                    config_cls = candidate
            except Exception:
                config_cls = cls
        tc = d.pop("tpu_config", {})
        moe = tc.pop("_moe", False) if isinstance(tc, dict) else False
        tpu_config = (MoETpuConfig if moe else TpuConfig).from_dict(tc)
        obj = config_cls.__new__(config_cls)
        obj.tpu_config = tpu_config
        obj.attribute_map = {}
        obj.metadata = {}
        for k, v in d.items():
            if isinstance(v, dict) and "_config_class" in v:
                v = InferenceConfig.from_dict(v)
            setattr(obj, k, v)
        return obj

    def save(self, path: str):
        """Save next to the compiled artifact (reference application_base.py:299)."""
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, CONFIG_FILE), "w") as f:
            json.dump(self.to_dict(), f, indent=2, default=_default_json)

    @classmethod
    def load(cls, path: str) -> "InferenceConfig":
        fname = path if path.endswith(".json") else os.path.join(path, CONFIG_FILE)
        with open(fname) as f:
            return cls.from_dict(json.load(f))


def _json_safe(v) -> bool:
    if isinstance(v, (str, int, float, bool, type(None))):
        return True
    if isinstance(v, (list, tuple)):
        return all(_json_safe(x) for x in v)
    if isinstance(v, dict):
        return all(isinstance(k, str) and _json_safe(x) for k, x in v.items())
    return False


def _default_json(v):
    if hasattr(v, "to_dict"):
        return v.to_dict()
    return str(v)
