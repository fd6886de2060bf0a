"""Shared program harness for the graph / shard / memory audit suites.

Every HLO-level analyzer needs the same expensive artifact: the committed
(phase, bucket) programs of a tiny tp-sharded model, traced/lowered/compiled
on the 8-virtual-device CPU mesh (the same GSPMD path hardware takes). This
module builds them ONCE per process and hands each suite a
:class:`ProgramRecord` carrying every view the rules consume:

- the jaxpr (bucket-skeleton / dtype rules),
- the donation-annotated StableHLO text (donation attrs),
- the partitioned executable (collective census, realized shardings,
  ``input_output_alias`` table, memory analysis),
- the DECLARED sharding contract (builder/mesh PartitionSpec trees via
  ``TpuModelForCausalLM.declared_pspecs()``), and
- the flat HLO parameter-number range of the donated cache leaves (what the
  alias table is checked against).

Program families:

- the committed tags the graph audit covers —
  ``context_encoding`` / ``token_generation`` / ``fused_speculation``, the
  ``*_kvq8`` quantized-cache variants (contiguous cache; the
  ``fused_speculation_kvq8`` variant quantizes BOTH the draft and target
  caches — the spec-decode path the cost model covers), and ``mixed_step``
  (the ragged mixed prefill+decode serving program on the int8 paged
  cache, bucketed by TOTAL packed query tokens), and
- two cache-VARIANT decode programs for the memory audit's donation proof:
  ``token_generation_ring`` (ring-bounded sliding-window cache) and
  ``token_generation_paged`` (paged block cache), both compiled with
  ``kv_cache_dtype="int8"`` so the QuantizedKV code+scale leaves are audited
  in every variant.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

TAG_CONTEXT_ENCODING = "context_encoding"
TAG_TOKEN_GENERATION = "token_generation"
TAG_FUSED_SPECULATION = "fused_speculation"
TAG_CONTEXT_ENCODING_KVQ8 = "context_encoding_kvq8"
TAG_TOKEN_GENERATION_KVQ8 = "token_generation_kvq8"
# fused-speculation TKG on the int8 contiguous cache (draft AND target
# quantized): the spec-decode path ROADMAP item 2 optimizes — committed so
# the graph/shard/memory/cost audits cover it like the plain kvq8 pair
TAG_FUSED_SPECULATION_KVQ8 = "fused_speculation_kvq8"
TAG_TOKEN_GENERATION_RING = "token_generation_ring"
TAG_TOKEN_GENERATION_PAGED = "token_generation_paged"
# ragged mixed prefill+decode serving step (serving_ragged): int8 PAGED
# cache, bucket axis = total packed query tokens (runtime/model_runner.py
# MixedStepRunner) — committed so the graph/shard/memory audits cover the
# one-dispatch serving program family from day one
TAG_MIXED_STEP = "mixed_step"
# the w4 family (weight_dtype="int4", ISSUE 17): decode programs whose
# weights are packed grouped-int4 (uint8 codes + f32 group scales,
# ops/quant_matmul) — committed so the graph/shard/memory audits cover the
# packed-weight leaves and the cost audit (COST501) accounts decode
# weight-read bytes at 0.5 byte/param (~0.25x the bf16 stream)
TAG_TOKEN_GENERATION_W4 = "token_generation_w4"
TAG_MIXED_STEP_W4 = "mixed_step_w4"

#: the committed program set (graph + shard audits)
COMMITTED_TAGS = (
    TAG_CONTEXT_ENCODING,
    TAG_TOKEN_GENERATION,
    TAG_FUSED_SPECULATION,
    TAG_CONTEXT_ENCODING_KVQ8,
    TAG_TOKEN_GENERATION_KVQ8,
    TAG_FUSED_SPECULATION_KVQ8,
    TAG_MIXED_STEP,
    TAG_TOKEN_GENERATION_W4,
    TAG_MIXED_STEP_W4,
)
#: cache-variant decode programs (memory audit: donation across variants)
CACHE_VARIANT_TAGS = (
    TAG_TOKEN_GENERATION_RING,
    TAG_TOKEN_GENERATION_PAGED,
)
ALL_TAGS = COMMITTED_TAGS + CACHE_VARIANT_TAGS

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

PHASE_CTE = "cte"
PHASE_TKG = "tkg"


def path_str(path) -> str:
    """Canonical "/"-joined string for a pytree key path — the ONE leaf-path
    format shared by the shard-audit census keys and the memory-audit
    finding names (e.g. ``layers/mlp/gate_proj/weight``, ``k/scale``)."""
    parts = []
    for p in path:
        for attr in ("key", "name", "idx"):
            v = getattr(p, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def census(hlo_text: str) -> Dict[str, int]:
    """Collective census of a compiled HLO module (result definitions, so
    fused start/done pairs count once)."""
    counts = {}
    for op in COLLECTIVE_OPS:
        counts[op] = len(
            re.findall(r"%?" + op + r"(?:-start)?(?:\.\d+)? = ", hlo_text)
        )
    return counts


def donation_count(lowered_text: str) -> int:
    """Donation/alias attrs that survived to the StableHLO lowering."""
    return lowered_text.count("tf.aliasing_output") + lowered_text.count(
        "jax.buffer_donor"
    )


@dataclass(frozen=True)
class ShapeMeta:
    """FLOP-relevant shape metadata of one (tag, bucket) program — recorded
    at build time, where the config is in hand, so the cost audit
    (:mod:`.cost_audit`) can turn graph-derived FLOP counts into an HBM
    traffic model without re-deriving the cache layout:

    - ``rows``: batch rows the step serves (serving slots for mixed_step);
    - ``q_tokens``: query tokens processed per dispatch (CTE: B·S, TKG: B,
      fused: B·(spec_len+1) verify positions, mixed: the packed bucket);
    - ``kv_width``: cache positions attention READS per row this bucket
      (0 for CTE — prefill K/V are activations, not cache reads);
    - ``cache_capacity_tokens``: total token slots of the cache pool (per
      cache stream), so per-token cache bytes = leaf bytes / capacity;
    - ``q_tile``/``spec_len``: the mixed-step packing granule and the
      fused-speculation draft length (COST503's packing contract).
    """

    rows: int
    q_tokens: int
    kv_width: int
    cache_capacity_tokens: int
    hidden: int
    layers: int
    vocab: int
    q_tile: int = 0
    spec_len: int = 0


@dataclass
class ProgramRecord:
    """One committed (tag, bucket) program plus its audit views."""

    tag: str
    phase: str
    bucket: int
    jaxpr: object  # ClosedJaxpr of the traced step
    lowered_text: str  # StableHLO with donation attrs
    compiled: object  # jax Compiled (partitioned executable)
    census: Dict[str, int]
    donation_count: int
    params: object  # committed param tree (tiny arrays)
    cache: object  # committed cache tree
    declared_param_pspecs: object
    declared_cache_pspecs: object
    realized_param_shardings: object  # pytree of NamedSharding, params slot
    realized_cache_shardings: object  # pytree of NamedSharding, cache slot
    output_cache_shardings: Optional[object]  # realized cache OUTPUT shardings
    mesh: object
    n_param_leaves: int
    cache_param_range: Tuple[int, int]  # flat HLO param numbers of cache leaves
    shape_meta: Optional[ShapeMeta] = None  # cost-audit metadata
    _compiled_text: Optional[str] = field(default=None, repr=False)

    @property
    def n_cache_leaves(self) -> int:
        return self.cache_param_range[1] - self.cache_param_range[0]

    @property
    def compiled_text(self) -> str:
        if self._compiled_text is None:
            self._compiled_text = self.compiled.as_text()
        return self._compiled_text


# ---------------------------------------------------------------------------
# tiny audit model
# ---------------------------------------------------------------------------


def _tiny_hf_attrs(vocab: int = 128) -> dict:
    return dict(
        model_type="llama",
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=2,
        vocab_size=vocab,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=256,
        hidden_act="silu",
        tie_word_embeddings=False,
    )


def tiny_config(hf_attrs: Optional[dict] = None, **tpu_overrides):
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig

    attrs = _tiny_hf_attrs()
    if hf_attrs:
        attrs.update(hf_attrs)

    def load_config(cfg):
        for k, v in attrs.items():
            setattr(cfg, k, v)

    tc_kwargs = dict(
        batch_size=2,
        seq_len=128,
        dtype="bfloat16",
        tp_degree=2,
        context_encoding_buckets=[64, 128],
        token_generation_buckets=[64, 128],
    )
    tc_kwargs.update(tpu_overrides)
    return LlamaInferenceConfig(TpuConfig(**tc_kwargs), load_config=load_config)


# ---------------------------------------------------------------------------
# record assembly
# ---------------------------------------------------------------------------


def _input_shardings(compiled):
    """The compiled executable's realized per-argument shardings (a tuple of
    pytrees matching the step function's positional args)."""
    ish = compiled.input_shardings
    # jax returns (arg_shardings, kwarg_shardings)
    return ish[0] if isinstance(ish, tuple) and len(ish) == 2 else ish


def _output_cache_shardings(compiled, attr: str = "cache"):
    """Realized sharding subtree of the step OUTPUT's cache field (None when
    the output structure doesn't expose one — audits degrade gracefully)."""
    try:
        out = compiled.output_shardings
        return getattr(out, attr, None)
    except Exception:
        return None


def _cache_capacity(cache, paged: bool) -> int:
    """Total token slots of a cache pool: rows × positions for the
    contiguous/ring layout (L, rows, S, H, D), blocks × block_size for the
    paged layout (L, blocks, H, block_size, D)."""
    import jax

    for leaf in jax.tree.leaves(cache):
        if getattr(leaf, "ndim", 0) >= 4:
            return int(leaf.shape[1] * (leaf.shape[3] if paged else leaf.shape[2]))
    return 0


def _record_from_runner(
    tag: str,
    phase: str,
    runner,
    app,
    bucket: int,
    declared_pp,
    declared_cp,
    shape_meta: Optional[ShapeMeta] = None,
) -> ProgramRecord:
    import jax

    inputs = runner.example_inputs(bucket)
    traced, lowered, compiled = runner.trace_program(
        app.params, app.kv_cache, inputs, None
    )
    lowered_text = lowered.as_text()
    compiled_text = compiled.as_text()
    n_p = len(jax.tree.leaves(app.params))
    n_c = len(jax.tree.leaves(app.kv_cache))
    ish = _input_shardings(compiled)
    return ProgramRecord(
        tag=tag,
        phase=phase,
        bucket=bucket,
        jaxpr=traced.jaxpr,
        lowered_text=lowered_text,
        compiled=compiled,
        census=census(compiled_text),
        donation_count=donation_count(lowered_text),
        params=app.params,
        cache=app.kv_cache,
        declared_param_pspecs=declared_pp,
        declared_cache_pspecs=declared_cp,
        realized_param_shardings=ish[0],
        realized_cache_shardings=ish[1],
        output_cache_shardings=_output_cache_shardings(compiled),
        mesh=app.mesh,
        n_param_leaves=n_p,
        cache_param_range=(n_p, n_p + n_c),
        shape_meta=shape_meta,
        _compiled_text=compiled_text,
    )


def _build_causal(
    kv_quant: bool = False,
    variant: Optional[str] = None,
    weight_dtype: Optional[str] = None,
) -> Dict[str, Dict[int, ProgramRecord]]:
    """CTE + TKG programs of the tiny causal LM.

    ``kv_quant``: contiguous cache with kv_cache_dtype="int8" (the kvq8 tag
    pair). ``variant``: "ring" (sliding-window ring-bounded cache), "paged"
    (block cache) or "mixed" (the ragged mixed-step serving program on the
    paged cache, serving_ragged) — compiled int8 so the QuantizedKV
    code+scale leaves are covered in every cache variant.
    ``weight_dtype="int4"``: the w4 family — packed grouped-int4 weights
    (ops/quant_matmul) through the plain TKG and mixed-step programs.
    """
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    overrides = {}
    if kv_quant or variant:
        overrides["kv_cache_dtype"] = "int8"
    if weight_dtype:
        overrides["weight_dtype"] = weight_dtype
    if variant == "ring":
        overrides["sliding_window"] = 32
    elif variant == "paged":
        overrides.update(
            is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=18
        )
    elif variant == "mixed":
        from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

        overrides.update(
            is_block_kv_layout=True,
            pa_block_size=16,
            pa_num_blocks=24,
            is_continuous_batching=True,
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=16
            ),
            serving_ragged=True,
        )
    hf_attrs = None
    if weight_dtype == "int4":
        # w4 runs the kernel-eligible tiny shape: every decode linear has
        # K ≥ one double-group (256) so packing isn't padding-dominated and
        # the COST501 census shows the real weight-byte halving, and
        # head_dim 64 is lane-aligned so mixed_step_w4 satisfies the
        # ragged-dispatch gate the sharded kernel serves on hardware
        hf_attrs = dict(hidden_size=256, intermediate_size=512)
    cfg = tiny_config(hf_attrs=hf_attrs, **overrides)
    app = TpuModelForCausalLM(None, cfg)
    app.load(random_weights=True)
    declared_pp, declared_cp = app.declared_pspecs()

    if variant == "ring":
        pairs = [(TAG_TOKEN_GENERATION_RING, PHASE_TKG, app.token_generation_model)]
    elif variant == "paged":
        pairs = [(TAG_TOKEN_GENERATION_PAGED, PHASE_TKG, app.token_generation_model)]
    elif variant == "mixed" and weight_dtype == "int4":
        pairs = [(TAG_MIXED_STEP_W4, PHASE_TKG, app.mixed_step_model)]
    elif weight_dtype == "int4":
        pairs = [(TAG_TOKEN_GENERATION_W4, PHASE_TKG, app.token_generation_model)]
    elif variant == "mixed":
        pairs = [(TAG_MIXED_STEP, PHASE_TKG, app.mixed_step_model)]
    elif kv_quant:
        pairs = [
            (TAG_CONTEXT_ENCODING_KVQ8, PHASE_CTE, app.context_encoding_model),
            (TAG_TOKEN_GENERATION_KVQ8, PHASE_TKG, app.token_generation_model),
        ]
    else:
        pairs = [
            (TAG_CONTEXT_ENCODING, PHASE_CTE, app.context_encoding_model),
            (TAG_TOKEN_GENERATION, PHASE_TKG, app.token_generation_model),
        ]
    window = overrides.get("sliding_window", 0)
    capacity = _cache_capacity(app.kv_cache, paged=variant in ("paged", "mixed"))
    B = cfg.tpu_config.batch_size

    def meta(tag, phase, runner, bucket) -> ShapeMeta:
        base = dict(
            cache_capacity_tokens=capacity,
            hidden=cfg.hidden_size,
            layers=cfg.num_hidden_layers,
            vocab=cfg.vocab_size,
        )
        if tag in (TAG_MIXED_STEP, TAG_MIXED_STEP_W4):
            # packed bucket = query tokens; decode rows read the widest
            # committed kv bucket (the width example_inputs compiles at)
            return ShapeMeta(
                rows=runner.num_rows, q_tokens=bucket,
                kv_width=runner.kv_buckets[-1], q_tile=runner.q_tile, **base
            )
        if phase == PHASE_CTE:
            return ShapeMeta(rows=B, q_tokens=B * bucket, kv_width=0, **base)
        return ShapeMeta(
            rows=B, q_tokens=B,
            kv_width=min(bucket, window) if window else bucket, **base
        )

    out: Dict[str, Dict[int, ProgramRecord]] = {}
    for tag, phase, runner in pairs:
        out[tag] = {
            bucket: _record_from_runner(
                tag, phase, runner, app, bucket, declared_pp, declared_cp,
                shape_meta=meta(tag, phase, runner, bucket),
            )
            for bucket in runner.buckets
        }
    return out


def _build_fused(kv_quant: bool = False) -> Dict[str, Dict[int, ProgramRecord]]:
    """The fused-speculation decode program across ≥2 TKG bucket widths
    (draft chain + target verify in ONE graph). Params/caches/specs are
    keyed ``{"draft": ..., "target": ...}`` in the program's arg order.
    ``kv_quant``: both caches on kv_cache_dtype="int8" (the spec-decode
    path the cost model must cover — ROADMAP item 2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_inference_tpu.config import (
        FusedSpecConfig,
        OnDeviceSamplingConfig,
    )
    from neuronx_distributed_inference_tpu.models.base import StepInputs
    from neuronx_distributed_inference_tpu.modules.sampling import (
        prepare_sampling_params,
    )
    from neuronx_distributed_inference_tpu.runtime.fused_spec import (
        TpuFusedSpecModelForCausalLM,
    )

    spec_len = 3
    overrides = {"kv_cache_dtype": "int8"} if kv_quant else {}
    cfg = tiny_config(
        speculation_length=spec_len,
        enable_fused_speculation=True,
        on_device_sampling_config=OnDeviceSamplingConfig(do_sample=False),
        **overrides,
    )
    cfg.fused_spec_config = FusedSpecConfig(
        draft_model_name="tiny-draft", draft_config=tiny_config(**overrides)
    )
    tag = TAG_FUSED_SPECULATION_KVQ8 if kv_quant else TAG_FUSED_SPECULATION
    app = TpuFusedSpecModelForCausalLM(None, cfg)
    app.load(random_weights=True)
    declared_pp, declared_cp = app.declared_pspecs()

    B = cfg.tpu_config.batch_size
    sp = prepare_sampling_params(B)
    params = {"draft": app.draft_params, "target": app.target_params}
    cache = {"draft": app.draft_cache, "target": app.target_cache}
    n_p = len(jax.tree.leaves(params))
    n_c = len(jax.tree.leaves(cache))
    capacity = _cache_capacity(app.target_cache, paged=False)
    per_bucket: Dict[int, ProgramRecord] = {}
    for bucket in app.tkg_buckets:
        inputs = StepInputs(
            input_ids=jnp.zeros((B, 1), jnp.int32),
            attention_mask=jnp.zeros((B, bucket), jnp.int32),
            position_ids=jnp.full((B, 1), 7, jnp.int32),
            seq_ids=jnp.asarray(np.arange(B, dtype=np.int32)),
            sampling_params=jnp.asarray(sp, jnp.float32),
        )
        traced, lowered, compiled = app.trace_tkg_program(inputs, None)
        lowered_text = lowered.as_text()
        compiled_text = compiled.as_text()
        ish = _input_shardings(compiled)
        per_bucket[bucket] = ProgramRecord(
            tag=tag,
            phase=PHASE_TKG,
            bucket=bucket,
            jaxpr=traced.jaxpr,
            lowered_text=lowered_text,
            compiled=compiled,
            census=census(compiled_text),
            donation_count=donation_count(lowered_text),
            params=params,
            cache=cache,
            declared_param_pspecs=declared_pp,
            declared_cache_pspecs=declared_cp,
            realized_param_shardings={"draft": ish[0], "target": ish[1]},
            realized_cache_shardings={"draft": ish[2], "target": ish[3]},
            output_cache_shardings=None,
            mesh=app.mesh,
            n_param_leaves=n_p,
            cache_param_range=(n_p, n_p + n_c),
            shape_meta=ShapeMeta(
                rows=B,
                q_tokens=B * (spec_len + 1),
                kv_width=bucket,
                cache_capacity_tokens=capacity,
                hidden=cfg.hidden_size,
                layers=cfg.num_hidden_layers,
                vocab=cfg.vocab_size,
                spec_len=spec_len,
            ),
            _compiled_text=compiled_text,
        )
    return {tag: per_bucket}


# ---------------------------------------------------------------------------
# memoized collection
# ---------------------------------------------------------------------------

_MEMO: Dict[str, Dict[int, ProgramRecord]] = {}

_BUILDERS = (
    # (tags produced together, builder thunk)
    ((TAG_CONTEXT_ENCODING, TAG_TOKEN_GENERATION), lambda: _build_causal()),
    (
        (TAG_CONTEXT_ENCODING_KVQ8, TAG_TOKEN_GENERATION_KVQ8),
        lambda: _build_causal(kv_quant=True),
    ),
    ((TAG_FUSED_SPECULATION,), _build_fused),
    ((TAG_FUSED_SPECULATION_KVQ8,), lambda: _build_fused(kv_quant=True)),
    ((TAG_MIXED_STEP,), lambda: _build_causal(variant="mixed")),
    ((TAG_TOKEN_GENERATION_W4,), lambda: _build_causal(weight_dtype="int4")),
    (
        (TAG_MIXED_STEP_W4,),
        lambda: _build_causal(variant="mixed", weight_dtype="int4"),
    ),
    ((TAG_TOKEN_GENERATION_RING,), lambda: _build_causal(variant="ring")),
    ((TAG_TOKEN_GENERATION_PAGED,), lambda: _build_causal(variant="paged")),
)


def collect_programs(
    tags: Tuple[str, ...] = COMMITTED_TAGS,
) -> Dict[str, Dict[int, ProgramRecord]]:
    """Trace/lower/compile the requested program families (memoized per
    process: the graph, shard and memory suites — and the tier-1 tests —
    share one build of each family)."""
    unknown = set(tags) - set(ALL_TAGS)
    if unknown:
        raise ValueError(f"unknown program tag(s) {sorted(unknown)}; pick from {ALL_TAGS}")
    for family, build in _BUILDERS:
        if any(t in tags and t not in _MEMO for t in family):
            _MEMO.update(build())
    return {t: _MEMO[t] for t in tags}


def clear_memo():
    """Drop the per-process program memo (tests that rebuild with doctored
    configs use this; the CLI never needs it)."""
    _MEMO.clear()
