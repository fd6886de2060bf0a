"""Application layer: compile / load / generate lifecycle.

TPU-native re-design of the reference application stack
(reference: models/application_base.py:68 ``NeuronApplicationBase``,
models/model_base.py:3069 ``NeuronBaseForCausalLM``, and the host sampling
loop of utils/hf_adapter.py:101-916).

Lifecycle mapping (SURVEY §3.1-3.3):
- ``compile()``   = AOT-build all (sub-model, bucket) programs via jit +
  persistent XLA compilation cache; save ``tpu_config.json``
  (reference: ModelBuilder.trace -> neuronx-cc -> model.pt).
- ``load()``      = load HF checkpoint -> GSPMD-sharded global arrays on the
  mesh; allocate the donated KV cache
  (reference: nxd_model.initialize(weights) per rank).
- ``generate()``  = host loop: CTE once, then TKG steps with bucketed cache
  masks — the reference's per-token host dispatch (model_base.py:3656-3854).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.config import InferenceConfig, to_dtype
from neuronx_distributed_inference_tpu.models.base import (
    PHASE_CONTEXT_ENCODING,
    PHASE_TOKEN_GENERATION,
)
from neuronx_distributed_inference_tpu.models.registry import get_model_builder
from neuronx_distributed_inference_tpu.modules import autobucketing
from neuronx_distributed_inference_tpu.modules.kvcache import (
    KVCache,
    PAD_POSITION_SENTINEL,
    cache_spec,
    init_cache,
)
from neuronx_distributed_inference_tpu.modules.sampling import (
    prepare_sampling_params,
    validate_sampling_params,
)
from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
from neuronx_distributed_inference_tpu.parallel.sharding import shard_pytree
from neuronx_distributed_inference_tpu.runtime.model_runner import (
    SubModelRunner,
    TAG_CONTEXT_ENCODING,
    TAG_TOKEN_GENERATION,
)
from neuronx_distributed_inference_tpu.telemetry.tracing import default_session
from neuronx_distributed_inference_tpu.utils.compile_cache import configure_compile_cache
from neuronx_distributed_inference_tpu.utils.hf_checkpoint import load_state_dict


# tokens per device dispatch when EOS is requested: small enough that a
# finished batch wastes little compute past EOS, large enough to amortize the
# host round-trip (reference: per-token host dispatch, model_base.py:3656)
_EOS_CHUNK = 8




def _pick_chunk(remaining: int, has_eos: bool, headroom: int) -> int:
    """Decode-chunk size (device steps) for the host loop.

    Without an EOS the whole remaining budget runs as one device program;
    with an EOS we dispatch fixed-size chunks so termination is observed at
    chunk boundaries. The size stays _EOS_CHUNK even for the budget tail
    (surplus tokens are discarded on the host) so decode programs are
    normally keyed by a single num_steps; the one exception is the last
    ``headroom`` positions of the cache window, where the chunk shrinks to
    fit and a residue-sized program may compile once.
    """
    if not has_eos:
        # round up to a power of two so the jit cache holds at most
        # log2(seq_len) decode programs instead of one per budget; surplus
        # steps are computed and discarded (cheaper than an XLA recompile)
        chunk = 8
        while chunk < remaining:
            chunk *= 2
    else:
        chunk = _EOS_CHUNK
    if chunk > headroom:
        # clamp to the largest power of two that fits, so the cache-window
        # tail also reuses pow2-keyed programs instead of compiling a
        # residue-sized one per distinct headroom
        chunk = 1
        while chunk * 2 <= headroom:
            chunk *= 2
    return chunk


@dataclass
class GenerationOutput:
    sequences: np.ndarray  # (B, S_in + new)
    logits: Optional[np.ndarray] = None  # (B, new, V) when output_logits
    num_generated: int = 0


class TpuModelForCausalLM:
    """The causal-LM application (reference NeuronBaseForCausalLM)."""

    def __init__(self, model_path: Optional[str], config: InferenceConfig, mesh=None):
        self.model_path = model_path
        self.config = config
        tc = config.tpu_config
        model_type = getattr(config, "model_type", "llama")
        self.builder = get_model_builder(model_type)(config)
        self.spec = self.builder.model_spec()
        self.mesh = mesh if mesh is not None else mesh_from_config(tc)
        self.params = None
        # weight provenance: True once load(random_weights=True) ran —
        # compile() keys the presharded artifact on it so a --random-weights
        # demo run can never poison the real checkpoint's artifact
        self._random_weights = False
        self.kv_cache: Optional[KVCache] = None
        self._cache_pspecs = None
        self._rng_key = jax.random.PRNGKey(tc.seed)
        self._call_key = self._rng_key
        self.lora_manager = None

        cte_buckets = autobucketing.generate_context_encoding_buckets(tc)
        tkg_buckets = autobucketing.generate_token_generation_buckets(tc)
        if self.spec.bounded_window:
            # ring cache: exactly one decode shape (the W-slot window)
            tkg_buckets = [self.spec.bounded_window]
        if tc.is_block_kv_layout:
            # block-table gathers need bucket % block_size == 0
            tkg_buckets = sorted(
                {autobucketing.round_up(b, tc.pa_block_size) for b in tkg_buckets}
            )
        mlp_fn = self.builder.mlp_fn()
        layer_fn = self.builder.layer_fn()
        block_kwargs = dict(
            block_kv=tc.is_block_kv_layout, block_size=tc.pa_block_size,
            layer_fn=layer_fn,
        )
        # per-sub-model specialized config (reference deep-copied configs,
        # model_base.py:3099-3222)
        self.context_encoding_model = SubModelRunner(
            TAG_CONTEXT_ENCODING,
            PHASE_CONTEXT_ENCODING,
            self.spec,
            cte_buckets,
            tc.ctx_batch_size,
            self.mesh,
            mlp_fn,
            **block_kwargs,
        )
        self.token_generation_model = SubModelRunner(
            TAG_TOKEN_GENERATION,
            PHASE_TOKEN_GENERATION,
            self.spec,
            tkg_buckets,
            tc.tkg_batch_size,
            self.mesh,
            mlp_fn,
            # a block-step model's decode step is its block wide
            n_active_tokens=self.spec.block_step.block_length if self.spec.block_step else 1,
            **block_kwargs,
        )
        self.runners = [self.context_encoding_model, self.token_generation_model]
        if self.spec.block_step is not None:
            # which programs a block-step session dispatches, and every way
            # it feeds them, is this application's to say: only such an
            # application HAS the attribute (a caller asks with hasattr)
            self.warm_serving = self._warm_block_serving
        # ragged mixed-step program family (serving_ragged): ONE dispatch per
        # serving step covers prefill chunks AND decode rows; its bucket axis
        # is the TOTAL packed query-token count, not a per-phase shape
        self.mixed_step_model = None
        if tc.serving_ragged:
            from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
                RAGGED_Q_TILE,
            )
            from neuronx_distributed_inference_tpu.runtime.model_runner import (
                MixedStepRunner,
            )

            cpc = tc.chunked_prefill_config
            chunk = cpc.kernel_q_tile_size if cpc else 128
            max_seqs = cpc.max_num_seqs if cpc else 8
            num_rows = tc.kv_cache_batch_size or tc.max_batch_size
            # worst-case packed step: max_seqs tile-aligned prefill chunks
            # plus every slot decoding (one q tile each)
            top = (
                autobucketing.round_up(chunk, RAGGED_Q_TILE) * max_seqs
                + num_rows * RAGGED_Q_TILE
            )
            mixed_buckets = []
            b = RAGGED_Q_TILE
            while b < top:
                mixed_buckets.append(b)
                b *= 2
            mixed_buckets.append(b)
            self.mixed_step_model = MixedStepRunner(
                self.spec,
                mixed_buckets,
                num_rows,
                self.mesh,
                mlp_fn,
                tc.pa_block_size,
                tkg_buckets,
                layer_fn=layer_fn,
            )

    # ---- weights / cache -------------------------------------------------

    def load(self, model_path: Optional[str] = None, state_dict=None, random_weights=False):
        """Load weights onto the mesh + allocate the KV cache
        (reference application_base.py:317-419)."""
        tc = self.config.tpu_config
        configure_compile_cache(tc.compilation_cache_dir)
        from neuronx_distributed_inference_tpu.ops.quant import (
            has_quantized_checkpoint,
            load_quantized_checkpoint,
            prepare_quantized_params,
            quantized_pspecs,
            save_quantized_checkpoint,
        )

        use_ckpt = (
            tc.quantized
            and not random_weights
            and state_dict is None
            and model_path is None
            and has_quantized_checkpoint(tc.quantized_checkpoints_path, tc)
        )
        if use_ckpt:
            # pre-quantized artifact: skip HF conversion + re-quantization
            # (reference quantized_checkpoints_path, application_base.py:636).
            # Explicit state dicts / random weights always win over the
            # artifact, and a recipe mismatch re-quantizes.
            params = load_quantized_checkpoint(tc.quantized_checkpoints_path)
            pspecs = quantized_pspecs(self.builder.param_pspecs(), params)
        else:
            if random_weights:
                # generate on host whenever the full-precision model must
                # not stage on ONE chip: quantize-at-load (int8 8B on a 16G
                # chip), and any multi-device mesh — device-side generation
                # builds every leaf whole on the default device before
                # shard_pytree re-places it, and a tp-sharded bf16 8B does
                # not pass through one 16G chip
                params = self.builder.random_params(
                    on_host=tc.quantized or tc.weight_int4 or self.mesh.size > 1
                )
            else:
                sd = state_dict if state_dict is not None else load_state_dict(
                    model_path or self.model_path
                )
                params = self.builder.convert_hf_state_dict(sd)
            pspecs = self.builder.param_pspecs()
            if tc.quantized:
                params, pspecs = prepare_quantized_params(params, pspecs, tc)
                if tc.quantized_checkpoints_path and not random_weights:
                    save_quantized_checkpoint(params, tc.quantized_checkpoints_path, tc)
            elif tc.weight_int4:
                # weight_dtype=int4: pack grouped sub-byte codes at load
                # (mxfp4 checkpoints land here too — gpt-oss experts dequant
                # to fp32 in convert_hf_state_dict, then regroup to int4, so
                # they stream at 0.5 byte/param like everything else)
                from neuronx_distributed_inference_tpu.ops.quant import (
                    prepare_int4_params,
                )

                params, pspecs = prepare_int4_params(params, pspecs, tc)
        self._pspecs = pspecs
        self.params = shard_pytree(params, pspecs, self.mesh)
        self._random_weights = bool(random_weights)
        self.init_kv_cache()
        return self

    def declared_pspecs(self):
        """(param PartitionSpec tree, cache PartitionSpec tree) as committed
        at load() — the sharding contract the static analyzer audits realized
        programs against (analysis/shard_audit.py GRAPH301/302). The param
        tree reflects every load-time transform (quantization scale leaves,
        LoRA adapters); the cache tree is the builder's declaration (or the
        block-cache spec for the paged layout)."""
        if self.params is None or self._cache_pspecs is None:
            raise RuntimeError("call load() before declared_pspecs()")
        return self._pspecs, self._cache_pspecs

    @property
    def paged_layers(self) -> int:
        """How many layers page K/V over the block pool (the builder's
        declaration; every layer, for a model without per-slot state)."""
        from neuronx_distributed_inference_tpu.modules.block_kvcache import PAGED_KV

        return sum(1 for kind in self.builder.cache_layers() if kind == PAGED_KV)

    def init_kv_cache(self):
        tc = self.config.tpu_config
        dt = to_dtype(tc.kv_cache_dtype or tc.dtype)
        if tc.is_block_kv_layout:
            from neuronx_distributed_inference_tpu.modules.block_kvcache import (
                HybridBlockCache,
                block_cache_spec,
                init_block_cache,
                kv_block_bytes,
            )

            # the pool spans the layers that page; a layer that keeps a
            # constant-size state per slot (builder.cache_layers) costs no
            # block, and its state is built beside the pool below
            paged = self.paged_layers
            # what a token leaves in a paging layer is the builder's to say
            streams = self.builder.cache_streams()
            if not paged:
                # no layer pages (models/brumby.py): no pool. The cache keeps
                # its K and V leaves, of zero layers and one (garbage) block:
                # no byte, nothing to budget and nothing to divide by
                tc.pa_num_blocks = 0
            elif tc.pa_num_blocks is None and tc.pa_pool_bytes is not None:
                # byte-budgeted pool: the block count follows the TRUE
                # per-block cost in the cache dtype — a quantized cache
                # admits ~2x the blocks for the same HBM budget
                tc.pa_num_blocks = max(
                    1,
                    tc.pa_pool_bytes
                    // kv_block_bytes(paged, tc.pa_block_size, dtype=dt, streams=streams),
                )
            cache = init_block_cache(
                paged, tc.pa_num_blocks, tc.pa_block_size, dtype=dt, streams=streams
            )
            self._cache_pspecs = block_cache_spec(quantized=tc.kv_quantized, streams=streams)
            slot_state = self.builder.init_slot_state(
                tc.kv_cache_batch_size or tc.max_batch_size
            )
            if slot_state is not None:
                state, state_pspecs = slot_state
                cache = HybridBlockCache(k=cache.k, v=cache.v, state=state)
                self._cache_pspecs = HybridBlockCache(
                    k=self._cache_pspecs.k, v=self._cache_pspecs.v, state=state_pspecs
                )
            self.kv_cache = shard_pytree(cache, self._cache_pspecs, self.mesh)
            return
        self._cache_pspecs = self.builder.cache_pspecs()
        self.kv_cache = self.builder.init_kv_cache(self.mesh)

    def load_lora_adapters(self, adapters=None, dynamic: bool = False):
        """Attach multi-adapter LoRA weights (reference LoraModel.inject_adapter
        + LoraWeightManager, lora_serving/lora_model.py:35-260).

        ``adapters``: {adapter_name: PEFT-format state dict | directory path}.
        ``dynamic``: serve MORE adapters than device slots — a host cache with
        LRU slot eviction + on-device swap (reference AdapterCache,
        lora_model.py:262-392); register further adapters any time with
        :meth:`register_lora_adapter`.
        """
        from neuronx_distributed_inference_tpu.modules.lora import (
            DynamicLoraManager,
            LoraWeightManager,
            attach_lora_params,
            lora_pspecs,
        )

        tc = self.config.tpu_config
        if tc.lora_config is None:
            raise ValueError("lora_config must be set to serve LoRA adapters")
        if self.params is None:
            raise RuntimeError("call load() before load_lora_adapters()")
        adapters = adapters or {}
        if dynamic:
            self.lora_manager = DynamicLoraManager(tc.lora_config)
            params = attach_lora_params(
                self.params, {}, self.lora_manager, self.spec.num_layers,
                dtype=to_dtype(tc.dtype), init_all=True,
            )
        else:
            self.lora_manager = LoraWeightManager(tc.lora_config)
            params = attach_lora_params(
                self.params, adapters, self.lora_manager, self.spec.num_layers,
                dtype=to_dtype(tc.dtype),
            )
        self._pspecs = lora_pspecs(self._pspecs, params)
        self.params = shard_pytree(params, self._pspecs, self.mesh)
        if dynamic:
            for name, value in adapters.items():
                self.register_lora_adapter(name, value)
        return self

    def register_lora_adapter(self, name: str, value):
        """Host-register an adapter for dynamic serving (preprocessed into
        the CPU cache; swapped on device on first use)."""
        from neuronx_distributed_inference_tpu.modules.lora import DynamicLoraManager

        if not isinstance(self.lora_manager, DynamicLoraManager):
            raise RuntimeError(
                "register_lora_adapter needs load_lora_adapters(dynamic=True)"
            )
        self.lora_manager.register_cpu(name, value, self.params, self.spec.num_layers)
        return self

    def resolve_adapter_ids(self, adapter_names) -> Optional[np.ndarray]:
        if adapter_names is None:
            return None
        if self.lora_manager is None:
            raise RuntimeError("no LoRA adapters loaded (call load_lora_adapters)")
        from neuronx_distributed_inference_tpu.modules.lora import DynamicLoraManager

        if isinstance(self.lora_manager, DynamicLoraManager):
            # cache-miss adapters swap into device slots before dispatch
            self.params = self.lora_manager.ensure_on_device(
                self.params, adapter_names
            )
        return self.lora_manager.resolve(adapter_names)

    def compile(self, compiled_model_path: Optional[str] = None):
        """AOT-compile every (sub-model, bucket) program
        (reference application_base.py:292-315). The persistent XLA
        compilation cache (utils/compile_cache.py says where it lives) keeps
        the executables across processes.

        With ``save_sharded_checkpoint`` a PRESHARDED weight artifact lives
        next to the cache (utils/presharded.py; reference
        application_base.py:240-265): later compiles restore the sharded
        weights directly — no HF conversion, no quantize-at-load, no
        resharding.
        """
        tc = self.config.tpu_config
        configure_compile_cache(tc.compilation_cache_dir)
        presharded_dir = None
        if compiled_model_path:
            os.makedirs(compiled_model_path, exist_ok=True)
            self.config.save(compiled_model_path)
            presharded_dir = os.path.join(compiled_model_path, "presharded")
        # LoRA-attached trees never round-trip through the artifact: adapter
        # identity isn't part of the fingerprint, and serving adapter weights
        # a later run's flags never requested would be silently wrong
        use_artifact = (
            presharded_dir and tc.save_sharded_checkpoint and tc.lora_config is None
        )
        if use_artifact:
            from neuronx_distributed_inference_tpu.utils.presharded import (
                config_fingerprint,
                has_presharded,
                load_presharded,
                save_presharded,
            )

            # weight provenance keys the artifact (ADVICE r5): a
            # --random-weights run (params already loaded as random, or no
            # model_path to load from) must never save/restore under the
            # real checkpoint's fingerprint
            random_prov = (
                self._random_weights
                if self.params is not None
                else self.model_path is None
            )
            fp = config_fingerprint(
                self.config,
                model_path=(
                    os.path.abspath(self.model_path) if self.model_path else None
                ),
                random_weights=random_prov,
            )
        if self.params is None and use_artifact and has_presharded(presharded_dir, fp):
            try:
                restored = load_presharded(presharded_dir, self.mesh, fingerprint=fp)
            except Exception as e:
                # manifest intact but weights damaged (partial delete,
                # killed rewrite): degrade to the normal load + rewrite
                import logging

                logging.getLogger(__name__).warning(
                    "presharded restore failed (%s); falling back to a full load", e
                )
                import shutil

                shutil.rmtree(presharded_dir, ignore_errors=True)
                restored = None
            if restored is not None:
                self.params, self._pspecs = restored
                # the artifact was keyed by random_prov above — keep the
                # in-object provenance consistent so a second compile() on
                # this app recomputes the SAME fingerprint
                self._random_weights = random_prov
                self.init_kv_cache()
        if self.params is None:
            self.load(random_weights=self.model_path is None, model_path=self.model_path)
        if (
            use_artifact
            and not has_presharded(presharded_dir, fp)
            and not (random_prov and self.model_path)
        ):
            # absent OR stale (recipe changed): (re)write so the next run
            # restores instead of paying the cold load forever. Random-init
            # params over a REAL model_path never write: they would clobber
            # (or stand in for) the real checkpoint's artifact for no gain
            save_presharded(self.params, self._pspecs, presharded_dir, fingerprint=fp)
        if not tc.skip_warmup:
            self.warmup()
        return self

    def warmup(self):
        tc = self.config.tpu_config
        chunk_q = None
        if tc.is_chunked_prefill or tc.is_prefix_caching:
            chunk_q = autobucketing.generate_chunk_q_buckets(tc)
        elif tc.max_context_length < tc.seq_len or self.spec.bounded_window:
            # windowed prefill compiles one (C, kv) multi-token shape
            c = self.context_encoding_model.buckets[-1]
            if self.spec.bounded_window:
                c = min(c, self.spec.bounded_window)
            chunk_q = [c]
        # disaggregated serving (reference is_prefill_stage): a stage app
        # compiles ONLY its stage's programs — the prefill stage serves CTE,
        # the decode stage serves TKG (runtime/disaggregated.py hands KV over)
        runners = self.runners
        if tc.is_prefill_stage is True:
            runners = [self.context_encoding_model]
        elif tc.is_prefill_stage is False:
            runners = [self.token_generation_model]
        for runner in runners:
            self.kv_cache = runner.warmup(
                self.params, self.kv_cache, self._sample_key(0),
                chunk_q_lens=chunk_q if runner is self.token_generation_model else None,
            )
        if self.mixed_step_model is not None and tc.is_prefill_stage is None:
            # ragged mixed-step family: every total-token bucket compiles at
            # the largest kv width; smaller widths compile lazily like the
            # chunk-q ladder, so the runner stays unsealed here (callers
            # pinning zero steady-state recompiles warm their mix and seal())
            self.kv_cache = self.mixed_step_model.warmup(
                self.params, self.kv_cache, self._sample_key(0)
            )
        from neuronx_distributed_inference_tpu.analysis.retrace_guard import (
            guard_enabled,
        )

        if guard_enabled(tc) and chunk_q is None:
            # seal the warmed step programs: a steady-state retrace now
            # raises (analysis/retrace_guard.py) instead of silently
            # recompiling mid-serve. Any multi-token chunk config
            # (chunked/prefix prefill, windowed prefill, bounded windows —
            # exactly the cases that set chunk_q) stays unsealed: their
            # smaller-kv chunk programs compile lazily at first use by
            # design (model_runner.warmup docstring) and sealing would turn
            # that designed lazy compile into a serve-time RetraceError.
            for runner in runners:
                runner.seal()

    def _warm_block_serving(self, shapes):
        """``warm_serving(shapes)`` of a block-step application (bound in
        ``__init__``): compile every program a :class:`ServingSession`
        dispatches for ``shapes`` = [(q length, kv bucket), ...]. ``(1,
        bucket)`` is the block step at that kv bucket, run once on ids from
        the host (a block's first pass) and once on ids still on the device
        (the pass before's ``next_ids``, chained): a denoise and a commit
        pass are one program. ``(q, bucket)`` with q > 1 is the chunk
        program. Every write goes to the garbage block; nothing is waited
        for (a program compiles as it is dispatched)."""
        tkg = self.token_generation_model
        for q, bucket in shapes:
            inputs = tkg.example_inputs(bucket, q_len=q if q > 1 else None)
            out = tkg(self.params, self.kv_cache, inputs, None)
            if q == 1:
                chained = jnp.where(
                    jnp.ones(inputs.input_ids.shape, bool), out.next_ids, inputs.input_ids
                )
                out = tkg(
                    self.params, out.cache, dataclasses.replace(inputs, input_ids=chained), None
                )
            self.kv_cache = out.cache

    def capture_forward(
        self,
        input_ids: np.ndarray,
        attention_mask: Optional[np.ndarray] = None,
        replacements: Optional[dict] = None,
    ):
        """Debug prefill pass with tensor taps (reference tensor capture +
        teacher-forcing replacement, config.py:987/:1038 +
        utils/tensor_replacement/registry.py).

        Captures the points named in ``tpu_config.tensor_capture_config`` and
        substitutes host goldens for the points named in
        ``tensor_replacement_config`` (``replacements`` maps point name ->
        array; per-layer points use (L, ...) stacked goldens).

        Returns (tokens (B, 1) np, captured {point: np array}). The KV cache
        is left untouched (a debug pass must not corrupt live state).
        """
        from functools import partial as _partial

        from neuronx_distributed_inference_tpu.models.base import forward
        from neuronx_distributed_inference_tpu.modules import tensor_taps

        tc = self.config.tpu_config
        cap_cfg = tc.tensor_capture_config
        rep_cfg = tc.tensor_replacement_config
        if cap_cfg is None and rep_cfg is None:
            raise ValueError(
                "set tpu_config.tensor_capture_config and/or "
                "tensor_replacement_config to use capture_forward"
            )
        points = tuple(cap_cfg.points) if cap_cfg else ()
        allowed = tuple(rep_cfg.points) if rep_cfg else ()
        replacements = dict(replacements or {})
        unknown = set(replacements) - set(allowed)
        if unknown:
            raise ValueError(
                f"replacement(s) {sorted(unknown)} not declared in "
                f"tensor_replacement_config.points {list(allowed)}"
            )

        input_ids = np.asarray(input_ids)
        B, S = input_ids.shape
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        position_ids = np.tile(np.arange(S, dtype=np.int32), (B, 1))
        inputs, _ = self.context_encoding_model.prepare(
            input_ids, np.asarray(attention_mask), position_ids,
            np.arange(B, dtype=np.int32),
        )

        mlp_fn = self.builder.mlp_fn()
        layer_fn = self.builder.layer_fn()

        key = (points, allowed, tuple(sorted(replacements)))
        if not hasattr(self, "_tap_fns"):
            self._tap_fns = {}
        fn = self._tap_fns.get(key)
        if fn is None:

            def tapped(params, cache, step_inputs, goldens):
                with tensor_taps.TapContext(capture=points, replacements=goldens) as ctx:
                    out = forward(
                        params, cache, step_inputs, None,
                        spec=self.spec, phase=PHASE_CONTEXT_ENCODING,
                        mlp_fn=mlp_fn, layer_fn=layer_fn,
                    )
                    return out.tokens, dict(ctx.captured)

            fn = self._tap_fns[key] = jax.jit(tapped)
        with jax.set_mesh(self.mesh):
            tokens, captured = fn(
                self.params, self.kv_cache, inputs,
                {k: jnp.asarray(v) for k, v in replacements.items()},
            )
        return (
            np.asarray(tokens)[:B],
            {k: np.asarray(v) for k, v in captured.items()},
        )

    def _sample_key(self, step: int):
        if not self.spec.do_sample:
            return None
        return jax.random.fold_in(self._call_key, step)

    def _advance_rng(self):
        """Fresh key per generate() call so successive calls draw different
        samples; deterministic mode keeps the seeded sequence reproducible
        from construction (reference deterministic flag, sampling.py)."""
        self._rng_key, self._call_key = jax.random.split(self._rng_key)

    def _windowed_prefill(self, input_ids, attention_mask, seq_ids, sampling_params, adapter_ids):
        """Prefill a prompt LONGER than one context program in windows
        (reference windowed context encoding, model_base.py:957-1010).

        Chunk 0 runs through the CTE program; every later chunk is a
        multi-token PHASE_TOKEN_GENERATION pass attending the populated cache
        (the same prior-KV pattern chunked prefill uses on the paged cache).
        Activation memory stays bounded by the chunk size instead of S².
        Returns (first_tokens (B,1) device array, first_logits (B,1,V)|None).
        """
        tc = self.config.tpu_config
        B, S_in = input_ids.shape
        W = self.spec.bounded_window
        C = self.context_encoding_model.buckets[-1]
        ring_w = W or self.spec.ring_window
        if ring_w:
            C = min(C, ring_w)  # ring slots must stay distinct within one chunk
        ctx_lens = attention_mask.sum(axis=1).astype(np.int64)
        first_tok = np.zeros((B,), np.int64)
        first_logits = (
            np.zeros((B, 1, self.spec.vocab_size), np.float32)
            if self.spec.output_logits
            else None
        )

        tel = default_session()

        # --- chunk 0: CTE ---
        n0 = min(C, S_in)
        pos0 = np.tile(np.arange(n0, dtype=np.int32), (B, 1))
        with tel.span("app.prefill_windowed", tokens=n0):
            inputs, _ = self.context_encoding_model.prepare(
                input_ids[:, :n0], attention_mask[:, :n0], pos0, seq_ids,
                sampling_params, adapter_ids=adapter_ids,
            )
            out = self.context_encoding_model(
                self.params, self.kv_cache, inputs, self._sample_key(1_000_000)
            )
        self.kv_cache = out.cache
        tel.step("prefill")
        tel.bucket_dispatch(
            self.context_encoding_model.tag, self.context_encoding_model.last_bucket
        )
        rows = ctx_lens <= n0
        if rows.any():
            # ONE host round-trip for the step: tokens + logits batched into
            # a single device_get (tpulint TPU102 pins this count)
            t0, l0 = jax.device_get(
                (out.tokens, out.logits if first_logits is not None else None)
            )
            first_tok[rows] = np.asarray(t0)[:B][rows, -1]
            if first_logits is not None:
                first_logits[rows, 0] = np.asarray(l0)[:B][rows, -1]

        # --- later chunks: multi-token prior-KV passes ---
        start = n0
        step = 1
        while start < S_in:
            end = min(start + C, S_in)
            n = end - start
            # every chunk is padded to the SAME length C so windowed prefill
            # compiles exactly one multi-token TKG shape per kv bucket;
            # sentinel positions drop the padded writes and mask their reads
            ids = np.zeros((B, C), input_ids.dtype)
            ids[:, :n] = input_ids[:, start:end]
            pos = np.full((B, C), PAD_POSITION_SENTINEL, np.int32)
            pos[:, :n] = np.arange(start, end, dtype=np.int32)
            valid = pos < ctx_lens[:, None]
            pos = np.where(valid, pos, PAD_POSITION_SENTINEL)
            width = W or autobucketing.get_target_bucket(
                self.token_generation_model.buckets, end
            )
            # full-width carrier: per-token causal bounds make junk columns
            # unreachable for valid queries; junk slots are overwritten
            # (write-then-attend) before any query can see them
            mask = np.ones((B, width), np.int32)
            with tel.span("app.prefill_windowed", tokens=n):
                inputs, _ = self.token_generation_model.prepare(
                    ids, mask, pos, seq_ids, sampling_params, adapter_ids=adapter_ids
                )
                # prefill chunks draw from their own key domain so decode
                # chunks (step 1, 2, ...) never reuse a prefill key
                out = self.token_generation_model(
                    self.params, self.kv_cache, inputs,
                    self._sample_key(1_000_000 + step),
                )
            self.kv_cache = out.cache
            tel.step("prefill")
            tel.bucket_dispatch(
                self.token_generation_model.tag,
                self.token_generation_model.last_bucket,
            )
            rows = (ctx_lens > start) & (ctx_lens <= end)
            if rows.any():
                toks, lg = jax.device_get(
                    (out.tokens, out.logits if first_logits is not None else None)
                )
                toks = np.asarray(toks)[:B]
                idx = np.clip(ctx_lens - 1 - start, 0, n - 1)
                first_tok[rows] = toks[rows, idx[rows]]
                if first_logits is not None:
                    first_logits[rows, 0] = np.asarray(lg)[:B][rows, idx[rows]]
            start = end
            step += 1
        fl = jnp.asarray(first_logits) if first_logits is not None else None
        return jnp.asarray(first_tok[:, None], jnp.int32), fl

    def validate_prefill_length(self, S: int):
        """Shared pre-checks for any prompt/history prefill (generate and
        utils.snapshot.reconstruct_kv_cache use the same rule)."""
        tc = self.config.tpu_config
        if S > tc.seq_len:
            raise ValueError(f"prompt length {S} exceeds seq_len {tc.seq_len}")
        windowed = S > tc.max_context_length or (
            self.spec.bounded_window and S > self.spec.bounded_window
        ) or (
            # interleaved ring cache: prompts longer than the window must
            # prefill in ≤W chunks so in-chunk ring slots stay distinct
            self.spec.ring_window and S > self.spec.ring_window
        )
        if (
            windowed
            and not self.spec.bounded_window
            and S > self.token_generation_model.buckets[-1]
        ):
            raise ValueError(
                f"prompt length {S} exceeds the largest token-generation "
                f"bucket ({self.token_generation_model.buckets[-1]}) needed "
                f"for windowed prefill; raise token_generation_buckets/seq_len"
            )
        return windowed

    def forward(
        self,
        input_ids: np.ndarray,
        position_ids: np.ndarray,
        seq_ids: np.ndarray,
        *,
        attention_mask: Optional[np.ndarray] = None,
        sampling_params: Optional[np.ndarray] = None,
        slot_mapping: Optional[np.ndarray] = None,
        block_table: Optional[np.ndarray] = None,
        phase: Optional[str] = None,
        key=None,
    ):
        """External-scheduler forward: ONE model pass with caller-provided
        cache placement — the entry point a vLLM-style continuous-batching
        engine drives when IT owns slot tables and block tables instead of
        :class:`~..runtime.serving.ServingSession` (VERDICT r4 next #10;
        reference public forward with slot_mapping/block_table,
        model_base.py:3392-3396).

        ``input_ids``/``position_ids``: (B, S). ``seq_ids``: (B,) cache-line
        ids; -1 marks an inactive row (writes land in the garbage line).
        ``slot_mapping``: (B, S) flat block-cache write slots for prefill on
        the paged cache (-1 drops the write; wider than a decode step the
        write moves whole blocks, so a row's valid slots are a prefix of the
        row at consecutive positions of one sequence, else ``ValueError``:
        block_kvcache.update_block_cache_at_layer); decode on the paged cache
        derives slots in-graph from ``block_table`` (B, max_blocks), exactly
        like the serving path. ``attention_mask``: (B, width) cache
        occupancy; defaults to "everything up to the max position".
        ``phase``: "cte"/"tkg"; inferred from S when omitted (S > 1 →
        context encoding). Chunked/prior-KV prefill passes multi-token
        inputs through the TKG program — pass ``phase="tkg"`` explicitly.

        A paged chunk pass (``phase="tkg"`` with BOTH ``slot_mapping`` and
        ``block_table``) runs the chunk program, which is
        ``runner.chunk_rows`` wide and addresses its rows by slot: row ``i``
        belongs to slot ``seq_ids[i]`` whatever ``i`` is, more rows than
        the program is wide are run in groups, and the results come back in
        the caller's row order. Its head runs where a token can leave it: it
        returns ``tokens (B, 1)``, the token after each row's last fed
        position (a row's fed positions are those with a slot in
        ``slot_mapping``, a prefix of the row; a row that sits out returns
        garbage), and, under ``TpuConfig.output_logits``
        alone, logits at every position, ``(B, S, V)``.

        Returns (tokens (B, K) np.ndarray, logits (B, K, V) np.ndarray or
        None; a paged chunk pass as above) and, under
        ``TpuConfig.output_choices``, a third value: the
        choices the pass made, ``name -> int np.ndarray (B, S, ...)`` (an
        expert layer: ``(B, S, L_moe, k)``). Updates the app's KV cache in
        place; all scheduling state stays with the caller.
        """
        input_ids = np.asarray(input_ids)
        position_ids = np.asarray(position_ids)
        seq_ids = np.asarray(seq_ids, np.int32)
        B, S = input_ids.shape
        if phase is None:
            phase = "cte" if S > 1 else "tkg"
        if phase not in ("cte", "tkg"):
            raise ValueError("phase must be 'cte' or 'tkg'")
        runner = (
            self.context_encoding_model if phase == "cte"
            else self.token_generation_model
        )
        block = self.spec.block_step
        if (
            block is not None and phase == "tkg" and S == block.block_length
            and block_table is not None
        ):
            # a block wide on the paged cache is the SERVED block step, whose
            # write slots derive in-graph from the block table; a row that
            # sits out (seq id -1) writes to the garbage block
            positions = np.where(seq_ids[:, None] >= 0, position_ids, 0)
            block_table = np.where(seq_ids[:, None] >= 0, np.asarray(block_table), 0)
            if slot_mapping is not None:
                bs = self.config.tpu_config.pa_block_size
                derived = np.take_along_axis(block_table, positions // bs, axis=1) * bs + positions % bs
                given = np.asarray(slot_mapping)
                if not np.array_equal(np.where(given >= 0, given, derived), derived):
                    raise ValueError(
                        "a block step writes its block's positions where the block table "
                        "puts them: the slot mapping given names other slots"
                    )
                slot_mapping = None
        if slot_mapping is not None:
            from neuronx_distributed_inference_tpu.modules.block_kvcache import (
                check_block_form_rows,
                takes_block_form,
            )

            # asked as the writer asks it: with the pool row's width
            if takes_block_form(S, self.kv_cache.k.shape[-1]):
                check_block_form_rows(slot_mapping, self.config.tpu_config.pa_block_size)
        R = runner.chunk_rows
        if runner.is_paged_chunk(slot_mapping, block_table) and B > R:
            per_row = [input_ids, position_ids, seq_ids, attention_mask, sampling_params,
                       slot_mapping, block_table]
            parts = []
            for i in range(0, B, R):
                ids, pos, sid, mask, sp, sm, bt = (
                    None if a is None else np.asarray(a)[i : i + R] for a in per_row
                )
                parts.append(self.forward(
                    ids, pos, sid, attention_mask=mask, sampling_params=sp,
                    slot_mapping=sm, block_table=bt, phase=phase, key=key,
                ))
            tokens = np.concatenate([p[0] for p in parts])
            logits = None
            if parts[0][1] is not None:
                logits = np.concatenate([p[1] for p in parts])
            if len(parts[0]) == 2:
                return tokens, logits
            return tokens, logits, {
                name: np.concatenate([p[2][name] for p in parts]) for name in parts[0][2]
            }
        if sampling_params is None:
            sampling_params = prepare_sampling_params(B)
        if attention_mask is None:
            if phase == "cte":
                attention_mask = np.ones_like(input_ids)
            else:
                width = self._decode_bucket(int(position_ids.max()) + 1)
                attention_mask = (
                    np.arange(width)[None, :] <= position_ids.max(axis=1)[:, None]
                ).astype(np.int32)
        tel = default_session()
        with tel.span(f"app.forward.{phase}", tokens=S):
            inputs, _ = runner.prepare(
                input_ids,
                np.asarray(attention_mask),
                position_ids,
                seq_ids,
                np.asarray(sampling_params, np.float32),
                slot_mapping=slot_mapping,
                block_table=block_table,
            )
            out = runner(self.params, self.kv_cache, inputs, key)
        self.kv_cache = out.cache
        tel.step("prefill" if phase == "cte" else "decode")
        tel.bucket_dispatch(runner.tag, runner.last_bucket)
        # one host round-trip per step: tokens + logits in a single fetch
        tokens, logits, aux = jax.device_get((out.tokens, out.logits, out.aux))
        tokens = np.asarray(tokens)[:B]
        if logits is not None:
            logits = np.asarray(logits)[:B]
        if aux is None:
            return tokens, logits
        return tokens, logits, {name: np.asarray(a)[:B] for name, a in aux.items()}

    def _pos_limit(self) -> int:
        """Largest writable position: a ring cache bounds SLOTS, not
        positions; otherwise the largest compiled TKG bucket bounds it."""
        tc = self.config.tpu_config
        if self.spec.bounded_window:
            return tc.seq_len
        return min(tc.seq_len, self.token_generation_model.buckets[-1])

    def _decode_bucket(self, needed: int) -> int:
        if self.spec.bounded_window:
            return self.spec.bounded_window
        return autobucketing.get_target_bucket(
            self.token_generation_model.buckets, needed
        )

    # ---- generation loop -------------------------------------------------

    def generate(
        self,
        input_ids: np.ndarray,
        attention_mask: Optional[np.ndarray] = None,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        top_k=None,
        top_p=None,
        temperature=None,
        seq_ids: Optional[np.ndarray] = None,
        lora_adapter_names=None,
        inputs_embeds=None,
    ) -> GenerationOutput:
        """Host generation loop (reference hf_adapter _sample, hf_adapter.py:129).

        input_ids: (B, S) RIGHT-padded; attention_mask: (B, S) 1=valid.
        ``inputs_embeds`` (B, S, H) replaces the prompt's token embeddings at
        prefill (multimodal merge; reference inputs_embeds path) — decode
        continues from sampled token ids as usual.
        """
        tc = self.config.tpu_config
        if tc.is_block_kv_layout:
            raise NotImplementedError(
                "block-KV layout generation runs through ServingSession "
                "(runtime/serving.py) or the low-level forward API"
            )
        self._advance_rng()
        input_ids = np.asarray(input_ids)
        B, S_in = input_ids.shape
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        attention_mask = np.asarray(attention_mask)
        if seq_ids is None:
            seq_ids = np.arange(B, dtype=np.int32)
        sampling_params = prepare_sampling_params(B, top_k, top_p, temperature)
        validate_sampling_params(sampling_params, tc.max_topk)

        windowed = self.validate_prefill_length(S_in)
        max_total = min(tc.seq_len, S_in + max_new_tokens)
        n_new = max_total - S_in
        if n_new <= 0:
            return GenerationOutput(sequences=input_ids, num_generated=0)

        adapter_ids = self.resolve_adapter_ids(lora_adapter_names)
        ctx_lens = attention_mask.sum(axis=1).astype(np.int32)
        tel = default_session()
        if windowed:
            if inputs_embeds is not None:
                raise NotImplementedError(
                    "inputs_embeds with windowed prefill is not implemented; "
                    "raise max_context_length to cover the multimodal prompt"
                )
            # long-prompt prefill in windows (reference windowed context
            # encoding, model_base.py:957-1010): chunk 0 through the CTE
            # program, later chunks as multi-token prior-KV passes
            first_tokens, first_logits = self._windowed_prefill(
                input_ids, attention_mask, seq_ids, sampling_params, adapter_ids
            )
        else:
            # CTE: positions are slot indices [0, S) — padded slots write into
            # the masked tail (reference fill_prefix semantics, kvcache/utils.py)
            position_ids = np.tile(np.arange(S_in, dtype=np.int32), (B, 1))
            with tel.span("app.cte", tokens=S_in):
                inputs, _ = self.context_encoding_model.prepare(
                    input_ids, attention_mask, position_ids, seq_ids, sampling_params,
                    adapter_ids=adapter_ids, inputs_embeds=inputs_embeds,
                )
                out = self.context_encoding_model(
                    self.params, self.kv_cache, inputs, self._sample_key(0)
                )
            self.kv_cache = out.cache
            tel.step("prefill")
            tel.bucket_dispatch(
                self.context_encoding_model.tag,
                self.context_encoding_model.last_bucket,
            )
            first_tokens = out.tokens[:B]  # device (B, 1)
            first_logits = out.logits[:B] if self.spec.output_logits else None
        pos = ctx_lens.copy()  # next write position per row
        remaining = n_new - 1
        step = 1

        # chunked multi-step decode: whole chunks of the token loop run as one
        # device program (models/base.py decode_steps). Syncing with the
        # device costs a full round trip, so:
        # - no EOS: chain CTE -> chunks entirely with device-resident tokens
        #   (async dispatch) and fetch everything in ONE sync at the end;
        # - EOS: fetch tokens at each chunk boundary to test termination —
        #   that per-chunk sync is the feature being paid for.
        if eos_token_id is None:
            # chunks are sliced to the true batch B on device: the CTE and
            # TKG runners may be compiled at different batch sizes
            token_chunks = [first_tokens]  # device (B, 1)
            logit_chunks = [first_logits] if self.spec.output_logits else []
            last = first_tokens[:, -1:].astype(jnp.int32)
            # positions must stay inside the largest compiled TKG bucket as
            # well as the cache window — pow2 rounding must not push past it
            pos_limit = self._pos_limit()
            while remaining > 0:
                headroom = pos_limit - int(pos.max())
                if headroom < 1:
                    raise ValueError(
                        f"generation needs positions past the largest TKG "
                        f"bucket/cache window ({pos_limit}); raise "
                        f"token_generation_buckets or seq_len"
                    )
                chunk = _pick_chunk(remaining, False, headroom)
                take = min(chunk, remaining)
                bucket = self._decode_bucket(int(pos.max()) + chunk)
                with tel.span("app.decode_chunk", steps=chunk):
                    tokens_c, logits_c, cache = self.token_generation_model.decode_chunk(
                        self.params,
                        self.kv_cache,
                        last,
                        pos[:, None],
                        seq_ids,
                        sampling_params,
                        self._sample_key(step),
                        num_steps=chunk,
                        bucket=bucket,
                        adapter_ids=adapter_ids,
                    )
                self.kv_cache = cache
                tel.step("decode")
                tel.bucket_dispatch(self.token_generation_model.tag, bucket)
                token_chunks.append(tokens_c[:B, :take])
                if self.spec.output_logits:
                    logit_chunks.append(logits_c[:B, :take])
                last = tokens_c[:B, take - 1 : take]
                pos = pos + take
                remaining -= take
                step += 1
                if not tc.async_mode:
                    # sync at every chunk boundary (debugging; reference
                    # async_mode=False per-step dispatch semantics)
                    jax.block_until_ready(tokens_c)
            # everything the loop produced comes back in ONE fetch
            gen, logits = jax.device_get(
                (
                    jnp.concatenate(token_chunks, axis=1),
                    jnp.concatenate(logit_chunks, axis=1) if logit_chunks else None,
                )
            )
            gen = np.asarray(gen)
            tel.tokens_generated(gen.size)
            sequences = np.concatenate([input_ids, gen.astype(np.int64)], axis=1)
            if logits is not None:
                logits = np.asarray(logits)
            return GenerationOutput(
                sequences=sequences, logits=logits, num_generated=gen.shape[1]
            )

        eos_arr = np.atleast_1d(np.asarray(eos_token_id)).astype(np.int64)
        eos_fill = int(eos_arr[0])
        # tokens + logits in ONE device_get per step (tpulint TPU102 pins
        # the count); logits land on host each chunk so device memory stays
        # bounded regardless of generation length
        tokens, first_l = jax.device_get(
            (first_tokens, first_logits if self.spec.output_logits else None)
        )
        tokens = np.asarray(tokens)  # (B, 1)
        logits_acc: List[np.ndarray] = []
        if first_l is not None:
            logits_acc.append(np.asarray(first_l))
        generated = [tokens[:, -1]]
        done = np.zeros(B, bool)
        done |= np.isin(generated[-1], eos_arr)
        last = generated[-1][:, None].astype(np.int32)
        pos_limit = self._pos_limit()
        while remaining > 0 and not done.all():
            headroom = pos_limit - int(pos.max())
            if headroom < 1:
                raise ValueError(
                    f"generation needs positions past the largest TKG "
                    f"bucket/cache window ({pos_limit}); raise "
                    f"token_generation_buckets or seq_len"
                )
            chunk = _pick_chunk(remaining, True, headroom)
            take = min(chunk, remaining)
            bucket = self._decode_bucket(int(pos.max()) + chunk)
            with tel.span("app.decode_chunk", steps=chunk):
                tokens_c, logits_c, cache = self.token_generation_model.decode_chunk(
                    self.params,
                    self.kv_cache,
                    last,
                    pos[:, None],
                    seq_ids,
                    sampling_params,
                    self._sample_key(step),
                    num_steps=chunk,
                    bucket=bucket,
                    adapter_ids=adapter_ids,
                )
            self.kv_cache = cache
            tel.step("decode")
            tel.bucket_dispatch(self.token_generation_model.tag, bucket)
            # the chunk boundary must sync anyway to test EOS; riding the
            # logits on the SAME fetch keeps it one round-trip per chunk
            tokens_c, logits_h = jax.device_get(
                (
                    tokens_c,
                    logits_c[:B, :take] if self.spec.output_logits else None,
                )
            )
            tokens_c = np.asarray(tokens_c)[:B]  # (B, chunk)
            if logits_h is not None:
                logits_acc.append(np.asarray(logits_h))
            for j in range(take):
                step_tokens = tokens_c[:, j]
                step_tokens = np.where(done, eos_fill, step_tokens)
                done |= np.isin(step_tokens, eos_arr)
                generated.append(step_tokens)
            last = tokens_c[:, take - 1 : take].astype(np.int32)
            pos = pos + take
            remaining -= take
            step += 1

        gen = np.stack(generated, axis=1).astype(np.int64)  # (B, n)
        tel.tokens_generated(gen.size)
        sequences = np.concatenate([input_ids, gen], axis=1)
        logits = np.concatenate(logits_acc, axis=1) if logits_acc else None
        return GenerationOutput(sequences=sequences, logits=logits, num_generated=gen.shape[1])


def load_model(compiled_model_path: str, model_path: Optional[str] = None) -> TpuModelForCausalLM:
    """Reload an application from a saved artifact dir (reference
    application_base.py:82-83 — reloadable by path alone)."""
    config = InferenceConfig.load(compiled_model_path)
    app = TpuModelForCausalLM(model_path, config)
    app.compile(compiled_model_path)
    return app
