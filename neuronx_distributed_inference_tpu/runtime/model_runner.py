"""Per-sub-model runner: bucket dispatch, host padding, jit execution.

TPU-native re-design of the reference ``ModelWrapper``
(reference: models/model_wrapper.py:45-1574).

One :class:`SubModelRunner` per compiled sub-model tag (context_encoding,
token_generation, ...; reference model_wrapper.py:32-37). Responsibilities:

- hold ONE jitted step function; each (bucket, batch) shape is a separate XLA
  program in the jit cache — the analogue of the reference's per-bucket NEFFs.
- pad inputs to the bucket (reference pad_inputs, model_wrapper.py:778-1013)
  and the batch to the compiled batch size with the sorted-seq_id convention
  (reference _forward_with_pad, model_wrapper.py:582-751).
- donate the KV cache so XLA updates it in place (reference aliasing,
  model_wrapper.py:1673-1743).
- warmup() runs every bucket once to populate the compile cache
  (reference application_base.py:348-372).
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from neuronx_distributed_inference_tpu.analysis.retrace_guard import trace_marker
from neuronx_distributed_inference_tpu.models.base import (
    PHASE_CONTEXT_ENCODING,
    PHASE_TOKEN_GENERATION,
    ModelSpec,
    StepInputs,
    forward,
    is_paged_chunk,
)
from neuronx_distributed_inference_tpu.modules.autobucketing import get_target_bucket
from neuronx_distributed_inference_tpu.modules.kvcache import KVCache, cache_spec
from neuronx_distributed_inference_tpu.modules.moe import noting_masked_sorts
from neuronx_distributed_inference_tpu.modules.sampling import prepare_sampling_params
from neuronx_distributed_inference_tpu.ops.kernel_mode import CHUNK_ROWS
from neuronx_distributed_inference_tpu.utils.snapshot import debug_log_step

TAG_CONTEXT_ENCODING = "context_encoding_model"
TAG_TOKEN_GENERATION = "token_generation_model"
TAG_SPECULATION = "speculation_model"
TAG_FUSED_SPECULATION = "fused_speculation_model"
TAG_MIXED_STEP = "mixed_step_model"


class SubModelRunner:
    def __init__(
        self,
        tag: str,
        phase: str,
        spec: ModelSpec,
        buckets: List[int],
        batch_size: int,
        mesh,
        mlp_fn: Callable,
        n_active_tokens: int = 1,
        block_kv: bool = False,
        block_size: int = 16,
        layer_fn=None,
    ):
        self.tag = tag
        self.phase = phase
        self.spec = spec
        self.buckets = sorted(buckets)
        self.batch_size = batch_size
        self.mesh = mesh
        self.n_active_tokens = n_active_tokens
        self.block_kv = block_kv
        self.block_size = block_size
        self.mlp_fn = mlp_fn
        self.layer_fn = layer_fn
        self._decode_fns = {}  # (num_steps, bucket) -> jitted multi-step program
        # telemetry census source: the bucket the LAST prepare()/decode_chunk
        # resolved to — the host loops record it so the bucket-dispatch
        # census can never drift from what actually padded/dispatched
        self.last_bucket: Optional[int] = None
        # retrace guard (analysis/retrace_guard.py): the step fn notes every
        # jit trace; after warmup() the application may seal() the runner so a
        # steady-state retrace raises instead of silently recompiling
        self._sealed = False

        # params/cache arrive as committed GSPMD-sharded arrays (device_put in
        # load()); jit follows their shardings, so no in_shardings needed —
        # and the param tree can change shape (e.g. quantization adds scale
        # leaves) without invalidating the runner
        # the (rows, positions a row) of the programs traced so far whose
        # expert layers sorted the pass's real positions alone
        # (modules/moe.noting_masked_sorts): the session's counter asks
        self.masked_sort_shapes = set()

        def step(*args):
            with noting_masked_sorts() as shapes:
                out = forward(*args, spec=spec, phase=phase, mlp_fn=mlp_fn, layer_fn=layer_fn)
            self.masked_sort_shapes |= shapes
            return out

        def program(name):
            return jax.jit(
                trace_marker(tag, step, owner=self, name=name),
                donate_argnums=(1,),  # cache in-place (reference KV aliasing)
            )

        # a token-generation runner serves two kinds of program from the
        # one function: the step at n_active_tokens and the multi-token
        # chunk / prefix-prefill pass. Each gets its own jitted callable so
        # a profiler trace names them apart (``jit_<tag>_decode`` and
        # ``jit_<tag>_chunk``); shapes never overlap, so the number of
        # compilations and the programs themselves are what one callable
        # gave.
        if phase == PHASE_CONTEXT_ENCODING:
            self._step_program, self._chunk_program = program(tag), None
        else:
            self._step_program = program(f"{tag}_decode")
            self._chunk_program = program(f"{tag}_chunk")

    @property
    def chunk_rows(self) -> int:
        """Rows of the PAGED chunk program (a token-generation pass handed
        both a slot mapping and a block table: chunked and prefix prefill).
        Its rows are addressed by slot, so it is compiled at this fixed
        small width whatever the slot count; callers pack the rows that
        prefill into groups of it (ops/kernel_mode.CHUNK_ROWS)."""
        return min(CHUNK_ROWS, self.batch_size)

    def is_paged_chunk(self, slot_mapping, block_table) -> bool:
        """Whether a call with these fields runs the paged chunk program
        (models/base.is_paged_chunk: the test the step itself makes on its
        inputs)."""
        return is_paged_chunk(self.phase, slot_mapping, block_table)

    def program_for(self, inputs: StepInputs):
        """The jitted callable that serves these inputs."""
        if self._chunk_program is not None and inputs.input_ids.shape[1] != self.n_active_tokens:
            return self._chunk_program
        return self._step_program

    def _fn(self, params, cache, inputs: StepInputs, rng=None):
        """Every dispatch of a step program goes through here (the timing,
        tap and capture hooks of utils/ and chip_smoke replace it per
        instance)."""
        return self.program_for(inputs)(params, cache, inputs, rng)

    def seal(self):
        """Arm the retrace guard: any later trace of this runner's step
        program raises RetraceError. Multi-step decode programs (pow2-keyed,
        built lazily in :meth:`decode_chunk`) are each allowed ONE compile —
        their first trace per (num_steps, bucket) key — and raise on any
        re-trace after that: steady state must reuse, first use may build.
        Call only after warmup() has compiled every bucket this runner will
        serve."""
        self._sealed = True

    @contextmanager
    def seal_suspended(self):
        """Temporarily lift the seal while a composite app (image-to-text,
        encoder-decoder) compiles additional program variants post-warmup;
        the previous seal state is restored even on failure."""
        was_sealed, self._sealed = self._sealed, False
        try:
            yield self
        finally:
            self._sealed = was_sealed

    # ---- host-side padding (reference model_wrapper.py:582-1013) ---------

    def _pad_batch(self, arrs: Dict[str, np.ndarray], batch: int) -> Dict[str, np.ndarray]:
        out = {}
        for name, a in arrs.items():
            if a.shape[0] == batch:
                out[name] = a
                continue
            pad = batch - a.shape[0]
            if pad < 0:
                raise ValueError(
                    f"{self.tag}: input batch {a.shape[0]} > compiled batch {batch}"
                )
            fill = -1 if name in ("seq_ids", "slot_mapping") else 0
            if isinstance(a, jax.Array):
                # device-resident input (async-chained from a previous step):
                # pad on device so the chain stays sync-free
                out[name] = jnp.concatenate(
                    [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0
                )
            else:
                out[name] = np.concatenate(
                    [a, np.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0
                )
        return out

    def prepare(
        self,
        input_ids: np.ndarray,
        attention_mask: np.ndarray,
        position_ids: np.ndarray,
        seq_ids: np.ndarray,
        sampling_params: Optional[np.ndarray] = None,
        slot_mapping: Optional[np.ndarray] = None,
        block_table: Optional[np.ndarray] = None,
        adapter_ids: Optional[np.ndarray] = None,
        inputs_embeds: Optional[np.ndarray] = None,
    ) -> Tuple[StepInputs, int]:
        """Pad to (compiled batch, bucket) and build StepInputs: the host
        arrays (:meth:`prepare_host`), then their copies to the device
        (:meth:`to_device`)."""
        arrs, B = self.prepare_host(
            input_ids, attention_mask, position_ids, seq_ids, sampling_params,
            slot_mapping, block_table, adapter_ids, inputs_embeds,
        )
        return self.to_device(arrs), B

    @staticmethod
    def to_device(arrs: Dict[str, np.ndarray]) -> StepInputs:
        """The host-to-device half of :meth:`prepare`: one copy an array (an
        array already on the device, a chained input, stays where it is)."""
        return StepInputs(**{k: jnp.asarray(v) for k, v in arrs.items()})

    def prepare_host(
        self,
        input_ids: np.ndarray,
        attention_mask: np.ndarray,
        position_ids: np.ndarray,
        seq_ids: np.ndarray,
        sampling_params: Optional[np.ndarray] = None,
        slot_mapping: Optional[np.ndarray] = None,
        block_table: Optional[np.ndarray] = None,
        adapter_ids: Optional[np.ndarray] = None,
        inputs_embeds: Optional[np.ndarray] = None,
    ) -> Tuple[Dict[str, np.ndarray], int]:
        """The host half of :meth:`prepare`: ``{StepInputs field: array}``
        padded to (compiled batch, bucket), nothing copied to the device
        yet, and the caller's batch. The serving step copies them under a
        span of its own (``serving.h2d``)."""
        B, S = input_ids.shape
        bounded = self.spec.bounded_window
        if self.phase == PHASE_CONTEXT_ENCODING:
            bucket = get_target_bucket(self.buckets, S)
            pad_s = bucket - S
            if pad_s:
                input_ids = np.pad(input_ids, ((0, 0), (0, pad_s)))
                attention_mask = np.pad(attention_mask, ((0, 0), (0, pad_s)))
                if inputs_embeds is not None:
                    inputs_embeds = np.pad(
                        np.asarray(inputs_embeds), ((0, 0), (0, pad_s), (0, 0))
                    )
                if bounded or self.spec.ring_window:
                    # ring cache (uniform or interleaved per-layer): sentinel
                    # positions make padded writes DROP instead of wrapping
                    # (mod W) onto live ring slots
                    from neuronx_distributed_inference_tpu.modules.kvcache import (
                        PAD_POSITION_SENTINEL,
                    )

                    tail = np.full((position_ids.shape[0], pad_s), PAD_POSITION_SENTINEL)
                else:
                    # pad positions continue the sequence so padded K/V lands
                    # in the masked tail, not on real slots
                    tail = position_ids[:, -1:] + 1 + np.arange(pad_s)[None, :]
                position_ids = np.concatenate([position_ids, tail], axis=1)
                if slot_mapping is not None:
                    # padded tokens write to the garbage block
                    slot_mapping = np.pad(
                        slot_mapping, ((0, 0), (0, pad_s)), constant_values=-1
                    )
        elif bounded:
            # ring cache: the mask is derived in-graph from positions; the
            # attention_mask is only the (B, W) width carrier
            bucket = bounded
            attention_mask = np.ones((B, bounded), np.int32)
        else:
            # TKG: bucket over cache length = attention_mask width
            bucket = get_target_bucket(self.buckets, attention_mask.shape[1])
            pad_s = bucket - attention_mask.shape[1]
            if pad_s:
                attention_mask = np.pad(attention_mask, ((0, 0), (0, pad_s)))

        self.last_bucket = bucket
        if sampling_params is None:
            sampling_params = prepare_sampling_params(B)
        arrs = {
            "input_ids": input_ids.astype(np.int32),
            "attention_mask": attention_mask.astype(np.int32),
            "position_ids": position_ids.astype(np.int32),
            "seq_ids": seq_ids.astype(np.int32),
            "sampling_params": sampling_params.astype(np.float32),
        }
        if slot_mapping is not None:
            arrs["slot_mapping"] = slot_mapping.astype(np.int32)
        if block_table is not None:
            arrs["block_table"] = block_table.astype(np.int32)
        if adapter_ids is not None:
            arrs["adapter_ids"] = adapter_ids.astype(np.int32)
        if inputs_embeds is not None:
            # keep the caller's dtype (the merged-embedding table's compute
            # dtype) — forcing fp32 would silently run bf16 prefill in fp32
            arrs["inputs_embeds"] = np.asarray(inputs_embeds)
        # the paged chunk program is chunk_rows wide (rows addressed by slot);
        # every other program has one row per slot
        paged_chunk = self.is_paged_chunk(slot_mapping, block_table)
        return self._pad_batch(arrs, self.chunk_rows if paged_chunk else self.batch_size), B

    def trace_program(self, params, cache: KVCache, inputs: StepInputs, rng=None):
        """Trace + lower + compile this runner's step program WITHOUT
        executing it — the static analyzer's entry point
        (analysis/programs.py). Returns (traced, lowered, compiled): the
        jaxpr, the donation-annotated StableHLO, and the partitioned
        executable whose HLO carries the realized shardings and the
        ``input_output_alias`` table the shard/memory audits parse. Runs
        under the runner's mesh so in-graph constraints resolve exactly as
        they do in :meth:`__call__`."""
        with jax.set_mesh(self.mesh):
            traced = self.program_for(inputs).trace(params, cache, inputs, rng)
            lowered = traced.lower()
            compiled = lowered.compile()
        return traced, lowered, compiled

    def __call__(self, params, cache: KVCache, inputs: StepInputs, rng=None):
        """Run one step. Returns StepOutput (tokens/logits device arrays + new cache).

        Runs under the mesh context so in-graph sharding constraints
        (CP/SP hints) resolve against the right axes."""
        with jax.set_mesh(self.mesh):
            out = self._fn(params, cache, inputs, rng)
        debug_log_step(self.tag, inputs, out)
        return out

    def decode_chunk(
        self,
        params,
        cache,
        last: np.ndarray,  # (B, 1)
        pos: np.ndarray,  # (B, 1)
        seq_ids: np.ndarray,
        sampling_params: np.ndarray,
        rng,
        num_steps: int,
        bucket: int,
        adapter_ids: Optional[np.ndarray] = None,
    ):
        """Multi-step decode on the contiguous cache: num_steps tokens in one
        device dispatch (models/base.py decode_steps). Host pays one call per
        chunk."""
        from neuronx_distributed_inference_tpu.models.base import decode_steps

        self.last_bucket = bucket
        B = self.batch_size
        arrs = self._pad_batch(
            {
                "last": last.astype(np.int32),
                "pos": pos.astype(np.int32),
                "seq_ids": seq_ids.astype(np.int32),
                "sampling_params": sampling_params.astype(np.float32),
                **(
                    {"adapter_ids": adapter_ids.astype(np.int32)}
                    if adapter_ids is not None
                    else {}
                ),
            },
            B,
        )
        key = (num_steps, bucket, adapter_ids is not None)
        fn = self._decode_fns.get(key)
        if fn is None:
            from neuronx_distributed_inference_tpu.analysis import retrace_guard

            inner = partial(
                decode_steps,
                spec=self.spec,
                num_steps=num_steps,
                bucket=bucket,
                mlp_fn=self.mlp_fn,
                layer_fn=self.layer_fn,
            )
            tag = f"{self.tag}:decode[{num_steps},{bucket}]"
            state = {"traced": False}
            runner = self

            def decode_step_fn(*args, **kwargs):
                # decode programs build lazily (this very call may be the
                # first): the first trace per key is legitimate even when
                # sealed; a RE-trace of an existing program in a sealed
                # runner is the steady-state recompile the guard forbids
                retrace_guard.note_trace(
                    tag, sealed=state["traced"] and runner._sealed
                )
                out = inner(*args, **kwargs)
                # only a COMPLETED first trace counts: a failed compile must
                # not make the retry look like a steady-state recompile
                state["traced"] = True
                return out

            fn = jax.jit(decode_step_fn, donate_argnums=(1,))
            self._decode_fns[key] = fn
        kwargs = {}
        if adapter_ids is not None:
            kwargs["adapter_ids"] = jnp.asarray(arrs["adapter_ids"])
        with jax.set_mesh(self.mesh):
            return fn(
                params,
                cache,
                jnp.asarray(arrs["last"]),
                jnp.asarray(arrs["pos"]),
                jnp.asarray(arrs["seq_ids"]),
                jnp.asarray(arrs["sampling_params"]),
                rng,
                **kwargs,
            )

    # ---- warmup ----------------------------------------------------------

    def example_inputs(self, bucket: int, q_len: Optional[int] = None) -> StepInputs:
        """Reference: input_generator (model_wrapper.py:203-367).

        ``q_len`` > 1 builds a chunked/prefix-prefill example: multi-token
        TKG inputs with BOTH slot_mapping and block_table at ``chunk_rows``
        rows, matching ServingSession._prefill_chunks' call shape (what
        :meth:`prepare` pads such a call to)."""
        B = self.batch_size
        if q_len and self.block_kv and self.phase != PHASE_CONTEXT_ENCODING:
            B = self.chunk_rows
        if self.phase == PHASE_CONTEXT_ENCODING:
            S = bucket
            ids = np.zeros((B, S), np.int32)
            mask = np.ones((B, S), np.int32)
            pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
        else:
            S = q_len or self.n_active_tokens
            ids = np.zeros((B, S), np.int32)
            mask = np.ones((B, bucket), np.int32)
            pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
        kwargs = {}
        if self.block_kv:
            # warmup writes go to the garbage block; table reads block 0.
            # Field presence must match real serving calls (CTE: slot mapping
            # only; TKG decode: block table only, slot mapping generated
            # in-graph; chunk/prefix prefill: both) or the warmup program is
            # never reused.
            if self.phase == PHASE_CONTEXT_ENCODING or q_len:
                kwargs["slot_mapping"] = jnp.full((B, ids.shape[1]), -1, jnp.int32)
            if self.phase != PHASE_CONTEXT_ENCODING:
                kwargs["block_table"] = jnp.zeros(
                    (B, max(1, bucket // self.block_size)), jnp.int32
                )
        return StepInputs(
            input_ids=jnp.asarray(ids),
            attention_mask=jnp.asarray(mask),
            position_ids=jnp.asarray(pos),
            seq_ids=jnp.asarray(np.arange(B, dtype=np.int32)),
            sampling_params=jnp.asarray(prepare_sampling_params(B)),
            **kwargs,
        )

    def warmup(self, params, cache: KVCache, rng=None, chunk_q_lens=None) -> KVCache:
        """Compile + execute every bucket once (reference warmup,
        application_base.py:348-372). ``chunk_q_lens`` additionally compiles
        the 2-D chunk/prefix-prefill programs (q ladder x largest kv bucket;
        smaller kv buckets compile lazily at first use)."""
        with jax.set_mesh(self.mesh):
            for bucket in self.buckets:
                out = self._fn(params, cache, self.example_inputs(bucket), rng)
                out.tokens.block_until_ready()
                cache = out.cache
            for q in chunk_q_lens or ():
                out = self._fn(
                    params, cache, self.example_inputs(self.buckets[-1], q_len=q), rng
                )
                out.tokens.block_until_ready()
                cache = out.cache
        return cache


class MixedStepRunner:
    """Runner of the RAGGED mixed prefill+decode serving program family.

    Unlike :class:`SubModelRunner`, whose bucket axis is per-phase (context
    length for CTE, cache width for TKG), this family's primary bucket axis
    is the TOTAL packed query-token count of one serving step — prefill
    chunks and decode rows share it — crossed with the kv-width ladder the
    block table covers (a 2-D (q_total, kv_width) family, like the chunked-
    prefill programs). One ``step()`` dispatch of one program replaces the
    CTE/TKG pair the split serving path interleaved on the host.

    Packing contract: row r's segment starts at ``row_start[r]`` (a multiple
    of :attr:`q_tile`, so a kernel q tile never spans two rows) and row
    index == serving slot == block-table row. :meth:`prepare` pads the
    packed axis to the bucket and the block table to the kv width.
    """

    def __init__(
        self,
        spec: ModelSpec,
        buckets: List[int],  # total-query-token ladder
        num_rows: int,  # serving slot count (fixed R axis)
        mesh,
        mlp_fn: Callable,
        block_size: int,
        kv_buckets: List[int],  # kv-width ladder (block-aligned TKG buckets)
        layer_fn=None,
    ):
        from neuronx_distributed_inference_tpu.models.base import mixed_forward
        from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
            RAGGED_Q_TILE,
        )

        self.tag = TAG_MIXED_STEP
        self.phase = "mixed"
        self.spec = spec
        self.buckets = sorted(buckets)
        self.num_rows = num_rows
        self.mesh = mesh
        self.block_size = block_size
        self.kv_buckets = sorted(kv_buckets)
        self.q_tile = RAGGED_Q_TILE
        self.last_bucket: Optional[int] = None
        self._sealed = False
        step = partial(mixed_forward, spec=spec, mlp_fn=mlp_fn, layer_fn=layer_fn)
        self._fn = jax.jit(
            trace_marker(self.tag, step, owner=self),
            donate_argnums=(1,),  # paged cache in-place (same KV aliasing)
        )

    def seal(self):
        """Arm the retrace guard (see SubModelRunner.seal): call after every
        (q_total, kv_width) program this runner will serve has compiled."""
        self._sealed = True

    @contextmanager
    def seal_suspended(self):
        was_sealed, self._sealed = self._sealed, False
        try:
            yield self
        finally:
            self._sealed = was_sealed

    def prepare(
        self,
        input_ids: np.ndarray,  # (T,) packed tokens
        positions: np.ndarray,  # (T,) absolute positions; -1 = padded
        slot_mapping: np.ndarray,  # (T,) flat paged write slots; -1 = drop
        row_start: np.ndarray,  # (R,)
        row_len: np.ndarray,  # (R,)
        ctx_len: np.ndarray,  # (R,)
        block_table: np.ndarray,  # (R, mb) covering each row's blocks
        width: int,  # kv width bucket (block-aligned)
        sampling_params: Optional[np.ndarray] = None,
        chain_src: Optional[np.ndarray] = None,  # (T,) int32; -1 = host id
        chain_tokens=None,  # (R, 1) int32; may be an UNFETCHED device array
    ):
        """Pad the packed axis to its total-token bucket and the block table
        to ``width // block_size`` columns; build MixedStepInputs. Returns
        (inputs, T_real).

        ``chain_src``/``chain_tokens`` feed the async 1-ahead chained-id
        gather (models/base.mixed_forward): omitted, INERT values (all -1 /
        zeros) are substituted so the synchronous path dispatches the SAME
        program identity as the pipelined one — the warmed program is the
        served program in both modes."""
        from neuronx_distributed_inference_tpu.models.base import MixedStepInputs

        T = int(input_ids.shape[0])
        bucket = get_target_bucket(self.buckets, max(T, self.q_tile))
        pad = bucket - T
        if chain_src is None:
            chain_src = np.full(T, -1, np.int32)
        if chain_tokens is None:
            chain_tokens = np.zeros((self.num_rows, 1), np.int32)
        if pad:
            input_ids = np.pad(input_ids, (0, pad))
            positions = np.pad(positions, (0, pad), constant_values=-1)
            slot_mapping = np.pad(slot_mapping, (0, pad), constant_values=-1)
            chain_src = np.pad(chain_src, (0, pad), constant_values=-1)
        mb = max(1, width // self.block_size)
        R, mb_in = block_table.shape
        if R != self.num_rows:
            raise ValueError(
                f"{self.tag}: block table has {R} rows, compiled for "
                f"{self.num_rows}"
            )
        if mb_in < mb:
            block_table = np.pad(block_table, ((0, 0), (0, mb - mb_in)))
        elif mb_in > mb:
            raise ValueError(
                f"{self.tag}: block table covers {mb_in} blocks > width "
                f"bucket {width} ({mb} blocks)"
            )
        self.last_bucket = bucket
        if sampling_params is None:
            sampling_params = prepare_sampling_params(self.num_rows)
        inputs = MixedStepInputs(
            input_ids=jnp.asarray(input_ids.astype(np.int32)[None, :]),
            position_ids=jnp.asarray(positions.astype(np.int32)[None, :]),
            slot_mapping=jnp.asarray(slot_mapping.astype(np.int32)[None, :]),
            block_table=jnp.asarray(block_table.astype(np.int32)),
            row_start=jnp.asarray(row_start.astype(np.int32)),
            row_len=jnp.asarray(row_len.astype(np.int32)),
            ctx_len=jnp.asarray(ctx_len.astype(np.int32)),
            sampling_params=jnp.asarray(sampling_params.astype(np.float32)),
            chain_src=jnp.asarray(chain_src.astype(np.int32)[None, :]),
            # a device-resident (R, 1) token array passes through untouched
            # (jnp.asarray is a no-op on a committed jax.Array) — the chain
            # never forces a host round-trip
            chain_tokens=jnp.asarray(chain_tokens, dtype=jnp.int32),
        )
        return inputs, T

    def trace_program(self, params, cache, inputs, rng=None):
        """Trace + lower + compile WITHOUT executing (the static analyzer's
        entry point — see SubModelRunner.trace_program)."""
        with jax.set_mesh(self.mesh):
            traced = self._fn.trace(params, cache, inputs, rng)
            lowered = traced.lower()
            compiled = lowered.compile()
        return traced, lowered, compiled

    def __call__(self, params, cache, inputs, rng=None):
        with jax.set_mesh(self.mesh):
            out = self._fn(params, cache, inputs, rng)
        debug_log_step(self.tag, inputs, out)
        return out

    # ---- warmup ----------------------------------------------------------

    def example_inputs(self, bucket: int, width: Optional[int] = None):
        """A warmup/audit step at one total-token bucket: as many rows as
        fit claim one q-tile decode segment each (writes dropped via slot
        -1, reads off the reserved garbage block — the field-presence and
        shapes match real serving calls exactly, so the warmed program IS
        the served program)."""
        width = width if width is not None else self.kv_buckets[-1]
        R = self.num_rows
        tq = self.q_tile
        n_fit = min(R, bucket // tq)
        ids = np.zeros(bucket, np.int32)
        pos = np.full(bucket, -1, np.int32)
        sm = np.full(bucket, -1, np.int32)
        row_start = np.zeros(R, np.int32)
        row_len = np.zeros(R, np.int32)
        ctx_len = np.zeros(R, np.int32)
        for r in range(n_fit):
            row_start[r] = r * tq
            row_len[r] = 1
            ctx_len[r] = 1
            pos[r * tq] = 0
        bt = np.zeros((R, max(1, width // self.block_size)), np.int32)
        inputs, _ = self.prepare(
            ids, pos, sm, row_start, row_len, ctx_len, bt, width
        )
        return inputs

    def warmup(self, params, cache, rng=None):
        """Compile + execute every total-token bucket once at the LARGEST kv
        width (smaller widths compile lazily at first use, like the chunked-
        prefill q-ladder programs — model_runner.warmup docstring)."""
        with jax.set_mesh(self.mesh):
            for bucket in self.buckets:
                out = self._fn(params, cache, self.example_inputs(bucket), rng)
                out.tokens.block_until_ready()
                cache = out.cache
        return cache
