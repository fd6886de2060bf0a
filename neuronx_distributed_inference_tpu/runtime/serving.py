"""Continuous-batching serving session.

TPU-native re-design of the reference continuous-batching runtime behavior
(reference: is_continuous_batching config; seq_id-addressed KV lines with
batch padding + sorting in ModelWrapper._forward_with_pad,
model_wrapper.py:582-751; vLLM-style request lifecycle).

A :class:`ServingSession` owns the KV cache slot table:
- ``add_request`` assigns a free cache line (seq_id), prefills it (whole
  prompt, or only the uncached suffix under prefix caching, or nothing yet
  under chunked prefill), and queues the request for decoding.
- ``step`` advances the session: one batched PREFILL-CHUNK pass for requests
  with pending prompt tokens (chunked prefill), then one decode pass for all
  decoding requests.
- finished requests free their slot immediately — a new request can claim it
  on the next ``add_request`` (continuous batching).

Prefix caching (reference perform_prefix_prefill, attention_base.py:893 +
vLLM content addressing): cached prompt-prefix blocks are attached by content
hash and only the suffix runs through the model — a multi-token
PHASE_TOKEN_GENERATION pass whose per-token masks (masks.spec_token_gen_mask)
give exactly "attend prior KV + causal among new tokens".

Chunked prefill (reference modules/chunked_prefill/scheduler.py
GridTileScheduler + flash_pa_with_schedule): long prompts are processed in
fixed-size chunks through the SAME prior-KV pass, advancing up to
``max_num_seqs`` different requests per step. The chunk program is
``ops/kernel_mode.CHUNK_ROWS`` (8) rows wide whatever the slot count: its
rows are COMPACT and addressed by slot (block table, slot mapping and
``seq_ids`` are data), so a pass over n requests is ceil(n / 8) dispatches of
the one program and computes nothing for the slots that sit the pass out
(per pass real + padded == dispatches x 8 x q_bucket). Programs are keyed by
the 2-D (q_bucket, kv_bucket) shape — the TPU answer to the reference's 2-D
chunked-prefill buckets (autobucketing.py:101). Decode runs as its own
batched pass instead of being concatenated into the prefill tile schedule:
two async dispatches with static shapes beat one megakernel under XLA.
"""

from __future__ import annotations

import functools
import json
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from neuronx_distributed_inference_tpu.modules.autobucketing import (
    get_target_bucket,
    pow2_bucket,
)
from neuronx_distributed_inference_tpu.modules.sampling import prepare_sampling_params
from neuronx_distributed_inference_tpu.runtime.faults import (
    RETRYABLE_DISPATCH_ERRORS,
    WatchdogError,
    fill_kv_rows,
    fill_slot_state,
)
from neuronx_distributed_inference_tpu.telemetry.tracing import (
    NULL_SPAN,
    default_session,
)

# ---------------------------------------------------------------------------
# fault containment: request statuses, typed admission verdicts, retry policy
# (docs/SERVING.md "Failure containment")
# ---------------------------------------------------------------------------

#: request lifecycle statuses. ACTIVE = holds a slot; WAITING = preempted and
#: queued for re-admission (ahead of new arrivals); the rest are terminal.
STATUS_ACTIVE = "active"
STATUS_WAITING = "waiting"
STATUS_FINISHED = "finished"
STATUS_FAILED = "failed"
STATUS_REJECTED = "rejected"

#: finish reasons that mark a request FAILED rather than FINISHED
#: ("handoff" = a disaggregated KV hand-off delivered a corrupt/truncated
#: payload, or exhausted its bounded retry — one request, typed, contained)
FAILURE_REASONS = frozenset(
    {"non_finite", "dispatch_error", "deadline_exceeded", "preempted",
     "handoff"}
)

#: capped exponential backoff for transient dispatch retries:
#: base * 2**attempt, clamped to the cap (sleeps through the session's
#: injectable sleep so tests stay fast and deterministic)
DISPATCH_BACKOFF_BASE_S = 0.02
DISPATCH_BACKOFF_CAP_S = 0.5

#: ``session.rejected`` keeps the most recent terminal-REJECTED requests
#: (prompt included, for diagnostics) and evicts oldest-first past this cap:
#: rejection volume is attacker-controlled (malformed traffic), so the
#: record must not grow host memory without bound
REJECTED_HISTORY_MAX = 1024


@dataclass(frozen=True)
class AdmissionResult:
    """Typed verdict from :meth:`ServingSession.add_request`. Truthiness ==
    admitted, so existing ``assert sess.add_request(...)`` call sites keep
    working; ``reason`` carries the reject/drop cause (``no_slot`` /
    ``kv_blocks`` / ``backlog`` for capacity, or a validation reason like
    ``token_id_out_of_range`` — then the request is terminal REJECTED and
    queryable via ``session.rejected``)."""

    admitted: bool
    reason: Optional[str] = None

    def __bool__(self) -> bool:
        return self.admitted


ADMITTED = AdmissionResult(True)


@dataclass
class Request:
    req_id: str
    input_ids: np.ndarray  # (S,) effective prompt (re-admission folds
    # previously-generated tokens in; `absorbed` counts them)
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    slot: int = -1
    pos: int = 0  # next write position
    prefill_pos: int = 0  # prompt tokens already in the KV cache
    generated: List[int] = field(default_factory=list)
    finished: bool = False
    preempted: bool = False  # currently evicted (queued for re-admission)
    # --- fault containment ------------------------------------------------
    status: str = STATUS_ACTIVE
    fail_reason: Optional[str] = None  # set when status == failed/rejected
    deadline_s: Optional[float] = None  # wall-clock TTL from submission
    t_submit: float = 0.0  # session-clock submission time
    preemptions: int = 0  # pool-exhaustion evictions survived
    absorbed: int = 0  # generated tokens folded into input_ids (re-admission)
    epoch: int = 0  # bumped per eviction: stale in-flight rows are discarded
    # --- speculation (SpeculativeServingSession): the per-request
    # draft-acceptance-rate EWMA (_note_acceptance).
    accept_ewma: float = 1.0
    # --- a block-step model (runtime/block_step.py): the prompt tokens the
    # chunk program carries (its whole blocks; None = all of the prompt), the
    # block in progress, and per generated token the ordinal within its
    # block of the pass that revealed it (always recorded; None for a model
    # that generates one position after another). ``pos`` is the first
    # position of the block in progress; ``generated`` grows at a commit.
    prefill_end: Optional[int] = None
    block: Optional[object] = None
    revealed_at: Optional[List[int]] = None

    @property
    def prompt_len(self) -> int:
        return int(self.input_ids.shape[0])

    @property
    def prefill_target(self) -> int:
        return self.prompt_len if self.prefill_end is None else self.prefill_end

    @property
    def prefilling(self) -> bool:
        return not self.finished and self.prefill_pos < self.prefill_target

    @property
    def last_token(self) -> int:
        return self.generated[-1] if self.generated else int(self.input_ids[-1])


class ServingSession:
    #: class marker the router's tier validation reads: True when this
    #: session class supports add_prefilled_request (the disaggregated KV
    #: hand-off); the speculative session overrides it (the hand-off
    #: carries target KV only — the draft cache needs its own prefill)
    prefilled_admission = True

    def __init__(
        self,
        app,
        telemetry=None,
        fault_injector=None,
        clock: Optional[Callable[[], float]] = None,
        sleep_fn: Optional[Callable[[float], None]] = None,
    ):
        """``telemetry``: a :class:`~..telemetry.TelemetrySession` observing
        this session; defaults to the process-default session (inert unless
        ``telemetry.enable_default_session()`` ran). Recording is host-side
        bookkeeping riding the fetches the session already performs — the
        fetch-parity test pins that enabling it adds ZERO device round
        trips per step.

        ``fault_injector``: a :class:`~.faults.FaultInjector` whose armed
        faults fire at this session's host boundaries (tests only; an idle
        injector is byte-identical to none). ``clock``/``sleep_fn``: the
        wall-clock source for deadlines/backoff (default ``time.monotonic``
        / ``time.sleep``) — injectable so deadline and backoff policies pin
        deterministically."""
        self.app = app
        self.tel = telemetry if telemetry is not None else default_session()
        # the (program, q, kv) of the split step's dispatches while the
        # session records: what its stop() writes the device scope tables of
        self._programs_noted = set()
        self.tel.add_scope_source(self)
        tc = app.config.tpu_config
        # --- fault containment (docs/SERVING.md "Failure containment") ----
        self.faults = fault_injector
        self._clock = clock if clock is not None else time.monotonic
        self._sleep = sleep_fn if sleep_fn is not None else time.sleep
        self.admission_validation = bool(getattr(tc, "admission_validation", True))
        self.deadline_s = getattr(tc, "request_deadline_s", None)
        self.max_dispatch_retries = int(getattr(tc, "dispatch_max_retries", 2))
        self.watchdog_steps = int(getattr(tc, "watchdog_no_progress_steps", 0))
        self.rejected: Dict[str, Request] = {}  # terminal REJECTED requests
        self._readmit: deque = deque()  # preempted, aged ahead of arrivals
        self._step_index = 0
        self._no_progress = 0
        self._watchdog_fired = False
        self._prefilled_total = 0  # monotone: prompt tokens written
        self._committed_total = 0  # monotone: tokens committed to requests
        self._revealed_total = 0  # monotone: block passes fetched (a block-step model)
        self._terminal_total = 0  # monotone: requests reaching a terminal state
        self._last_dispatch_error: Optional[str] = None
        if not tc.is_continuous_batching:
            raise ValueError("ServingSession requires is_continuous_batching=True")
        self.num_slots = tc.kv_cache_batch_size or tc.max_batch_size
        self.slots: List[Optional[Request]] = [None] * self.num_slots
        self.requests: Dict[str, Request] = {}
        self.block_mode = tc.is_block_kv_layout
        self.prefix_caching = tc.is_prefix_caching
        self.chunked = tc.is_chunked_prefill
        cpc = tc.chunked_prefill_config
        self.chunk_size = cpc.kernel_q_tile_size if cpc else 128
        self.max_prefill_seqs = cpc.max_num_seqs if cpc else 8
        self.allocator = None
        #: whether block mode has a pool at all (a model none of whose
        #: layers pages has none: modules/block_kvcache.NoPoolAllocator)
        self.pooled = False
        self.block_bytes = 0
        self.latent_layers = 0
        self.sparse_layers = self.sparse_topk = 0
        if self.block_mode:
            from neuronx_distributed_inference_tpu.modules.block_kvcache import (
                BlockAllocator,
                NoPoolAllocator,
                PrefixCachingAllocator,
                chunk_write_blocks,
                kv_block_bytes,
                write_form,
            )
            from neuronx_distributed_inference_tpu.models.base import decode_kernel_runs
            from neuronx_distributed_inference_tpu.modules.kvcache import QuantizedKV

            if tc.pa_num_blocks is None:
                # pa_pool_bytes configs resolve the count at cache init
                raise RuntimeError(
                    "pa_num_blocks is unresolved — load the application "
                    "(init_kv_cache sizes the pool from pa_pool_bytes and "
                    "the cache dtype) before creating a ServingSession"
                )
            # the layers that page (a hybrid model's state-space layers keep
            # a per-slot state and cost no block): the builder's to say
            paged_layers = getattr(app, "paged_layers", app.spec.num_layers)
            self.pooled = paged_layers > 0
            if self.pooled:
                cls = PrefixCachingAllocator if self.prefix_caching else BlockAllocator
                self.allocator = cls(tc.pa_num_blocks, tc.pa_block_size)
            else:
                # no layer pages (models/brumby.py): no pool, admission by
                # free slots alone, no preemption for blocks
                self.allocator = NoPoolAllocator(tc.pa_block_size)
            # true per-block HBM cost in the CACHE dtype (NOT a hardcoded
            # bf16 itemsize): quantized caches admit ~2x the blocks for the
            # same pool budget, and this is what capacity reporting uses
            # what a token leaves in each paging layer: the builder's to say
            streams = app.builder.cache_streams()
            self.block_bytes = kv_block_bytes(
                paged_layers, tc.pa_block_size, dtype=tc.kv_dtype, streams=streams
            )
            # layers whose pool stream is a compressed latent (nxdi_latent_*)
            self.latent_layers = paged_layers if streams[0].name == "latent" else 0
            # layers that keep an indexer's key and attend its top-k
            # (nxdi_sparse_*): the builder's own to say
            if any(s.name == "index_key" for s in streams):
                self.sparse_layers, self.sparse_topk = paged_layers, app.builder.indexer_spec().topk
            # what the paged kernels attend for a row (host-known: it follows
            # from the pool's shape a head shard, as the kernels' does): the
            # decode kernel's walk and that of the chunk kernel the pool
            # serves (a latent pool's picks its own groups)
            from neuronx_distributed_inference_tpu.ops import (
                decode_attention,
                latent_attention,
                paged_flash_attention,
            )

            pool = app.kv_cache.k
            shape = dict(
                n_kv=pool.shape[2] // app.spec.attn.model_parallel,
                bs=pool.shape[3], head_dim=pool.shape[4], cache_dtype=pool.dtype,
            )
            self._kv_blocks_walked = functools.partial(
                decode_attention.kv_blocks_walked, **shape
            )
            chunk_kernel = latent_attention if self.latent_layers else paged_flash_attention
            self._chunk_kv_blocks_walked = functools.partial(
                chunk_kernel.kv_blocks_walked, **shape
            )
            if self.sparse_layers:
                # and what the indexers score of the index keys' stream for a
                # row (shape and dtype alone: the pool is donated every step)
                from neuronx_distributed_inference_tpu.ops import index_scores

                keys = app.kv_cache.extra[0]
                self._index_blocks_walked = functools.partial(
                    index_scores.index_blocks_walked,
                    index_cache=jax.ShapeDtypeStruct(keys.shape, keys.dtype),
                )
            # and what the paged KV write of a chunk pass moves, where it
            # moves whole blocks
            batch_sharded = app.spec.attention_dp * app.spec.data_parallel > 1
            self._chunk_write_blocks = functools.partial(
                chunk_write_blocks, block_size=pool.shape[3], head_dim=pool.shape[4],
                batch_sharded=batch_sharded,
            )
            # and the form a decode pass's write takes at a kv bucket, asked
            # as the step program asks it (models/base.paged_write_attend)
            # (shapes and flags only: the pool itself is donated every step)
            spec, k_shape, v_shape = app.spec, pool.shape, app.kv_cache.v.shape
            form_of = functools.partial(
                write_form, head_dim=pool.shape[4], heads=shape["n_kv"],
                quantised=isinstance(pool, QuantizedKV), batch_sharded=batch_sharded,
            )
            self._decode_write_form = lambda q_len, kv_width: form_of(
                q_len, kernel_runs=decode_kernel_runs(
                    spec, q_len, kv_width, kv_width, k_shape, v_shape
                ),
            )
        # async 1-ahead decode (reference modules/async_execution.py:190):
        # the decode step dispatched last step(), not yet fetched —
        # (device tokens (B, 1), [(req, pos_dispatched), ...])
        self._pending = None
        self.async_decode = bool(tc.async_mode)
        # ragged mixed-step dispatch (TpuConfig.serving_ragged): step() packs
        # admitted prefill chunks AND active decode rows into ONE dispatch of
        # the mixed_step program family — the CTE/TKG split collapses on the
        # serving path (ops/ragged_paged_attention.py)
        self.ragged = bool(getattr(tc, "serving_ragged", False))
        self.mixed_runner = None
        # the ragged step pipelines exactly when async_mode does (as the
        # split path's 1-ahead decode): mixed step k+1 chains decode rows on
        # step k's still-on-device tokens (device-side chained-id gather) and
        # step k's fetch starts non-blocking at dispatch — host bookkeeping
        # overlaps the device executing k+1. Tokens are consumed one step()
        # LATE, with the same epoch-guard/speculative-extra-step semantics as
        # the split path's 1-ahead decode.
        self.ragged_async = self.ragged and bool(tc.async_mode)
        # cached (R, 3) sampling params: constant for the session's fixed
        # slot count, hoisted out of the per-step dispatch closures
        self._sampling_cache: Optional[np.ndarray] = None
        # per-slot block-table row cache ((R, MB_max) matrix + per-slot block
        # counts), refreshed incrementally on alloc/free/preempt/quarantine —
        # the steady-state descriptor build reads it instead of walking the
        # allocator's python block lists every step
        self._bt_matrix: Optional[np.ndarray] = None
        self._bt_count: Optional[np.ndarray] = None
        # accumulated blocking-fetch wait inside the current _ragged_step
        # (host-frac telemetry: step wall minus this is pure host time)
        self._step_fetch_wait_s = 0.0
        # the span of the step that runs (``step()``), for what the split
        # step notes on it from inside: the rows and dispatches it decided
        self._step_span = NULL_SPAN
        # router-managed sessions carry their replica id (set by
        # ReplicaHandle) so step-timing/watchdog records land on the
        # replica's timeline track; standalone sessions stay None
        self._tel_replica: Optional[int] = None
        if self.ragged:
            self.mixed_runner = getattr(app, "mixed_step_model", None)
            if self.mixed_runner is None:
                raise ValueError(
                    "serving_ragged=True but the application carries no "
                    "mixed_step program family (build the app with the same "
                    "config that constructs this session)"
                )
            # the split-path 1-ahead machinery stays off: the ragged pipeline
            # has its own pending-step consume (`_consume_ragged`)
            self.async_decode = False
            if self.block_mode:
                mb_max = max(
                    1,
                    -(-max(app.token_generation_model.buckets[-1], tc.seq_len)
                      // tc.pa_block_size),
                )
                self._bt_matrix = np.zeros((self.num_slots, mb_max), np.int32)
                self._bt_count = np.zeros(self.num_slots, np.int64)
            # tp>1 meshes are first-class on the ragged path since ISSUE 17:
            # the mixed step shard_maps the Pallas kernel over the
            # head-parallel grid axis, so no warning/fallback here — see
            # docs/SERVING.md "Sharded meshes"
        # a model whose layers keep a constant-size per-slot state
        # (HybridBlockCache.state: state-space layers, a one-token carry):
        # the scrub of a slot covers it, and the family its KIND names
        # counts it (nxdi_ssm_*, nxdi_kda_*, nxdi_power_*, nxdi_latent_carry_*); a model with routed
        # experts that says so is counted by nxdi_moe_* (_count_pass)
        state = getattr(app.kv_cache, "state", None)
        self.slot_state = state is not None
        self.slot_state_kind = getattr(state, "KIND", None)
        self.slot_state_bytes = state.nbytes if self.slot_state else 0
        # delta-rule layers: whether a chunk pass of q positions a row runs
        # their recurrence as the chunk-scan kernel (what modules/kda.kda_mixer
        # asks of the same gate), over how many layers
        self.kda_layers = 0
        if self.slot_state_kind == "kda":
            from neuronx_distributed_inference_tpu.ops.kernel_mode import use_kda_chunk_scan

            kspec = app.builder.kda_spec()
            self.kda_layers = state.ssm.shape[0]
            self._kda_chunk_kernel = functools.partial(
                use_kda_chunk_scan, kspec.head_dim, chunk_size=kspec.chunk_size,
                shards=app.spec.attn.model_parallel,
            )
        # a stack of window and full attention layers: the window layers' ring
        # of blocks a slot (block_kvcache.WindowRing) beside the allocator's
        # pool over the full layers. A slot holds its ring from admission to
        # release; nothing of it is allocated, freed or scanned as it grows
        self.window_layers = self.full_layers = self.window = 0
        self.window_slot_bytes = 0
        if self.slot_state_kind == "window_ring":
            self.window_layers, self.full_layers = state.num_layers, app.paged_layers
            self.window, self._ring_blocks = app.builder.window, state.ring_blocks
            self.window_slot_bytes = state.slot_bytes
        self.expert_layers = app.builder.expert_layers()
        if self.expert_layers is not None:
            # the strategy a step program's expert layers were traced with,
            # from its shape (nxdi_moe_grouped_rows_total)
            from neuronx_distributed_inference_tpu.config import to_dtype
            from neuronx_distributed_inference_tpu.modules import moe

            moe_spec = app.builder.moe_spec()
            self._expert_path = functools.lru_cache(maxsize=None)(functools.partial(
                moe.expert_path, moe_spec,
                moe.stacked_experts(app.params["layers"]), dtype=to_dtype(tc.dtype),
            ))
            # a reader tells a held share from a whole by this gauge
            self.tel.moe_held(self.expert_layers[1], moe_spec.num_experts)
        # a looped stack (models/ouro.py): the layer passes a dispatch runs,
        # each with a stream of its own in the pool (nxdi_loop_*, nxdi_kv_streams)
        self.loop_layer_passes = (
            app.spec.loop_steps * app.spec.num_layers if app.spec.loop_steps > 1 else 0
        )
        # a model whose builder declares a block step generates block by
        # block: its rows' blocks, plans and commits (runtime/block_step.py)
        self.blocks = None
        if app.spec.block_step is not None:
            from neuronx_distributed_inference_tpu.runtime.block_step import BlockRows

            self.blocks = BlockRows(app.spec.block_step, app._pos_limit())
        self.tel.pool_gauges(0, self.kv_pool_bytes, self.kv_free_bytes)

    @property
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    @property
    def kv_pool_bytes(self) -> int:
        """Total block-pool HBM cost in the cache dtype (0 off block mode):
        the allocator's pool and, for a cache of two lifetimes, the window
        layers' rings."""
        if not self.block_mode:
            return 0
        return self.allocator.num_blocks * self.block_bytes + self.num_slots * self.window_slot_bytes

    @property
    def kv_free_bytes(self) -> int:
        """Free pool capacity in bytes — the admission headroom a scheduler
        sees; derived from the cache dtype, so a quantized cache reports ~2x
        the token capacity of bf16 for the same pool budget. Prefix-caching
        pools count evictable (refcount-0, LRU-reclaimable) blocks too —
        allocation evicts them on demand."""
        if not self.block_mode:
            return 0
        reclaimable = len(getattr(self.allocator, "evictable", ()))
        free = (len(self.allocator.free) + reclaimable) * self.block_bytes
        if self.window_slot_bytes:  # a free slot's rings in the window layers
            free += sum(r is None for r in self.slots) * self.window_slot_bytes
        return free

    def add_request(
        self,
        req_id: str,
        input_ids: np.ndarray,
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> AdmissionResult:
        """Admit one request into a free KV line. Returns a truthy
        :class:`AdmissionResult` when admitted; falsy with a ``reason``
        otherwise. Malformed requests (out-of-range token ids, empty
        prompt, over-long prompt, non-positive budget) get a terminal
        REJECTED verdict at the door instead of raising mid-batch —
        ``admission_validation=False`` restores the legacy raise-late
        behavior. ``deadline_s`` overrides the config-wide
        ``request_deadline_s`` wall-clock TTL for this request."""
        with self.tel.span("serving.admit", req_id=req_id) as sp:
            req = self._new_request(req_id, input_ids, max_new_tokens,
                                    eos_token_id, deadline_s)
            verdict = self._front_door(req)
            if verdict is None:
                verdict = self._admit(req, self.free_slots[0])
            sp.note(verdict="admitted" if verdict else verdict.reason)
        return verdict

    def _new_request(self, req_id, input_ids, max_new_tokens, eos_token_id,
                     deadline_s) -> Request:
        self.tel.request_submitted(req_id)
        return Request(
            req_id=req_id,
            input_ids=np.asarray(input_ids, np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id,
            deadline_s=deadline_s if deadline_s is not None else self.deadline_s,
            t_submit=self._clock(),
            revealed_at=None if self.blocks is None else [],
        )

    def _front_door(self, req: Request) -> Optional[AdmissionResult]:
        """The ONE admission gate both doors (:meth:`add_request` and
        :meth:`add_prefilled_request`) run: typed validation, re-admission
        aging (preempted requests re-enter AHEAD of new arrivals — a new
        request may not claim the capacity an older evicted one is waiting
        for, so repeated pool exhaustion cannot starve it), then capacity.
        Returns a falsy :class:`AdmissionResult` to bounce the request, or
        None when a free slot is available (``self.free_slots[0]``)."""
        if self.admission_validation:
            reason = self._validate_request(req)
            if reason is not None:
                return self._reject(req, reason)
        self._readmit_preempted()
        if self._readmit:
            self.tel.request_dropped(req.req_id, "backlog")
            return AdmissionResult(False, "backlog")
        if not self.free_slots:
            self.tel.request_dropped(req.req_id, "no_slot")
            return AdmissionResult(False, "no_slot")
        return None

    def admission_capacity(self) -> Optional[str]:
        """Capacity pre-check for callers that must pay for work BEFORE
        admitting (the router's disaggregated hand-off runs a whole prefill
        pass before ``add_prefilled_request``): the aging + capacity legs
        of :meth:`_front_door` without a request — ``"backlog"`` /
        ``"no_slot"`` / None (would admit). Advisory only: the admission
        call re-runs the full gate."""
        self._readmit_preempted()
        if self._readmit:
            return "backlog"
        if not self.free_slots:
            return "no_slot"
        return None

    def add_prefilled_request(
        self,
        req_id: str,
        input_ids: np.ndarray,
        kv_payload: Dict,
        first_token: int,
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> AdmissionResult:
        """Admit a request whose prompt was ALREADY context-encoded on a
        disaggregated prefill replica: validate the handed-over KV payload,
        scatter it into a free cache line (:func:`~.disaggregated.
        inject_request_kv`), and commit ``first_token`` as the request's
        first generated token — decode proceeds exactly as if this session
        had prefilled locally (byte-identical, pinned by
        tests/test_disagg_router.py).

        Containment contract (docs/SERVING.md "Disaggregated prefill
        tier"): a payload that fails validation (corrupt / truncated /
        wrong format — :func:`~.disaggregated.validate_handoff_payload`)
        admits and then TERMINALLY fails ONLY this request with typed
        ``FAILED(handoff)``, its destination cache line zero-scrubbed;
        co-batched rows are untouched. The truthy return then carries a
        request whose terminal verdict is already readable in
        ``session.requests`` — the router's terminal sync folds it like any
        other session-side failure. Capacity refusals (``backlog`` /
        ``no_slot``) and validation rejects behave exactly like
        :meth:`add_request`."""
        from neuronx_distributed_inference_tpu.runtime.disaggregated import (
            inject_request_kv,
            validate_handoff_payload,
        )

        if self.block_mode:
            raise ValueError(
                "add_prefilled_request scatters whole contiguous cache "
                "lines: the paged cache is not supported (config validation "
                "forbids router_prefill_replicas with is_block_kv_layout)"
            )
        req = self._new_request(req_id, input_ids, max_new_tokens,
                                eos_token_id, deadline_s)
        bounce = self._front_door(req)
        if bounce is not None:
            return bounce
        slot = self.free_slots[0]
        req.slot = slot
        req.status = STATUS_ACTIVE
        self.slots[slot] = req
        self.requests[req.req_id] = req
        self.tel.request_admitted(req.req_id)
        bad = validate_handoff_payload(self.app, kv_payload, 1, req.prompt_len)
        if bad is not None:
            # ONE request dies, typed; the destination line (never written —
            # scrubbed anyway, it is about to recycle) cannot leak payload
            # garbage to a later occupant; co-batched rows byte-identical.
            # The validator's typed cause (handoff_corrupt / _truncated /
            # _format / _malformed / _shape) labels the failure counter —
            # the request record keeps the FAILURE_REASONS verdict "handoff"
            self.tel.handoff_failure(req.req_id, bad)
            self._finish(req, "handoff", scrub=True)
            return ADMITTED
        inject_request_kv(self.app, np.array([slot], np.int32), kv_payload)
        req.prefill_pos = req.prompt_len
        self._note_prefill(req, req.prompt_len)
        self.tel.step("prefill")
        self.tel.pool_gauges(
            len(self.active), self.kv_pool_bytes, self.kv_free_bytes
        )
        self._finish_prefill(req, first_token)
        return ADMITTED

    def _validate_request(self, req: Request) -> Optional[str]:
        """Typed admission checks; returns a reject reason or None. Every
        reason here is a malformed INPUT the model could only answer with
        garbage (or a mid-batch exception) — capacity refusals stay on the
        drop path and are retryable by the caller."""
        if req.prompt_len == 0:
            return "empty_prompt"
        if req.max_new_tokens < 1:
            return "invalid_max_new_tokens"
        ids = req.input_ids
        vocab = int(self.app.config.vocab_size)
        if int(ids.min()) < 0 or int(ids.max()) >= vocab:
            # an out-of-vocab id gathers garbage embeddings (the
            # ROADMAP-named NaN-row source) — refuse it at the door
            return "token_id_out_of_range"
        if req.prompt_len > self._max_admissible_prompt():
            return "prompt_too_long"
        return None

    def _max_admissible_prompt(self) -> int:
        """Longest prompt this session can admit without raising mid-batch:
        at least one position must remain for the first generated token, and
        a paged cache WITHOUT chunked prefill runs admission through a
        single context program (runtime paths mirror _full_prefill)."""
        limit = self.app._pos_limit() - 1
        if self.block_mode and not self.chunked:
            ring_w = self.app.spec.bounded_window or self.app.spec.ring_window
            limit = min(limit, self.app.context_encoding_model.buckets[-1])
            if ring_w:
                limit = min(limit, ring_w)
        return limit

    def _reject(self, req: Request, reason: str) -> AdmissionResult:
        """Terminal REJECTED: recorded (queryable via ``session.rejected``,
        bounded oldest-evicted at REJECTED_HISTORY_MAX) but never admitted —
        no slot, no dispatch, no effect on co-batched requests."""
        req.finished = True
        req.status = STATUS_REJECTED
        req.fail_reason = reason
        self.rejected[req.req_id] = req
        while len(self.rejected) > REJECTED_HISTORY_MAX:
            self.rejected.pop(next(iter(self.rejected)))
        self.tel.request_rejected(req.req_id, reason)
        return AdmissionResult(False, reason)

    def _admit(self, req: Request, slot: int, fresh: bool = True) -> AdmissionResult:
        """Bind ``req`` to ``slot`` and run its admission prefill.
        ``fresh=False`` is the re-admission path: a capacity failure
        re-queues the request instead of dropping it."""
        req.slot = slot
        req.status = STATUS_ACTIVE
        req.preempted = False
        req.prefill_pos = 0
        req.pos = 0
        if self.blocks is not None:
            req.prefill_end, req.block = self.blocks.prefill_end(req.prompt_len), None
        if self.prefix_caching:
            req.prefill_pos = self.allocator.match_prefix(slot, req.input_ids)
            req.pos = req.prefill_pos
            self._bt_sync(slot)
        self.slots[slot] = req
        self.requests[req.req_id] = req
        self.tel.request_admitted(req.req_id, cached_prefix_tokens=req.prefill_pos)

        if self.chunked:
            # prompt runs in chunks inside step(); nothing dispatched yet
            return ADMITTED
        if req.prefill_pos > 0:
            # prefix hit: only the uncached suffix runs (prior-KV prefill)
            ok = self._prefill_chunks([req], req.prompt_len - req.prefill_pos)
        else:
            ok = self._full_prefill(req)
        if ok:
            return ADMITTED
        # out of KV blocks at admission-time prefill
        self._release_slot(req)
        if fresh:
            self.requests.pop(req.req_id, None)
            self.tel.request_dropped(req.req_id, "kv_blocks")
        return AdmissionResult(False, "kv_blocks")

    # ---- fault containment: release/scrub, preempt/re-admit, deadlines, ---
    # ---- watchdog, bounded dispatch retry ---------------------------------

    def _release_slot(self, req: Request, scrub: bool = False):
        """Free a request's KV line/blocks and slot. ``scrub=True`` zeroes
        the released KV on device FIRST (quarantine path): a poisoned row's
        NaNs must not survive into the free pool, where a later request
        would gather them as masked-but-non-finite stale positions
        (0 * NaN = NaN — the same coupling the garbage-block read scrub
        closes for block 0)."""
        if req.slot < 0:
            return
        if self.block_mode:
            if scrub:
                # allocator-mediated: with prefix caching, blocks a live
                # sharer still references must NOT be zeroed (their content
                # is a healthy prefill's), and the victim's registered
                # blocks must leave the match index before they recycle
                blocks = self.allocator.quarantine_seq(req.slot)
                if blocks:
                    self.app.kv_cache = fill_kv_rows(self.app.kv_cache, blocks, 0.0)
                if self.slot_state:
                    # the slot's recurrent state too: the next occupant
                    # starts from zero anyway (position-0 rule), but a
                    # non-finite state must not sit in a free slot
                    self.app.kv_cache = fill_slot_state(self.app.kv_cache, [req.slot], 0.0)
            else:
                self.allocator.free_seq(req.slot)
            self._bt_sync(req.slot)
        elif scrub:
            self.app.kv_cache = fill_kv_rows(
                self.app.kv_cache, [self._cache_line_of_slot(req.slot)], 0.0
            )
        self.slots[req.slot] = None
        req.slot = -1
        if self.window_layers and self.tel.enabled:
            self._window_gauges()  # the slot's rings are free again

    def _cache_line_of_slot(self, slot: int) -> int:
        """Contiguous-cache line for a serving slot (the attention-DP layout
        interleaves one garbage line per dp shard; kvcache.init_cache)."""
        dp = int(getattr(self.app.config.tpu_config, "attention_dp_degree", 1) or 1)
        if dp <= 1:
            return slot
        sr = self.num_slots // dp
        return (slot // sr) * (sr + 1) + slot % sr

    def _garbage_lines(self) -> List[int]:
        """Contiguous-cache garbage line indices (one per dp shard)."""
        dp = int(getattr(self.app.config.tpu_config, "attention_dp_degree", 1) or 1)
        if dp <= 1:
            return [self.num_slots]
        sr = self.num_slots // dp
        return [shard * (sr + 1) + sr for shard in range(dp)]

    def _alloc(self, slot: int, num_tokens: int):
        """Allocator gateway for the serving step paths: the fault injector
        forces pool exhaustion here without shrinking the real pool."""
        if self.faults is not None and self.faults.pool_exhausted(self):
            raise RuntimeError("out of KV blocks (injected fault)")
        blocks = self.allocator.alloc_seq(slot, num_tokens)
        self._bt_sync(slot)
        return blocks

    def _bt_sync(self, slot: int):
        """Refresh the cached block-table row for ``slot`` against the
        allocator (no-op unless the block list changed — the steady-state
        decode step allocates nothing and pays an O(1) length compare).
        Called at every block-list mutation point: alloc, free/preempt/
        quarantine release, and prefix-cache attach."""
        if self._bt_matrix is None:
            return
        blocks = self.allocator.seq_blocks.get(slot)
        n = len(blocks) if blocks else 0
        if n == int(self._bt_count[slot]):
            return
        row = self._bt_matrix[slot]
        row[:] = 0
        if n:
            m = min(n, row.shape[0])
            row[:m] = blocks[:m]
        self._bt_count[slot] = n

    def _session_sampling_params(self) -> np.ndarray:
        """prepare_sampling_params(R) is constant for the session's fixed
        slot count: built once and reused by every dispatch closure
        (rebuilt only if the slot count ever changes)."""
        sp = self._sampling_cache
        if sp is None or sp.shape[0] != self.num_slots:
            sp = prepare_sampling_params(self.num_slots)
            self._sampling_cache = sp
        return sp

    def _preempt(self, req: Request):
        """NON-terminal pool-exhaustion eviction: roll the request back to
        its committed host state (any in-flight device step is discarded —
        greedy decode regenerates the identical token after re-admission),
        free its slot/blocks, and queue it for re-admission AHEAD of new
        arrivals (aging: repeated exhaustion cannot starve it forever)."""
        if req.finished or req.preempted:
            return
        req.preempted = True
        req.preemptions += 1
        req.epoch += 1  # stale in-flight rows are dropped on consume
        req.status = STATUS_WAITING
        self._release_slot(req)
        self._readmit.append(req)  # FIFO among evicted: oldest first
        self.tel.request_preempted(req.req_id)

    def _readmit_preempted(self) -> int:
        """Re-admit evicted requests (oldest first) into free capacity.
        The committed tokens fold into the prefill prompt, so the request
        resumes exactly where it rolled back — byte-identical to a run that
        was never preempted. Stops at the first request that still cannot
        fit (FIFO order is the aging guarantee)."""
        n = 0
        while self._readmit and self.free_slots:
            req = self._readmit[0]
            new = req.generated[req.absorbed:]
            if new:
                req.input_ids = np.concatenate(
                    [req.input_ids, np.asarray(new, np.int32)]
                )
                req.absorbed += len(new)
            never_fits = req.prompt_len > self._max_admissible_prompt() or (
                # re-prefilling prompt+committed can NEVER fit the whole
                # pool: retrying would spin (each cycle preempts again)
                self.pooled
                and -(-req.prompt_len // self.allocator.block_size)
                > self.allocator.num_blocks
            )
            if never_fits or len(req.generated) >= req.max_new_tokens:
                # can never re-admit (or nothing left to generate): terminal
                self._readmit.popleft()
                self._finish(req, "preempted" if len(req.generated) <
                             req.max_new_tokens else None)
                continue
            self._readmit.popleft()
            if not self._admit(req, self.free_slots[0], fresh=False):
                req.preempted = True
                req.status = STATUS_WAITING
                if not self.active:
                    # nothing live will ever free more capacity: terminal
                    self._finish(req, "preempted")
                    continue
                # pool still exhausted: back to the FRONT, stop trying
                self._readmit.appendleft(req)
                break
            n += 1
        return n

    def _expire_deadlines(self):
        """Drop every live request past its wall-clock TTL (terminal
        ``deadline_exceeded``); checked at step boundaries, so the observed
        overrun is bounded by step latency."""
        now = self._clock()
        live = [r for r in self.slots if r is not None] + list(self._readmit)
        for req in live:
            if req.finished or req.deadline_s is None:
                continue
            overrun = now - (req.t_submit + req.deadline_s)
            if overrun <= 0:
                continue
            try:
                self._readmit.remove(req)
            except ValueError:
                pass
            self.tel.deadline_exceeded(req.req_id, overrun)
            self._finish(req, "deadline_exceeded")

    def _progress_signature(self):
        """Monotone progress markers: admissions (new requests), committed
        tokens, terminal transitions, and prefilled prompt tokens. A step
        that changes none of these made zero forward progress. All four are
        O(1) session counters — the signature must not walk ``requests``
        (which grows for the life of the session) on the per-step hot
        path."""
        return (
            len(self.requests),
            self._committed_total,
            self._terminal_total,
            self._prefilled_total,
            self._revealed_total,
        )

    def _watchdog_tick(self, progressed: bool):
        """No-forward-progress watchdog: after ``watchdog_no_progress_steps``
        consecutive zero-progress steps with live work, preempt the largest
        request (frees the most pool — the likely deadlock hold-and-wait);
        if a FULL second window then passes with still zero progress, fail
        loudly with a diagnostic snapshot instead of spinning forever."""
        if self.watchdog_steps <= 0:
            return
        if progressed or not (self.active or self._readmit):
            self._no_progress = 0
            self._watchdog_fired = False
            return
        self._no_progress += 1
        if self._no_progress < self.watchdog_steps:
            return
        window = self._no_progress
        self._no_progress = 0
        victim = None
        if not self._watchdog_fired:
            victim = max(
                self.active,
                key=lambda r: max(r.pos, r.prefill_pos),
                default=None,
            )
        if victim is not None:
            self._watchdog_fired = True
            self.tel.watchdog_preempted(victim.req_id)
            self._preempt(victim)
            return
        self.tel.watchdog_tripped(window, replica=self._tel_replica)
        snap = self.diagnostic_snapshot()
        raise WatchdogError(
            f"serving session made no forward progress for {window} "
            f"consecutive steps (zero committed tokens, zero prefill "
            f"advance, zero admissions) after a watchdog preemption already "
            f"fired — failing loudly instead of spinning. Diagnostic "
            f"snapshot: {json.dumps(snap, default=str)}",
            snapshot=snap,
        )

    def diagnostic_snapshot(self) -> dict:
        """Host-state dump for the watchdog's loud failure (and operators):
        who holds what, who waits, and what the pool looks like."""
        return {
            "step_index": self._step_index,
            "watchdog_window": self.watchdog_steps,
            "active": [
                {
                    "req_id": r.req_id,
                    "slot": r.slot,
                    "status": r.status,
                    "pos": r.pos,
                    "prefill_pos": r.prefill_pos,
                    "generated": len(r.generated),
                    "preemptions": r.preemptions,
                }
                for r in self.active
            ],
            "waiting": [r.req_id for r in self._readmit],
            "free_slots": self.free_slots,
            "kv_pool_bytes": self.kv_pool_bytes,
            "kv_free_bytes": self.kv_free_bytes,
            "free_blocks": len(self.allocator.free) if self.block_mode else None,
            "last_dispatch_error": self._last_dispatch_error,
        }

    def _guarded_dispatch(self, label: str, reqs: List[Request], fn, on_give_up=None):
        """Run one device dispatch with bounded-backoff retry. Transient
        errors (RETRYABLE_DISPATCH_ERRORS) retry up to
        ``dispatch_max_retries`` times with capped exponential backoff;
        exhaustion terminally FAILs only the in-flight ``reqs``
        (dispatch_error) and returns None — the session, and every other
        request, keeps running. Anything non-transient propagates: that is
        a programming error, not weather. ``on_give_up`` runs BEFORE the
        in-flight rows are failed — the pipelined ragged path uses it to
        consume the already-executed previous step, so a request failing at
        step k+1 still keeps its step-k token (the order the synchronous
        path commits in)."""
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.on_dispatch(self, label)
                return fn()
            except RETRYABLE_DISPATCH_ERRORS as e:
                attempt += 1
                if attempt > self.max_dispatch_retries:
                    self._last_dispatch_error = repr(e)
                    if self.faults is not None:
                        self.faults.dispatch_gave_up(self)
                    if on_give_up is not None:
                        on_give_up()
                    for r in reqs:
                        if not r.finished:
                            self._finish(r, "dispatch_error")
                    return None
                self.tel.dispatch_retry(label)
                self._sleep(
                    min(
                        DISPATCH_BACKOFF_CAP_S,
                        DISPATCH_BACKOFF_BASE_S * (2 ** (attempt - 1)),
                    )
                )

    def _quarantine(self, req: Request):
        """FAILED(non_finite): the host observed the non-finite sentinel on
        this row's fetched tokens. Only this row dies — its KV is zero-
        scrubbed on release so the recycled blocks/line cannot poison a
        later request, and co-batched rows stay byte-identical (pinned)."""
        if req.finished:
            return
        self.tel.row_quarantined(req.req_id)
        self._finish(req, "non_finite", scrub=True)

    def _note_prefill(self, req: Request, n: int):
        self._prefilled_total += n
        self.tel.prefill_dispatch(req.req_id, n)

    @staticmethod
    def _start_fetch(tokens) -> None:
        """Start the device->host token copy non-blocking AT DISPATCH (the
        PR-8 decode-side pattern, extended to the legacy split path's
        prefill fetches): by the time the consume below reads the array,
        the transfer has overlapped the telemetry/pool bookkeeping in
        between instead of hard-blocking on a cold fetch. Not a host sync —
        the fetch-count parity pin (tests/test_router.py) proves the
        consumed-fetch census is unchanged with this call present."""
        start = getattr(tokens, "copy_to_host_async", None)
        if start is not None:
            start()

    def _commit_tokens(self, req: Request, n: int):
        """Every decode-token commit routes through here so the watchdog's
        progress counter cannot drift from what ``generated`` received."""
        self._committed_total += n
        self.tel.request_tokens(req.req_id, n)

    def _full_prefill(self, req: Request) -> bool:
        """Whole-prompt context encoding (flash-kernel eligible CTE path).

        Prompts longer than a ring-bounded window (or the largest CTE
        program) run through the app's windowed prefill instead — chunk 0 via
        the CTE program, later chunks as multi-token prior-KV passes
        (application._windowed_prefill; reference windowed context encoding,
        model_base.py:957-1010). Other live rows are untouched: padded rows
        carry seq_id -1 (garbage line) and sentinel positions drop their
        writes.
        """
        S = req.prompt_len
        W = self.app.spec.bounded_window
        ring_w = W or self.app.spec.ring_window
        cte_max = self.app.context_encoding_model.buckets[-1]
        if ((ring_w and S > ring_w) or S > cte_max) and self.block_mode:
            # the contiguous-cache windowed prefill cannot write a paged
            # cache (no slot mapping, no block reservation)
            raise ValueError(
                f"prompt of {S} tokens exceeds the largest context program "
                f"({cte_max}) on a paged cache: enable chunked prefill "
                "(is_chunked_prefill) to admit long prompts"
            )
        if (ring_w and S > ring_w) or S > cte_max:
            self.app.validate_prefill_length(S)
            return self._windowed_admit(req)
        ids = req.input_ids[None, :]
        mask = np.ones((1, S), np.int32)
        pos = np.arange(S, dtype=np.int32)[None, :]
        seq_ids = np.array([req.slot], np.int32)
        slot_mapping = None
        if self.block_mode:
            try:
                self._alloc(req.slot, S)
            except RuntimeError:
                return False  # out of KV blocks
            slot_mapping = self.allocator.slot_mapping(req.slot, np.arange(S))[None, :]
        cte = self.app.context_encoding_model

        def dispatch():
            with self.tel.span("serving.prefill", req_id=req.req_id, tokens=S):
                inputs, _ = cte.prepare(
                    ids, mask, pos, seq_ids, slot_mapping=slot_mapping
                )
                return cte(self.app.params, self.app.kv_cache, inputs, None)

        out = self._guarded_dispatch("prefill", [req], dispatch)
        if out is None:
            return True  # terminal FAILED(dispatch_error); slot released
        self._start_fetch(out.tokens)
        self.app.kv_cache = out.cache
        self.tel.step("prefill")
        self.tel.bucket_dispatch(cte.tag, cte.last_bucket)
        self._note_prefill(req, S)
        self.tel.pool_gauges(
            len(self.active), self.kv_pool_bytes, self.kv_free_bytes
        )
        first = int(np.asarray(out.tokens)[0, -1])
        req.prefill_pos = S
        self._finish_prefill(req, first)
        return True

    def _windowed_admit(self, req: Request) -> bool:
        """Admit a prompt longer than one context program (or a ring window)
        in windows, like application._windowed_prefill — but SLOT-ALIGNED:
        the multi-token TKG chunks run at the session's full batch with the
        request in row == slot, because TKG programs read cache line b for
        row b (the sorted-batch convention; a B=1 pass with seq_ids=[slot]
        would read line 0 while writing line slot).

        Deliberately mirrors application._windowed_prefill's chunk shape
        rules (C clipped to the ring window; bounded-or-bucket width carrier;
        sentinel positions for padding) — change them together."""
        from neuronx_distributed_inference_tpu.modules.kvcache import (
            PAD_POSITION_SENTINEL,
        )

        app = self.app
        S = req.prompt_len
        s = req.slot
        C = app.context_encoding_model.buckets[-1]
        ring_w = app.spec.bounded_window or app.spec.ring_window
        if ring_w:
            C = min(C, ring_w)  # ring slots must stay distinct within a chunk

        # chunk 0 through the CTE program (writes go to line `slot` via
        # seq_ids; CTE reads nothing from the cache, so B=1 is fine)
        n0 = min(C, S)
        ids0 = req.input_ids[None, :n0]
        pos0 = np.arange(n0, dtype=np.int32)[None, :]

        def dispatch_cte():
            with self.tel.span(
                "serving.prefill_windowed", req_id=req.req_id, tokens=n0
            ):
                inputs, _ = app.context_encoding_model.prepare(
                    ids0, np.ones((1, n0), np.int32), pos0,
                    np.array([s], np.int32), prepare_sampling_params(1),
                )
                return app.context_encoding_model(
                    app.params, app.kv_cache, inputs, None
                )

        out = self._guarded_dispatch("prefill_windowed", [req], dispatch_cte)
        if out is None:
            return True  # terminal FAILED(dispatch_error); slot released
        app.kv_cache = out.cache
        self.tel.step("prefill")
        self.tel.bucket_dispatch(
            app.context_encoding_model.tag, app.context_encoding_model.last_bucket
        )
        self._note_prefill(req, n0)
        # no fetch here: this path only triggers for S > C, so the chunk loop
        # below always runs and the final chunk's token is the one emitted

        B = self.num_slots
        start = n0
        n = 0
        while start < S:
            end = min(start + C, S)
            n = end - start
            ids = np.zeros((B, C), np.int32)
            ids[s, :n] = req.input_ids[start:end]
            pos = np.full((B, C), PAD_POSITION_SENTINEL, np.int32)
            pos[s, :n] = np.arange(start, end, dtype=np.int32)
            # width carrier: bounded ring caches hold W slots; interleaved
            # models keep FULL-length global layers, so the carrier is the
            # full decode bucket (ring layers bound themselves per layer)
            width = app.spec.bounded_window or get_target_bucket(
                app.token_generation_model.buckets, end
            )
            mask = np.ones((B, width), np.int32)
            seq_ids = np.full((B,), -1, np.int32)
            seq_ids[s] = s

            def dispatch_chunk(ids=ids, mask=mask, pos=pos, seq_ids=seq_ids, n=n):
                with self.tel.span(
                    "serving.prefill_windowed", req_id=req.req_id, tokens=n
                ):
                    inputs, _ = app.token_generation_model.prepare(
                        ids, mask, pos, seq_ids, prepare_sampling_params(B)
                    )
                    return app.token_generation_model(
                        app.params, app.kv_cache, inputs, None
                    )

            out = self._guarded_dispatch("prefill_windowed", [req], dispatch_chunk)
            if out is None:
                return True  # terminal FAILED(dispatch_error); slot released
            if end >= S:
                # final chunk: its token is the ONE fetched below — start
                # the copy now so it overlaps the chunk's bookkeeping
                self._start_fetch(out.tokens)
            app.kv_cache = out.cache
            self.tel.step("prefill")
            self.tel.bucket_dispatch(
                app.token_generation_model.tag, app.token_generation_model.last_bucket
            )
            self._note_prefill(req, n)
            start = end
        # ONE host sync for the whole admission: only the last chunk's token
        # at the final prompt position matters
        first = int(np.asarray(jax.device_get(out.tokens))[s, n - 1])
        req.prefill_pos = S
        self._finish_prefill(req, first)
        return True

    def _finish_prefill(self, req: Request, first_token: int):
        if first_token < 0:
            # the non-finite sentinel (models/base.NON_FINITE_TOKEN): this
            # row's logits were NaN/Inf at its final prompt position —
            # quarantine it instead of committing a garbage token
            req.pos = req.prompt_len
            self._quarantine(req)
            return
        req.pos = req.prompt_len
        req.generated.append(first_token)
        self._committed_total += 1
        self.tel.request_first_token(req.req_id)
        if self.prefix_caching:
            self.allocator.commit_seq(req.slot, req.input_ids)
        if (req.eos_token_id is not None and first_token == req.eos_token_id) or (
            len(req.generated) >= req.max_new_tokens
        ):
            self._finish(req)

    def _prefill_chunks(self, reqs: List[Request], chunk_size: int) -> bool:
        """One chunk pass at admission time (a prefix hit's uncached suffix):
        dispatched and committed back to back. False when a request cannot
        get KV blocks: nothing was dispatched and the caller drops it."""
        flights = self._dispatch_chunks(reqs, chunk_size)
        if flights is None:
            return False
        self._commit_chunks(flights, {})
        return True

    def _dispatch_chunks(
        self, reqs: List[Request], chunk_size: int, preempt: bool = False
    ) -> Optional[list]:
        """The dispatching half of one batched prior-KV prefill pass: each
        request advances by up to ``chunk_size`` prompt tokens (2-D
        (q_bucket, kv_bucket) program, ``chunk_rows`` wide: one dispatch per
        group of that many requests). Nothing here waits for the device.
        Returns the pass's flights for :meth:`_commit_chunks`, one
        ``(rows, unfetched tokens)`` a dispatch with ``rows`` =
        [(req, tokens fed, req.epoch)].

        A request that cannot get KV blocks is preempted when ``preempt``
        (step()-driven chunked serving — never stalls the session); otherwise
        the pass returns None and the caller drops the request
        (admission-time prefill)."""
        tel = self.tel
        with tel.span("serving.schedule"):
            rows = []
            for req in reqs:
                n = min(chunk_size, req.prefill_target - req.prefill_pos)
                if n <= 0:
                    continue
                try:
                    self._alloc(req.slot, req.prefill_pos + n)
                except RuntimeError:
                    if not preempt:
                        return None
                    self._preempt(req)
                    continue
                rows.append((req, n))
            if not rows:
                return []

            # the chunk program is R rows wide and its rows are addressed by
            # slot (block table, slot mapping, seq_ids): the pass packs the
            # requests that prefill into rows 0..n-1 in groups of R, one
            # dispatch a group
            tkg = self.app.token_generation_model
            R = tkg.chunk_rows
            groups = [rows[i : i + R] for i in range(0, len(rows), R)]
            qb = pow2_bucket(max(n for _, n in rows))
            bs = self.allocator.block_size
            max_pos = max(r.prefill_pos + n for r, n in rows)
            width = get_target_bucket(tkg.buckets, max_pos)
            mb = width // bs
            real = sum(n for _, n in rows)
            sampling = self._session_sampling_params()[:R]
            # the chunk program projects each row's last fed position and
            # returns tokens (R, 1); an application that returns logits keeps
            # the head at every position beside it (models/base.model_logits)
            head_q = qb if self.app.spec.output_logits else 1
        with tel.span(
            "serving.prefill_chunk", rows=len(rows), real_tokens=real,
            padded_tokens=len(groups) * R * qb - real, q_bucket=qb, kv_bucket=width,
            dispatches=len(groups), head_positions=len(groups) * R * head_q,
        ):
            # a failed dispatch fails its own rows and the other groups go on
            flights = []
            for group in groups:
                with tel.span("serving.prefill_chunk.prepare"):
                    ids = np.zeros((R, qb), np.int32)
                    positions = np.zeros((R, qb), np.int32)
                    mask = np.zeros((R, width), np.int32)
                    slot_mapping = np.full((R, qb), -1, np.int32)
                    block_table = np.zeros((R, mb), np.int32)
                    seq_ids = np.full((R,), -1, np.int32)
                    for row, (req, n) in enumerate(group):
                        s = req.slot
                        start = req.prefill_pos
                        ids[row, :n] = req.input_ids[start : start + n]
                        # padded tail positions continue so their (garbage)
                        # writes/reads stay in the masked region
                        positions[row] = start + np.arange(qb, dtype=np.int32)
                        mask[row, : start + n] = 1
                        slot_mapping[row, :n] = self.allocator.slot_mapping(
                            s, np.arange(start, start + n)
                        )
                        block_table[row] = self.allocator.block_table(s, mb)
                        seq_ids[row] = s
                    arrs, _ = tkg.prepare_host(
                        ids, mask, positions, seq_ids, sampling,
                        slot_mapping=slot_mapping, block_table=block_table,
                    )
                    with tel.span("serving.h2d", **self._h2d_fields(arrs)):
                        inputs = tkg.to_device(arrs)

                fields = self._program_fields("chunk", qb, tkg.last_bucket)

                def dispatch(inputs=inputs, fields=fields):
                    with tel.span("serving.prefill_chunk.dispatch", **fields):
                        return tkg(self.app.params, self.app.kv_cache, inputs, None)

                out = self._guarded_dispatch(
                    "prefill_chunk", [r for r, _ in group], dispatch
                )
                if out is None:
                    continue  # this group's rows terminally FAILED(dispatch_error)
                with tel.span("serving.prefill_chunk.fetch_start"):
                    self._start_fetch(out.tokens)
                self.app.kv_cache = out.cache
                flights.append(([(r, n, r.epoch) for r, n in group], out.tokens))
            if not flights:
                return []
            # what the pass records of itself, after its last dispatch
            # returned: the telemetry's own cost of a chunk pass, by name
            with tel.span("serving.account"):
                ran = [(r, n) for group, _ in flights for r, n, _ in group]
                ran_real = sum(n for _, n in ran)
                tel.step("prefill")
                for _ in flights:
                    tel.bucket_dispatch(tkg.tag, tkg.last_bucket)
                # what the program really ran over: R rows at the q bucket a
                # dispatch, whatever the number of rows prefilling
                tel.prefill_pass(
                    ran_real, len(flights) * R * qb - ran_real, dispatches=len(flights),
                    rows=(len(ran), len(flights) * R - len(ran)),
                )
                kv_blocks = None
                if tel.enabled and self.pooled:
                    # the blocks a row's causal context holds once this chunk is in
                    live = [-(-(r.prefill_pos + n) // bs) for r, n in ran]
                    kv_blocks = (sum(live), self._chunk_kv_blocks_walked(live, mb))
                    tel.kv_write_blocks(
                        *self._chunk_write_blocks([(r.prefill_pos, n) for r, n in ran], qb)
                    )
                self._count_pass(
                    "chunk", (R, qb), len(ran), ran_real, len(flights),
                    resets=sum(1 for r, _ in ran if r.prefill_pos == 0),
                    kv_blocks=kv_blocks, kv_width=width,
                    spans=(
                        [(r.prefill_pos, n) for r, n in ran]
                        if self.sparse_layers or self.window_layers else ()
                    ),
                )
                for req, n in ran:
                    self._note_prefill(req, n)
                tel.pool_gauges(
                    len(self.active), self.kv_pool_bytes, self.kv_free_bytes
                )
            if self.blocks is not None:
                # a block-step row generates nothing at its prompt's end:
                # what is left of the prompt opens its first block, and the
                # host knows that without the pass's token. So the row opens
                # here and takes its first decode pass in THIS step, behind
                # the chunk pass it reads; the commit only looks for the
                # non-finite sentinel
                for req, n in ran:
                    req.prefill_pos += n
                    if not req.prefilling:
                        req.pos = req.prefill_pos
        return flights

    def _commit_chunks(self, flights: list, results: Dict[str, int]) -> None:
        """The committing half of a chunk pass: waits for the tokens of its
        ``flights`` (:meth:`_dispatch_chunks`), advances the prompts and
        commits a finished prompt's first token, into ``results`` too. A row
        evicted or failed since its dispatch (stale epoch: a decode pass
        dispatched in between could not get its blocks) is skipped; it
        prefills again from position 0."""
        if not flights:
            return
        tel = self.tel
        with tel.span("serving.prefill_chunk.fetch_wait") as wait:
            fetched = [(group, np.asarray(tokens)) for group, tokens in flights]
        self._step_fetch_wait_s += wait.dur_s
        with tel.span("serving.prefill_chunk.commit"):
            for group, tokens in fetched:
                for row, (req, n, epoch) in enumerate(group):
                    if req.finished or req.epoch != epoch:
                        continue
                    if self.blocks is None:
                        req.prefill_pos += n
                    if req.prefill_pos < req.prefill_target:
                        continue
                    # tokens (R, 1): the output at the row's last fed
                    # position, the one the program projects
                    first = int(tokens[row, 0])
                    if self.blocks is None:
                        # the last prompt token's output IS the first
                        # generated token
                        self._finish_prefill(req, first)
                        if first >= 0:
                            results[req.req_id] = first
                    elif first < 0:
                        self._quarantine(req)

    def _finish(self, req: Request, reason: Optional[str] = None, scrub: bool = False):
        # _finish can legitimately run twice for one request (an already-
        # dispatched row's token is consumed one step later and may hit a
        # termination condition again) — telemetry must count the FIRST
        # finish only. ``reason=None`` derives eos/length from the stream;
        # explicit reasons come from the containment paths (non_finite /
        # dispatch_error / deadline_exceeded / terminal preempted).
        already_finished = req.finished
        req.finished = True
        if not already_finished:
            self._terminal_total += 1
            if reason is None:
                reason = (
                    "eos"
                    if (
                        req.eos_token_id is not None
                        and req.generated
                        and req.generated[-1] == req.eos_token_id
                    )
                    else "length"
                )
            if reason in FAILURE_REASONS:
                req.status = STATUS_FAILED
                req.fail_reason = reason
            else:
                req.status = STATUS_FINISHED
            self.tel.request_finished(req.req_id, reason)
        self._release_slot(req, scrub=scrub)

    @property
    def active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    @property
    def decoding(self) -> List[Request]:
        return [r for r in self.slots if r is not None and not r.prefilling]

    @property
    def prefilling(self) -> List[Request]:
        return [r for r in self.slots if r is not None and r.prefilling]

    def _is_done(self, req: Request, tok: int) -> bool:
        """Request-termination predicate, shared by every consume path — the
        split, multi-step-chunk and ragged dispatch modes must agree on it
        (the ragged mode's byte-identical-outputs contract depends on it)."""
        return (
            (req.eos_token_id is not None and tok == req.eos_token_id)
            or len(req.generated) >= req.max_new_tokens
            or req.pos + 1 >= self.app.config.tpu_config.seq_len
        )

    def step(self) -> Dict[str, int]:
        """Advance the session: one chunked-prefill pass (if pending) + one
        decode step for every decoding request. Returns {req_id: token} for
        tokens produced this step.

        Order of a split step that holds both kinds of row: dispatch the
        chunk pass, dispatch the decode pass behind it, THEN wait for the
        chunk pass's tokens and commit them, then consume the decode pass
        that is due. Every pass is queued on the device before the host waits
        for any, so the device does not idle through the commit and the
        decode rows' preparation. The decode rows need no token of the chunk
        pass: a request whose prompt ends in it starts decoding next step
        (its first token, this step's entry in the result, is the chunk
        pass's), and the device runs the two programs in dispatch order with
        the cache threaded between them. What the order costs: blocks freed
        by a request that FINISHES at the chunk commit (one output token, an
        EOS first) are free only after this step's decode rows took theirs,
        so under an exhausted pool a decode row can be preempted a step
        earlier; it resumes byte-identically.

        Containment wrapper (docs/SERVING.md "Failure containment"): each
        step also expires deadlines, re-admits preempted requests (aged
        ahead of new arrivals), fires any armed fault injections, and feeds
        the no-forward-progress watchdog. All of it is host bookkeeping —
        zero extra device fetches (fetch-parity pinned).

        Async 1-ahead semantics (``async_mode=True``, the default): decode
        results are consumed one step() LATE — a request's first decode token
        appears on the step() AFTER the one that dispatched it, and its final
        token/termination is observed on the following step()'s consume
        (each terminating request runs one extra speculative device step
        whose writes land in masked slots and whose token is discarded).
        Per-step-latency-sensitive callers should construct the session's app
        with ``async_mode=False`` for dispatch+fetch-per-step behavior.
        """
        self._step_index += 1
        tel = self.tel
        with tel.span("serving.step", step=self._step_index) as step_span:
            self._step_span = step_span
            self._step_fetch_wait_s = 0.0
            with tel.span("serving.housekeeping"):
                # progress baseline BEFORE re-admission: a successful
                # re-admission commits real tokens (the resumed prefill's
                # next token) and those must count as forward progress, or a
                # preempt/re-admit churn that advances one token per cycle
                # would trip a spurious WatchdogError. The genuinely-
                # livelocked case still escalates: a failed re-admission
                # moves none of the signature counters.
                before = self._progress_signature()
                if self.faults is not None:
                    self.faults.on_step_begin(self)
                self._expire_deadlines()
                self._readmit_preempted()
            if self.faults is not None and self.faults.stalled(self):
                results: Dict[str, int] = {}
            else:
                results = self._step_inner()
            with tel.span("serving.housekeeping"):
                progressed = bool(results) or self._progress_signature() != before
                self._watchdog_tick(progressed)
        if step_span is not NULL_SPAN and not self.ragged:
            # the split step's host / fetch-wait split, from the spans' own
            # two numbers (a ragged step times itself, _note_step_timing)
            wait_s = min(self._step_fetch_wait_s, step_span.dur_s)
            tel.step_timing(
                (step_span.dur_s - wait_s) * 1e3, wait_s * 1e3,
                replica=self._tel_replica,
            )
        return results

    def _step_inner(self) -> Dict[str, int]:
        if self.ragged:
            return self._ragged_step()
        results: Dict[str, int] = {}
        tel = self.tel
        # every pass the step holds is DISPATCHED before the step waits for
        # any pass's tokens, and the waits come in dispatch order: the device
        # runs chunk pass then decode pass back to back (the cache is threaded
        # from one to the other without a fetch) while the host commits
        flights: list = []
        if self.chunked and self.prefilling:
            batch = self.prefilling[: self.max_prefill_seqs]
            flights = self._dispatch_chunks(batch, self.chunk_size, preempt=True)

        with tel.span("serving.schedule"):
            # the decode rows need no token of the chunk pass: a request
            # whose prompt ends in it is still ``prefilling`` until the commit
            # below and starts decoding NEXT step (its first token is the
            # chunk pass's, and stays this step's entry in results); a
            # block-step row generates nothing there and was opened by the
            # dispatch
            active = self.decoding

            # async 1-ahead (reference modules/async_execution.py:190):
            # dispatch step k+1 CHAINED on step k's still-on-device tokens
            # BEFORE fetching step k — the host-side fetch + bookkeeping
            # overlaps with the device executing k+1. The fetch gates only
            # termination: rows whose request terminates at step k ran one
            # speculative step whose writes land in masked/overwritten slots
            # and whose token is discarded at the next consume. The
            # synchronous mode (async_mode=False debugging) takes the same
            # order with nothing pending: what it dispatches it consumes
            # below, at the end of this step.
            pend = self._pending
            self._pending = None
            # chain only rows whose pending entry is still CURRENT: a row that
            # was preempted/quarantined since its dispatch carries a stale
            # epoch and must restart from host state (its in-flight token is
            # discarded and — greedy — regenerated identically after
            # re-admission)
            pend_pos = (
                {
                    id(req): p
                    for req, p, _s, e, *_ in pend[1]
                    if e == req.epoch and not req.finished and not req.preempted
                }
                if pend
                else {}
            )
            rows: List = []
            chained_slots: List[int] = []
            if self.blocks is not None:
                # which pass of which block, and whose ids are still on the
                # device, is the blocks' own to say (a block that was
                # preempted re-opens from the host's state)
                rows, chained_slots = self.blocks.plan(active)
            else:
                for r in active:
                    if id(r) in pend_pos:
                        rows.append((r, pend_pos[id(r)] + 1))
                        chained_slots.append(r.slot)
                    else:
                        rows.append((r, r.pos))
        ahead = None
        if rows:
            last_override = (pend[0], chained_slots) if chained_slots else None
            out, snap = self._dispatch_decode(rows, last_override)
            if out is not None:
                ahead = (self._step_ids(out), snap)
        if self._step_span is not NULL_SPAN:
            # what the scheduler decided this step, on the step's span event
            self._step_span.note(
                chunk_rows=sum(len(group) for group, _ in flights),
                chunk_dispatches=len(flights),
                decode_rows=len(ahead[1]) if ahead else 0,
            )
        if flights:
            # the step holds a chunk pass: was a decode pass queued behind it
            # while its tokens were still unfetched?
            self.tel.chunk_step(decode_behind=ahead is not None)
            self._commit_chunks(flights, results)
        if self.async_decode:
            self._pending = ahead
        else:
            pend = ahead
        if pend is not None:
            self._consume(pend, results)
        return results

    def _ragged_step(self) -> Dict[str, int]:
        """One RAGGED mixed dispatch: admitted prefill chunks (up to
        ``max_prefill_seqs``, ``chunk_size`` tokens each) and every decoding
        row pack into ONE launch of the ``mixed_step`` program — no CTE/TKG
        split, no per-phase padding, chunked prefill co-scheduled with
        decode. Row index == slot; segments are q-tile aligned (the ragged
        kernel's packing contract); one CONSUMED host fetch per step.
        Returns {req_id: token} exactly like the split step().

        Pipelined mode (``ragged_async``, docs/SERVING.md "Pipelined
        dispatch"): step k+1 is scheduled from each row's EFFECTIVE state
        (its in-flight step counted as done), chained decode rows take their
        input id from step k's still-on-device tokens via the mixed
        program's chained-id gather, the step-k fetch was started
        non-blocking at dispatch, and step k is consumed AFTER step k+1
        dispatches — so all host bookkeeping here overlaps the device
        executing k+1. Rows preempted/quarantined since their dispatch carry
        a stale epoch: their in-flight token is discarded and (greedy)
        regenerated identically after re-admission."""
        results: Dict[str, int] = {}
        t_step0 = self.tel.clock()
        self._step_fetch_wait_s = 0.0
        pend = self._pending
        self._pending = None
        pend_map: Dict[int, tuple] = {}
        if pend is not None:
            for ent in pend[1]:
                req = ent[0]
                if (
                    ent[5] == req.epoch
                    and not req.finished
                    and not req.preempted
                ):
                    pend_map[id(req)] = ent

        rows = self._schedule_mixed(pend_map)
        if not rows:
            if pend is not None:
                self._consume_ragged(pend, results)
            self._note_step_timing(t_step0)
            return results

        mr = self.mixed_runner
        d = self._build_mixed_descriptors(rows)
        chain_tokens = pend[0] if (pend is not None and d["chained"]) else None

        def dispatch():
            with self.tel.span(
                "serving.mixed_step", rows=len(rows), tokens=d["T"]
            ):
                inputs, _ = mr.prepare(
                    d["ids"], d["positions"], d["slot_mapping"],
                    d["row_start"], d["row_len"], d["ctx_len"],
                    d["block_table"], d["width"],
                    self._session_sampling_params(),
                    chain_src=d["chain_src"], chain_tokens=chain_tokens,
                )
                return mr(self.app.params, self.app.kv_cache, inputs, None)

        consumed = [False]

        def give_up():
            # the previous step already executed on device: commit it BEFORE
            # the in-flight rows terminally fail, so a request failing at
            # step k+1 keeps its step-k token (sync-path commit order)
            if pend is not None and not consumed[0]:
                consumed[0] = True
                self._consume_ragged(pend, results)

        out = self._guarded_dispatch(
            "mixed_step", [t[0] for t in rows], dispatch, on_give_up=give_up
        )
        if out is None:
            # in-flight rows terminally FAILED(dispatch_error); the previous
            # step was consumed by give_up
            self._note_step_timing(t_step0)
            return results
        self.app.kv_cache = out.cache
        self.tel.step("mixed")
        self.tel.bucket_dispatch(mr.tag, mr.last_bucket)
        n_prefill = sum(1 for t in rows if t[1] == "prefill")
        real_tokens = int(sum(t[2] for t in rows))
        self.tel.mixed_step(
            prefill_rows=n_prefill,
            decode_rows=len(rows) - n_prefill,
            padded_slots=mr.last_bucket - real_tokens,
            query_tokens=real_tokens,
        )
        for req, kind, n, _p0, _c in rows:
            if kind == "prefill":
                self._note_prefill(req, n)
        self.tel.pool_gauges(
            len(self.active), self.kv_pool_bytes, self.kv_free_bytes
        )
        snap = [
            (req, kind, n, p0, req.slot, req.epoch)
            for req, kind, n, p0, _c in rows
        ]
        if self.ragged_async:
            # start the device->host token copy NOW (non-blocking): by the
            # time next step() consumes it, the transfer has overlapped this
            # step's remaining host work and the device executing k+1
            start_copy = getattr(out.tokens, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
            self._pending = (out.tokens, snap)
            if pend is not None:
                self._consume_ragged(pend, results)
        else:
            self._consume_ragged((out.tokens, snap), results)
        self._note_step_timing(t_step0)
        return results

    def _schedule_mixed(self, pend_map: Dict[int, tuple]) -> List[tuple]:
        """Build this step's row list [(req, kind, n, p0, chained), ...]
        from each row's EFFECTIVE state: a row with a current pending entry
        (``pend_map``, epoch-matched) is scheduled as if that dispatched
        step already committed — its prefill cursor advanced, its decode
        position +1, its next input id chained from the on-device tokens.
        With pipelining off ``pend_map`` is always empty and this reduces
        exactly to the synchronous schedule."""
        rows: List[tuple] = []
        seq_len = self.app.config.tpu_config.seq_len
        if self.chunked:
            pref = []
            for r in self.slots:
                if r is None or r.finished:
                    continue
                e = pend_map.get(id(r))
                eff = (
                    e[3] + e[2]
                    if (e is not None and e[1] == "prefill")
                    else r.prefill_pos
                )
                if eff < r.prompt_len:
                    pref.append((r, eff))
            for req, eff in pref[: self.max_prefill_seqs]:
                n = min(self.chunk_size, req.prompt_len - eff)
                try:
                    self._alloc(req.slot, eff + n)
                except RuntimeError:
                    # pool exhausted: preempt (re-queued with aging) so the
                    # session never stalls — _prefill_chunks(preempt=True)
                    self._preempt(req)
                    continue
                rows.append((req, "prefill", n, eff, False))
        scheduled = {id(t[0]) for t in rows}
        for r in list(self.slots):
            if r is None or r.finished or id(r) in scheduled:
                continue
            e = pend_map.get(id(r))
            if e is not None and e[1] == "prefill":
                eff = e[3] + e[2]
                if eff < r.prompt_len:
                    continue  # still mid-prompt (or waiting for a chunk slot)
                # completed its prompt in flight: its first generated token
                # is on device — chained decode at position == prompt_len
                p0, chained, committed_after = eff, True, len(r.generated) + 1
            elif e is not None:
                p0, chained, committed_after = (
                    e[3] + 1, True, len(r.generated) + 1
                )
            else:
                if r.prefilling:
                    continue  # beyond max_prefill_seqs this step
                p0, chained, committed_after = r.pos, False, len(r.generated)
            if chained and (
                committed_after >= r.max_new_tokens
                or (e[1] == "decode" and e[3] + 2 >= seq_len)
            ):
                # the pending token predictably terminates this request at
                # consume (budget / position limit): don't burn a
                # speculative row on it. EOS terminations are NOT host-
                # predictable — those rows do run one extra speculative
                # step whose token is discarded, like the split path.
                continue
            try:
                self._alloc(r.slot, p0 + 1)
            except RuntimeError:
                self._preempt(r)
                continue
            rows.append((r, "decode", 1, p0, chained))
        return rows

    def _build_mixed_descriptors(self, rows: List[tuple]) -> Dict:
        """Vectorized mixed-step descriptor build. ``rows`` is the schedule
        [(req, kind, n, p0, chained), ...]; returns the packed arrays the
        MixedStepRunner consumes. Decode rows — the steady-state bulk — are
        built with whole-array numpy ops off the incrementally-maintained
        block-table matrix (no allocator walks, no per-row python loops);
        only prefill chunks (bounded by ``max_prefill_seqs``) take a
        per-row slice write. Equivalent, per element, to the per-row
        reference build (pinned by tests/test_ragged_serving.py)."""
        rows.sort(key=lambda t: t[0].slot)
        mr = self.mixed_runner
        tq = mr.q_tile
        R = self.num_slots
        bs = self.allocator.block_size
        k = len(rows)
        slots = np.fromiter((t[0].slot for t in rows), np.int64, k)
        ns = np.fromiter((t[2] for t in rows), np.int64, k)
        p0s = np.fromiter((t[3] for t in rows), np.int64, k)
        dec = np.fromiter((t[1] == "decode" for t in rows), np.bool_, k)
        chain = np.fromiter((t[4] for t in rows), np.bool_, k)
        seg = -(-ns // tq) * tq  # q-tile-aligned segment sizes
        starts = np.zeros(k, np.int64)
        np.cumsum(seg[:-1], out=starts[1:])
        T = int(seg.sum())
        row_start = np.zeros(R, np.int32)
        row_len = np.zeros(R, np.int32)
        ctx_len = np.zeros(R, np.int32)
        row_start[slots] = starts
        row_len[slots] = ns
        ctx_len[slots] = p0s + ns
        ids = np.zeros(T, np.int32)
        positions = np.full(T, -1, np.int32)
        slot_mapping = np.full(T, -1, np.int32)
        chain_src = np.full(T, -1, np.int32)
        if dec.any():
            dst = starts[dec]
            dslot = slots[dec]
            dpos = p0s[dec]
            ids[dst] = np.fromiter(
                (t[0].last_token for t in rows if t[1] == "decode"),
                np.int64, int(dec.sum()),
            )  # chained rows' host value is a placeholder the gather replaces
            positions[dst] = dpos
            slot_mapping[dst] = (
                self._bt_matrix[dslot, dpos // bs] * bs + dpos % bs
            )
            dchain = chain[dec]
            chain_src[dst[dchain]] = dslot[dchain]
        for i in np.flatnonzero(~dec):
            req, _kind, n, p0, _c = rows[i]
            s = int(starts[i])
            pr = np.arange(p0, p0 + n)
            positions[s : s + n] = pr
            slot_mapping[s : s + n] = (
                self._bt_matrix[req.slot, pr // bs] * bs + pr % bs
            )
            ids[s : s + n] = req.input_ids[p0 : p0 + n]
        width = get_target_bucket(
            self.app.token_generation_model.buckets, int((p0s + ns).max())
        )
        mb = max(1, width // bs)
        block_table = np.zeros((R, mb), np.int32)
        take = min(mb, self._bt_matrix.shape[1])
        block_table[:, :take] = self._bt_matrix[:, :take]
        return {
            "T": T,
            "ids": ids,
            "positions": positions,
            "slot_mapping": slot_mapping,
            "row_start": row_start,
            "row_len": row_len,
            "ctx_len": ctx_len,
            "block_table": block_table,
            "width": width,
            "chain_src": chain_src,
            "chained": bool(chain.any()),
        }

    def _consume_ragged(self, pend, results: Dict[str, int]):
        """Fetch one dispatched mixed step — the step's ONE consumed host
        sync (started non-blocking at dispatch under pipelining, so the
        wait here is only whatever the overlap didn't cover) — and apply
        the commit/termination bookkeeping. Rows whose request finished or
        was evicted since dispatch (stale epoch) are speculative leftovers
        and are discarded; rows carrying the non-finite sentinel are
        quarantined (only that row dies, pinned)."""
        t0 = self.tel.clock()
        tokens = np.asarray(pend[0])  # (R, 1)
        self._step_fetch_wait_s += self.tel.clock() - t0
        if self.faults is not None:
            tokens = self.faults.corrupt_tokens(self, tokens)
        for req, kind, n, p0, slot, epoch in pend[1]:
            if req.finished or req.preempted or req.epoch != epoch:
                continue
            tok = int(tokens[slot, 0])
            if kind == "prefill":
                req.prefill_pos = p0 + n
                if req.prefill_pos >= req.prompt_len:
                    # the last prompt token's output IS the first generated
                    # token (same contract as _prefill_chunks)
                    self._finish_prefill(req, tok)
                    if req.status != STATUS_FAILED:  # not quarantined
                        results[req.req_id] = tok
                continue
            if tok < 0:
                # non-finite sentinel: only this row dies, co-batched rows
                # stay byte-identical (pinned by the fault suite)
                self._quarantine(req)
                continue
            req.generated.append(tok)
            self._commit_tokens(req, 1)
            req.pos = p0 + 1
            results[req.req_id] = tok
            if self._is_done(req, tok):
                self._finish(req)

    def _note_step_timing(self, t_step0: float):
        """Host-vs-device split for this ragged step: everything except the
        blocking part of the token fetch is host bookkeeping (descriptor
        build, admission, commits, telemetry). Feeds the
        ``nxdi_serving_host_frac`` gauge — the fraction of serving wall
        time the HOST is the bottleneck for."""
        if not self.tel.enabled:
            return
        total_s = self.tel.clock() - t_step0
        wait_s = min(self._step_fetch_wait_s, total_s)
        self.tel.step_timing(
            (total_s - wait_s) * 1e3, wait_s * 1e3,
            replica=self._tel_replica,
        )

    def _dispatch_decode(self, rows, last_override=None):
        """Dispatch ONE batched decode pass for ``rows`` = [(req, pos), ...]
        without waiting for its result. ``last_override``: (device tokens
        (B, K) from the pending step, chained slot list) — those rows' input
        tokens come straight from the device (no host round-trip).
        Returns (StepOutput, snapshot rows) — StepOutput.tokens is an
        UNFETCHED device array. K is 1, or a block-step model's block: its
        rows carry their block's ids (``pos`` its first position), whichever
        pass of the block each is in."""
        import jax.numpy as jnp

        B = self.num_slots
        tel = self.tel
        tkg = self.app.token_generation_model
        K = tkg.n_active_tokens
        with tel.span("serving.decode", rows=len(rows)) as decode_span:
            with tel.span("serving.decode.prepare"):
                last = np.zeros((B, K), np.int32)
                pos = np.zeros((B, K), np.int32)
                seq_ids = np.full((B,), -1, np.int32)
                offsets = np.arange(K)
                for r, p in rows:
                    if self.blocks is None:
                        last[r.slot, 0] = r.last_token
                    pos[r.slot] = p + offsets
                    seq_ids[r.slot] = r.slot
                block_table = kv_blocks = None
                if self.block_mode:
                    bs = self.allocator.block_size
                    width = get_target_bucket(tkg.buckets, int(pos.max()) + 1)
                    mb = width // bs
                    block_table = np.zeros((B, mb), np.int32)
                    for r, p in list(rows):
                        try:
                            self._alloc(r.slot, p + K)
                        except RuntimeError:
                            # pool exhausted mid-decode: preempt this request
                            # so the others keep running (vLLM-style
                            # preemption; it re-queues AHEAD of new arrivals
                            # and resumes byte-identically once blocks free
                            # up)
                            self._preempt(r)
                            rows.remove((r, p))
                            continue
                        block_table[r.slot] = self.allocator.block_table(r.slot, mb)
                    if not rows:
                        return None, []
                    if tel.enabled and self.pooled:
                        live = [-(-(p + K) // bs) for _, p in rows]
                        kv_blocks = (sum(live), self._kv_blocks_walked(live, mb))
                    # no host slot mapping: decode writes derive their slots
                    # IN-GRAPH from the block table
                    # (models/base.run_decoder_layers; reference
                    # generate_tokengen_slot_mapping)
                else:
                    width = int(pos.max()) + 1
                mask = (np.arange(width)[None, :] <= pos[:, -1:]).astype(np.int32)
                block_rows = None
                if self.blocks is not None:
                    last = self.blocks.ids(rows, B)
                    commit = sum(r.block.dispatched == r.block.denoise for r, _ in rows)
                    block_rows = (len(rows) - commit, commit)
                ch = None
                if last_override is not None:
                    ch = np.zeros((B, 1), bool)
                    ch[np.asarray(last_override[1], np.int64)] = True
                # inactive rows: mask garbage anyway
                arrs, _ = tkg.prepare_host(
                    last, mask, pos, seq_ids, self._session_sampling_params(),
                    block_table=block_table,
                )
                with tel.span("serving.h2d", **self._h2d_fields(arrs, ch)):
                    if ch is not None:
                        # the chained rows' tokens never leave the device
                        arrs["input_ids"] = jnp.where(
                            jnp.asarray(ch), last_override[0].astype(jnp.int32),
                            jnp.asarray(arrs["input_ids"]),
                        )
                    inputs = tkg.to_device(arrs)
            decode_span.note(rows=len(rows))
            if block_rows is not None:
                decode_span.note(denoise_rows=block_rows[0], commit_rows=block_rows[1])
            fields = self._program_fields("decode", K, tkg.last_bucket)

            def dispatch():
                with tel.span("serving.decode.dispatch", **fields):
                    return tkg(self.app.params, self.app.kv_cache, inputs, None)

            out = self._guarded_dispatch("decode", [r for r, _ in rows], dispatch)
            if out is None:
                return None, []  # in-flight rows terminally FAILED(dispatch_error)
            self.app.kv_cache = out.cache
            # what the pass records of itself, after its dispatch returned
            # (the snapshot the consume reads among it): the telemetry's own
            # cost of a decode pass, by name
            with tel.span("serving.account"):
                tel.step("decode")
                tel.bucket_dispatch(tkg.tag, tkg.last_bucket)
                tel.decode_pass(len(rows), B)
                if self.pooled and tel.enabled:
                    tel.kv_write_rows(self._decode_write_form(K, width), len(rows))
                self._count_pass(
                    "decode", (B, K), len(rows), len(rows) * K, 1, kv_blocks=kv_blocks,
                    block_rows=block_rows, kv_width=width,
                    spans=[(p, K) for _, p in rows] if self.sparse_layers or self.window_layers else (),
                )
                tel.pool_gauges(len(rows), self.kv_pool_bytes, self.kv_free_bytes)
                snap = [(r, p, r.slot, r.epoch) for r, p in rows]
                if self.blocks is not None:
                    # a block row's entry also says which pass of which block it was
                    snap = [
                        entry + extra
                        for entry, extra in zip(snap, self.blocks.dispatched(rows))
                    ]
        return out, snap

    def _h2d_fields(self, arrs: dict, *more) -> dict:
        """What a ``serving.h2d`` span says at entry: how many host arrays
        the pass copies to the device (a pass's padded inputs and whatever
        ``more`` rides with them) and their bytes. A stopped session builds
        nothing."""
        if not self.tel.enabled:
            return {}
        host = [a for a in (*arrs.values(), *more) if isinstance(a, np.ndarray)]
        return {"arrays": len(host), "bytes": int(sum(a.nbytes for a in host))}

    def _program_fields(self, program: str, q: int, kv: int) -> dict:
        """What a dispatch span of the split step says AT ENTRY (so it is on
        the ``TraceAnnotation``): which step program it launches, ``decode``
        or ``chunk`` by the pass that dispatches, at which query and kv
        bucket. A recording session also notes the program for
        :meth:`device_scope_tables`; a stopped one does nothing."""
        if not self.tel.enabled:
            return {}
        q, kv = int(q), int(kv)
        self._programs_noted.add((program, q, kv))
        return {"program": program, "q": q, "kv": kv}

    def device_scope_tables(self) -> Dict[str, dict]:
        """``{"<program>:q<q>:kv<kv>": {"module", "ops": {instruction:
        scope}}}`` for the step programs dispatched while the session
        recorded, and forgets them (telemetry/device_scopes.py; what
        ``TelemetrySession.stop()`` writes beside a trace). The lowering is
        the one the dispatch compiled: it comes from the compile cache."""
        from neuronx_distributed_inference_tpu.telemetry import device_scopes

        tkg = self.app.token_generation_model
        # a replica's step thread may be noting while the table is asked for
        noted = sorted(tuple(self._programs_noted))
        self._programs_noted.difference_update(noted)
        tables = {}
        with tkg.seal_suspended():
            for program, q, kv in noted:
                inputs = tkg.example_inputs(kv, q_len=q if program == "chunk" else None)
                compiled = tkg.trace_program(self.app.params, self.app.kv_cache, inputs, None)[2]
                tables[device_scopes.table_key(program, q, kv)] = device_scopes.scope_table(
                    compiled.as_text()
                )
        return tables

    def _step_ids(self, out):
        """What of a dispatched decode step the session chains on and fetches:
        (B, K) on the device. The last token a row, or a block-step model's
        ids of the row's next pass."""
        if self.blocks is not None:
            return out.next_ids
        # at one token a row the slice is the array itself, and JAX hands it
        # back, after ~50 us of index arithmetic on the host a step
        return out.tokens if out.tokens.shape[1] == 1 else out.tokens[:, -1:]

    def _count_pass(self, program: str, shape, rows: int, tokens: int, dispatches: int,
                    resets: int = 0, kv_blocks=None, block_rows=None, kv_width: int = 0,
                    spans=()) -> None:
        """What a pass of the split serving step ("decode" or "chunk") did
        to per-slot state and routed experts, from what the step already
        knows: the program's ``shape`` (rows, positions a row), ``rows`` live
        rows over ``tokens`` real token positions in ``dispatches``
        dispatches, ``resets`` of the rows from position 0;
        ``kv_blocks``: the pass's (live, walked) pool blocks;
        ``block_rows``: a block step's (denoise, commit) rows; ``kv_width``
        the pass's kv bucket and ``spans`` its rows' (first position, real
        positions): what a selection of keys is counted from."""
        if kv_blocks is not None:
            self.tel.kv_blocks(program, *kv_blocks)
        if block_rows is not None:
            self.tel.block_pass(*block_rows, positions=tokens)
        if self.slot_state_kind in ("ssm", "kda", "power"):
            self.tel.ssm_pass(program, rows, self.slot_state_bytes, resets=resets,
                              kind=self.slot_state_kind)
        elif self.slot_state_kind == "latent_carry":
            self.tel.carry_pass(program, rows)
        if program == "chunk" and self.kda_layers and self._kda_chunk_kernel(shape[1]):
            self.tel.kda_chunk_rows(
                rows * self.kda_layers, (dispatches * shape[0] - rows) * self.kda_layers
            )
        if self.latent_layers:
            self.tel.latent_pass(program, tokens * self.latent_layers)
        if self.sparse_layers and self.tel.enabled:
            # query t of a row has t + 1 live keys and attends min(t + 1,
            # index_topk); at a kv width of no more than index_topk the
            # program scores nothing (every live key is chosen)
            k, scored, attended = self.sparse_topk, 0, 0
            for first, n in spans:
                live = np.arange(first + 1, first + n + 1, dtype=np.int64)
                scored += int(live.sum()) if kv_width > k else 0
                attended += int(np.minimum(live, k).sum())
            self.tel.sparse_pass(
                program, tokens * self.sparse_layers, scored * self.sparse_layers,
                attended * self.sparse_layers,
            )
            if kv_width > k:
                # the blocks of index keys the pass's indexers scored, of the
                # bucket's width over every row of its dispatches
                bs = self.allocator.block_size
                mb = kv_width // bs
                walked = self._index_blocks_walked([-(-(first + n) // bs) for first, n in spans], mb)
                self.tel.index_key_blocks(
                    program, walked * self.sparse_layers,
                    (dispatches * shape[0] * mb - walked) * self.sparse_layers,
                )
        if self.window_layers and self.tel.enabled:
            # query t of a row has t + 1 live keys and attends min(t + 1,
            # window) of them in a window layer; a pass that enters a logical
            # block past the ring's length writes over the ring's oldest
            W, R, bs = self.window, self._ring_blocks, self.allocator.block_size
            live = attended = recycled = 0
            for first, n in spans:
                keys = np.arange(first + 1, first + n + 1, dtype=np.int64)
                live += int(keys.sum())
                attended += int(np.minimum(keys, W).sum())
                recycled += max(0, -(-(first + n) // bs) - max(-(-first // bs), R))
            self.tel.window_pass(program, live, attended, self.full_layers, self.window_layers,
                                 recycled * self.window_layers)
            self._window_gauges()
        if self.loop_layer_passes:
            self.tel.loop_pass(program, dispatches, self.loop_layer_passes)
        if self.expert_layers is not None:
            layers, experts, top_k = self.expert_layers
            # the padded positions' rows, where the program was traced with
            # its expert layers handed the pass's real positions
            masked = tuple(shape) in self.app.token_generation_model.masked_sort_shapes
            padded = dispatches * shape[0] * shape[1] - tokens
            self.tel.moe_pass(
                program, tokens * layers * top_k, dispatches * layers * experts,
                self._expert_path(shape[1], shape[0] * shape[1]),
                rows_left_out=padded * layers * top_k if masked else 0,
            )

    def _window_gauges(self) -> None:
        """The window layers' pool as live slots hold it (a recording session
        alone asks): every ring, the rings of live slots, and of those the
        blocks that hold a key."""
        R, bs, L = self._ring_blocks, self.allocator.block_size, self.window_layers
        live = [r for r in self.slots if r is not None]
        in_use = sum(min(-(-max(r.pos, r.prefill_pos) // bs), R) for r in live)
        self.tel.window_pool(self.num_slots * R * L, len(live) * R * L, in_use * L)

    def _consume(self, pend, results: Dict[str, int]):
        """Fetch a dispatched decode step and apply termination bookkeeping.
        Rows whose request already finished (terminated after that dispatch)
        or was evicted since (stale epoch) are speculative leftovers —
        discarded; rows carrying the non-finite sentinel are quarantined."""
        tel = self.tel
        with tel.span("serving.fetch_wait") as wait:
            tokens = np.asarray(pend[0])  # the only device sync per step
        self._step_fetch_wait_s += wait.dur_s
        if self.blocks is None:
            tokens = tokens[:, -1]
        with tel.span("serving.commit"):
            if self.faults is not None:
                tokens = self.faults.corrupt_tokens(self, tokens)
            for req, p, slot, epoch, *block in pend[1]:
                if req.finished or req.preempted or req.epoch != epoch:
                    continue
                if block:
                    self._consume_block(req, p, block, tokens[slot], results)
                    continue
                tok = int(tokens[slot])
                if tok < 0:
                    self._quarantine(req)
                    continue
                req.generated.append(tok)
                self._commit_tokens(req, 1)
                req.pos = p + 1
                results[req.req_id] = tok
                if self._is_done(req, tok):
                    self._finish(req)

    def _consume_block(self, req: Request, start: int, block, next_ids, results):
        """One fetched pass of a block-step row: a denoise pass reveals, the
        commit appends the block's new tokens to ``generated`` (in position
        order, cut at the budget and after an EOS) and moves ``pos`` to the
        next block."""
        if int(next_ids.min()) < 0:
            self._quarantine(req)
            return
        self._revealed_total += 1
        new = self.blocks.consume(req, *block, next_ids)
        if new is None:
            return
        if not req.generated:
            # the first block's first token is the request's first token
            self.tel.request_first_token(req.req_id)
            self._committed_total += 1
            self._commit_tokens(req, len(new) - 1)
        else:
            self._commit_tokens(req, len(new))
        req.generated.extend(new)
        self.tel.block_commit(len(new))
        req.pos = start + self.blocks.length
        results[req.req_id] = new[-1]
        if (
            (req.eos_token_id is not None and new[-1] == req.eos_token_id)
            or len(req.generated) >= req.max_new_tokens
            or req.pos + self.blocks.length > self.blocks.pos_limit
        ):
            self._finish(req)

    def run_to_completion(self) -> Dict[str, List[int]]:
        """Drain the session by :meth:`step` and return every request's
        generated tokens."""
        while self.active or self._readmit:
            self.step()
        return {rid: r.generated for rid, r in self.requests.items()}


class SpeculativeServingSession(ServingSession):
    """Draft-assisted continuous batching (reference: fused/EAGLE speculation
    under vLLM continuous batching): each step() the DRAFT app proposes k-1
    tokens for every decoding request in one batched pass, the TARGET app
    verifies all k candidates in one multi-token pass, and each request
    advances by its own accepted count. Greedy verification (the serving
    sessions are greedy throughout): emitted tokens are byte-equal to the
    target's own greedy decoding, so a weak draft only costs speed.

    Cache discipline matches runtime/assisted.py: write-then-attend on both
    apps leaves rejected candidates as masked-stale entries that the next
    round overwrites. Contiguous caches only (speculative writes need the
    position==slot invariant): draft propose + target verify as two
    dispatches per step, host-side acceptance.
    """

    def __init__(
        self,
        app,
        draft_app,
        speculation_length: int = 4,
        telemetry=None,
        fault_injector=None,
        clock=None,
        sleep_fn=None,
    ):
        super().__init__(
            app,
            telemetry=telemetry,
            fault_injector=fault_injector,
            clock=clock,
            sleep_fn=sleep_fn,
        )
        if self.slot_state or getattr(draft_app.kv_cache, "state", None) is not None:
            from neuronx_distributed_inference_tpu.config import SlotStateServingError

            raise SlotStateServingError(
                "a model with state-space layers cannot be served with speculation: "
                "rejected drafts would need a snapshot of the state to roll back to"
            )
        tc_d = draft_app.config.tpu_config
        spec = app.spec
        if self.block_mode or self.chunked:
            raise NotImplementedError(
                "speculative serving runs on the contiguous cache (no "
                "paged/chunked-prefill layouts)"
            )
        if spec.bounded_window or spec.ring_window or (
            draft_app.spec.bounded_window or draft_app.spec.ring_window
        ):
            raise NotImplementedError(
                "speculative serving over ring-bounded caches is not "
                "implemented (rejected speculative writes would corrupt live "
                "ring slots)"
            )
        if not tc_d.is_continuous_batching:
            raise ValueError("the draft app needs is_continuous_batching=True")
        if speculation_length < 2:
            raise ValueError("speculation_length must be >= 2")
        # fail at construction, not mid-stream: the batched rounds need the
        # draft compiled for the same slot count and at least the target's
        # decode reach
        d_batch = tc_d.tkg_batch_size or tc_d.max_batch_size or tc_d.batch_size
        if d_batch < self.num_slots:
            raise ValueError(
                f"draft app batch ({d_batch}) smaller than the session's "
                f"{self.num_slots} slots"
            )
        if (
            draft_app.token_generation_model.buckets[-1]
            < app.token_generation_model.buckets[-1]
        ):
            raise ValueError(
                "draft token_generation_buckets must reach at least as far "
                "as the target's"
            )
        self.draft = draft_app
        self.k = speculation_length
        self.async_decode = False  # accept/reject is a host decision per step
        #: session-wide acceptance-rate EWMA — the router's least_loaded
        #: placement signal (None until the first spec round)
        self.acceptance_ewma: Optional[float] = None
        #: optional CPU-harness draft-agreement gate (the workload engine's
        #: per-tenant spec-acceptance profiles, workload/generator.py
        #: make_accept_gate): callable (req_id, drafted) -> max draft tokens
        #: to accept this verify round, or None for no cap. Capping is
        #: OUTPUT-INVARIANT — the accepted window holds the target's own
        #: greedy tokens, so accepting fewer merely regenerates them in
        #: later rounds; only measured acceptance (and with it the router's
        #: acceptance signal) moves.
        self.draft_accept_cap = None

    prefilled_admission = False  # see ServingSession.prefilled_admission

    def add_prefilled_request(self, *args, **kwargs) -> AdmissionResult:
        raise NotImplementedError(
            "the disaggregated prefill tier does not support speculative "
            "decode sessions: the hand-off carries TARGET KV only, and the "
            "draft app's cache needs its own prompt prefill "
            "— route speculative traffic to non-tier replicas"
        )

    def _capped_accept(self, req: Request, count: int, drafted: int) -> int:
        """Apply the draft-agreement gate (if installed) to one verify
        round's device-computed accepted count. ``count`` includes the
        bonus token (in [1, drafted+1]); the gate speaks in DRAFT tokens."""
        if self.draft_accept_cap is None or drafted <= 0:
            return count
        cap = self.draft_accept_cap(req.req_id, drafted)
        if cap is None:
            return count
        return max(1, min(count, 1 + int(cap)))

    def _max_admissible_prompt(self) -> int:
        # the speculative session cannot run the windowed admission path
        # (the draft prefill is a single CTE pass): cap admission at one
        # context program of BOTH apps so _full_prefill's
        # NotImplementedError becomes a typed REJECT at the door
        tc = self.app.config.tpu_config
        limit = min(
            super()._max_admissible_prompt(),
            tc.max_context_length,
            self.draft.context_encoding_model.buckets[-1],
        )
        if self.app.spec.bounded_window:
            limit = min(limit, self.app.spec.bounded_window)
        if self.app.spec.ring_window:
            limit = min(limit, self.app.spec.ring_window)
        return limit

    def _full_prefill(self, req: Request) -> bool:
        # fail BEFORE any state mutates: the draft prefill below is a single
        # CTE pass, so prompts needing the windowed path are rejected here
        if self.app.validate_prefill_length(req.prompt_len) or (
            req.prompt_len > self.draft.context_encoding_model.buckets[-1]
        ):
            raise NotImplementedError(
                "speculative serving of prompts longer than one context "
                "program is not implemented; raise max_context_length (both "
                "apps) to cover the prompt"
            )
        ok = super()._full_prefill(req)
        if not ok or req.finished:
            # already terminated at prefill (EOS / 1-token budget): no draft
            # state will ever be consulted
            return ok
        return self._draft_prefill(req)

    def _draft_prefill(self, req: Request) -> bool:
        """Prefill the DRAFT's cache line for ``req`` (one CTE pass; the
        draft's own first token is discarded — proposals chain from the
        target's tokens). Guarded like every other dispatch: a transient
        draft failure must not leak the slot — past the retry budget the
        request terminally FAILs (dispatch_error, slot released) and the
        session keeps serving."""
        S = req.prompt_len
        ids = req.input_ids[None, :]
        mask = np.ones((1, S), np.int32)
        pos = np.arange(S, dtype=np.int32)[None, :]
        seq_ids = np.array([req.slot], np.int32)

        def dispatch_draft():
            inputs, _ = self.draft.context_encoding_model.prepare(
                ids, mask, pos, seq_ids, prepare_sampling_params(1)
            )
            return self.draft.context_encoding_model(
                self.draft.params, self.draft.kv_cache, inputs, None
            )

        out = self._guarded_dispatch("prefill_draft", [req], dispatch_draft)
        if out is None:
            return True  # terminal FAILED(dispatch_error); slot released
        self.draft.kv_cache = out.cache
        return True

    #: per-request acceptance-EWMA smoothing (fast: the policy must react
    #: within a few rounds when a request's text regime shifts)
    SPEC_EWMA_ALPHA = 0.5
    #: session-level smoothing for the router's placement signal
    SESSION_EWMA_ALPHA = 0.2
    def _note_acceptance(self, req: Request, accepted: int, drafted: int):
        """Fold one spec round's outcome into the per-request and session
        EWMAs."""
        rate = accepted / max(1, drafted)
        a = self.SPEC_EWMA_ALPHA
        req.accept_ewma = (1 - a) * req.accept_ewma + a * rate
        b = self.SESSION_EWMA_ALPHA
        self.acceptance_ewma = (
            rate if self.acceptance_ewma is None
            else (1 - b) * self.acceptance_ewma + b * rate
        )
        # the histogram sum is exactly the drafted-token total, which is what
        # a measured-acceptance rate divides by
        self.tel.spec_round(drafted, req.accept_ewma, req_id=req.req_id)

    def _step_inner(self) -> Dict[str, int]:
        """One speculation round for every decoding request. Returns ALL
        tokens accepted this round, {req_id: last_accepted_token} (use
        request.generated for the full stream). The containment wrapper
        (deadlines, re-admission, watchdog, fault hooks) lives in the base
        class's :meth:`ServingSession.step`."""
        import jax

        results: Dict[str, int] = {}
        active = self.decoding
        if not active:
            return results
        from neuronx_distributed_inference_tpu.runtime.assisted import (
            draft_propose,
            target_verify,
        )

        tc = self.app.config.tpu_config
        k = self.k
        B = self.num_slots
        pos_limit = self.app._pos_limit()
        rows = [r for r in active if r.pos + k <= pos_limit]
        tail = [r for r in active if r not in rows]
        if tail:
            # rows within k-1 positions of the limit: plain single-step
            # decode keeps emitting the same tokens the non-speculative
            # session would (no early truncation)
            out, snap = self._dispatch_decode([(r, r.pos) for r in tail])
            if out is not None:
                self._consume((out.tokens[:, -1:], snap), results)
        if not rows:
            return results

        last = np.zeros((B, 1), np.int32)
        pos = np.zeros((B, 1), np.int32)
        seq_ids = np.full((B,), -1, np.int32)
        for r in rows:
            last[r.slot, 0] = r.last_token
            pos[r.slot, 0] = r.pos
            seq_ids[r.slot] = r.slot
        sp = prepare_sampling_params(B)

        # --- draft proposes k-1 tokens per row; target verifies all k -------
        def dispatch():
            with self.tel.span("serving.speculate", rows=len(rows)):
                proposals, _ = draft_propose(self.draft, last, pos, seq_ids, sp, k)
                cand = np.concatenate([last, proposals], axis=1).astype(np.int32)
                v_out = target_verify(self.app, cand, pos, seq_ids, sp)
                return cand, v_out

        res = self._guarded_dispatch("speculate", rows, dispatch)
        if res is None:
            return results  # in-flight rows terminally FAILED(dispatch_error)
        cand, v_out = res
        self.tel.step("speculate")
        self.tel.bucket_dispatch(
            self.app.token_generation_model.tag,
            self.app.token_generation_model.last_bucket,
        )
        self.tel.pool_gauges(len(rows), self.kv_pool_bytes, self.kv_free_bytes)
        with self.tel.span("serving.fetch_wait") as wait:
            greedy = np.asarray(jax.device_get(v_out.tokens))[:B]  # (B, k)
        self._step_fetch_wait_s += wait.dur_s
        if self.faults is not None:
            greedy = self.faults.corrupt_tokens(self, greedy)

        # --- contiguous-match acceptance, per-request bookkeeping -----------
        matches = (cand[:, 1:] == greedy[:, :-1]).astype(np.int64)
        counts = np.cumprod(matches, axis=1).sum(axis=1) + 1  # in [1, k]
        for r in rows:
            s = r.slot
            counts[s] = self._capped_accept(r, int(counts[s]), k - 1)
            if (greedy[s, : counts[s]] < 0).any():
                # non-finite sentinel inside the accepted window: a poisoned
                # TARGET row — quarantine it (a poisoned DRAFT merely
                # mis-proposes and costs acceptance length, never output
                # correctness: the target's own greedy tokens are emitted)
                self._quarantine(r)
                continue
            row = greedy[s, : counts[s]].tolist()
            if r.eos_token_id is not None and r.eos_token_id in row:
                row = row[: row.index(r.eos_token_id) + 1]
            room = r.max_new_tokens - len(r.generated)
            row = row[:room]
            r.generated.extend(row)
            # acceptance-length telemetry: committed (post EOS/budget
            # truncation) tokens this round — the histogram's sum is exactly
            # the decode tokens speculation delivered for this session
            self.tel.spec_accept(len(row))
            # the per-request and session acceptance signals (the router's
            # least_loaded placement bonus, the workload engine's tenant
            # separation)
            self._note_acceptance(r, accepted=int(counts[s]) - 1,
                                  drafted=k - 1)
            self._commit_tokens(r, len(row))
            r.pos += len(row)
            if row:
                results[r.req_id] = row[-1]
            if (
                (r.eos_token_id is not None and row and row[-1] == r.eos_token_id)
                or len(r.generated) >= r.max_new_tokens
                or r.pos + 1 >= tc.seq_len
            ):
                self._finish(r)
        return results
