"""Request-lifecycle tracing, step-timeline spans, and the JSONL event log.

One :class:`TelemetrySession` observes one serving session (or one demo/bench
process via the module default). Everything here is HOST-side bookkeeping:

- **Request lifecycle** — submitted → admitted → (prefill chunks) → first
  token (TTFT) → per-token decode (ITL) → finished/preempted/dropped. Each
  request keeps an exact :class:`RequestTrace` (for percentile math) and
  feeds the fixed-bucket histograms in :mod:`.metrics`.
- **Step timeline** — :meth:`TelemetrySession.span` wraps each dispatch in a
  ``jax.profiler.TraceAnnotation`` named scope (visible in XProf host lines
  next to the device ops it launched) and logs a structured span event.
- **Event log** — every lifecycle/step event appends one JSON object; with
  ``jsonl_path`` set they stream to disk for offline replay
  (:func:`load_events`).

Timing contract (the zero-device-round-trip rule): timestamps are taken with
``time.perf_counter`` when the HOST observes a value that an already-issued
fetch returned. Nothing here calls ``device_get``/``block_until_ready`` —
the fetch-parity test in tests/test_telemetry.py pins that a serving run
performs the identical number of device fetches with telemetry on and off.
Two consequences, documented rather than hidden:

- under async 1-ahead decode, a token's timestamp is its *observation* time
  (one step() late), not its device-completion time;
- a pass that commits N tokens of a request in one fetch (a block step, a
  speculation verify) observes them together, so ITL is amortized — the
  elapsed time since the previous observation divided by N, observed N times
  (sum and count stay exact; per-token jitter inside the pass is invisible
  by construction).

The retrace-guard bridge: an enabled session registers a listener with
``analysis.retrace_guard`` so every jit trace increments
``nxdi_jit_traces_total{tag}`` and a forbidden post-seal retrace increments
``nxdi_sealed_retrace_total{tag}`` — steady-state recompiles become an
operable counter instead of only an assertion.
"""

from __future__ import annotations

import glob
import json
import os
import re
import threading
import time
import warnings
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

from neuronx_distributed_inference_tpu.analysis import retrace_guard
from neuronx_distributed_inference_tpu.telemetry import device_scopes
from neuronx_distributed_inference_tpu.telemetry import metrics as metrics_mod
from neuronx_distributed_inference_tpu.telemetry import spans as spans_mod

#: env override for the in-memory event ring AND the span store bound —
#: long chaos drains must not grow either without limit (ISSUE 19)
TELEMETRY_EVENT_MAX_ENV = "TELEMETRY_EVENT_MAX"

#: session-side failover-incarnation suffix (runtime/router.py
#: RouterRequest.session_id): ``{base}~f{N}``
_INCARNATION_RE = re.compile(r"^(?P<base>.+)~f(?P<inc>\d+)$")


def _default_event_max() -> int:
    try:
        return max(1, int(os.environ.get(TELEMETRY_EVENT_MAX_ENV, "10000")))
    except ValueError:
        return 10000


def _split_incarnation(req_id: str):
    """``base~fN`` -> (base, N); bare ids are incarnation 0."""
    m = _INCARNATION_RE.match(req_id)
    if m:
        return m.group("base"), int(m.group("inc"))
    return req_id, 0

FINISH_REASONS = (
    "eos", "length", "preempted", "dropped",
    # fault-containment terminals (runtime/serving.py, runtime/faults.py):
    # rejected by admission validation, wall-clock TTL expiry, non-finite
    # quarantine, and dispatch-retry exhaustion
    "rejected", "deadline_exceeded", "non_finite", "dispatch_error",
)


@dataclass
class RequestTrace:
    """Exact per-request lifecycle record (histograms are for fleets;
    traces are for percentiles and tests)."""

    req_id: str
    t_submit: float
    t_admit: Optional[float] = None
    t_first_dispatch: Optional[float] = None
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None
    t_finish: Optional[float] = None
    tokens: int = 0
    prefill_chunks: int = 0
    cached_prefix_tokens: int = 0
    finish_reason: Optional[str] = None
    itl_s: List[float] = field(default_factory=list)

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_first_dispatch is None:
            return None
        return self.t_first_dispatch - self.t_submit


class _NullSpan:
    """What a stopped session's :meth:`TelemetrySession.span` returns: one
    shared object, nothing recorded, no clock read."""

    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def note(self, **fields) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """One open step-timeline span of a started session (see
    :meth:`TelemetrySession.span`)."""

    __slots__ = ("tel", "name", "fields", "t0", "dur_s", "_ann", "_stack")

    def __init__(self, tel, name, fields):
        self.tel = tel
        self.name = name
        self.fields = fields
        self.dur_s = 0.0

    def note(self, **fields) -> None:
        """Fields learnt inside the span: they go on its span event (the
        ``TraceAnnotation`` carries those known at entry)."""
        self.fields.update(fields)

    def __enter__(self):
        stack = self._stack = self.tel._span_stack.open
        if "step" not in self.fields and stack and "step" in stack[-1].fields:
            self.fields["step"] = stack[-1].fields["step"]
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.fields)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = self.tel.clock()
        return self

    def __exit__(self, *exc):
        # the annotation closes LAST: what recording this span costs (its
        # event) lies under its own name in a trace, not in the self time of
        # the span around it; ``dur_s`` is the block alone
        t1 = self.tel.clock()
        self.dur_s = t1 - self.t0
        self._stack.pop()
        parent = self._stack[-1].name if self._stack else None
        self.tel.event("span", name=self.name, t0=self.t0, t1=t1,
                       dur_ms=self.dur_s * 1e3, parent=parent, **self.fields)
        self._ann.__exit__(*exc)
        return None


class _SpanStack(threading.local):
    """The open step-timeline spans of the calling thread, innermost last
    (one session is shared by every replica thread of a router)."""

    def __init__(self):
        self.open: List[_Span] = []


def newest_xplane(profile_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` (or ``.gz``) under a profiler directory,
    by modification time: repeated captures into one directory resolve to
    the latest trace."""
    paths = [
        p
        for pat in ("*.xplane.pb", "*.xplane.pb.gz")
        for p in glob.glob(os.path.join(profile_dir, "**", pat), recursive=True)
    ]
    return max(paths, key=os.path.getmtime) if paths else None


class TelemetrySession:
    """Metrics + traces + events for one serving session / process.

    ``enabled=False`` builds a STOPPED session: every record method returns
    immediately, no instruments are created, no retrace listener installs —
    the stopped path is a handful of attribute loads per call, and
    :meth:`span` hands back one shared null context. :meth:`start` turns
    recording on in the running process (creating the instruments the first
    time) and, given a directory, the device profiler with it;
    :meth:`stop` turns both off again. ``enabled=True`` is a session
    started at construction.

    Thread safety (the CONC601 contract): one TelemetrySession is shared by
    every replica of a thread-per-replica router
    (``TpuConfig.router_threading``), so record methods that mutate session
    state (the trace table, the completed/event deques, the cumulative
    timing sums, the JSONL stream) take ``self._lock`` — an RLock, because
    locked methods log through :meth:`event` which locks again on the same
    thread. Instrument mutations (``inc``/``set``/``observe``) are atomic
    inside :mod:`.metrics` (per-instrument locks, acquired strictly INSIDE
    this one — the router → replica → telemetry-session → instrument lock
    order CONC602 checks). Methods that only touch instruments take no
    session lock.
    """

    def __init__(
        self,
        registry: Optional[metrics_mod.MetricsRegistry] = None,
        enabled: bool = True,
        jsonl_path: Optional[str] = None,
        clock=time.perf_counter,
        max_events: Optional[int] = None,
        max_completed: int = 10000,
    ):
        self.registry = registry if registry is not None else metrics_mod.MetricsRegistry()
        self.clock = clock
        self._lock = threading.RLock()
        self.traces: Dict[str, RequestTrace] = {}
        if max_events is None:
            max_events = _default_event_max()  # TELEMETRY_EVENT_MAX
        # exact traces are for percentiles and tests; the fleet metrics live
        # in the (bounded) histograms — cap retention so a long-lived
        # serving process cannot grow trace memory linearly with requests
        self.completed = deque(maxlen=max_completed)
        self.events = deque(maxlen=max_events)
        self._jsonl_path = jsonl_path
        self._jsonl_file = None
        self._listener = None
        #: the causal span timeline (ISSUE 19) — None until the first start()
        self.spans: Optional[spans_mod.SpanStore] = None
        #: optional live SLO monitor (attach_slo_monitor)
        self.slo_monitor = None
        # span bookkeeping, all guarded by self._lock: failovers observed
        # per base request id (incarnation / flow-id numbering), last-seen
        # health per replica / tier member (transition instants), per-track
        # step-span counters, and an optional base-id -> tenant map
        self._failover_count: Dict[str, int] = {}
        self._replica_health_seen: Dict[int, int] = {}
        self._tier_health_seen: Dict[int, int] = {}
        self._replica_step_count: Dict[int, int] = {}
        self._phase_count: Dict[str, int] = {}
        self._tenant_of: Dict[str, str] = {}
        self._dropped_events = 0
        self._max_spans = max_events
        #: directory of the profiler trace this session started (None: none)
        self._profile_dir: Optional[str] = None
        #: who dispatches step programs (``device_scope_tables()``), held
        #: weakly: asked once, when a profile this session started stops
        self._scope_sources = weakref.WeakSet()
        self._span_stack = _SpanStack()
        self.enabled = False
        if enabled:
            self.start()

    def _build_instruments(self) -> None:
        """Create the span store and every metric family, once. A session
        that is never started creates none: its registry stays empty."""
        self.spans = spans_mod.SpanStore(max_spans=self._max_spans)
        r = self.registry
        self._tel_dropped = r.counter(
            "nxdi_telemetry_dropped_total",
            "oldest telemetry records evicted past the in-memory bound "
            "(TELEMETRY_EVENT_MAX); the JSONL stream, when enabled, keeps "
            "everything", labels=("kind",))
        self._submitted = r.counter(
            "nxdi_requests_submitted_total", "requests offered to the session")
        self._admitted = r.counter(
            "nxdi_requests_admitted_total", "requests that got a KV line")
        self._dropped = r.counter(
            "nxdi_requests_dropped_total",
            "requests refused at admission (capacity)", labels=("reason",))
        self._rejected = r.counter(
            "nxdi_requests_rejected_total",
            "requests refused by admission validation (terminal REJECTED)",
            labels=("reason",))
        self._preempted = r.counter(
            "nxdi_requests_preempted_total",
            "pool-exhaustion evictions (requests re-queue for re-admission)")
        self._quarantined = r.counter(
            "nxdi_rows_quarantined_total",
            "rows failed and evicted on a non-finite logits/tokens "
            "observation (FAILED(non_finite); KV scrubbed on release)")
        self._retries = r.counter(
            "nxdi_dispatch_retries_total",
            "transient dispatch errors retried with capped backoff")
        self._deadline_overrun = r.histogram(
            "nxdi_deadline_overrun_ms",
            "how far past its wall-clock deadline a request was when dropped",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._watchdog_preempt = r.counter(
            "nxdi_watchdog_preemptions_total",
            "largest-request preemptions forced by the no-progress watchdog")
        self._watchdog_trips = r.counter(
            "nxdi_watchdog_trips_total",
            "no-progress windows that tripped the watchdog (a second "
            "consecutive trip raises WatchdogError)")
        self._finished = r.counter(
            "nxdi_requests_finished_total", "requests completed",
            labels=("reason",))
        self._ttft = r.histogram(
            "nxdi_ttft_ms", "submit -> first token (host-observed)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._itl = r.histogram(
            "nxdi_itl_ms",
            "inter-token latency (amortized over multi-token fetches)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._queue_wait = r.histogram(
            "nxdi_queue_wait_ms", "submit -> first model dispatch",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._chunks_per_req = r.histogram(
            "nxdi_prefill_chunks_per_request",
            "prefill passes a request consumed before its first token",
            buckets=metrics_mod.CHUNK_COUNT_BUCKETS)
        self._tokens = r.counter(
            "nxdi_tokens_generated_total", "tokens committed to requests")
        self._prefill_tokens = r.counter(
            "nxdi_tokens_prefilled_total", "prompt tokens written to KV")
        self._steps = r.counter(
            "nxdi_steps_total", "model dispatches", labels=("kind",))
        self._chunk_steps = r.counter(
            "nxdi_chunk_steps_total",
            "steps of the split serving path that held a chunk pass")
        self._chunk_steps_behind = r.counter(
            "nxdi_chunk_steps_decode_behind_total",
            "of those, the steps whose decode pass was dispatched behind the "
            "chunk pass while its tokens were still unfetched (over "
            "nxdi_chunk_steps_total: the share of chunk-holding steps in "
            "which the device had its next program queued)")
        self._bucket = r.counter(
            "nxdi_bucket_dispatch_total",
            "compiled-program census: which (model, bucket) served",
            labels=("model", "bucket"))
        self._prefill_real = r.counter(
            "nxdi_prefill_real_tokens_total",
            "prompt tokens the split path's chunk passes really advanced "
            "(sum of each prefilling row's chunk)")
        self._prefill_padded = r.counter(
            "nxdi_prefill_padded_tokens_total",
            "token positions the split path's chunk passes ran beyond the "
            "real ones: dispatches x chunk rows x q_bucket - real, per pass")
        self._prefill_dispatches = r.counter(
            "nxdi_prefill_chunk_dispatches_total",
            "dispatches of the chunk program by the split path's chunk "
            "passes (over nxdi_steps_total{kind=prefill}: dispatches a pass)")
        self._chunk_rows = r.counter(
            "nxdi_chunk_rows_total",
            "rows of the chunk program over the split path's chunk "
            "dispatches: kind=live held a prefilling request, kind=empty ran "
            "with none (live + empty = dispatches x chunk rows; live over "
            "both: the share of the program's rows that did work)",
            labels=("kind",))
        self._decode_rows = r.counter(
            "nxdi_decode_rows_total",
            "live rows in the split path's decode dispatches")
        self._decode_slots = r.counter(
            "nxdi_decode_slots_total",
            "rows the split path's decode dispatches were run over "
            "(num_slots per dispatch; rows / slots = the useful share)")
        self._ssm_rows = r.counter(
            "nxdi_ssm_rows_advanced_total",
            "rows whose recurrent (state-space) state a dispatch of the "
            "split serving step advanced", labels=("program",))
        self._ssm_resets = r.counter(
            "nxdi_ssm_state_resets_total",
            "rows a chunk pass started from a zero recurrent state (first "
            "position 0: a new request, or a re-prefill after preemption)")
        self._ssm_bytes = r.gauge(
            "nxdi_ssm_state_bytes",
            "HBM of the per-slot recurrent state (conv tails + float32 SSM "
            "state, every state-space layer, every slot)")
        self._kda_rows = r.counter(
            "nxdi_kda_rows_advanced_total",
            "rows whose delta-rule (KDA linear attention) state a dispatch of "
            "the split serving step advanced", labels=("program",))
        self._kda_resets = r.counter(
            "nxdi_kda_state_resets_total",
            "rows a chunk pass started from a zero delta-rule state (first "
            "position 0: a new request, or a re-prefill after preemption)")
        self._kda_bytes = r.gauge(
            "nxdi_kda_state_bytes",
            "HBM of the per-slot delta-rule state (conv tails + the float32 "
            "matrix state a head, every KDA layer, every slot)")
        self._kda_chunk_rows = r.counter(
            "nxdi_kda_chunk_rows_total",
            "rows x KDA layers of the chunk program's dispatches where its "
            "delta-rule recurrence runs as the chunk-scan kernel on the "
            "stacked state (ops/kda_chunk_scan.py): kind=advanced, a row's "
            "state read once and written once; kind=skipped, a row that "
            "prefills nothing, for which the kernel moved nothing (advanced + "
            "skipped = dispatches x chunk rows x KDA layers). Not fed where "
            "the layers take the scan",
            labels=("kind",))
        self._power_rows = r.counter(
            "nxdi_power_rows_advanced_total",
            "rows whose power-retention state a dispatch of the split serving "
            "step advanced", labels=("program",))
        self._power_resets = r.counter(
            "nxdi_power_state_resets_total",
            "rows a chunk pass started from a zero power-retention state (first "
            "position 0: a new request, or a re-prefill after preemption)")
        self._power_bytes = r.gauge(
            "nxdi_power_state_bytes",
            "HBM of the per-slot power-retention state (the float32 state and "
            "normaliser a KV head, every layer, every slot)")
        self._carry_rows = r.counter(
            "nxdi_latent_carry_rows_advanced_total",
            "rows whose one-token carry (latent attention with conv mixing: "
            "the last token's conv inputs and shifted value half, every "
            "layer) a dispatch of the split serving step advanced",
            labels=("program",))
        self._latent_tokens = r.counter(
            "nxdi_latent_tokens_written_total",
            "compressed latents (with their one rotary key: what a token "
            "leaves in an MLA layer) a pass of the split serving step wrote to "
            "the pool: real token positions x latent-attention layers",
            labels=("program",))
        self._index_keys = r.counter(
            "nxdi_index_keys_written_total",
            "indexer keys (what a token leaves in the pool's third stream in "
            "a layer of learned sparse attention) the split serving step "
            "wrote: real token positions x layers")
        self._sparse_scored = r.counter(
            "nxdi_sparse_keys_scored_total",
            "live keys an indexer scored: over the rows, layers and queries "
            "of a pass, the keys at or before the query (0 in a program whose "
            "kv width is no more than index_topk: every live key is chosen)",
            labels=("program",))
        self._sparse_attended = r.counter(
            "nxdi_sparse_keys_attended_total",
            "keys attended after the selection: over the rows, layers and "
            "queries of a pass, min(live keys, index_topk)",
            labels=("program",))
        self._index_key_blocks = r.counter(
            "nxdi_index_key_blocks_total",
            "pool blocks of indexer keys per pass of the split serving step "
            "whose kv width is past index_topk, over the layers with an "
            "indexer: kind=walked, the block-table entries the paged "
            "index-score kernel scores (whole groups of blocks up to each "
            "live row's frontier; every entry of a live row's table where "
            "the bucket is gathered instead); kind=skipped, the rest of the "
            "bucket's width for the pass's live rows and all of it for the "
            "empty rows of its dispatches. skipped / (walked + skipped) = "
            "the share of the bucket the indexer left alone",
            labels=("program", "kind"))
        self._attn_keys_live = r.counter(
            "nxdi_attn_keys_live_total",
            "a stack that mixes window and full attention layers: live keys "
            "(at or before the query) over the rows, layers and queries of a "
            "pass, by the layers' kind",
            labels=("program", "layer_kind"))
        self._attn_keys_attended = r.counter(
            "nxdi_attn_keys_attended_total",
            "and the keys a query attends: its live keys in a full layer, "
            "min(live keys, sliding_window) in a window layer",
            labels=("program", "layer_kind"))
        self._window_recycled = r.counter(
            "nxdi_kv_window_blocks_recycled_total",
            "ring blocks of window layers written over a block that has left "
            "every window (a slot's ring wrapping): blocks x window layers")
        self._window_blocks = {
            name: r.gauge(f"nxdi_kv_window_blocks_{name}", text) for name, text in (
                ("total", "ring blocks of the window layers' pool: slots x ring x window layers"),
                ("held", "ring blocks live slots hold (a slot holds its ring from admission "
                         "to release, whatever its context)"),
                ("in_use", "ring blocks that hold a key of a live request"),
            )}
        self._moe_rows = r.counter(
            "nxdi_moe_rows_routed_total",
            "token rows the split serving step routed to an expert: real "
            "token positions x expert layers x experts per token; choices "
            "MADE, wherever the expert lies: under a held share "
            "(nxdi_moe_experts_held) only the device knows which land here",
            labels=("program",))
        self._moe_experts = r.counter(
            "nxdi_moe_experts_hit_total",
            "experts whose weights a dispatch of the split serving step "
            "streamed, summed over expert layers: EVERY expert a layer holds "
            "here (the held share's, where the layer holds one), "
            "since the decode strategy computes all of them (and at 48 rows "
            "top-1 of 16 hits 15.3 on average); not a count of the distinct "
            "experts with a live row, which only the device knows",
            labels=("program",))
        self._moe_held = r.gauge(
            "nxdi_moe_experts_held",
            "routed experts of an expert layer whose weights this program "
            "holds, of the published count in the label: less than it under a "
            "held share (one rank of an expert-parallel group served without "
            "its exchange: modules/moe.MoESpec.held_experts), else equal. Set "
            "once, when a session is built",
            labels=("of",))
        self._loop_passes = r.counter(
            "nxdi_loop_layer_passes_total",
            "layer passes the dispatches of the split serving step ran over a "
            "looped stack (models/ouro.py): dispatches x loop_steps x layers, "
            "each pass with a K/V stream of its own; counted on the host from "
            "what the step knows. Absent for a stack that runs once",
            labels=("program",))
        self._kv_streams = r.gauge(
            "nxdi_kv_streams",
            "K/V streams a token holds in the paged pool of a looped stack: "
            "loop_steps x layers (builder.cache_layers()). Set at every pass "
            "of a session over a looped stack (a session built while "
            "recording was off sets it at its first recorded pass)")
        self._moe_grouped_rows = r.counter(
            "nxdi_moe_grouped_rows_total",
            "the routed token rows of nxdi_moe_rows_routed_total by the expert "
            "strategy the pass's program was traced with (modules/moe.expert_path): "
            "kernel = dropless grouped through ops/grouped_matmul.py on the "
            "stacked weights in place, ragged_dot = grouped through "
            "jax.lax.ragged_dot, dense = every expert over every row (capacity, "
            "fused: the two configured strategies)",
            labels=("program", "path"))
        self._moe_sorted_rows = r.counter(
            "nxdi_moe_sorted_rows_total",
            "the token rows a pass's grouped expert sort carried (path kernel "
            "or ragged_dot of nxdi_moe_grouped_rows_total; a dense pass sorts "
            "nothing and counts nothing): kind=live the rows of real positions "
            "(what nxdi_moe_rows_routed_total counts), kind=padding the rows "
            "of the pass's padded positions (a live row's tail, a row that "
            "sits the pass out: x expert layers x experts per token) that the "
            "sort put in no group, so that the grouped products never visit "
            "them; padding is counted ONLY for a program whose expert layers "
            "were traced with the pass's real positions (models/base."
            "expert_positions -> modules/moe.moe_layer's valid, noted at "
            "trace time): a program that routes its padded positions like "
            "real ones reads 0",
            labels=("program", "kind"))
        self._block_row_passes = r.counter(
            "nxdi_block_row_passes_total",
            "a block-step model (runtime/block_step.py): live rows x passes "
            "of the block step, kind=denoise (predicts at the masked "
            "positions and reveals the most confident) or kind=commit (runs "
            "the finished block once more; its K and V stay)",
            labels=("kind",))
        self._block_positions = r.counter(
            "nxdi_block_positions_total",
            "positions the block step ran: live rows x block length, a pass")
        self._block_blocks = r.counter(
            "nxdi_block_blocks_committed_total", "blocks a commit pass finished")
        self._block_tokens = r.counter(
            "nxdi_block_tokens_committed_total",
            "tokens the commits appended to their requests' generated tokens "
            "(a block's positions the prompt did not fill, cut at the budget "
            "and after an EOS)")
        self._decode_kv_blocks = r.counter(
            "nxdi_decode_kv_blocks_total",
            "pool blocks of the decoding rows per decode dispatch of the "
            "split serving step: kind=live, the blocks their contexts hold; "
            "kind=walked, the block-table entries the paged decode kernel's "
            "kv axis attends for them (whole groups of blocks up to a row's "
            "last live one). live / walked = the share of attended KV that "
            "was live", labels=("kind",))
        self._chunk_kv_blocks = r.counter(
            "nxdi_chunk_kv_blocks_total",
            "pool blocks of the prefilling rows per chunk pass of the split "
            "serving step: kind=live, the blocks their causal contexts hold "
            "with the chunk in; kind=walked, the block-table entries the "
            "paged prefill kernel attends for them (whole groups of blocks up "
            "to a row's frontier; every entry of the table where the kernel "
            "keeps a block a grid step). walked / live = how far the walk is "
            "from the context", labels=("kind",))
        self._chunk_kv_write_blocks = r.counter(
            "nxdi_chunk_kv_write_blocks_total",
            "pool blocks the paged KV write of a chunk pass moved, where it "
            "moves whole blocks (modules/block_kvcache: chunk widths, head_dim "
            "on the 128 lanes): kind=whole, blocks a row's tokens cover and "
            "the write stores as they are; kind=merged, a row's first or last "
            "block that it read and merged with what the pool held. Counted a "
            "layer and a stream once", labels=("kind",))
        self._decode_kv_write_rows = r.counter(
            "nxdi_decode_kv_write_rows_total",
            "live rows of the decode passes of the split serving step over a "
            "paged cache, by the form their paged KV write took "
            "(modules/block_kvcache.write_form, asked by the session as the "
            "program asks it): form=kernel, the paged decode kernel placed "
            "the row's token in the block it attends; form=per_head, a "
            "scatter with an index row a (row, position, head); form=window, "
            "a scatter with the heads in its window", labels=("form",))
        self._occupancy = r.gauge(
            "nxdi_batch_occupancy", "live rows in the last decode dispatch")
        self._kv_pool = r.gauge(
            "nxdi_kv_pool_bytes", "total paged-pool HBM (cache dtype)")
        self._kv_free = r.gauge(
            "nxdi_kv_free_bytes", "free + evictable paged-pool HBM")
        self._accept = r.histogram(
            "nxdi_spec_accept_len",
            "tokens committed per speculation round (sums to committed "
            "decode tokens)", buckets=metrics_mod.ACCEPT_LEN_BUCKETS)
        self._spec_draft_len = r.histogram(
            "nxdi_spec_draft_len",
            "tokens drafted per speculation round (sums to the drafted-"
            "token total a measured acceptance rate divides by)",
            buckets=metrics_mod.DRAFT_LEN_BUCKETS)
        self._spec_ewma = r.histogram(
            "nxdi_spec_accept_ewma",
            "per-request draft-acceptance-rate EWMA observed after each "
            "speculation round", buckets=metrics_mod.SPEC_EWMA_BUCKETS)
        self._step_host_ms = r.histogram(
            "nxdi_step_host_ms",
            "host-side bookkeeping per serving step (scheduling, descriptor "
            "build, commits, telemetry — everything except the blocking part "
            "of the token fetch)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._step_fetch_wait_ms = r.histogram(
            "nxdi_step_fetch_wait_ms",
            "blocking wait on the step's consumed token fetch (under "
            "pipelined dispatch this is only what the host/device overlap "
            "did not cover)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._host_frac = r.gauge(
            "nxdi_serving_host_frac",
            "cumulative host-time fraction of serving step wall time "
            "(host_ms / (host_ms + fetch_wait_ms)); ~1.0 means the host, "
            "not the chip, is the serving bottleneck")
        self._host_ms_sum = 0.0
        self._fetch_wait_ms_sum = 0.0
        self._mixed = r.histogram(
            "nxdi_mixed_step_rows",
            "ragged mixed-step dispatch composition: prefill_rows / "
            "decode_rows / padded_slots / query_tokens per dispatch (each "
            "label's observation count == mixed dispatches)",
            labels=("kind",), buckets=metrics_mod.MIXED_STEP_BUCKETS)
        # --- multi-replica router family (runtime/router.py) --------------
        # all host-side router bookkeeping: placement decisions, failovers,
        # per-replica load gauges, and the per-step occupancy-spread
        # histogram the balance contract is judged by
        self._router_placements = r.counter(
            "nxdi_router_placements_total",
            "router placement decisions by policy and reason (fresh / "
            "failover / spill = first-choice replica refused capacity)",
            labels=("policy", "reason"))
        self._router_failovers = r.counter(
            "nxdi_router_failovers_total",
            "requests re-queued off a failed replica (they resume from "
            "committed host state on a surviving replica)",
            labels=("cause",))
        self._router_rejected = r.counter(
            "nxdi_router_rejected_total",
            "requests refused by router front-door validation "
            "(terminal REJECTED, never placed on a replica)",
            labels=("reason",))
        self._router_queue = r.gauge(
            "nxdi_router_queue_depth",
            "requests waiting in the router's global placement queue")
        self._router_occ = r.gauge(
            "nxdi_router_replica_occupancy",
            "live rows on this replica", labels=("replica",))
        self._router_qd = r.gauge(
            "nxdi_router_replica_queue_depth",
            "active + re-admission-waiting requests on this replica",
            labels=("replica",))
        self._router_health = r.gauge(
            "nxdi_router_replica_health",
            "replica health state (2 = healthy, 1 = degraded, 0 = dead)",
            labels=("replica",))
        self._router_elastic = r.counter(
            "nxdi_router_elastic_total",
            "elastic fleet events: replicas added to / retired from the "
            "pool mid-run (retire = placement stopped, drain begun; "
            "retire_done = drained, worker joined, mesh freed)",
            labels=("event",))
        self._router_spread = r.histogram(
            "nxdi_router_occupancy_spread",
            "max - min live rows across alive replicas per router step "
            "(0 == perfectly balanced; the rebalance signal)",
            buckets=metrics_mod.ROUTER_SPREAD_BUCKETS)
        # --- disaggregated prefill tier (router_prefill_replicas) ----------
        # the KV hand-off as a failure domain: attempt/retry/failure census
        # (failures by typed reason — handoff_corrupt / handoff_truncated /
        # handoff_exhausted / ...), per-hand-off wall time, tier member
        # health, and the LOUD local-prefill fallback counter (every
        # placement served by a decode replica's own prefill because the
        # whole tier is dead)
        self._handoff_attempts = r.counter(
            "nxdi_handoff_attempts_total",
            "KV hand-off attempts (prefill + extract + inject; retries of "
            "one hand-off count individually)")
        self._handoff_retries = r.counter(
            "nxdi_handoff_retries_total",
            "hand-off attempts that failed in transit and were retried "
            "with capped backoff (bounded by handoff_max_retries)")
        self._handoff_failures = r.counter(
            "nxdi_handoff_failures_total",
            "hand-offs that terminally failed their ONE in-flight request "
            "(typed FAILED(handoff)), by reason",
            labels=("reason",))
        self._handoff_ms = r.histogram(
            "nxdi_handoff_ms",
            "wall time of one completed hand-off (prefill dispatch through "
            "inject, retries included)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._handoff_local = r.counter(
            "nxdi_handoff_local_prefill_total",
            "placements served by the decode replica's LOCAL monolithic "
            "prefill because no prefill-tier member was alive (tier-wide "
            "graceful degradation — loud by design)")
        self._handoff_tier_health = r.gauge(
            "nxdi_handoff_tier_health",
            "prefill-tier member health (2 = healthy, 1 = degraded, "
            "0 = dead)", labels=("replica",))
        self._handoff_tier_alive = r.gauge(
            "nxdi_handoff_tier_alive",
            "alive (healthy + degraded) prefill-tier members; 0 means "
            "every placement falls back to local monolithic prefill")
        # --- thread-per-replica stepping (TpuConfig.router_threading) -----
        # per-replica step wall time + the router's replica-stepping-phase
        # span: overlap_frac = 1 - phase_wall / sum(replica walls) is the
        # measured concurrency win (0 == host-serialized sequential
        # stepping; (N-1)/N == N replicas perfectly overlapped)
        self._replica_step_ms = r.histogram(
            "nxdi_replica_step_ms",
            "one replica's session.step() wall time (host clock; recorded "
            "by the router thread after the per-step barrier)",
            labels=("replica",), buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._router_step_ms = r.histogram(
            "nxdi_router_step_ms",
            "wall time of the router step's replica-stepping phase (all "
            "replicas dispatched, barrier waited)",
            buckets=metrics_mod.LATENCY_MS_BUCKETS)
        self._router_overlap = r.gauge(
            "nxdi_router_step_overlap_frac",
            "cumulative 1 - stepping-phase wall / sum of per-replica step "
            "walls: ~0 = sequential, (N-1)/N = N replicas fully overlapped")
        self._router_step_wall_ms_sum = 0.0
        self._replica_step_ms_sum = 0.0
        # --- workload engine (workload/driver.py + workload/slo.py) -------
        # open-loop traffic bookkeeping: the driver's arrival backlog and
        # admission refusals, and the post-hoc SLO scorer's miss census —
        # all host-side (recorded on the driver/router thread or offline
        # after the run; TPU107-clean by construction)
        self._slo_missed = r.counter(
            "nxdi_slo_missed_total",
            "requests that missed their SLO, by miss kind (ttft / itl / "
            "failed / never_served) and tenant — recorded by the workload "
            "SLO scorer; goodput counts only tokens from requests NOT in "
            "this census",
            labels=("kind", "tenant"))
        self._wl_backlog = r.gauge(
            "nxdi_workload_backlog_depth",
            "arrivals waiting in the open-loop driver's retry backlog "
            "(arrived, offered, refused for capacity — their SLO clocks "
            "keep running)")
        self._wl_refused = r.counter(
            "nxdi_workload_refusals_total",
            "open-loop admission attempts refused for capacity (the "
            "arrival re-queues and retries; a terminal give-up records "
            "nxdi_requests_rejected_total{reason=backlog} instead)",
            labels=("reason",))
        self._jit_traces = r.counter(
            "nxdi_jit_traces_total", "jit traces observed (compiles)",
            labels=("tag",))
        self._sealed_retrace = r.counter(
            "nxdi_sealed_retrace_total",
            "forbidden post-seal retraces (steady-state recompiles)",
            labels=("tag",))

    # ---- lifecycle of the session itself ---------------------------------

    def start(self, profile_dir: Optional[str] = None) -> "TelemetrySession":
        """Turn recording on in the running process: spans, counters, the
        event log and the retrace-guard bridge. Idempotent; the instruments
        are created on the first start. With ``profile_dir`` the device
        profiler (``jax.profiler``) starts too, writing there, unless this
        session already runs it — so a started session can add the profiler
        later by calling ``start(profile_dir=...)`` again. The program's
        spans are ``TraceAnnotation``s: they land in that trace on the
        device trace's clock.

        Requests admitted while the session was stopped have no
        :class:`RequestTrace` and no span-tree node; every lifecycle record
        tolerates them (they count in counters and step spans only)."""
        with self._lock:
            if self.spans is None:
                self._build_instruments()
            if self._jsonl_path and self._jsonl_file is None:
                self._jsonl_file = open(self._jsonl_path, "a")
            if self._listener is None:
                self._listener = self._on_trace
                retrace_guard.add_trace_listener(self._listener)
            self.enabled = True
            if profile_dir is not None and self._profile_dir is None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # TraceAnnotation spans only
                opts.host_tracer_level = 2
                jax.profiler.start_trace(profile_dir, profiler_options=opts)
                self._profile_dir = profile_dir
        return self

    def stop(self) -> Optional[str]:
        """Turn recording off; stop the profiler if :meth:`start` started
        it and return its trace file (``*.xplane.pb``; None without a
        profile). In-flight request traces are dropped and every open span
        is closed at the stop time, so nothing dangles and a later
        :meth:`start` treats those requests as admitted while stopped.
        Last, beside a trace this call stopped, the device scope tables of
        the step programs dispatched while recording
        (:meth:`add_scope_source`): after the profiler, so that the
        lowerings are in no trace."""
        trace_path = profile_dir = None
        with self._lock:
            if self._profile_dir is not None:
                profile_dir, self._profile_dir = self._profile_dir, None
                jax.profiler.stop_trace()
                trace_path = newest_xplane(profile_dir)
            if self.enabled:
                self.enabled = False
                if self._listener is not None:
                    retrace_guard.remove_trace_listener(self._listener)
                    self._listener = None
                self.traces.clear()
                self.spans.close_all(self.clock(), reason="telemetry_stopped")
            if profile_dir is not None:
                tables = {}
                for source in list(self._scope_sources):
                    tables.update(source.device_scope_tables())
                device_scopes.write_tables(profile_dir, tables)
        return trace_path

    def add_scope_source(self, source) -> None:
        """``source.device_scope_tables() -> {key: table}`` names the device
        ops of the step programs it dispatched while this session recorded
        (a serving session registers itself). :meth:`stop` writes them to
        ``<profile_dir>/device_scopes.json`` when it stops a profile it
        started (telemetry/device_scopes.py)."""
        with self._lock:
            self._scope_sources.add(source)

    def close(self) -> None:
        """Release what the session holds outside itself: the retrace
        listener, the JSONL stream, a profiler it started. What it recorded
        stays readable."""
        with self._lock:
            if self._profile_dir is not None:
                self._profile_dir = None
                jax.profiler.stop_trace()
            if self._listener is not None:
                retrace_guard.remove_trace_listener(self._listener)
                self._listener = None
            if self._jsonl_file is not None:
                self._jsonl_file.close()
                self._jsonl_file = None

    def __enter__(self) -> "TelemetrySession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- event log -------------------------------------------------------

    def event(self, etype: str, **fields) -> None:
        if not self.enabled:
            return
        rec = {"ts": self.clock(), "type": etype, **fields}
        with self._lock:
            if (
                self.events.maxlen is not None
                and len(self.events) >= self.events.maxlen
            ):
                # the deque would evict silently — count the drop so a
                # bounded ring on a long drain is an observable condition
                self._dropped_events += 1
                self._tel_dropped.child(("events",)).inc()
            self.events.append(rec)
            if self._jsonl_file is not None:
                # under the lock so concurrent replica threads cannot
                # interleave half-written JSONL lines
                self._jsonl_file.write(json.dumps(rec) + "\n")
                self._jsonl_file.flush()

    # ---- span timeline + SLO monitor plumbing (ISSUE 19) -----------------

    def set_tenants(self, tenant_of: Dict[str, str]) -> None:
        """Map base request ids to tenant names (WorkloadTrace.tenants_of)
        so request spans land on the right ``tenant:*`` track. Without it
        the tenant is parsed from the workload id convention
        ``{tenant}-{NNNN}`` (non-workload ids land on tenant 'default')."""
        if not self.enabled:
            return
        with self._lock:
            self._tenant_of.update(tenant_of)

    def attach_slo_monitor(self, monitor) -> "TelemetrySession":
        """Route first-token / token / terminal records into a live
        :class:`~.slo_monitor.SloMonitor` and bind its gauges to this
        session's registry."""
        if not self.enabled:
            return self
        monitor.bind(self.registry)
        with self._lock:
            self.slo_monitor = monitor
        return self

    def _tenant_track(self, base: str) -> str:
        tenant = self._tenant_of.get(base)
        if tenant is None:
            tenant = base.rsplit("-", 1)[0] if "-" in base else "default"
        return f"tenant:{tenant}"

    def _req_ids(self, req_id: str):
        """(base, incarnation, track, root span id, incarnation span id)."""
        base, inc = _split_incarnation(req_id)
        track = self._tenant_track(base)
        root = f"req:{base}"
        return base, inc, track, root, f"{root}/i{inc}"

    def _close_request_spans(self, req_id: str, now: float, reason: str) -> None:
        """Close every open span of one incarnation (phase children first),
        then the incarnation, then the request root — the terminal record's
        span-side mirror. Called with self._lock held."""
        if self.spans is None:
            return
        base, _inc, _track, root, inode = self._req_ids(req_id)
        for phase in ("queue", "prefill", "handoff", "decode"):
            self.spans.end(f"{inode}/{phase}", now)
        self.spans.end(inode, now, reason=reason)
        self.spans.end(root, now, reason=reason)

    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        """The whole run as Chrome trace-event JSON (Perfetto-loadable):
        one process track per tenant / replica / prefill-tier member /
        driver, spans as complete events, kills and health transitions as
        instants, failover continuations as flow arrows. Safe against an
        ACTIVE drain: the span state is snapshotted under the session
        RLock before any serialization (the ISSUE-19 bugfix — same
        family-copy pattern as the metrics exposition), so a racing
        replica thread cannot half-mutate what gets written."""
        if self.spans is None:
            trace = {
                "traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": 0},
            }
        else:
            with self._lock:
                now = self.clock()
                spans, instants, flows = self.spans.snapshot()
                dropped = self.spans.dropped
            trace = spans_mod.to_chrome_trace(
                spans, instants, flows, now=now, dropped=dropped
            )
        if path:
            spans_mod.dump_chrome_trace(trace, path)
        return trace

    def span_tree(self) -> Dict[str, tuple]:
        """Order-free comparable span tree (the determinism pin)."""
        if self.spans is None:
            return {}
        return self.spans.span_tree()

    def span(self, name: str, **fields):
        """Named step-timeline scope, used as ``with tel.span(...) as sp``:
        a ``jax.profiler.TraceAnnotation`` on the host timeline (on the
        device trace's clock when the profiler runs) plus a structured span
        event carrying name, start, end, the enclosing span of this thread
        (``parent``) and the ``step`` it belongs to (given, or inherited
        from the enclosing span). ``sp.dur_s`` reads the duration after the
        block. Bounds the HOST side only: no sync is forced. A stopped
        session returns one shared null context — no clock read, no
        allocation."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, fields)

    # ---- request lifecycle -----------------------------------------------

    def request_submitted(self, req_id: str) -> None:
        if not self.enabled:
            return
        self._submitted.inc()
        with self._lock:
            now = self.clock()
            self.traces[req_id] = RequestTrace(
                req_id=req_id, t_submit=now
            )
            base, inc, track, root, inode = self._req_ids(req_id)
            self.spans.begin(root, f"request {base}", track, now, lane=base)
            self.spans.begin(
                inode, f"incarnation {inc}", track, now,
                parent_id=root, lane=base, incarnation=inc,
            )
            self.spans.begin(
                f"{inode}/queue", "queue", track, now,
                parent_id=inode, lane=base,
            )
            if inc > 0:
                # destination endpoint of the failover arrow the matching
                # router_failover opened (flow ids number by failover index)
                self.spans.flow(
                    f"flow:{base}:{inc - 1}", "f", track, now, lane=base
                )
            mon = self.slo_monitor
        if mon is not None:
            # a retry supersedes any premature non-finished verdict (the
            # driver re-submits after a `dropped:no_slot` admission refusal)
            mon.note_submitted(req_id)
        self.event("request_submitted", req_id=req_id)

    def request_admitted(self, req_id: str, cached_prefix_tokens: int = 0) -> None:
        if not self.enabled:
            return
        with self._lock:
            tr = self.traces.get(req_id)
            if tr is not None and tr.t_admit is not None:
                # RE-admission after a pool-exhaustion eviction: the request
                # already holds its admission accounting (t_admit, the
                # admitted counter) — re-counting would make admitted >
                # submitted and shift queue-wait/TTFT baselines. Only the
                # event log records the resumption.
                base, _inc, track, _root, _inode = self._req_ids(req_id)
                self.spans.instant(
                    "readmitted", track, self.clock(), lane=base,
                    req_id=req_id,
                )
                self.event("request_readmitted", req_id=req_id,
                           cached_prefix_tokens=cached_prefix_tokens)
                return
            self._admitted.inc()
            if tr is not None:
                tr.t_admit = self.clock()
                tr.cached_prefix_tokens = cached_prefix_tokens
        self.event("request_admitted", req_id=req_id,
                   cached_prefix_tokens=cached_prefix_tokens)

    def request_dropped(self, req_id: str, reason: str) -> None:
        if not self.enabled:
            return
        self._dropped.child((reason,)).inc()
        with self._lock:
            now = self.clock()
            tr = self.traces.pop(req_id, None)
            if tr is not None:
                tr.finish_reason = "dropped"
                tr.t_finish = now
                self.completed.append(tr)
            self._close_request_spans(req_id, now, f"dropped:{reason}")
            mon = self.slo_monitor
        if mon is not None:
            mon.note_finish(req_id, "dropped", now)
        self.event("request_dropped", req_id=req_id, reason=reason)

    def request_rejected(self, req_id: str, reason: str) -> None:
        """Admission validation refused this request (terminal REJECTED):
        malformed input — out-of-vocab token ids, empty prompt, over-long
        prompt, invalid budget — never reaches a dispatch."""
        if not self.enabled:
            return
        self._rejected.child((reason,)).inc()
        with self._lock:
            now = self.clock()
            tr = self.traces.pop(req_id, None)
            if tr is not None:
                tr.finish_reason = "rejected"
                tr.t_finish = now
                self.completed.append(tr)
            self._close_request_spans(req_id, now, f"rejected:{reason}")
            mon = self.slo_monitor
        if mon is not None:
            mon.note_finish(req_id, f"rejected:{reason}", now)
        self.event("request_rejected", req_id=req_id, reason=reason)

    def request_preempted(self, req_id: str) -> None:
        """NON-terminal pool-exhaustion eviction: the request re-queues for
        re-admission (aging), so its trace stays open — only the preemption
        counter and the event log record the eviction."""
        if not self.enabled:
            return
        self._preempted.inc()
        with self._lock:
            base, _inc, track, _root, inode = self._req_ids(req_id)
            now = self.clock()
            self.spans.instant(
                "preempted", track, now, lane=base, req_id=req_id
            )
            # the eviction cuts the in-flight phase short — the preempted
            # gap reads as bare incarnation time between the instant and
            # the resumed activity
            for phase in ("prefill", "decode"):
                self.spans.end(f"{inode}/{phase}", now)
        self.event("request_preempted", req_id=req_id)

    def row_quarantined(self, req_id: str) -> None:
        """A consumed row carried the non-finite sentinel: the serving
        session fails the request and scrubs+releases its KV. The terminal
        accounting rides the matching request_finished("non_finite")."""
        if not self.enabled:
            return
        self._quarantined.inc()
        with self._lock:
            base, _inc, track, _root, _inode = self._req_ids(req_id)
            self.spans.instant(
                "quarantined", track, self.clock(), lane=base, req_id=req_id
            )
        self.event("row_quarantined", req_id=req_id)

    def dispatch_retry(self, label: str) -> None:
        if not self.enabled:
            return
        self._retries.inc()
        self.event("dispatch_retry", label=label)

    def deadline_exceeded(self, req_id: str, overrun_s: float) -> None:
        """Observed at drop time: how late past its TTL the request was when
        the session noticed (bounded by step latency — deadlines are checked
        at step boundaries). Terminal accounting rides
        request_finished("deadline_exceeded")."""
        if not self.enabled:
            return
        self._deadline_overrun.observe(max(0.0, overrun_s) * 1e3)
        self.event("deadline_exceeded", req_id=req_id, overrun_s=overrun_s)

    def watchdog_preempted(self, req_id: str) -> None:
        if not self.enabled:
            return
        self._watchdog_preempt.inc()
        self.event("watchdog_preempted", req_id=req_id)

    def watchdog_tripped(
        self, no_progress_steps: int, replica: Optional[int] = None
    ) -> None:
        if not self.enabled:
            return
        self._watchdog_trips.inc()
        if replica is not None:
            with self._lock:
                self.spans.instant(
                    "watchdog_tripped", f"replica:{int(replica)}",
                    self.clock(), no_progress_steps=no_progress_steps,
                )
        self.event("watchdog_tripped", no_progress_steps=no_progress_steps)

    def prefill_dispatch(self, req_id: str, n_tokens: int) -> None:
        """One prefill pass advanced this request by ``n_tokens`` prompt
        tokens (whole-prompt CTE counts as one chunk)."""
        if not self.enabled:
            return
        self._prefill_tokens.inc(n_tokens)
        with self._lock:
            tr = self.traces.get(req_id)
            if tr is not None:
                tr.prefill_chunks += 1
                if tr.t_first_dispatch is None:
                    tr.t_first_dispatch = self.clock()
                    self._queue_wait.observe(
                        (tr.t_first_dispatch - tr.t_submit) * 1e3
                    )
                    base, _inc, track, _root, inode = self._req_ids(req_id)
                    self.spans.end(f"{inode}/queue", tr.t_first_dispatch)
                    self.spans.begin(
                        f"{inode}/prefill", "prefill", track,
                        tr.t_first_dispatch, parent_id=inode, lane=base,
                    )

    def request_first_token(self, req_id: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            tr = self.traces.get(req_id)
            if tr is not None and tr.t_first_token is not None:
                # the resumed prefill of a RE-admitted request emits a token
                # the same way a fresh admission does, but the request's
                # first token happened before its eviction: record a regular
                # token observation (its "ITL" spans the preempted gap — the
                # latency the user actually saw) and leave t_first_token/
                # TTFT alone, so "TTFT count == finished requests" holds
                # under preemption.
                self.request_tokens(req_id, 1)
                return
            now = self.clock()
            self._tokens.inc()
            if tr is not None:
                if tr.t_first_dispatch is None:
                    # non-chunked admission: prefill dispatch == first one
                    tr.t_first_dispatch = now
                    self._queue_wait.observe((now - tr.t_submit) * 1e3)
                tr.t_first_token = tr.t_last_token = now
                tr.tokens += 1
                self._ttft.observe((now - tr.t_submit) * 1e3)
                self._chunks_per_req.observe(max(1, tr.prefill_chunks))
            base, _inc, track, _root, inode = self._req_ids(req_id)
            if self.spans.is_open(f"{inode}/prefill"):
                self.spans.end(f"{inode}/prefill", now)
            else:
                self.spans.end(f"{inode}/queue", now)
            if self.spans.is_open(inode):
                # a request admitted while the session was stopped has no
                # incarnation span to hang a decode phase on
                self.spans.begin(
                    f"{inode}/decode", "decode", track, now,
                    parent_id=inode, lane=base,
                )
            mon = self.slo_monitor
        if mon is not None:
            mon.note_first_token(req_id, now)
        self.event("first_token", req_id=req_id)

    def request_tokens(self, req_id: str, n: int) -> None:
        """``n`` decode tokens observed for this request in one fetch; ITL is
        the elapsed time since the previous observation amortized over n."""
        if not self.enabled or n <= 0:
            return
        now = self.clock()
        self._tokens.inc(n)
        mon = None
        with self._lock:
            tr = self.traces.get(req_id)
            if tr is not None and tr.t_last_token is not None:
                per_tok = (now - tr.t_last_token) / n
                for _ in range(n):
                    self._itl.observe(per_tok * 1e3)
                    tr.itl_s.append(per_tok)
                tr.t_last_token = now
                tr.tokens += n
                mon = self.slo_monitor
        if mon is not None:
            mon.note_tokens(req_id, n, now)

    def tokens_generated(self, n: int) -> None:
        """Bare token count for host loops with no request identity
        (application.generate, the fused-spec loop)."""
        if not self.enabled or n <= 0:
            return
        self._tokens.inc(n)

    def request_finished(self, req_id: str, reason: str = "length") -> None:
        # NOTE: preemption is counted at EVICTION time (request_preempted) —
        # it is no longer a terminal event (the session re-admits with
        # aging); reason="preempted" here means re-admission was impossible
        if not self.enabled:
            return
        self._finished.child((reason,)).inc()
        with self._lock:
            now = self.clock()
            tr = self.traces.pop(req_id, None)
            if tr is not None:
                tr.finish_reason = reason
                tr.t_finish = now
                self.completed.append(tr)
            self._close_request_spans(req_id, now, reason)
            mon = self.slo_monitor
        if mon is not None:
            mon.note_finish(req_id, reason, now)
        self.event("request_finished", req_id=req_id, reason=reason)

    # ---- step-level ------------------------------------------------------

    def step(self, kind: str) -> None:
        if not self.enabled:
            return
        self._steps.child((kind,)).inc()

    def bucket_dispatch(self, model: str, bucket: int) -> None:
        if not self.enabled:
            return
        self._bucket.child((model, str(int(bucket)))).inc()

    def prefill_pass(self, real_tokens: int, padded_tokens: int, dispatches: int = 1,
                     rows=(0, 0)) -> None:
        """One chunk pass of the split serving step: the prompt tokens it
        advanced, the padded positions the program ran besides, the
        dispatches of the chunk program it took
        (real + padded == dispatches x chunk rows x q_bucket), and of the
        program's ``rows`` those that held a request and those that ran
        empty (live + empty == dispatches x chunk rows)."""
        if not self.enabled:
            return
        self._prefill_real.inc(real_tokens)
        self._prefill_padded.inc(padded_tokens)
        self._prefill_dispatches.inc(dispatches)
        self._chunk_rows.child(("live",)).inc(rows[0])
        self._chunk_rows.child(("empty",)).inc(rows[1])

    def chunk_step(self, decode_behind: bool) -> None:
        """One step of the split serving path that holds a chunk pass, told
        before the pass's tokens are waited for: ``decode_behind`` says that
        the step's decode pass is already dispatched behind it. Also a field
        of the enclosing span, the step's."""
        if not self.enabled:
            return
        self._chunk_steps.inc()
        if decode_behind:
            self._chunk_steps_behind.inc()
        if self._span_stack.open:
            self._span_stack.open[-1].note(decode_behind_chunk=decode_behind)

    def decode_pass(self, rows: int, slots: int) -> None:
        """One decode dispatch of the split serving step: its live rows and
        the slot batch the program ran over."""
        if not self.enabled:
            return
        self._decode_rows.inc(rows)
        self._decode_slots.inc(slots)

    def ssm_pass(self, program: str, rows: int, state_bytes: int, resets: int = 0,
                 kind: str = "ssm") -> None:
        """One dispatch of the split serving step over a model whose layers
        keep a recurrent state a slot: the rows whose state it advanced
        (``program``: "decode" or "chunk"), of those the rows it started
        from zero, and the bytes the state of all slots holds. ``kind`` is
        the state's ``KIND``: ``ssm`` (state-space layers, ``nxdi_ssm_*``),
        ``kda`` (delta-rule linear attention, ``nxdi_kda_*``) or ``power``
        (power retention, ``nxdi_power_*``). Counted from what the step
        already knows."""
        if not self.enabled:
            return
        rows_total, resets_total, bytes_held = {
            "kda": (self._kda_rows, self._kda_resets, self._kda_bytes),
            "power": (self._power_rows, self._power_resets, self._power_bytes),
        }.get(kind, (self._ssm_rows, self._ssm_resets, self._ssm_bytes))
        rows_total.child((program,)).inc(rows)
        bytes_held.set(state_bytes)
        if resets:
            resets_total.inc(resets)

    def kda_chunk_rows(self, advanced: int, skipped: int) -> None:
        """One chunk pass whose KDA layers ran the chunk-scan kernel: the
        rows x layers whose state it read and wrote, and those it moved
        nothing for."""
        if not self.enabled:
            return
        self._kda_chunk_rows.child(("advanced",)).inc(advanced)
        self._kda_chunk_rows.child(("skipped",)).inc(skipped)

    def carry_pass(self, program: str, rows: int) -> None:
        """One pass of the split serving step over a model that keeps a
        one-token carry per slot: the rows whose carry it advanced."""
        if not self.enabled:
            return
        self._carry_rows.child((program,)).inc(rows)

    def latent_pass(self, program: str, latents: int) -> None:
        """One pass of the split serving step over a latent pool: the
        latents it wrote (token positions x latent-attention layers)."""
        if not self.enabled:
            return
        self._latent_tokens.child((program,)).inc(latents)

    def sparse_pass(self, program: str, written: int, scored: int, attended: int) -> None:
        """One pass of the split serving step over layers of learned sparse
        attention: the indexer keys it wrote, the live keys its indexers
        scored and the keys its attention read after the selection."""
        if not self.enabled:
            return
        self._index_keys.inc(written)
        self._sparse_scored.child((program,)).inc(scored)
        self._sparse_attended.child((program,)).inc(attended)

    def index_key_blocks(self, program: str, walked: int, skipped: int) -> None:
        """One pass of the split serving step whose indexers scored: the
        blocks of index keys they walked, and those of the bucket's width
        (over every row of the pass's dispatches) they did not."""
        if not self.enabled:
            return
        self._index_key_blocks.child((program, "walked")).inc(walked)
        self._index_key_blocks.child((program, "skipped")).inc(skipped)

    def window_pass(self, program: str, live: int, attended: int, full_layers: int,
                    window_layers: int, recycled: int) -> None:
        """One pass of the split serving step over a stack of window and full
        attention layers: ``live`` keys a layer over its rows and queries,
        ``attended`` of them inside a window layer's window, and the ring
        blocks the pass wrote over (summed over the window layers)."""
        if not self.enabled:
            return
        self._attn_keys_live.child((program, "full")).inc(live * full_layers)
        self._attn_keys_attended.child((program, "full")).inc(live * full_layers)
        self._attn_keys_live.child((program, "window")).inc(live * window_layers)
        self._attn_keys_attended.child((program, "window")).inc(attended * window_layers)
        self._window_recycled.inc(recycled)

    def window_pool(self, total: int, held: int, in_use: int) -> None:
        if not self.enabled:
            return
        for name, value in (("total", total), ("held", held), ("in_use", in_use)):
            self._window_blocks[name].set(value)

    def loop_pass(self, program: str, dispatches: int, streams: int) -> None:
        """One pass of the split serving step over a looped stack of
        ``streams`` = loop_steps x layers layer passes a dispatch, each with a
        K/V stream of its own in the pool."""
        if not self.enabled:
            return
        self._loop_passes.child((program,)).inc(dispatches * streams)
        self._kv_streams.set(streams)

    def moe_held(self, held: int, published: int) -> None:
        """A session over a model with routed experts: how many of each
        layer's published experts the program holds."""
        if not self.enabled:
            return
        self._moe_held.child((str(int(published)),)).set(held)

    def moe_pass(self, program: str, rows_routed: int, experts: int, path: str,
                 rows_left_out: int = 0) -> None:
        """One pass of the split serving step over a model with routed
        experts: token rows routed (x layers x experts per token), the
        experts whose weights its dispatches streamed (every held expert of
        every expert layer, per dispatch), the expert strategy its
        program holds (``path``: modules/moe.expert_path) and, of a grouped
        strategy, the rows of padded positions its sort left out of every
        group (0 for a program that routes them like real ones)."""
        if not self.enabled:
            return
        self._moe_rows.child((program,)).inc(rows_routed)
        self._moe_experts.child((program,)).inc(experts)
        self._moe_grouped_rows.child((program, path)).inc(rows_routed)
        if path != "dense":
            self._moe_sorted_rows.child((program, "live")).inc(rows_routed)
            self._moe_sorted_rows.child((program, "padding")).inc(rows_left_out)

    def block_pass(self, denoise_rows: int, commit_rows: int, positions: int) -> None:
        """One dispatch of a block-step model's decode step: its live rows
        by the kind of pass each was in, and the positions it ran."""
        if not self.enabled:
            return
        self._block_row_passes.child(("denoise",)).inc(denoise_rows)
        self._block_row_passes.child(("commit",)).inc(commit_rows)
        self._block_positions.inc(positions)

    def block_commit(self, tokens: int) -> None:
        """A fetched commit pass of one row: one block, ``tokens`` appended."""
        if not self.enabled:
            return
        self._block_blocks.inc()
        self._block_tokens.inc(tokens)

    def kv_blocks(self, program: str, live: int, walked: int) -> None:
        """One pass ("decode" or "chunk") of the split serving step over a
        paged cache: the pool blocks its rows' contexts hold, and the
        block-table entries the program's paged kernel attends."""
        if not self.enabled:
            return
        counter = self._decode_kv_blocks if program == "decode" else self._chunk_kv_blocks
        counter.child(("live",)).inc(live)
        counter.child(("walked",)).inc(walked)

    def kv_write_blocks(self, whole: int, merged: int) -> None:
        """One chunk pass whose paged KV write moved whole blocks: the blocks
        it stored as they are, and the edge blocks it read and merged."""
        if not self.enabled:
            return
        self._chunk_kv_write_blocks.child(("whole",)).inc(whole)
        self._chunk_kv_write_blocks.child(("merged",)).inc(merged)

    def kv_write_rows(self, form: str, rows: int) -> None:
        """One decode pass over a paged cache: its live rows, under the form
        their paged KV write took."""
        if not self.enabled:
            return
        self._decode_kv_write_rows.child((form,)).inc(rows)

    def pool_gauges(self, occupancy: int, kv_pool_bytes: int, kv_free_bytes: int) -> None:
        if not self.enabled:
            return
        self._occupancy.set(occupancy)
        self._kv_pool.set(kv_pool_bytes)
        self._kv_free.set(kv_free_bytes)

    def step_timing(
        self,
        host_ms: float,
        fetch_wait_ms: float,
        replica: Optional[int] = None,
    ) -> None:
        """Host-vs-device split of ONE serving step, both measured with the
        session clock on the host (no device syncs added — the fetch timed
        here is one the runtime already performs): ``host_ms`` is the step's
        wall time minus the blocking fetch wait. The
        ``nxdi_serving_host_frac`` gauge tracks the cumulative fraction —
        the host-gap number the async-pipelining work drives down
        (PERF.md). With ``replica`` set (router-managed sessions) the split
        also lands as host / fetch_wait phase spans on that replica's
        timeline track."""
        if not self.enabled:
            return
        self._step_host_ms.observe(host_ms)
        self._step_fetch_wait_ms.observe(fetch_wait_ms)
        with self._lock:
            # the cumulative sums are plain floats shared by every replica
            # step thread: += is a read-modify-write, locked like the
            # instrument internals (CONC601/CONC603)
            self._host_ms_sum += max(0.0, host_ms)
            self._fetch_wait_ms_sum += max(0.0, fetch_wait_ms)
            denom = self._host_ms_sum + self._fetch_wait_ms_sum
            if denom > 0:
                self._host_frac.set(self._host_ms_sum / denom)
            if replica is not None:
                # one worker thread per replica steps its own session, so
                # the per-replica phase counter is deterministic across
                # sequential and threaded drains (the determinism pin)
                track = f"replica:{int(replica)}"
                pc = self._phase_count.get(track, 0) + 1
                self._phase_count[track] = pc
                now = self.clock()
                h_s = max(0.0, host_ms) / 1e3
                f_s = max(0.0, fetch_wait_ms) / 1e3
                self.spans.begin(
                    f"{track}/t{pc}/host", "host", track,
                    now - h_s - f_s, lane="phases",
                )
                self.spans.end(f"{track}/t{pc}/host", now - f_s)
                self.spans.begin(
                    f"{track}/t{pc}/fetch_wait", "fetch_wait", track,
                    now - f_s, lane="phases",
                )
                self.spans.end(f"{track}/t{pc}/fetch_wait", now)
        self.event(
            "step_timing", host_ms=host_ms, fetch_wait_ms=fetch_wait_ms
        )

    def mixed_step(
        self,
        prefill_rows: int,
        decode_rows: int,
        padded_slots: int,
        query_tokens: int,
    ) -> None:
        """Composition of ONE ragged mixed dispatch (serving_ragged): rows
        serving prefill chunks, rows serving decode, padded packed slots and
        real query tokens in the dispatched total-token bucket. Each label's
        observation COUNT equals the number of mixed dispatches (pinned by
        test); padded_slots/(padded_slots+query_tokens) is the padded-token
        fraction the split dispatch was paying per phase."""
        if not self.enabled:
            return
        self._mixed.child(("prefill_rows",)).observe(prefill_rows)
        self._mixed.child(("decode_rows",)).observe(decode_rows)
        self._mixed.child(("padded_slots",)).observe(padded_slots)
        self._mixed.child(("query_tokens",)).observe(query_tokens)

    # ---- multi-replica router (runtime/router.py) ------------------------

    def router_placement(
        self,
        policy: str,
        reason: str,
        req_id: Optional[str] = None,
        replica: Optional[int] = None,
    ) -> None:
        """One placement decision: a request was bound to a replica under
        ``policy`` (``reason``: fresh / failover / spill). With identity
        attached, the decision lands as a placement instant on the request's
        timeline and stamps the replica onto its open incarnation span."""
        if not self.enabled:
            return
        self._router_placements.child((policy, reason)).inc()
        if req_id is not None:
            with self._lock:
                base, _inc, track, _root, inode = self._req_ids(req_id)
                attrs = {"policy": policy, "reason": reason}
                if replica is not None:
                    attrs["replica"] = int(replica)
                self.spans.instant(
                    "placement", track, self.clock(), lane=base, **attrs
                )
                self.spans.set_attrs(inode, **attrs)
        self.event("router_placement", policy=policy, reason=reason,
                   req_id=req_id, replica=replica)

    def router_failover(self, req_id: str, cause: str) -> None:
        """One request re-queued off a failed replica; it resumes from its
        committed host state on a surviving replica (byte-identical greedy).
        ``req_id`` is the BASE id: the failed incarnation's spans close here
        and a flow arrow opens toward the next incarnation's submit."""
        if not self.enabled:
            return
        self._router_failovers.child((cause,)).inc()
        with self._lock:
            now = self.clock()
            n = self._failover_count.get(req_id, 0)
            base, _inc, track, root, _inode = self._req_ids(req_id)
            inode = f"{root}/i{n}"
            for phase in ("queue", "prefill", "handoff", "decode"):
                self.spans.end(f"{inode}/{phase}", now)
            self.spans.end(inode, now, failover_cause=cause)
            self.spans.instant(
                "failover", track, now, lane=base, cause=cause,
                incarnation=n,
            )
            self.spans.flow(f"flow:{base}:{n}", "s", track, now, lane=base)
            self._failover_count[req_id] = n + 1
        self.event("router_failover", req_id=req_id, cause=cause)

    def router_rejected(self, req_id: str, reason: str) -> None:
        if not self.enabled:
            return
        self._router_rejected.child((reason,)).inc()
        self.event("router_rejected", req_id=req_id, reason=reason)

    def router_elastic(self, event: str, replica: int) -> None:
        """One elastic fleet event: ``add`` (warmed handle joined pool +
        placement), ``retire`` (placement stopped, drain begun) or
        ``retire_done`` (drained, worker joined, replica left the pool)."""
        if not self.enabled:
            return
        self._router_elastic.child((event,)).inc()
        self.event("router_elastic", event=event, replica=replica)

    def router_replica_gauges(
        self, replica_id: int, occupancy: int, queue_depth: int, health: int
    ) -> None:
        if not self.enabled:
            return
        lab = (str(int(replica_id)),)
        self._router_occ.child(lab).set(occupancy)
        self._router_qd.child(lab).set(queue_depth)
        self._router_health.child(lab).set(health)
        with self._lock:
            prev = self._replica_health_seen.get(int(replica_id))
            if prev is not None and prev != int(health):
                self.spans.instant(
                    "health_transition", f"replica:{int(replica_id)}",
                    self.clock(), **{"from": prev, "to": int(health)},
                )
            self._replica_health_seen[int(replica_id)] = int(health)

    def router_step_gauges(self, queue_depth: int, spread: int) -> None:
        """Once per router step: global placement-queue depth and the
        occupancy spread (max - min live rows) across alive replicas."""
        if not self.enabled:
            return
        self._router_queue.set(queue_depth)
        self._router_spread.observe(spread)

    # ---- disaggregated prefill tier (router_prefill_replicas) ------------

    def handoff_attempt(self) -> None:
        """One KV hand-off attempt started (retries count individually)."""
        if not self.enabled:
            return
        self._handoff_attempts.inc()

    def handoff_retry(self) -> None:
        """One hand-off attempt failed in transit and will retry."""
        if not self.enabled:
            return
        self._handoff_retries.inc()

    def handoff_failure(self, req_id: str, reason: str) -> None:
        """One hand-off terminally failed its in-flight request (typed
        FAILED(handoff)): corrupt/truncated payload, or retry exhaustion."""
        if not self.enabled:
            return
        self._handoff_failures.child((reason,)).inc()
        with self._lock:
            base, _inc, track, _root, _inode = self._req_ids(req_id)
            self.spans.instant(
                "handoff_failure", track, self.clock(), lane=base,
                reason=reason,
            )
        self.event("handoff_failure", req_id=req_id, reason=reason)

    def handoff_done(
        self,
        ms: float,
        req_id: Optional[str] = None,
        replica: Optional[int] = None,
    ) -> None:
        """One hand-off completed (prefill through inject), wall ms. With
        identity attached, the interval lands as a ``handoff`` span under
        the request's CURRENT incarnation (its TTFT tax, readable per
        request in the timeline and joined by scripts/obs_report.py)."""
        if not self.enabled:
            return
        self._handoff_ms.observe(ms)
        if req_id is not None:
            with self._lock:
                now = self.clock()
                base, _inc, track, root, _inode = self._req_ids(req_id)
                inc = self._failover_count.get(base, 0)
                sid = f"{root}/i{inc}/handoff"
                attrs = {} if replica is None else {"prefill_replica": int(replica)}
                self.spans.begin(
                    sid, "handoff", track, now - max(0.0, ms) / 1e3,
                    parent_id=f"{root}/i{inc}", lane=base, **attrs,
                )
                self.spans.end(sid, now)
        self.event("handoff_done", ms=ms, req_id=req_id, replica=replica)

    def handoff_local_prefill(self, req_id: str) -> None:
        """Tier-wide degradation: this placement ran the decode replica's
        LOCAL monolithic prefill because no prefill-tier member is alive."""
        if not self.enabled:
            return
        self._handoff_local.inc()
        with self._lock:
            base, _inc, track, _root, _inode = self._req_ids(req_id)
            self.spans.instant(
                "handoff_local_prefill", track, self.clock(), lane=base
            )
        self.event("handoff_local_prefill", req_id=req_id)

    def handoff_tier_gauges(self, replica_id: int, health: int) -> None:
        if not self.enabled:
            return
        self._handoff_tier_health.child((str(int(replica_id)),)).set(health)
        with self._lock:
            prev = self._tier_health_seen.get(int(replica_id))
            if prev is not None and prev != int(health):
                self.spans.instant(
                    "health_transition", f"prefill:{int(replica_id)}",
                    self.clock(), **{"from": prev, "to": int(health)},
                )
            self._tier_health_seen[int(replica_id)] = int(health)

    def handoff_tier_alive(self, alive: int) -> None:
        if not self.enabled:
            return
        self._handoff_tier_alive.set(alive)

    def replica_step(self, replica_id: int, step_ms: float) -> None:
        """One replica's session.step() wall time (recorded on the ROUTER
        thread after the per-step barrier, so threaded and sequential
        stepping record through the identical path — and the step-span
        counter below is router-thread-only, hence deterministic)."""
        if not self.enabled:
            return
        self._replica_step_ms.child((str(int(replica_id)),)).observe(step_ms)
        with self._lock:
            track = f"replica:{int(replica_id)}"
            k = self._replica_step_count.get(int(replica_id), 0) + 1
            self._replica_step_count[int(replica_id)] = k
            now = self.clock()
            sid = f"{track}/step{k}"
            self.spans.begin(
                sid, f"step {k}", track, now - max(0.0, step_ms) / 1e3,
                lane="steps", step_ms=step_ms,
            )
            self.spans.end(sid, now)

    def router_step_timing(self, phase_wall_ms: float, replica_ms_sum: float) -> None:
        """Wall time of one router step's replica-stepping phase beside the
        sum of its per-replica step walls. The cumulative overlap gauge is
        ``1 - wall / sum``: ~0 when replicas step host-serialized, up to
        (N-1)/N when thread-per-replica stepping overlaps them fully — the
        bench row's ``router_step_overlap_frac`` source."""
        if not self.enabled:
            return
        self._router_step_ms.observe(phase_wall_ms)
        with self._lock:
            self._router_step_wall_ms_sum += max(0.0, phase_wall_ms)
            self._replica_step_ms_sum += max(0.0, replica_ms_sum)
            if self._replica_step_ms_sum > 0:
                self._router_overlap.set(max(
                    0.0,
                    1.0 - self._router_step_wall_ms_sum
                    / self._replica_step_ms_sum,
                ))

    def spec_accept(self, committed: int) -> None:
        """One speculation round committed ``committed`` tokens for one
        request (post EOS/budget truncation — the histogram's sum is exactly
        the decode tokens speculation delivered)."""
        if not self.enabled or committed <= 0:
            return
        self._accept.observe(committed)

    def spec_round(
        self,
        draft_len: int,
        accept_ewma: float,
        req_id: Optional[str] = None,
    ) -> None:
        """One speculation round of one request: the tokens it drafted and
        the request's acceptance-rate EWMA after the update
        (docs/OBSERVABILITY.md). With ``req_id`` the round also lands as an
        instant on the request's timeline."""
        if not self.enabled:
            return
        self._spec_draft_len.observe(draft_len)
        self._spec_ewma.observe(accept_ewma)
        if req_id is not None:
            with self._lock:
                base, _inc, track, _root, _inode = self._req_ids(req_id)
                self.spans.instant(
                    "spec_round", track, self.clock(), lane=base,
                    draft_len=int(draft_len),
                    accept_ewma=round(float(accept_ewma), 6),
                )

    # ---- workload engine (workload/driver.py + workload/slo.py) ----------

    def chaos_kill(self, replica_id: int, tier: str, step: int) -> None:
        """The chaos plan killed a replica at driver step ``step`` — the
        timeline's kill marker (the instant the recovery window in
        scripts/obs_report.py and the bench chaos row anchor on)."""
        if not self.enabled:
            return
        track = (
            f"prefill:{int(replica_id)}" if tier == "prefill"
            else f"replica:{int(replica_id)}"
        )
        with self._lock:
            self.spans.instant(
                "chaos_kill", track, self.clock(), tier=tier, step=int(step)
            )
        self.event("chaos_kill", replica=int(replica_id), tier=tier,
                   step=int(step))

    def workload_step(self, step: int, commits: Dict[str, int],
                      dt_s: float) -> None:
        """One open-loop driver step: per-request decode commits observed
        this step. Lands as a ``driver`` track span carrying the commit
        total — the goodput series a trace viewer (and the chaos-agreement
        test) reads straight off the timeline."""
        if not self.enabled:
            return
        total = int(sum(commits.values()))
        with self._lock:
            now = self.clock()
            sid = f"driver/step{int(step)}"
            self.spans.begin(
                sid, f"step {int(step)}", "driver", now,
                lane="steps", commit_tokens=total,
            )
            # the virtual clock advances AFTER _record_step — stamp the
            # step's nominal width so the timeline shows contiguous steps
            self.spans.end(sid, now + max(0.0, float(dt_s)))
        self.event("workload_step", step=int(step), commit_tokens=total,
                   commits=dict(commits))

    def slo_missed(self, kind: str, tenant: str) -> None:
        """One request missed its SLO (scored post-hoc by workload/slo.py):
        ``kind`` is ttft / itl / failed / never_served."""
        if not self.enabled:
            return
        self._slo_missed.child((kind, tenant)).inc()
        self.event("slo_missed", kind=kind, tenant=tenant)

    def workload_backlog(self, depth: int) -> None:
        """Arrivals currently waiting in the open-loop driver's retry
        backlog (observed once per driver step)."""
        if not self.enabled:
            return
        self._wl_backlog.set(depth)

    def workload_refused(self, reason: str) -> None:
        """One open-loop admission attempt refused for capacity; the
        arrival stays in the backlog and retries (NON-terminal — terminal
        give-ups ride request_rejected(reason='backlog'))."""
        if not self.enabled:
            return
        self._wl_refused.child((reason,)).inc()
        self.event("workload_refused", reason=reason)

    # ---- retrace-guard bridge --------------------------------------------

    def _on_trace(self, tag: str, sealed: bool) -> None:
        self._jit_traces.child((tag,)).inc()
        if sealed:
            self._sealed_retrace.child((tag,)).inc()
            self.event("sealed_retrace", tag=tag)


def load_events(jsonl_path: str) -> List[dict]:
    """Read a session's JSONL event log back for offline replay.

    Tolerant of a truncated/corrupt tail: a process killed mid-write (the
    chaos drains this log exists for) leaves a half-written last line —
    skip bad lines with a warning instead of losing the whole log."""
    out = []
    with open(jsonl_path) as f:
        for lineno, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                warnings.warn(
                    f"{jsonl_path}:{lineno}: skipping corrupt JSONL line "
                    f"({line[:40]!r}...)",
                    stacklevel=2,
                )
    return out


# ---- module default (demo / bench / fused-spec apps) -----------------------

_default_session = TelemetrySession(
    registry=metrics_mod.default_registry(), enabled=False
)


def default_session() -> TelemetrySession:
    """The process-default session. Disabled (inert) until
    :func:`enable_default_session` — ServingSession and the fused-spec host
    loops record into it when no explicit session is passed."""
    return _default_session


def set_default_session(session: TelemetrySession) -> TelemetrySession:
    global _default_session
    _default_session = session
    return session


def enable_default_session(jsonl_path: Optional[str] = None) -> TelemetrySession:
    """Swap in an ENABLED default session over the process-default registry
    (idempotent: an already-enabled default is returned as-is)."""
    global _default_session
    if not _default_session.enabled:
        _default_session = TelemetrySession(
            registry=metrics_mod.default_registry(), jsonl_path=jsonl_path
        )
    return _default_session
