"""Serving fault containment: typed faults + a deterministic FaultInjector.

The serving session's production contract is that ONE bad request degrades to
ONE failed request — never a corrupted batch, never a wedged process. This
module carries the pieces the session uses to prove that:

- :class:`TransientDispatchError` / :data:`RETRYABLE_DISPATCH_ERRORS` — the
  exception classes the session's bounded-backoff dispatch retry treats as
  transient (everything else propagates: a ValueError from bad host inputs is
  a programming error, not weather).
- :class:`WatchdogError` — raised (with a diagnostic snapshot attached) when
  the no-forward-progress watchdog trips twice: a loud, inspectable failure
  instead of an invisible spin.
- :class:`FaultInjector` — a deterministic, seedable fault source the tests
  drive every degradation policy with: NaN-poisoned KV rows, corrupted token
  fetches, forced pool exhaustion, raised dispatch exceptions, injected step
  latency, and full dispatch stalls. Injection happens at the session's host
  boundaries (the hooks below), so the same serving code path runs with and
  without faults — a clean run with an armed-but-idle injector is
  byte-identical to a run without one.

Injection model: faults are armed per SESSION STEP (``session.step()``
increments the index). Every hook is a no-op unless a fault
is armed for the current step, and each armed fault fires exactly once —
schedules built from the seed via :meth:`FaultInjector.random_schedule` are
reproducible run-to-run.

Device-poisoning faults (``poison_kv_row`` / ``poison_garbage_block``) write
real NaNs into the KV cache the way the ROADMAP-named bug would (a NaN row
poisoning co-batched rows through shared garbage block 0), so the tests can
pin the full containment pipeline: NaN cache -> non-finite logits -> sentinel
token (models/base.NON_FINITE_TOKEN) -> host quarantine + scrubbed release,
with healthy co-batched rows byte-identical to a clean run.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np


class TransientDispatchError(RuntimeError):
    """A dispatch failure worth retrying (the injected stand-in for driver
    hiccups / transient runtime errors)."""


class WatchdogError(RuntimeError):
    """The serving session made no forward progress for two consecutive
    watchdog windows. Carries the session's diagnostic snapshot so the
    operator sees WHAT was stuck, not just THAT it was stuck."""

    def __init__(self, message: str, snapshot: Optional[dict] = None):
        super().__init__(message)
        self.snapshot = snapshot or {}


class HandoffTransitError(RuntimeError):
    """One KV hand-off attempt failed IN TRANSIT (payload lost, transfer
    timed out, transfer stalled past the operator timeout): the payload
    never reached the decode replica, so the attempt is RETRYABLE — the
    router's bounded hand-off retry re-extracts and re-sends. Contrast with
    a corrupt/truncated payload, which DID arrive and is caught by the
    decode side's inject validation as a terminal FAILED(handoff)."""


def _retryable_classes() -> Tuple[type, ...]:
    """Transient dispatch exception classes: the injector's typed error plus
    the XLA runtime error jax raises for device-side failures (absent on
    older jaxlibs — gated, never a hard dependency)."""
    classes: List[type] = [TransientDispatchError]
    try:  # pragma: no cover - depends on the installed jaxlib
        from jax.errors import JaxRuntimeError

        classes.append(JaxRuntimeError)
    except ImportError:
        try:  # pragma: no cover
            from jaxlib.xla_extension import XlaRuntimeError

            classes.append(XlaRuntimeError)
        except ImportError:
            pass
    return tuple(classes)


RETRYABLE_DISPATCH_ERRORS: Tuple[type, ...] = _retryable_classes()

#: every fault kind random_schedule can draw (also the session-hook names)
FAULT_KINDS = (
    "nan_tokens",
    "poison_kv_row",
    "poison_garbage_block",
    "exhaust_pool",
    "dispatch_error",
    "latency",
    "stall",
)

#: KV hand-off fault modes (disaggregated prefill tier, runtime/router.py):
#: armed by HAND-OFF INDEX (the router's monotone hand-off counter, passed
#: into the transit/corrupt hooks), not session step — hand-offs happen at
#: placement time, outside any session step. drop/latency/stall are
#: TRANSIT faults (retryable, bounded by handoff_max_retries);
#: corrupt/truncate mutate the delivered payload so the decode side's
#: inject validation terminally fails ONE request (FAILED(handoff)).
HANDOFF_FAULT_KINDS = (
    "handoff_drop",
    "handoff_corrupt",
    "handoff_truncate",
    "handoff_latency",
    "handoff_stall",
)


class FaultInjector:
    """Deterministic, seedable fault source for serving sessions.

    Arm faults against step indices, hand the injector to
    ``ServingSession(app, fault_injector=...)``, and drive the session
    normally; ``injector.log`` records every fault that actually fired
    (step, kind, detail) for assertions.
    """

    def __init__(self, seed: int = 0):
        self.rng = np.random.RandomState(seed)
        self.log: List[dict] = []
        self._latency: Dict[int, float] = {}
        self._stall: Set[int] = set()
        self._exhaust_pool: Set[int] = set()
        # step -> remaining dispatch ATTEMPTS to fail at that step (a value
        # of n fails the first n attempts — n <= dispatch_max_retries means
        # the retry loop recovers, n > means the in-flight rows fail)
        self._dispatch_fail: Dict[int, int] = {}
        self._nan_tokens: Dict[int, List[int]] = {}
        self._poison_rows: Dict[int, List[int]] = {}
        self._poison_garbage: Set[int] = set()
        # KV hand-off faults, keyed by the router's hand-off index
        # (handoff #n is the n-th hand-off the router performs, attempts of
        # one hand-off share the index)
        self._handoff_drop: Dict[int, int] = {}  # index -> attempts to drop
        self._handoff_latency: Dict[int, float] = {}
        self._handoff_stall: Set[int] = set()
        self._handoff_corrupt: Set[int] = set()
        self._handoff_truncate: Set[int] = set()

    # ---- arming ----------------------------------------------------------

    def latency(self, step: int, seconds: float) -> "FaultInjector":
        """Sleep ``seconds`` (via the session's injectable sleep) at the
        start of ``step`` — models a host hiccup; with deadlines armed it is
        how deadline_exceeded paths are exercised deterministically."""
        self._latency[step] = float(seconds)
        return self

    def stall(self, *steps: int) -> "FaultInjector":
        """Suppress the model dispatch entirely at ``steps`` (the session
        observes zero progress) — the watchdog's test signal."""
        self._stall.update(int(s) for s in steps)
        return self

    def exhaust_pool(self, *steps: int) -> "FaultInjector":
        """Force every KV-block allocation at ``steps`` to fail as if the
        pool were empty — drives preemption/re-admission without needing a
        pathologically-sized pool."""
        self._exhaust_pool.update(int(s) for s in steps)
        return self

    def dispatch_error(self, step: int, attempts: int = 1) -> "FaultInjector":
        """Raise :class:`TransientDispatchError` for the first ``attempts``
        dispatch attempts at ``step``."""
        self._dispatch_fail[int(step)] = int(attempts)
        return self

    def nan_logits(self, step: int, slot: int) -> "FaultInjector":
        """Corrupt the HOST-fetched tokens of ``slot`` at ``step`` to the
        non-finite sentinel — the pure host-boundary fault (the device cache
        stays clean): exercises quarantine bookkeeping in isolation."""
        self._nan_tokens.setdefault(int(step), []).append(int(slot))
        return self

    def poison_kv_row(self, step: int, slot: int) -> "FaultInjector":
        """Write NaN over ``slot``'s live KV (its allocated blocks, or its
        contiguous cache line) at the start of ``step`` — the real
        ROADMAP-named pathology: the row's next attention pass produces
        non-finite logits on device."""
        self._poison_rows.setdefault(int(step), []).append(int(slot))
        return self

    def poison_garbage_block(self, step: int) -> "FaultInjector":
        """Write NaN over the SHARED garbage sink (paged block 0 / the
        contiguous garbage line) at ``step`` — simulates the
        post-propagation state of the garbage-block coupling bug; with the
        read scrub in place no healthy row may change by a byte."""
        self._poison_garbage.add(int(step))
        return self

    # ---- KV hand-off faults (disaggregated prefill tier) -----------------

    def handoff_drop(self, handoff: int, attempts: int = 1) -> "FaultInjector":
        """Lose hand-off ``handoff``'s payload in transit for its first
        ``attempts`` attempts (:class:`HandoffTransitError`) — n <=
        handoff_max_retries means the bounded retry recovers, n > means the
        in-flight request terminally fails FAILED(handoff)."""
        self._handoff_drop[int(handoff)] = int(attempts)
        return self

    def handoff_latency(self, handoff: int, seconds: float) -> "FaultInjector":
        """Sleep ``seconds`` (via the router's injectable sleep) inside the
        FIRST attempt of hand-off ``handoff`` (a one-shot hiccup — the
        fault retires once fired, so the retry runs latency-free). With
        ``handoff_timeout_s`` armed this deterministically exercises the
        timeout-observed-then-retry-recovers path; a latency that must
        defeat every retry is :meth:`handoff_stall`."""
        self._handoff_latency[int(handoff)] = float(seconds)
        return self

    def handoff_stall(self, handoff: int) -> "FaultInjector":
        """Stall hand-off ``handoff``'s transfer indefinitely. The router
        observes it as a timed-out attempt (the deterministic stand-in for
        'the operator timeout fired mid-transfer'): retryable, like drop."""
        self._handoff_stall.add(int(handoff))
        return self

    def handoff_corrupt(self, handoff: int) -> "FaultInjector":
        """Corrupt hand-off ``handoff``'s DELIVERED payload (NaN into the
        K stream — or into the running-absmax scales for quantized
        payloads): the decode side's inject validation must terminally fail
        ONE request with typed FAILED(handoff) and scrub the destination
        line, co-batched rows byte-identical (pinned)."""
        self._handoff_corrupt.add(int(handoff))
        return self

    def handoff_truncate(self, handoff: int) -> "FaultInjector":
        """Truncate hand-off ``handoff``'s payload along the position axis
        (half the prompt arrives): the shape-vs-declared-length check at
        inject catches it as terminal FAILED(handoff)."""
        self._handoff_truncate.add(int(handoff))
        return self

    def random_schedule(
        self,
        n_steps: int,
        rate: float,
        kinds: Tuple[str, ...] = ("exhaust_pool", "dispatch_error", "latency"),
        slots: Tuple[int, ...] = (0,),
    ) -> "FaultInjector":
        """Arm a reproducible random schedule from the seed: each step fires
        one fault of a random ``kind`` with probability ``rate``. Chaos-mode
        soak testing with a replayable seed."""
        for step in range(n_steps):
            if self.rng.rand() >= rate:
                continue
            kind = kinds[self.rng.randint(len(kinds))]
            if kind == "latency":
                self.latency(step, float(self.rng.rand()) * 0.01)
            elif kind == "dispatch_error":
                self.dispatch_error(step, attempts=1)
            elif kind == "exhaust_pool":
                self.exhaust_pool(step)
            elif kind == "stall":
                self.stall(step)
            elif kind == "nan_tokens":
                self.nan_logits(step, int(slots[self.rng.randint(len(slots))]))
            elif kind == "poison_kv_row":
                self.poison_kv_row(step, int(slots[self.rng.randint(len(slots))]))
            elif kind == "poison_garbage_block":
                self.poison_garbage_block(step)
            else:
                raise ValueError(f"unknown fault kind {kind!r}")
        return self

    # ---- session hooks ---------------------------------------------------

    def _fired(self, step: int, kind: str, **detail) -> None:
        self.log.append({"step": step, "kind": kind, **detail})

    def on_step_begin(self, session) -> None:
        """Start-of-step faults: injected latency and device KV poisoning."""
        step = session._step_index
        # pool-exhaustion arms stay live for their WHOLE step (several
        # allocations consult them); retire the past here
        self._exhaust_pool = {s for s in self._exhaust_pool if s >= step}
        delay = self._latency.pop(step, None)
        if delay is not None:
            session._sleep(delay)
            self._fired(step, "latency", seconds=delay)
        for slot in self._poison_rows.pop(step, ()):  # device NaN writes
            if _poison_row(session, slot):
                self._fired(step, "poison_kv_row", slot=slot)
        if step in self._poison_garbage:
            self._poison_garbage.discard(step)
            _poison_garbage(session)
            self._fired(step, "poison_garbage_block")

    def stalled(self, session) -> bool:
        step = session._step_index
        if step in self._stall:
            self._stall.discard(step)
            self._fired(step, "stall")
            return True
        return False

    def on_dispatch(self, session, label: str) -> None:
        """Called once per dispatch ATTEMPT inside the session's retry
        loop — raises while this step still has armed attempt-failures."""
        step = session._step_index
        remaining = self._dispatch_fail.get(step, 0)
        if remaining > 0:
            self._dispatch_fail[step] = remaining - 1
            self._fired(step, "dispatch_error", label=label)
            raise TransientDispatchError(
                f"injected dispatch fault (step {step}, {label})"
            )

    def dispatch_gave_up(self, session) -> None:
        """The session exhausted its retry budget and terminally failed the
        in-flight rows: retire this step's remaining armed attempt-failures
        so the fault stays scoped to the dispatch it hit — a later dispatch
        landing on the same step index (e.g. an admission-time prefill)
        starts clean."""
        self._dispatch_fail.pop(session._step_index, None)

    def pool_exhausted(self, session) -> bool:
        step = session._step_index
        if step in self._exhaust_pool:
            if not any(
                f["step"] == step and f["kind"] == "exhaust_pool" for f in self.log
            ):
                self._fired(step, "exhaust_pool")
            return True
        return False

    def corrupt_tokens(self, session, tokens: np.ndarray) -> np.ndarray:
        """Host-boundary corruption of a freshly-fetched slot-indexed token
        array (1-D ``(B,)`` or 2-D ``(B, K)``): armed slots read as the
        non-finite sentinel."""
        from neuronx_distributed_inference_tpu.models.base import NON_FINITE_TOKEN

        step = session._step_index
        slots = self._nan_tokens.pop(step, None)
        if not slots:
            return tokens
        tokens = np.array(tokens, copy=True)
        for slot in slots:
            tokens[slot] = NON_FINITE_TOKEN
            self._fired(step, "nan_tokens", slot=slot)
        return tokens

    # ---- hand-off hooks (router hand-off boundary, not session steps) ----

    def _fired_handoff(self, handoff: int, kind: str, **detail) -> None:
        self.log.append({"handoff": handoff, "kind": kind, **detail})

    def handoff_transit(self, handoff: int, sleep_fn) -> None:
        """Called once per hand-off ATTEMPT, between extract and inject.
        Applies injected latency (through the router's injectable sleep —
        with ``handoff_timeout_s`` armed the router observes the overrun
        and fails the attempt) and raises :class:`HandoffTransitError` for
        armed drop/stall faults. Drop retires per attempt (a retry can
        succeed); stall stays armed for every attempt of its hand-off (a
        stalled transfer never completes — the bounded retry exhausts)."""
        idx = int(handoff)
        delay = self._handoff_latency.pop(idx, None)
        if delay is not None:
            sleep_fn(delay)
            self._fired_handoff(idx, "handoff_latency", seconds=delay)
        if idx in self._handoff_stall:
            self._fired_handoff(idx, "handoff_stall")
            raise HandoffTransitError(
                f"injected hand-off stall (hand-off {idx}: transfer never "
                f"completed; observed as a timed-out attempt)"
            )
        remaining = self._handoff_drop.get(idx, 0)
        if remaining > 0:
            self._handoff_drop[idx] = remaining - 1
            self._fired_handoff(idx, "handoff_drop")
            raise HandoffTransitError(
                f"injected hand-off payload loss (hand-off {idx})"
            )

    def corrupt_handoff_payload(self, handoff: int, kv: Dict) -> Dict:
        """Transform hand-off ``handoff``'s delivered payload: truncate the
        position axis and/or write NaN into the K stream (quantized
        payloads corrupt the fp32 scales instead — int8 codes have no NaN).
        Fires once per armed hand-off; the inject-side validation must turn
        either into a terminal typed FAILED(handoff)."""
        idx = int(handoff)
        if idx in self._handoff_truncate:
            self._handoff_truncate.discard(idx)
            kv = dict(kv)
            S = int(kv["k"].shape[2])
            keep = max(1, S // 2)
            kv["k"] = kv["k"][:, :, :keep]
            kv["v"] = kv["v"][:, :, :keep]
            self._fired_handoff(idx, "handoff_truncate", kept=keep, of=S)
        if idx in self._handoff_corrupt:
            self._handoff_corrupt.discard(idx)
            kv = dict(kv)
            if kv.get("quantized"):
                kv["k_scale"] = kv["k_scale"].at[0, 0].set(float("nan"))
            else:
                kv["k"] = kv["k"].at[0, 0, 0, 0, 0].set(float("nan"))
            self._fired_handoff(idx, "handoff_corrupt")
        return kv


# ---------------------------------------------------------------------------
# device KV poisoning / filling helpers (shared with the serving session's
# quarantine scrub-on-release; host-side enqueues only — no fetches, no host
# syncs, and nothing here runs on a clean-traffic path)
# ---------------------------------------------------------------------------


def fill_kv_rows(cache, row_ids: np.ndarray, value: float):
    """Overwrite whole dim-1 rows (paged: block ids; contiguous/ring: cache
    lines) of EVERY stream in a KV cache pytree with ``value``, across all
    layers. Works on any of the cache dataclasses (KVCache,
    InterleavedKVCache, BlockKVCache): they all carry streams whose dim 1 is
    the row/block axis. Quantized streams only support value == 0 (codes of
    0 dequantize to exactly 0; NaN has no int8 encoding)."""
    import dataclasses

    from neuronx_distributed_inference_tpu.modules.kvcache import QuantizedKV

    idx = np.asarray(row_ids, np.int32)

    def fill(stream):
        if isinstance(stream, tuple):  # BlockKVCache.extra: further streams of blocks
            return tuple(fill(s) for s in stream)
        if isinstance(stream, QuantizedKV):
            if value != 0:
                raise ValueError(
                    "cannot write non-zero fill into a quantized KV stream "
                    "(int8/fp8 codes; poison faults need a float cache)"
                )
            return QuantizedKV(
                data=stream.data.at[:, idx].set(0), scale=stream.scale
            )
        return stream.at[:, idx].set(value)

    # a hybrid cache carries per-slot state beside its streams of blocks
    # (HybridBlockCache.SLOT_FIELDS): block ids do not index it
    keep = getattr(cache, "SLOT_FIELDS", ())
    return type(cache)(
        **{
            f.name: getattr(cache, f.name) if f.name in keep else fill(getattr(cache, f.name))
            for f in dataclasses.fields(cache)
        }
    )


def fill_slot_state(cache, slots, value: float):
    """Overwrite the constant-size per-slot state (every layer that keeps
    one: state-space layers, a one-token carry) of ``slots`` in a
    :class:`~..modules.block_kvcache.HybridBlockCache`; the block pool is
    left as it is."""
    import dataclasses

    return dataclasses.replace(cache, state=cache.state.fill_slots(slots, value))


def _poison_row(session, slot: int) -> bool:
    """NaN a live row's KV (paged: its allocated blocks; contiguous: its
    cache line). Returns False when the slot holds nothing to poison."""
    nan = float("nan")
    if session.block_mode:
        blocks = session.allocator.seq_blocks.get(slot)
        if not blocks:
            return False
        session.app.kv_cache = fill_kv_rows(session.app.kv_cache, blocks, nan)
        return True
    line = session._cache_line_of_slot(slot)
    session.app.kv_cache = fill_kv_rows(session.app.kv_cache, [line], nan)
    return True


def _poison_garbage(session) -> None:
    """NaN the SHARED garbage sink: paged reserved block 0, or the
    contiguous garbage line(s)."""
    if session.block_mode:
        from neuronx_distributed_inference_tpu.modules.block_kvcache import (
            GARBAGE_BLOCK,
        )

        rows = [GARBAGE_BLOCK]
    else:
        rows = session._garbage_lines()
    session.app.kv_cache = fill_kv_rows(session.app.kv_cache, rows, float("nan"))
