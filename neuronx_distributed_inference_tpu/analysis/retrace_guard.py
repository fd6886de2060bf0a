"""Retrace guard: fail when steady-state decode re-traces/recompiles.

The stack's core guarantee is a FIXED set of ahead-of-time compiled programs
(PAPER.md: AOT trace + compile of fixed-shape sub-models). A silent retrace
in the decode loop — a drifting input dtype, a new pytree structure, an
accidentally-fresh closure — recompiles mid-serve and destroys the latency
model without changing any output.

Mechanism: the hot-loop jitted entry points — ``SubModelRunner``'s step and
multi-step decode programs and the fused-speculation/EAGLE CTE/TKG programs
— are wrapped with :func:`trace_marker`, whose Python body executes ONLY
while jax is tracing (a jit cache hit replays the compiled program without
entering Python). Auxiliary apps (medusa, mllama, whisper, flux, encoders)
jit their own programs unwrapped: a RetraceGuard around THOSE loops observes
nothing — wrap their fns with trace_marker first. So
"the marker ran" == "the jit cache missed" == "a new program is being
traced". Two consumers:

- :class:`RetraceGuard` — a context manager that records every trace inside
  its scope and (by default) raises :class:`RetraceError` on exit if any
  happened. Tests wrap a steady-state decode loop with it to prove zero
  recompiles after warmup.
- *Sealing* — ``SubModelRunner.seal()`` (driven by
  ``TpuConfig.retrace_guard`` or ``NXDI_TPU_RETRACE_GUARD=1`` after
  ``warmup()``) arms the per-runner flag so any later trace of a sealed
  program raises immediately, even outside a guard scope.
"""

from __future__ import annotations

import os
import re
from typing import List, Optional

__all__ = [
    "RetraceError",
    "RetraceGuard",
    "add_trace_listener",
    "guard_enabled",
    "note_trace",
    "remove_trace_listener",
    "trace_marker",
]

_ACTIVE: List["RetraceGuard"] = []
# telemetry bridge: listeners called as fn(tag, sealed) on EVERY observed
# trace — including forbidden post-seal retraces, which are counted BEFORE
# the RetraceError raises so a steady-state recompile surfaces as an
# operable counter (nxdi_sealed_retrace_total) and not only an assertion.
# Kept as a plain callback list so this module never imports telemetry
# (note_trace executes at trace time; a static telemetry reference here
# would trip tpulint TPU107's recording-under-trace rule).
_LISTENERS: List = []


def add_trace_listener(fn) -> None:
    """Register ``fn(tag: str, sealed: bool)`` to observe every jit trace."""
    if fn not in _LISTENERS:
        _LISTENERS.append(fn)


def remove_trace_listener(fn) -> None:
    if fn in _LISTENERS:
        _LISTENERS.remove(fn)


class RetraceError(RuntimeError):
    """A jit-traced program re-traced where the contract forbids it."""


def guard_enabled(config=None) -> bool:
    """Config/env switch for post-warmup sealing (satisfied by either)."""
    if config is not None and getattr(config, "retrace_guard", False):
        return True
    return os.environ.get("NXDI_TPU_RETRACE_GUARD", "").lower() in ("1", "true")


def note_trace(tag: str, sealed: bool = False) -> None:
    """Record that the program ``tag`` is being traced right now.

    Called from INSIDE jitted function bodies, so it fires exactly once per
    jit cache miss. Raises when the owning runner is sealed; otherwise the
    trace is recorded into every active :class:`RetraceGuard`.
    """
    for g in _ACTIVE:
        g.traces.append(tag)
    for listener in _LISTENERS:
        listener(tag, sealed)
    if sealed:
        raise RetraceError(
            f"{tag}: jit re-trace after warmup()/seal() — a steady-state "
            f"recompile breaks the AOT latency contract. New input shape/"
            f"dtype/pytree reached a sealed program (or warmup missed a "
            f"bucket); run the jaxpr auditor "
            f"(python -m neuronx_distributed_inference_tpu.analysis) and "
            f"check the call that triggered this."
        )


def trace_marker(tag: str, fn, owner=None, name: Optional[str] = None):
    """Wrap ``fn`` (the function handed to ``jax.jit``) so each trace calls
    :func:`note_trace`. ``owner`` is the runner whose ``_sealed`` attribute
    arms the hard-failure mode; the attribute is read at trace time so
    sealing after wrap works.

    The wrapper is NAMED: ``jax.jit`` calls its program ``jit_<name>``, and
    that is what a profiler trace lists under ``XLA Modules``. ``name``
    defaults to the tag (characters outside ``[A-Za-z0-9_]`` become ``_``),
    so a trace tells the programs of different runners apart; a runner
    with several programs under one tag names each."""

    def wrapped(*args, **kwargs):
        note_trace(tag, sealed=bool(owner is not None and getattr(owner, "_sealed", False)))
        return fn(*args, **kwargs)

    wrapped.__name__ = wrapped.__qualname__ = re.sub(r"\W", "_", name or tag)
    return wrapped


class RetraceGuard:
    """Context manager: collect (and by default forbid) traces in scope.

    ``allowed`` traces are tolerated before failing — e.g. a test that
    expects exactly the first-call compile can pass ``allowed=1``.
    ``fail=False`` turns it into a pure observer (inspect ``.traces``).
    """

    def __init__(self, fail: bool = True, allowed: int = 0):
        self.fail = fail
        self.allowed = allowed
        self.traces: List[str] = []

    def __enter__(self) -> "RetraceGuard":
        _ACTIVE.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> Optional[bool]:
        _ACTIVE.remove(self)
        if exc_type is None and self.fail and len(self.traces) > self.allowed:
            raise RetraceError(
                f"{len(self.traces)} jit trace(s) inside a RetraceGuard scope "
                f"(allowed {self.allowed}): {self.traces} — steady-state "
                f"decode must reuse the warmed programs."
            )
        return None
