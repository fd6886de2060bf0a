"""Tensor capture + tensor replacement (teacher forcing) taps.

TPU-native re-design of the reference's tensor-capture/replacement debug
stack (reference: models/config.py:987 ``TensorCaptureConfig``,
models/model_base.py:1120-1226 capture plumbing,
utils/tensor_replacement/registry.py teacher-forcing registry).

The reference registers torch module hooks; a traced JAX graph has no
modules, so the same capability is built from TAP POINTS: named calls the
model code makes at interesting tensors. During TRACING, an active
:class:`TapContext` (a trace-time Python object) decides per point whether to

- CAPTURE: stash the tracer so the wrapped function returns it as an extra
  output — per-layer points ride the layer scan's ys and come back stacked
  (L, ...);
- REPLACE: substitute a host-provided golden (an extra traced input) for the
  computed value — per-layer goldens are (L, ...) stacked and indexed with
  the in-scan layer index (teacher forcing).

Capture configuration is static per compiled program (the reference also
bakes it in at trace time): the application jits a separate tapped program.

In-tree tap points (models/base.py):
    ``embed``         (B, S, H)  embedding output / inputs_embeds
    ``attn_out``      (L, B, S, Hq, D) per-layer attention context (pre-o)
    ``layer_out``     (L, B, S, H) per-layer decoder output
    ``final_hidden``  (B, S, H)  post-final-norm hidden
    ``logits``        (B, K, V)  lm-head output
    ``exit_gate``     (T, B, S)  a looped stack's exit gate after each loop
                                 (models/ouro.py; computed only when captured)
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: every name the model code taps; config validation checks against this
TAP_POINTS = ("embed", "attn_out", "layer_out", "final_hidden", "logits", "exit_gate")
PER_LAYER_POINTS = ("attn_out", "layer_out")

_ACTIVE: List["TapContext"] = []


class TapContext:
    """Trace-time tap configuration + collection state."""

    def __init__(
        self,
        capture: Sequence[str] = (),
        replacements: Optional[Dict[str, jax.Array]] = None,
    ):
        unknown = set(capture) - set(TAP_POINTS)
        if replacements:
            unknown |= set(replacements) - set(TAP_POINTS)
        if unknown:
            raise ValueError(
                f"unknown tap point(s) {sorted(unknown)}; available: {TAP_POINTS}"
            )
        self.capture = tuple(capture)
        self.replacements = dict(replacements or {})
        self.captured: Dict[str, jax.Array] = {}
        self._layer_slots: Dict[str, jax.Array] = {}

    # -- context management (trace-time only) -----------------------------

    def __enter__(self):
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc):
        _ACTIVE.pop()
        return False


def active() -> Optional[TapContext]:
    return _ACTIVE[-1] if _ACTIVE else None


def tap(name: str, value: jax.Array, layer_idx: Optional[jax.Array] = None) -> jax.Array:
    """Model-side tap call: returns the (possibly replaced) value and records
    a capture. ``layer_idx`` marks per-layer points (inside the layer scan);
    their captures are collected by :func:`collect_layer_taps` into the scan
    ys and their replacement goldens are (L, ...) stacked, indexed at
    ``layer_idx``."""
    ctx = active()
    if ctx is None:
        return value
    if name in ctx.replacements:
        golden = ctx.replacements[name]
        if layer_idx is not None:
            value = jax.lax.dynamic_index_in_dim(
                golden.astype(value.dtype), layer_idx, axis=0, keepdims=False
            )
        else:
            value = golden.astype(value.dtype)
    if name in ctx.capture:
        if layer_idx is not None:
            ctx._layer_slots[name] = value
        else:
            ctx.captured[name] = value
    return value


def collect_layer_taps(ctx: Optional[TapContext]):
    """Called by the layer-scan body after the layer fn: drains the per-layer
    capture slots; the body returns them as ys (stacked to (L, ...) by scan)."""
    if ctx is None or not ctx._layer_slots:
        return None
    out = dict(ctx._layer_slots)
    ctx._layer_slots.clear()
    return out


def merge_layer_taps(ctx: Optional[TapContext], ys) -> None:
    """Store the scan-stacked per-layer captures into the context."""
    if ctx is None or ys is None:
        return
    for k, v in ys.items():
        ctx.captured[k] = v
