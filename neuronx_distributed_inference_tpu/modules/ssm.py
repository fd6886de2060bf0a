"""Mamba-2 state-space mixer and its recurrent-state container.

A state-space layer keeps, per serving slot, a CONSTANT-size state instead of
a K/V stream that grows with the context: the last ``d_conv - 1`` inputs of
its depthwise causal convolution (the "conv tail") and the matrix state ``S``
of the selective recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,      y_t = S_t . C_t + D x_t

(Mamba-2, "Transformers are SSMs", Dao & Gu 2024; published Granite-4.0-H
implementation: ``transformers`` ``modeling_granitemoehybrid.py``).

Two forms of the same recurrence:

* :func:`mamba2_chunk` — a (rows, q) chunk given the incoming state, in the
  chunked "state-space dual" form: inside a sub-chunk of at most
  ``chunk_size`` positions the output is a masked quadratic form (matrix
  products), between sub-chunks a state is carried.
* :func:`mamba2_step` — q = 1, the plain update (the decode program runs
  ``ops/ssm_state_update.py`` instead, which is held to this function).

Both take a per-position validity mask. The contract every caller relies on
(serving: a padded chunk tail, a slot that is idle or decoding while others
prefill, a warm-up pass): **an invalid position leaves the conv tail and
``S`` bit-identical** — the recurrence sees ``dt = 0`` and ``x = 0`` there
(decay ``exp(0) = 1``, increment 0), the conv tail shifts by the number of
VALID positions only, and a row with no valid position is passed through by
a select. Valid positions are a prefix of the row (what chunked prefill
builds).

``S`` is float32 whatever the model dtype: the recurrence compounds over a
request's whole life. The conv tail holds copies of inputs in the model
dtype.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SSMSpec:
    """Static sizes of a Mamba-2 mixer (the published config's ``mamba_*``)."""

    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 256
    rms_eps: float = 1e-5
    #: equal parts of ``d_inner`` the gated norm divides each by its own root
    #: mean square (Granite-4.0-H: 1 whatever ``n_groups``; nemotron_h: ``n_groups``)
    norm_groups: int = 1

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size


@jax.tree_util.register_dataclass
@dataclass
class RecurrentState:
    """The per-slot state of every state-space layer of a model.

    conv: (L, d_conv - 1, slots, conv_dim), model dtype — slots on the
          second-minor axis so that the array tiles without padding.
    ssm:  (L, slots, heads, head_dim, state_size), float32.

    Which slot a row of a batch advances depends on the program. The
    decode program has one row per slot: row ``r`` owns slot ``r`` and
    ``seq_ids`` only says whether the row is live. The chunk program is
    ``ops/kernel_mode.CHUNK_ROWS`` rows wide and a row's slot is its
    ``seq_ids`` entry: the state of its rows is gathered at a layer's entry
    and written back at its exit (``models/granite_hybrid.mamba_layer``),
    and the state of every slot that sits the pass out is not touched."""

    conv: jax.Array
    ssm: jax.Array

    @property
    def num_slots(self) -> int:
        return self.ssm.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.conv.size * self.conv.dtype.itemsize + self.ssm.size * 4)

    #: which family of the serving step's counters counts this state
    KIND = "ssm"

    def fill_slots(self, slots, value: float) -> "RecurrentState":
        """Overwrite the state of whole slots in every layer (scrub: 0.0)."""
        idx = jnp.asarray(slots, jnp.int32)
        return type(self)(
            conv=self.conv.at[:, :, idx].set(value), ssm=self.ssm.at[:, idx].set(value)
        )


def init_recurrent_state(spec: SSMSpec, num_layers: int, num_slots: int, dtype) -> RecurrentState:
    return RecurrentState(
        conv=jnp.zeros((num_layers, spec.conv_kernel - 1, num_slots, spec.conv_dim), dtype),
        ssm=jnp.zeros(
            (num_layers, num_slots, spec.num_heads, spec.head_dim, spec.state_size), jnp.float32
        ),
    )


def recurrent_state_pspecs() -> RecurrentState:
    from jax.sharding import PartitionSpec as P

    return RecurrentState(conv=P(), ssm=P())


# ---------------------------------------------------------------------------
# depthwise causal convolution with a carried tail
# ---------------------------------------------------------------------------


def carried_window(x: jax.Array, tail: jax.Array) -> jax.Array:
    """``[tail ; x]`` along the positions: x (R, Q, C) this pass's inputs,
    tail (K-1, R, C) the last K-1 VALID inputs before this pass. Returns
    (R, K-1+Q, C) in ``x``'s dtype: position ``K-1+q`` is this pass's ``q``."""
    return jnp.concatenate([jnp.swapaxes(tail, 0, 1).astype(x.dtype), x], axis=1)


def tail_after(window: jax.Array, n_valid: jax.Array, taps: int, dtype) -> jax.Array:
    """The tail (taps, R, C) after a row's ``n_valid`` (R,) valid positions
    of ``window`` (:func:`carried_window`): ``window[n : n + taps]``, pure
    copies, so a row with n = 0 keeps its tail bit for bit."""
    idx = n_valid[:, None] + jnp.arange(taps, dtype=jnp.int32)[None, :]  # (R, taps)
    new_tail = jnp.take_along_axis(window, idx[:, :, None], axis=1)
    return jnp.swapaxes(new_tail, 0, 1).astype(dtype)


def causal_conv(
    xBC: jax.Array,  # (R, Q, C) this pass's inputs
    tail: jax.Array,  # (K-1, R, C) the last K-1 VALID inputs before this pass
    weight: jax.Array,  # (K, C)
    bias: Optional[jax.Array],  # (C,), or None: a conv without one
    n_valid: jax.Array,  # (R,) int32: valid positions are [0, n_valid)
    activation=jax.nn.silu,
) -> Tuple[jax.Array, jax.Array]:
    """``activation(conv(x) + b)`` (depthwise) over [tail ; x] and the tail
    after the row's valid positions. Returns (out (R, Q, C) float32, new
    tail (K-1, R, C)). ``activation`` None: the plain sum."""
    K = weight.shape[0]
    Q = xBC.shape[1]
    window = carried_window(xBC, tail)
    w = weight.astype(jnp.float32)
    acc = 0.0 if bias is None else bias.astype(jnp.float32)[None, None, :]
    for k in range(K):
        acc = acc + w[k][None, None, :] * window[:, k : k + Q].astype(jnp.float32)
    out = acc if activation is None else activation(acc)
    return out, tail_after(window, n_valid, K - 1, tail.dtype)


def conv_with_carry(conv_state, li, x, weight, bias, valid, reset, slots=None):
    """:func:`causal_conv` of one layer over the STACKED tails ``conv_state``
    (L, K-1, slots, C): the rows' tails taken at layer ``li`` (by ``slots``
    where the rows carry their slot, the chunk program; row r = slot r where
    None, the decode program), zeroed for ``reset`` rows, advanced over the
    ``valid`` (R, Q) positions and put back (an empty row's write is
    dropped). Returns (out (R, Q, C) float32, the stacked tails)."""
    tails = jax.lax.dynamic_index_in_dim(conv_state, li, 0, keepdims=False)
    tail = tails if slots is None else jnp.take(tails, slots, axis=1, mode="fill", fill_value=0)
    tail = jnp.where(reset[None, :, None], jnp.zeros((), tail.dtype), tail)
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    out, tail = causal_conv(x, tail, weight, bias, n_valid)
    if slots is not None:
        tail = tails.at[:, slots].set(tail, mode="drop", unique_indices=True)
    return out, jax.lax.dynamic_update_index_in_dim(conv_state, tail, li, 0)


def rows_state(state, li, reset, slots=None):
    """The rows' float32 state of layer ``li`` from the STACKED ``state`` (L,
    slots, ...): straight from the stack by ``slots`` (R x 2 MiB a layer,
    never a layer's whole slice) or the layer's slice where row r = slot r;
    ``reset`` rows start from zero."""
    if slots is None:
        s = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    else:
        s = state.at[li, slots].get(mode="fill", fill_value=0.0)
    return jnp.where(reset[(slice(None),) + (None,) * (s.ndim - 1)], 0.0, s)


def put_rows_state(state, s, li, slots=None):
    """The stacked ``state`` with the rows' state ``s`` back at layer ``li``
    (:func:`rows_state`'s inverse; an empty row's write is dropped)."""
    if slots is None:
        return jax.lax.dynamic_update_index_in_dim(state, s, li, 0)
    return state.at[li, slots].set(s, mode="drop", unique_indices=True)


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _grouped(a: jax.Array, axis: int, groups: int) -> jax.Array:
    """Split the head axis into (groups, heads per group): B and C are shared
    by the heads of a group, and are never repeated per head."""
    return a.reshape(a.shape[:axis] + (groups, a.shape[axis] // groups) + a.shape[axis + 1 :])


def mamba2_step(
    x: jax.Array,  # (R, H, P)
    B: jax.Array,  # (R, G, N)
    C: jax.Array,  # (R, G, N)
    dt: jax.Array,  # (R, H) after softplus
    A: jax.Array,  # (H,) negative
    state: jax.Array,  # (R, H, P, N) float32
    valid: jax.Array,  # (R,) bool
) -> Tuple[jax.Array, jax.Array]:
    """One token per row. Returns (y (R, H, P) float32 WITHOUT the D skip,
    new state); invalid rows keep their state bit for bit."""
    G = B.shape[1]
    f32 = jnp.float32
    dt = jnp.where(valid[:, None], dt.astype(f32), 0.0)
    dA = jnp.exp(dt * A.astype(f32)[None, :])  # (R, H)
    dtx = dt[:, :, None] * x.astype(f32)  # (R, H, P)
    s5 = _grouped(state, 1, G)  # (R, G, Hg, P, N)
    Bg, Cg = B.astype(f32)[:, :, None, None, :], C.astype(f32)[:, :, None, None, :]
    new = s5 * _grouped(dA, 1, G)[..., None, None] + _grouped(dtx, 1, G)[..., None] * Bg
    new = jnp.where(valid[:, None, None, None, None], new, s5)
    y = jnp.sum(new * Cg, axis=-1)
    return y.reshape(x.shape), new.reshape(state.shape)


def _ssd_subchunk(x, B, C, dt, A, state):
    """One sub-chunk in the quadratic form. x (R,Q,H,P), B,C (R,Q,G,N),
    dt (R,Q,H) float32 with dt = 0 at invalid positions, state (R,H,P,N)."""
    R, Q, H, P = x.shape
    G = B.shape[2]
    cum = jnp.cumsum(dt * A[None, None, :], axis=1)  # (R, Q, H), inclusive, <= 0
    cum_h = jnp.transpose(cum, (0, 2, 1))  # (R, H, Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cum_h[:, :, :, None] - cum_h[:, :, None, :]  # (R, H, i, j) = sum a_{j+1..i}
    decay = jnp.exp(jnp.where(causal[None, None], seg, -jnp.inf))  # 0 above the diagonal
    scores = jnp.einsum("rign,rjgn->rgij", C, B, precision=_HI)  # (R, G, i, j)
    M = scores[:, :, None] * _grouped(decay, 1, G)  # (R, G, Hg, i, j)
    dtx = _grouped(dt[..., None] * x, 2, G)  # (R, Q, G, Hg, P)
    s5 = _grouped(state, 1, G)  # (R, G, Hg, P, N)
    y_diag = jnp.einsum("rgkij,rjgkp->rigkp", M, dtx, precision=_HI)
    y_off = jnp.einsum("rign,rgkpn->rigkp", C, s5, precision=_HI)
    y = y_diag + y_off * _grouped(jnp.exp(cum), 2, G)[..., None]
    total = cum[:, -1, :]  # (R, H)
    w = jnp.exp(total[:, None, :] - cum)  # (R, Q, H): decay from position j to the end
    new = s5 * _grouped(jnp.exp(total), 1, G)[..., None, None] + jnp.einsum(
        "rjgkp,rjgn->rgkpn", dtx * _grouped(w, 2, G)[..., None], B, precision=_HI
    )
    return y.reshape(R, Q, H, P), new.reshape(state.shape)


def mamba2_chunk(
    x: jax.Array,  # (R, Q, H, P)
    B: jax.Array,  # (R, Q, G, N)
    C: jax.Array,  # (R, Q, G, N)
    dt: jax.Array,  # (R, Q, H) after softplus
    A: jax.Array,  # (H,) negative
    state: jax.Array,  # (R, H, P, N) float32, the state BEFORE this chunk
    valid: jax.Array,  # (R, Q) bool, a prefix of each row
    chunk_size: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """A chunk of Q positions per row from ``state``. Returns (y (R,Q,H,P)
    float32 WITHOUT the D skip, state after each row's valid positions).
    Matrix products run at ``Precision.HIGHEST``: their operands are float32
    (the state, cumulative decays), which the default would round to bf16."""
    f32 = jnp.float32
    R, Q, H, P = x.shape
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    x, B, C, A = x.astype(f32), B.astype(f32), C.astype(f32), A.astype(f32)
    sub = min(int(chunk_size), Q)
    n_sub = -(-Q // sub)
    if n_sub == 1:
        y, new = _ssd_subchunk(x, B, C, dt, A, state)
    else:
        pad = n_sub * sub - Q

        def split(a):  # (R, Q, ...) -> (n_sub, R, sub, ...), zero padded (dt = 0: no-ops)
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return jnp.swapaxes(a.reshape((R, n_sub, sub) + a.shape[2:]), 0, 1)

        def body(s, t):
            y_c, s = _ssd_subchunk(*t, A, s)
            return s, y_c

        new, ys = jax.lax.scan(body, state, (split(x), split(B), split(C), split(dt)))
        y = jnp.swapaxes(ys, 0, 1).reshape(R, n_sub * sub, H, P)[:, :Q]
    new = jnp.where(jnp.any(valid, axis=1)[:, None, None, None], new, state)
    return y, new


def gated_rms_norm(y: jax.Array, z: jax.Array, weight: jax.Array, eps: float,
                   groups: int = 1) -> jax.Array:
    """``rmsnorm_w(y * silu(z))`` over the last axis, each of its ``groups``
    equal parts divided by its own root mean square (the published
    ``nemotron_h`` gated norm has ``n_groups`` of them; Granite-4.0-H one):
    computed in float32, returned in ``z``'s dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups == 1:
        var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        return (g * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(z.dtype)
    parts = _grouped(g, g.ndim - 1, groups)
    var = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    normed = (parts * jax.lax.rsqrt(var + eps)).reshape(g.shape)
    return (normed * weight.astype(jnp.float32)).astype(z.dtype)
