"""Power retention (degree 2): a linear-attention mixer whose KV heads each
keep the SYMMETRIC SQUARE of their keys ("Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; Manifest AI's ``retention`` package,
``power_retention`` / ``power_retention_inference``; the published
``model_type: "brumby"``).

Per KV head ``g`` (``d = head_dim``; query head ``h`` reads KV head ``h //
n_rep``), per token ``t``, with one log decay ``lg_t <= 0`` a KV head:

    S_t = exp(lg_t) S_{t-1} + phi(k_t) v_t^T        (D, d) float32
    z_t = exp(lg_t) z_{t-1} + phi(k_t)              (D,)   float32
    y_t[h] = phi(q_t[h])^T S_t / (phi(q_t[h]) . z_t + eps)

``phi(u)`` is the symmetric square of ``u / d^(1/4)``, so that ``phi(q) .
phi(k) = ((q . k) / sqrt(d))^2``: the recurrence is, term by term, attention
with the weights ``exp(sum lg) ((q . k) / sqrt(d))^2`` normalised by their
sum. THE LAYOUT HELD (:func:`phi`): the ``d`` channels in ``n = d / 8`` blocks
of 8, the ``n (n + 1) / 2`` block pairs ``(I <= J)`` in lexicographic order,
and for each pair the full 8 x 8 products ``u[8I + a] u[8J + b]`` (row ``a *
8 + b``), times ``sqrt(2 / d)`` off the diagonal and ``sqrt(1 / d)`` on it (a
diagonal block holds ``u_a u_b`` and ``u_b u_a`` both, each once). ``D = 64 n
(n + 1) / 2``: 8704 at ``d = 128``, against the exact ``d (d + 1) / 2 = 8256``
(5.4% more) and the full square 16384. Every tile of 64 rows is one (8, 128)
register a value of ``a``: what ``ops/power_state_update.py`` builds in VMEM
from ``k`` alone.

What a layer keeps per serving slot is constant in the context length:
:class:`PowerState`, a :class:`~.ssm.RecurrentState` under another ``KIND``
whose ``ssm`` is ``S (L, slots, G, D, d)`` and whose ``conv`` field carries
the normaliser ``z (L, slots, G, D)`` float32 (the model has no convolution,
so no tail: the container's second array is the state's second part).

Three forms of the one recurrence:

* :func:`power_step` — one token a row, the definition.
* :func:`power_chunk` — a (rows, q) chunk from the incoming ``S``, ``z``:
  inside a sub-chunk (the serving path's is its whole chunk of 128
  positions: one call of :func:`advance_rows`) the masked quadratic form
  ``((Q K^T) / sqrt(d))^2`` times the decays, between sub-chunks ``phi(Q) S``
  and ``phi(K)^T V`` with the state carried. Decays are differences of a
  running sum of ``lg`` that are <= 0: nothing above ``exp(0)`` is formed.
  ``phi`` of a whole chunk is never laid down: the state is walked in tiles
  of ``D`` (:func:`advance_rows`), each read once and written once, and
  ``phi`` is formed for the tile in hand.
* ``ops/power_state_update.py`` — one token a row on the STACKED state in
  place (the decode program), held to :func:`power_step`.

The chunk pass touches the state of its LIVE rows only, in place
(:func:`advance_rows`: a loop over the live rows, each tile taken from and
put back into the stack by a dynamic slice): a slot's state is 33.8 MB a
layer at the published widths, and gathering the rows of a chunk pass
(``modules/ssm.rows_state``) would move eight of them for the one that is
live. The contract of ``modules/ssm.py`` holds: an invalid position leaves
``S`` and ``z`` bit-identical (a row with no valid position is not visited;
inside a live row an invalid position has ``lg = 0`` and weight 0); valid
positions are a prefix of the row.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.modules.ssm import RecurrentState

_HI = jax.lax.Precision.HIGHEST

#: channels a block of the layout (an (8, 128) float32 register's sublanes)
BLOCK = 8
#: block pairs a tile of the state walk holds, at most: 17 x 64 = 1088 rows of
#: ``d`` float32, 557 KB a KV head at d = 128 (136 pairs = 8 tiles)
PAIRS_PER_TILE = 17


@dataclass(frozen=True)
class PowerSpec:
    """Static sizes of a power-retention mixer."""

    num_heads: int  # query heads
    num_kv_heads: int
    head_dim: int
    #: what the normaliser adds to the sum of the weights
    norm_eps: float = 1e-6

    @property
    def state_dim(self) -> int:
        return state_dim(self.head_dim)


def state_dim(head_dim: int) -> int:
    """``D`` of the layout held: 64 rows a block pair."""
    n = head_dim // BLOCK
    return BLOCK * BLOCK * n * (n + 1) // 2


@functools.lru_cache(maxsize=None)
def pair_table(head_dim: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(I, J, c), each (pairs,): the block pairs ``I <= J`` in lexicographic
    order and each pair's coefficient (``sqrt(2 / d)`` off the diagonal,
    ``sqrt(1 / d)`` on it)."""
    if head_dim % BLOCK:
        raise ValueError(f"head_dim {head_dim} is not a multiple of {BLOCK}")
    n = head_dim // BLOCK
    I, J = np.triu_indices(n)
    c = np.sqrt(np.where(I < J, 2.0, 1.0) / head_dim)
    return I.astype(np.int32), J.astype(np.int32), c.astype(np.float32)


def pairs_per_tile(head_dim: int, want: int = PAIRS_PER_TILE) -> int:
    """The most block pairs a tile, at most ``want``, that divide their number."""
    pairs = len(pair_table(head_dim)[0])
    p = min(want, pairs)
    while pairs % p:
        p -= 1
    return p


def _phi_pairs(u: jax.Array, I, J, c) -> jax.Array:
    """``phi`` over the block pairs ``(I, J)`` with coefficients ``c`` (each
    (P,), constants or traced): u (..., d) -> (..., P * 64)."""
    ub = u.reshape(u.shape[:-1] + (u.shape[-1] // BLOCK, BLOCK))
    ua = jnp.take(ub, I, axis=-2)  # (..., P, 8)
    uj = jnp.take(ub, J, axis=-2) * c[:, None]
    prod = ua[..., :, None] * uj[..., None, :]  # (..., P, 8, 8)
    return prod.reshape(u.shape[:-1] + (-1,))


def phi(u: jax.Array) -> jax.Array:
    """The symmetric square of ``u / d^(1/4)`` in the layout held: u (..., d)
    float32 -> (..., D), ``phi(q) . phi(k) = ((q . k) / sqrt(d))^2``. (Two
    takes of the blocks by the constant pair table and a broadcast product:
    read on the chip, PR 66, static slices of each block against the blocks
    after it, concatenated, cost the decode program 2.6 x as much.)"""
    return _phi_pairs(u.astype(jnp.float32), *pair_table(u.shape[-1]))


@jax.tree_util.register_dataclass
@dataclass
class PowerState(RecurrentState):
    """The per-slot state of every power-retention layer: ``ssm (L, slots, G,
    D, d)`` float32 is ``S``; ``conv (L, slots, G, D)`` float32 is the
    normaliser ``z`` (no conv tail: the model has no convolution). Rows,
    slots and scrub as :class:`~.ssm.RecurrentState`."""

    KIND = "power"

    def fill_slots(self, slots, value: float) -> "PowerState":
        idx = jnp.asarray(slots, jnp.int32)
        return PowerState(
            conv=self.conv.at[:, idx].set(value), ssm=self.ssm.at[:, idx].set(value)
        )


def init_power_state(spec: PowerSpec, num_layers: int, num_slots: int) -> PowerState:
    G, D, d = spec.num_kv_heads, spec.state_dim, spec.head_dim
    return PowerState(
        conv=jnp.zeros((num_layers, num_slots, G, D), jnp.float32),
        ssm=jnp.zeros((num_layers, num_slots, G, D, d), jnp.float32),
    )


def power_state_pspecs() -> PowerState:
    from jax.sharding import PartitionSpec as P

    return PowerState(conv=P(), ssm=P())


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------


def _grouped(q: jax.Array, G: int) -> jax.Array:
    """q (..., H, d) -> (..., G, n_rep, d): query head h reads KV head h // n_rep."""
    return q.reshape(q.shape[:-2] + (G, q.shape[-2] // G, q.shape[-1]))


def normalised_read(num: jax.Array, pq_dot_z: jax.Array, eps: float) -> jax.Array:
    """``num / (phi(q) . z + eps)``: num (..., d), the dot product (...,)."""
    return num / (pq_dot_z[..., None] + eps)


def power_step(
    q: jax.Array,  # (R, H, d) float32, normalised and rotated
    k: jax.Array,  # (R, G, d) float32, normalised and rotated
    v: jax.Array,  # (R, G, d)
    lg: jax.Array,  # (R, G) float32 <= 0: the log decay of each KV head
    S: jax.Array,  # (R, G, D, d) float32
    z: jax.Array,  # (R, G, D) float32
    valid: jax.Array,  # (R,) bool
    eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token per row. Returns (y (R, H, d) float32, new S, new z); invalid
    rows keep their state bit for bit."""
    f32 = jnp.float32
    G = k.shape[1]
    a = jnp.exp(jnp.where(valid[:, None], lg.astype(f32), 0.0))
    pk = phi(k)  # (R, G, D)
    new_S = a[..., None, None] * S + pk[..., None] * v.astype(f32)[:, :, None, :]
    new_z = a[..., None] * z + pk
    new_S = jnp.where(valid[:, None, None, None], new_S, S)
    new_z = jnp.where(valid[:, None, None], new_z, z)
    pq = phi(_grouped(q, G))  # (R, G, n_rep, D)
    num = jnp.einsum("rgnD,rgDc->rgnc", pq, new_S, precision=_HI)
    den = jnp.einsum("rgnD,rgD->rgn", pq, new_z, precision=_HI)
    return normalised_read(num, den, eps).reshape(q.shape), new_S, new_z


def advance_rows(
    q: jax.Array,  # (R, Q, H, d) float32
    k: jax.Array,  # (R, Q, G, d) float32
    v: jax.Array,  # (R, Q, G, d)
    lg: jax.Array,  # (R, Q, G) float32 <= 0
    valid: jax.Array,  # (R, Q) bool, a prefix of each row
    reset: jax.Array,  # (R,) bool: the row starts from a zero state
    s_stack: jax.Array,  # (L, slots, G, D, d) float32
    z_stack: jax.Array,  # (L, slots, G, D) float32
    li,  # int32 scalar: the layer
    slots: jax.Array,  # (R,) int32: the slot of each row (past the last: an empty row)
    eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One sub-chunk of Q positions a row, on the STACKED state in place, the
    LIVE rows alone (a row with a valid position and a real slot): a loop
    over them, and inside it a walk over the tiles of ``D``, each taken from
    the stack once and put back once. Returns (y (R, Q, H, d) float32 — zero
    for a row that is not live —, the two stacks)."""
    f32 = jnp.float32
    R, Q, H, d = q.shape
    G = k.shape[2]
    n_rep = H // G
    I, J, c = (jnp.asarray(t) for t in pair_table(d))
    pp = pairs_per_tile(d)
    rows = pp * BLOCK * BLOCK
    n_tiles = len(I) // pp
    live = jnp.any(valid, axis=1) & (slots < s_stack.shape[1])
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live.astype(jnp.int32))
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    li = jnp.asarray(li, jnp.int32)

    def one_row(i, carry):
        s_stack, z_stack, out = carry
        r = order[i]
        slot = slots[r]
        take = lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False)
        ok = take(valid)  # (Q,)
        fresh = take(reset)
        # heads lead: (G, ...) products one KV head at a time, the group's
        # n_rep query heads side by side as rows (n, t)
        qr = jnp.transpose(_grouped(take(q).astype(f32), G), (1, 2, 0, 3)).reshape(G, n_rep * Q, d)
        kr = jnp.swapaxes(take(k).astype(f32), 0, 1)  # (G, Q, d)
        vr = jnp.swapaxes(take(v).astype(f32), 0, 1)
        lgr = jnp.where(ok[None, :], jnp.swapaxes(take(lg).astype(f32), 0, 1), 0.0)  # (G, Q)
        cum = jnp.cumsum(lgr, axis=1)  # inclusive, <= 0
        end = cum[:, -1]  # (G,)
        # inside the sub-chunk: ((q . k) / sqrt(d))^2 times exp(sum_{j < l <= t} lg_l)
        scores = jnp.einsum("gmd,gjd->gmj", qr, kr, precision=_HI).reshape(G, n_rep, Q, Q)
        seg = cum[:, :, None] - cum[:, None, :]  # (G, t, j)
        mask = causal[None] & ok[None, None, :]
        decay = jnp.exp(jnp.where(mask, seg, -jnp.inf))  # 0 above the diagonal and at invalid keys
        A = jnp.square(scores) * (decay / d)[:, None]
        num = jnp.einsum("gntj,gjc->gntc", A, vr, precision=_HI).reshape(G, n_rep * Q, d)
        den = jnp.sum(A, axis=-1).reshape(G, n_rep * Q)
        into = jnp.tile(jnp.exp(cum), (1, n_rep))  # (G, n_rep * Q): decay from the state to t
        out_w = jnp.where(ok[None, :], jnp.exp(end[:, None] - cum), 0.0)  # (G, Q): from j to the end
        carried = jnp.exp(end)  # (G,)

        def one_tile(t, carry):
            s_stack, z_stack, num, den = carry
            at = lambda a: jax.lax.dynamic_slice_in_dim(a, t * pp, pp)
            It, Jt, ct = at(I), at(J), at(c)
            s_at = (li, slot, 0, t * rows, 0)
            z_at = s_at[:-1]
            s_t = jax.lax.dynamic_slice(s_stack, s_at, (1, 1, G, rows, d))[0, 0]
            z_t = jax.lax.dynamic_slice(z_stack, z_at, (1, 1, G, rows))[0, 0]
            s_t = jnp.where(fresh, 0.0, s_t)
            z_t = jnp.where(fresh, 0.0, z_t)
            pq = _phi_pairs(qr, It, Jt, ct)  # (G, n_rep * Q, rows)
            num = num + into[..., None] * jnp.einsum("gmD,gDc->gmc", pq, s_t, precision=_HI)
            den = den + into * jnp.einsum("gmD,gD->gm", pq, z_t, precision=_HI)
            pk = _phi_pairs(kr, It, Jt, ct) * out_w[..., None]  # (G, Q, rows)
            s_t = carried[:, None, None] * s_t + jnp.einsum("gjD,gjc->gDc", pk, vr, precision=_HI)
            z_t = carried[:, None] * z_t + jnp.sum(pk, axis=1)
            s_stack = jax.lax.dynamic_update_slice(s_stack, s_t[None, None], s_at)
            z_stack = jax.lax.dynamic_update_slice(z_stack, z_t[None, None], z_at)
            return s_stack, z_stack, num, den

        s_stack, z_stack, num, den = jax.lax.fori_loop(
            0, n_tiles, one_tile, (s_stack, z_stack, num, den))
        y = normalised_read(num, den, eps).reshape(G, n_rep, Q, d)
        y = jnp.transpose(y, (2, 0, 1, 3)).reshape(1, Q, H, d)
        return s_stack, z_stack, jax.lax.dynamic_update_slice(out, y, (r, 0, 0, 0))

    out = jnp.zeros((R, Q, H, d), f32)
    s_stack, z_stack, out = jax.lax.fori_loop(0, n_live, one_row, (s_stack, z_stack, out))
    return out, s_stack, z_stack


def power_chunk(
    q: jax.Array,  # (R, Q, H, d)
    k: jax.Array,  # (R, Q, G, d)
    v: jax.Array,  # (R, Q, G, d)
    lg: jax.Array,  # (R, Q, G) <= 0
    S: jax.Array,  # (R, G, D, d) float32, the state BEFORE this chunk
    z: jax.Array,  # (R, G, D) float32
    valid: jax.Array,  # (R, Q) bool, a prefix of each row
    chunk_size: int = 128,
    eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A chunk of Q positions per row from ``S``, ``z``, in sub-chunks of
    ``chunk_size``. Returns (y (R, Q, H, d) float32, the state after each
    row's valid positions). Matrix products run at ``Precision.HIGHEST``:
    their operands are float32 (the state, ``phi``), which the default would
    round to bf16."""
    R, Q = q.shape[:2]
    c = min(int(chunk_size), Q)
    slots = jnp.arange(R, dtype=jnp.int32)
    never = jnp.zeros((R,), bool)
    s_stack, z_stack, ys = S[None], z[None], []
    for start in range(0, Q, c):
        part = lambda a: a[:, start : start + c]
        y, s_stack, z_stack = advance_rows(
            part(q), part(k), part(v), part(lg), part(valid), never, s_stack, z_stack, 0, slots, eps)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), s_stack[0], z_stack[0]


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def power_mixer(m: dict, x: jax.Array, cos, sin, state: PowerState, li, valid, reset,
                spec: PowerSpec, rms_eps: float, slots=None) -> Tuple[jax.Array, PowerState]:
    """The power-retention mixer of one layer on the normalised input x (R,
    Q, hidden): ``m`` Qwen3's ``self_attn`` tree with the gate ``g_proj``
    (weight (hidden, G), bias (G,)) beside it; ``state`` the stacked per-slot
    state of ALL layers, advanced at index ``li`` for the ``valid`` (R, Q)
    positions; ``reset`` (R,) rows start from zero; ``slots`` as
    ``models/granite_hybrid.mamba_layer`` takes them (the chunk program's
    rows carry their slot; None: row r owns slot r, the decode program, which
    runs ``ops/power_state_update``). q and k are normalised a head and
    rotated IN FLOAT32 and stay there: ``phi`` squares them. Returns (the
    mixer's output (R, Q, hidden), the state)."""
    from neuronx_distributed_inference_tpu.modules.norm import rms_norm
    from neuronx_distributed_inference_tpu.modules.rope import apply_rope
    from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret
    from neuronx_distributed_inference_tpu.ops.quant import linear

    f32 = jnp.float32
    R, Q, _ = x.shape
    H, G, d = spec.num_heads, spec.num_kv_heads, spec.head_dim
    heads = lambda a, n: a.astype(f32).reshape(R, Q, n, d)
    # the three projections and W_o under the scopes every decoder's are
    # (the caller's ``layer.power`` is the mixer's own work)
    with jax.named_scope("layer.qkv"):
        q, k, v = (linear(m[name], x) for name in ("q_proj", "k_proj", "v_proj"))
    q = apply_rope(rms_norm(heads(q, H), m["q_norm"]["weight"], rms_eps), cos, sin)
    k = apply_rope(rms_norm(heads(k, G), m["k_norm"]["weight"], rms_eps), cos, sin)
    v = heads(v, G)
    # the gate's product keeps its float32 sum (eight numbers a token): the
    # decay compounds over a request's life
    gate = jnp.einsum("rqh,hg->rqg", x, m["g_proj"]["weight"], precision=_HI,
                      preferred_element_type=f32)
    lg = jax.nn.log_sigmoid(gate + m["g_proj"]["bias"].astype(f32))

    if slots is None:  # the decode program: one position a row, row r on slot r
        from neuronx_distributed_inference_tpu.ops.power_state_update import power_state_update

        y, new_s, new_z = power_state_update(
            state.ssm, state.conv, li, q[:, 0], k[:, 0], v[:, 0], lg[:, 0], valid[:, 0], reset,
            eps=spec.norm_eps, interpret=kernel_interpret(),
        )
        y = y[:, None]
    else:
        y, new_s, new_z = advance_rows(
            q, k, v, lg, valid, reset, state.ssm, state.conv, li, slots, spec.norm_eps)
    with jax.named_scope("layer.o_proj"):
        out = linear(m["o_proj"], y.astype(x.dtype).reshape(R, Q, H * d))
    return out, PowerState(conv=new_z, ssm=new_s)
