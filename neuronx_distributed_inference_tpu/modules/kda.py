"""Kimi Delta Attention (KDA): a linear-attention mixer whose heads each keep
a MATRIX state that every token decays channel by channel, READS, and then
corrects by a rank-one delta (Kimi Linear, arXiv:2510.26692; the published
``modeling_kimi.py``; the gated delta rule of ``transformers``'
``models/qwen3_next`` with the decay a vector over the key channels where
that one has a scalar a head).

Per head (``d_k = d_v = head_dim``), per token ``t``:

    S' = diag(a_t) S_{t-1}                      a_t = exp(g_t) in (0, 1), one a KEY channel
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T       b_t in (0, 1), one a head
    o_t = S_t^T q_t

What a layer keeps per serving slot is constant in the context length: the
last ``conv_kernel - 1`` inputs of its depthwise causal convolution over
``[q | k | v]`` (the carry of ``modules/ssm.causal_conv``) and ``S``
``(heads, d_k, d_v)`` float32: :class:`DeltaState`, a
:class:`~.ssm.RecurrentState` under another ``KIND``.

Three forms of the one recurrence:

* :func:`kda_step` — one token a row, the definition.
* the chunked form — a (rows, q) chunk from the incoming state: inside a
  sub-chunk of ``chunk_size`` positions, with ``G_t = sum_{s<=t} g_s``,

      A_ij = b_i (k_i * e^{G_i - G_j}) . k_j   (j < i)      T = (I + A)^-1 diag(b)
      W = T (K * e^G),  U = T V,  V' = U - W S_0
      O = (Q * e^G) S_0 + P V',  P_ij = (q_i * e^{G_i - G_j}) . k_j   (j <= i)
      S_C = diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T V'

  and between sub-chunks the state is carried. Only differences ``G_i - G_j
  <= 0`` are ever exponentiated: the pairwise decays of a sub-chunk are
  taken one (i, j, channel) at a time, never as ``e^{G_i} e^{-G_j}``.
  ``(I + A)^-1`` of the strictly lower-triangular ``A`` is the finite series
  ``sum_k (-A)^k = (I - A)(I + A^2)(I + A^4)...``. What does not depend on
  the state (``W``, ``U``, ``Q e^G``, ``K e^{G_C - G}``, ``P``, ``G_C``) is
  :func:`chunk_operands`; the recurrence between sub-chunks then runs

  - as ``ops/kda_chunk_scan.py``, a kernel on the STACKED state in place
    that keeps a head's ``S`` in VMEM across a row's sub-chunks and moves
    nothing for a row with no valid position (the chunk program, wherever
    ``ops/kernel_mode.use_kda_chunk_scan`` admits the call), or
  - as the ``lax.scan`` of :func:`kda_chunk` over the rows' gathered state:
    the fallback (heads that are not whole lane rows, a chunk that is not
    whole sub-chunks, a sharded mesh) and the kernel's reference.
* ``ops/kda_state_update.py`` — one token a row on the STACKED state in
  place (the decode program), held to :func:`kda_step`.

The contract of ``modules/ssm.py`` holds here too: an invalid position (a
padded chunk tail, a row that sits a pass out) leaves the conv tail and ``S``
bit-identical (it sees ``g = 0`` and ``b = 0``: decay 1, no delta, and a row
with no valid position is passed through: by a select in the scan, untouched
by the kernel); valid positions are a prefix of the row.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.modules.ssm import RecurrentState

_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class KDASpec:
    """Static sizes of a KDA mixer (the published ``linear_attn_config``)."""

    num_heads: int
    head_dim: int  # d_k = d_v
    conv_kernel: int = 4
    #: the width between the two matrices of the decay's and the output
    #: gate's low-rank projections (the published modeling: ``head_dim``)
    gate_rank: int = 128
    #: positions of a sub-chunk of the chunked form
    chunk_size: int = 16
    rms_eps: float = 1e-5
    l2_eps: float = 1e-6

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.d_inner


@jax.tree_util.register_dataclass
@dataclass
class DeltaState(RecurrentState):
    """The per-slot state of every KDA layer: ``conv (L, conv_kernel - 1,
    slots, 3 x d_inner)`` in the model dtype, ``ssm (L, slots, heads, d_k,
    d_v)`` float32; rows, slots and scrub as :class:`~.ssm.RecurrentState`."""

    KIND = "kda"


def init_delta_state(spec: KDASpec, num_layers: int, num_slots: int, dtype) -> DeltaState:
    return DeltaState(
        conv=jnp.zeros((num_layers, spec.conv_kernel - 1, num_slots, spec.conv_dim), dtype),
        ssm=jnp.zeros(
            (num_layers, num_slots, spec.num_heads, spec.head_dim, spec.head_dim), jnp.float32
        ),
    )


def delta_state_pspecs() -> DeltaState:
    from jax.sharding import PartitionSpec as P

    return DeltaState(conv=P(), ssm=P())


def l2_normalize(x: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------


def kda_step(
    q: jax.Array,  # (R, H, D) float32, normalised and scaled
    k: jax.Array,  # (R, H, D) float32, normalised
    v: jax.Array,  # (R, H, D)
    g: jax.Array,  # (R, H, D) float32 <= 0: the log decay of each key channel
    beta: jax.Array,  # (R, H) float32 in (0, 1)
    state: jax.Array,  # (R, H, D, D) float32: (key channel, value channel)
    valid: jax.Array,  # (R,) bool
) -> Tuple[jax.Array, jax.Array]:
    """One token per row. Returns (o (R, H, D) float32, new state); invalid
    rows keep their state bit for bit."""
    f32 = jnp.float32
    g = jnp.where(valid[:, None, None], g.astype(f32), 0.0)
    beta = jnp.where(valid[:, None], beta.astype(f32), 0.0)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=2))
    new = s + k[..., None] * u[:, :, None, :]
    new = jnp.where(valid[:, None, None, None], new, state)
    return jnp.sum(new * q[..., None], axis=2), new


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a`` (..., c, c) strictly lower triangular (so
    nilpotent): ``sum_{k<c} (-a)^k = (I - a)(I + a^2)(I + a^4)...``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    power = -a
    inv = eye + power
    span = 2  # the series so far holds the powers below ``span``
    while span < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
        span *= 2
    return inv


def chunk_operands(q, k, v, g, beta, valid, chunk_size: int = 16):
    """What the chunked form takes between sub-chunks, from a chunk's (R, Q,
    H, ...) inputs (as :func:`kda_chunk` takes them): ``(W, U, q_in, k_out)``
    (R, H, n, c, D), ``P`` (R, H, n, c, c) and ``G_end`` (R, H, n, D), float32,
    for ``n`` sub-chunks of ``c = min(chunk_size, Q)`` positions, the last
    zero padded (``g = 0``, ``b = 0``: no-ops). One sub-chunk ``t`` from the
    state ``S`` before it:

        V' = U_t - W_t S,   O_t = q_in_t S + P_t V',   S <- diag(e^{G_end_t}) S + k_out_t^T V'

    Both executors of that recurrence call this (the scan of
    :func:`kda_chunk`, the kernel of ``ops/kda_chunk_scan.py``)."""
    f32 = jnp.float32
    R, Q, H, D = q.shape
    g = jnp.where(valid[..., None, None], g.astype(f32), 0.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    c = min(int(chunk_size), Q)
    n = -(-Q // c)
    pad = n * c - Q

    def split(a):  # (R, Q, H, ...) -> (R, H, n, c, ...), zero padded
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((R, n, c) + a.shape[2:])
        return jnp.transpose(a, (0, 3, 1, 2) + tuple(range(4, a.ndim)))

    q, k, v, g, beta = split(q), split(k), split(v), split(g), split(beta)
    G = jnp.cumsum(g, axis=3)  # (R, H, n, c, D), inclusive, <= 0
    # the pairwise decays, for A's rows (k_i) and P's rows (q_i) in one pass:
    # M[i, j] = sum_d x_id k_jd e^{G_i - G_j}, never exponentiated above 0
    x = jnp.concatenate([k, q], axis=3)  # (R, H, n, 2c, D)
    diff = jnp.concatenate([G, G], axis=3)[..., :, None, :] - G[..., None, :, :]
    M = jnp.sum(x[..., :, None, :] * k[..., None, :, :] * jnp.exp(jnp.minimum(diff, 0.0)), axis=-1)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    A = jnp.where(i > j, M[..., :c, :], 0.0) * beta[..., None]
    P = jnp.where(i >= j, M[..., c:, :], 0.0)
    T = _unit_lower_inverse(A) * beta[..., None, :]
    eG = jnp.exp(G)
    W = jnp.matmul(T, k * eG, precision=_HI)  # (R, H, n, c, D)
    U = jnp.matmul(T, v, precision=_HI)
    G_end = G[..., -1, :]  # (R, H, n, D)
    k_out = k * jnp.exp(G_end[..., None, :] - G)  # k_j decayed from j to the sub-chunk's end
    return W, U, q * eG, k_out, P, G_end


def kda_chunk(
    q: jax.Array,  # (R, Q, H, D) normalised and scaled
    k: jax.Array,  # (R, Q, H, D) normalised
    v: jax.Array,  # (R, Q, H, D)
    g: jax.Array,  # (R, Q, H, D) <= 0
    beta: jax.Array,  # (R, Q, H)
    state: jax.Array,  # (R, H, D, D) float32, the state BEFORE this chunk
    valid: jax.Array,  # (R, Q) bool, a prefix of each row
    chunk_size: int = 16,
) -> Tuple[jax.Array, jax.Array]:
    """A chunk of Q positions per row from ``state``. Returns (o (R, Q, H, D)
    float32, the state after each row's valid positions). Matrix products
    run at ``Precision.HIGHEST``: their operands are float32 (the state, the
    cumulative decays), which the default would round to bf16."""
    R, Q, H, D = q.shape
    # the sub-chunks lead: the scan takes its steps off the major axis (read on
    # the chip, PR 61: with them third, slicing a step's operands was a third
    # of the scan's time)
    operands = tuple(jnp.moveaxis(a, 2, 0) for a in chunk_operands(q, k, v, g, beta, valid, chunk_size))

    def body(s, t):  # s (R, H, D, D); one sub-chunk
        W_t, U_t, q_t, k_t, P_t, end_t = t
        v_new = U_t - jnp.matmul(W_t, s, precision=_HI)  # (R, H, c, D)
        o_t = jnp.matmul(q_t, s, precision=_HI) + jnp.matmul(P_t, v_new, precision=_HI)
        s = s * jnp.exp(end_t)[..., None] + jnp.einsum("rhck,rhcv->rhkv", k_t, v_new, precision=_HI)
        return s, o_t

    new, o = jax.lax.scan(body, state, operands)  # o (n, R, H, c, D)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(R, -1, H, D)[:, :Q]
    new = jnp.where(jnp.any(valid, axis=1)[:, None, None, None], new, state)
    return o, new


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def kda_gates(m: dict, x: jax.Array, spec: KDASpec) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """From the normalised input x (R, Q, hidden): the log decay ``g = -exp(A_log)
    softplus((x W_fa) W_fb + dt_bias)`` (R, Q, H, D) float32, the step size ``b =
    sigmoid(x W_b)`` (R, Q, H) float32 and the output gate's logits ``(x W_ga)
    W_gb`` (R, Q, H, D)."""
    from neuronx_distributed_inference_tpu.ops.quant import linear

    f32 = jnp.float32
    R, Q, _ = x.shape
    H, D = spec.num_heads, spec.head_dim
    decay = linear(m["f_b_proj"], linear(m["f_a_proj"], x)).astype(f32) + m["dt_bias"].astype(f32)
    g = -jnp.exp(m["A_log"].astype(f32))[:, None] * jax.nn.softplus(decay).reshape(R, Q, H, D)
    beta = jax.nn.sigmoid(linear(m["b_proj"], x).astype(f32))
    gate = linear(m["g_b_proj"], linear(m["g_a_proj"], x)).reshape(R, Q, H, D)
    return g, beta, gate


def gated_head_norm(o: jax.Array, gate: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """``rmsnorm(o) * w * sigmoid(gate)`` over each head's channels (o, gate
    (..., H, D); ``w`` (D,) shared by the heads), in float32, returned in
    ``gate``'s dtype."""
    o = o.astype(jnp.float32)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    normed = o * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return (normed * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)


def kda_mixer(m: dict, x: jax.Array, state: DeltaState, li, valid, reset, spec: KDASpec,
              slots=None) -> Tuple[jax.Array, DeltaState]:
    """The KDA mixer of one layer on the normalised input x (R, Q, hidden):
    ``state`` the stacked per-slot state of ALL such layers, advanced at
    index ``li`` for the ``valid`` (R, Q) positions; ``reset`` (R,) rows start
    from zero; ``slots`` as ``models/granite_hybrid.mamba_layer`` takes them
    (the chunk program's rows carry their slot; None: row r owns slot r, the
    decode program, which runs ``ops/kda_state_update``). A chunk runs
    ``ops/kda_chunk_scan`` on the stacked state where the gate admits it
    (the kernel addresses ``(li, slot)`` itself: no rows' state is gathered
    or scattered), else :func:`kda_chunk` over the rows' state. Returns (the
    mixer's output (R, Q, hidden), the state)."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.ops.quant import linear
    from neuronx_distributed_inference_tpu.parallel.sharding import head_shard_degree

    R, Q, _ = x.shape
    H, D, d_inner = spec.num_heads, spec.head_dim, spec.d_inner
    qkv = linear(m["qkv_proj"], x)  # (R, Q, [q | k | v])
    qkv, conv = ssm.conv_with_carry(state.conv, li, qkv, m["conv1d"]["weight"], None, valid, reset, slots)
    qkv = qkv.astype(x.dtype)
    heads = lambda a: a.reshape(R, Q, H, D)
    q = l2_normalize(heads(qkv[..., :d_inner]), spec.l2_eps) * (D ** -0.5)
    k = l2_normalize(heads(qkv[..., d_inner : 2 * d_inner]), spec.l2_eps)
    v = heads(qkv[..., 2 * d_inner :])
    g, beta, gate = kda_gates(m, x, spec)

    if Q == 1 and slots is None:
        from neuronx_distributed_inference_tpu.ops.kda_state_update import kda_state_update

        o, new = kda_state_update(
            state.ssm, li, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], valid[:, 0], reset,
            interpret=kernel_mode.kernel_interpret(),
        )
        o = o[:, None]
    elif kernel_mode.use_kda_chunk_scan(D, Q, spec.chunk_size, head_shard_degree()):
        from neuronx_distributed_inference_tpu.ops import kda_chunk_scan as scan

        o, new = scan.kda_chunk_scan(
            state.ssm, li, q, k, v, g, beta, valid, reset, slots, chunk_size=spec.chunk_size,
            interpret=kernel_mode.kernel_interpret(),
        )
    else:
        s = ssm.rows_state(state.ssm, li, reset, slots)
        o, s = kda_chunk(q, k, v, g, beta, s, valid, chunk_size=spec.chunk_size)
        new = ssm.put_rows_state(state.ssm, s, li, slots)
    gated = gated_head_norm(o, gate, m["o_norm"]["weight"], spec.rms_eps)
    return linear(m["o_proj"], gated.reshape(R, Q, d_inner)), DeltaState(conv=conv, ssm=new)
