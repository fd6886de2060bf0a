"""Compressed Convolutional Attention (CCA; ZAYA1): what turns the normalised
hidden state into the q, k and v that are attended INSIDE a latent narrower
than the model, and the one-token carry it keeps per serving slot.

Published description: Compressed Convolutional Attention (arXiv:2510.04476)
and the ZAYA1 report (arXiv:2511.17127). ``x_t`` the normalised hidden
state, ``H_q`` query and ``H_kv`` key/value heads of ``d``, ``G = H_q / H_kv``:

    q~_t = W_q x_t,  k~_t = W_k x_t,  u_t = [q~_t ; k~_t]                 (H_q + H_kv) d channels
    a_t  = w0[0] * u_{t-1} + w0[1] * u_t + b0                             depthwise, kernel 2
    c_t[g] = a_{t-1}[g] W1[0, g] + a_t[g] W1[1, g] + b1[g]                grouped by head, d -> d, kernel 2
    q_t = c_t[q] + (q~_t + repeat_G(k~_t)) / 2                            q-k mean
    k_t = c_t[k] + (mean_G(q~_t) + k~_t) / 2
    v_t = [W_v1 x_t ; W_v2 x_{t-1}]   split into H_kv heads               value shift
    q_t = sqrt(d) q_t / |q_t|,   k_t = exp(tau_head) sqrt(d) k_t / |k_t|  per head

K and V enter the block pool at the ordinary ``(H_kv, d)``, so the paged
attention kernels serve them as they serve any grouped-query model; what is
new is what a token needs of the token BEFORE it: ``u_{t-1}``, ``a_{t-1}``
and ``W_v2 x_{t-1}``. Across the passes of a request (prefill chunks, decode
steps) they are kept per slot and layer in :class:`TokenCarry`, in the model
dtype (they are copies of bf16 tensors).

The contract every caller relies on is ``modules/ssm.causal_conv``'s, whose
window helpers this file shares: the carry advances by the number of VALID
positions of a row (a prefix of the row), an invalid position or row leaves
it bit-identical (pure copies), and a row that starts at position 0 starts
from a zero carry (the caller zeroes it: models/zaya.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.ssm import (
    carried_window,
    causal_conv,
    tail_after,
)
from neuronx_distributed_inference_tpu.ops.quant import linear


@dataclass(frozen=True)
class CCASpec:
    """Static sizes of the CCA front end."""

    num_heads: int
    num_kv_heads: int
    head_dim: int

    @property
    def groups(self) -> int:
        """Heads the convs run over: query heads, then key heads."""
        return self.num_heads + self.num_kv_heads

    @property
    def channels(self) -> int:
        return self.groups * self.head_dim

    @property
    def value_half(self) -> int:
        return self.num_kv_heads * self.head_dim // 2

    @property
    def carry_dim(self) -> int:
        """``u_{t-1}``, ``a_{t-1}`` and ``W_v2 x_{t-1}`` side by side."""
        return 2 * self.channels + self.value_half


@jax.tree_util.register_dataclass
@dataclass
class TokenCarry:
    """What every CCA layer keeps of a slot's last token.

    last: (L, slots, carry_dim), model dtype — ``[u | a | W_v2 x]`` of the
    last VALID token the slot's request has passed through the layer.

    The per-slot state of a ``HybridBlockCache`` beside a pool over ALL
    layers; rows find their slot as ``modules/ssm.RecurrentState``'s do (the
    decode program's row r owns slot r, the chunk program's rows carry
    their slot in ``seq_ids``)."""

    last: jax.Array

    #: which family of the serving step's counters counts this state
    KIND = "latent_carry"

    @property
    def num_slots(self) -> int:
        return self.last.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.last.size * self.last.dtype.itemsize)

    def fill_slots(self, slots, value: float) -> "TokenCarry":
        """Overwrite the carry of whole slots in every layer (scrub: 0.0)."""
        return TokenCarry(last=self.last.at[:, jnp.asarray(slots, jnp.int32)].set(value))


def init_token_carry(spec: CCASpec, num_layers: int, num_slots: int, dtype) -> TokenCarry:
    return TokenCarry(last=jnp.zeros((num_layers, num_slots, spec.carry_dim), dtype))


def _unit(x: jax.Array) -> jax.Array:
    """x / |x| over the last axis in float32; |x| = sqrt(sum x^2 + 1e-12)."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + 1e-12)


def cca_qkv(
    params: dict,  # one layer's ``self_attn`` leaves
    x: jax.Array,  # (R, Q, H) normalised hidden state
    carry: jax.Array,  # (R, carry_dim) each row's carry BEFORE this pass
    n_valid: jax.Array,  # (R,) int32: valid positions are [0, n_valid)
    spec: CCASpec,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """q (R, Q, H_q, d), k and v (R, Q, H_kv, d) before the rotation, and
    each row's carry after its valid positions."""
    with jax.named_scope("layer.qkv"):
        R, Q, _ = x.shape
        Hq, Hkv, d = spec.num_heads, spec.num_kv_heads, spec.head_dim
        G, C = Hq // Hkv, spec.channels
        f32, dtype = jnp.float32, x.dtype
        u_prev, a_prev, v2_prev = carry[:, :C], carry[:, C : 2 * C], carry[:, 2 * C :]

        qt, kt = linear(params["q_proj"], x), linear(params["k_proj"], x)
        u = jnp.concatenate([qt, kt], axis=-1)  # (R, Q, C)
        conv0 = params["conv0"]
        a, u_tail = causal_conv(u, u_prev[None], conv0["weight"], conv0["bias"], n_valid,
                                activation=None)
        a = a.astype(dtype)  # what the next token is handed is what this one's second stage takes
        window = carried_window(a, a_prev[None])  # (R, 1 + Q, C)
        a_tail = tail_after(window, n_valid, 1, dtype)
        heads = window.reshape(R, 1 + Q, spec.groups, d)
        # both taps in ONE product per head: [a_{t-1} ; a_t] (2d) against the taps
        # stacked on the contraction, accumulated in float32 by the unit
        pairs = jnp.concatenate([heads[:, :Q], heads[:, 1:]], axis=-1)  # (R, Q, groups, 2d)
        w1 = params["conv1"]["weight"].astype(dtype)  # (2, groups, d, d)
        c = jnp.einsum("rqgi,gio->rqgo", pairs, jnp.concatenate([w1[0], w1[1]], axis=1))
        c = (c.astype(f32) + params["conv1"]["bias"].astype(f32).reshape(spec.groups, d)).astype(dtype)

        qt = qt.reshape(R, Q, Hq, d).astype(f32)
        kt = kt.reshape(R, Q, Hkv, d).astype(f32)
        q = c[:, :, :Hq].astype(f32) + (qt + jnp.repeat(kt, G, axis=2)) / 2
        k = c[:, :, Hq:].astype(f32) + (qt.reshape(R, Q, Hkv, G, d).mean(axis=3) + kt) / 2
        scale = jnp.sqrt(jnp.asarray(d, f32))
        temp = jnp.exp(params["key_temp"].astype(f32))[None, None, :, None]
        q = (scale * _unit(q.astype(dtype))).astype(dtype)
        k = (temp * scale * _unit(k.astype(dtype))).astype(dtype)

        v1, v2 = linear(params["v1_proj"], x), linear(params["v2_proj"], x)
        shifted = carried_window(v2, v2_prev[None])  # (R, 1 + Q, value_half): position q holds x_{q-1}'s
        v2_tail = tail_after(shifted, n_valid, 1, dtype)
        v = jnp.concatenate([v1, shifted[:, :Q]], axis=-1).reshape(R, Q, Hkv, d)
        return q, k, v, jnp.concatenate([u_tail[0], a_tail[0], v2_tail[0]], axis=-1)
