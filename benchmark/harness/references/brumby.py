"""The plain reference of a Brumby (``model_type: "brumby"``) decoder: Qwen3's
block with POWER RETENTION of degree 2 in attention's place, in its ATTENTION
FORM — in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
no state, no chunk, no kernel, no batching, no line of the program's code —
and its bf16 TWIN.

The interface is that of ``dense.py``:

    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, rounding=None, fault=None)
    twin_logits(params, geo, tokens, positions)

The equations ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; Manifest AI's ``retention`` package; the configuration
file's ``assumed`` lists what the published config does not carry), with ``x =
rmsnorm(h, ln1)``, ``d`` the head size, ``H`` query heads, ``G`` KV heads, head
``h`` reading KV head ``h // (H / G)``:

    h = embed[tokens]
    per layer:
      q = rope(rmsnorm_head(x W_q))  (H, d)    k = rope(rmsnorm_head(x W_k))  (G, d)    v = x W_v  (G, d)
      lg_t = log_sigmoid(x_t W_g + b_g)                  (G,): one log decay a KV head a token
      a_tj = exp(sum_{l = j+1 .. t} lg_l) ((q_t . k_j) / sqrt(d))^2            j <= t
      y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)
      h = h + [y[1] .. y[H]] W_o;   h = h + (silu(x' W_gate) * (x' W_up)) W_down,  x' = rmsnorm(h, ln2)
    logits = rmsnorm(h, norm) W_head

This is the DEFINITION: the weights of attention with the softmax's
exponential replaced by a square and a decay, normalised by their sum. The
program keeps instead, a KV head, the symmetric square of its keys as a
recurrent state (modules/power_retention.py); the two are equal term by term,
and this file computes the sum over ``j`` and nothing else, in blocks of
queries so that 8192 positions fit ((block, S) weights a head at a time).

The twin (``rounding=jnp.bfloat16``) rounds where a faultless bf16 deployment
holds a bf16 tensor: every product takes bf16 operands, accumulates in
float32 and rounds its result (the gate's, eight numbers a token, is kept in
float32: the decay compounds over a request's life); the residual stream and
the norms as ``dense.py`` rounds them; q and k after their head norm and
rotation are FLOAT32, never rounded (they are squared), and so are ``lg``, the
weights ``a`` and both sums; ``y`` is rounded as it leaves the mixer.
``rounding=None`` rounds nowhere; any other dtype (float8_e4m3fn: the
control) rounds at the same places to it.

The only thing this file knows of the program is the layout of its parameter
tree: Qwen3's tree under ``layers.power`` (matrices stored (in, out), stacked
over layers), with the gate ``self_attn.g_proj`` (``weight`` (hidden, G),
``bias`` (G,)) beside q, k and v.

``reference_logits`` takes, for the selftest alone, ``fault``: one of
``FAULTS``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from benchmark.harness.references.dense import _rope
from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

#: what ``fault`` may plant (selftest): the decay left out (``lg = 0``); the
#: weights not normalised by their sum; the weights of degree one (``(q . k) /
#: sqrt(d)`` where its square belongs); nothing carried from one chunk of 128
#: positions to the next (a key of an earlier chunk weighs nothing); the
#: recurrent state held in bf16 (the recurrence itself, its state rounded
#: after every token: what a deployment that keeps ``S`` and ``z`` in the
#: model dtype computes); query head ``h`` reading KV head ``h % G``
FAULTS = ("gate_ignored", "normaliser_dropped", "degree_one", "state_dropped_between_chunks",
          "state_bf16", "kv_group_misread")
FAULT_CHUNK = 128
#: queries a block of the weights
QUERY_BLOCK = 1024


@dataclass(frozen=True)
class Geometry:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    rms_eps: float
    rope_theta: float
    norm_eps: float
    degree: int

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the brumby reference is written for tp_degree 1")
        if attrs.get("power_degree", 2) != 2:
            raise ValueError("the brumby reference is written for retention of degree 2")
        heads = attrs["num_attention_heads"]
        return Geometry(
            hidden=attrs["hidden_size"], heads=heads,
            kv_heads=attrs.get("num_key_value_heads", heads),
            head_dim=attrs.get("head_dim") or attrs["hidden_size"] // heads,
            layers=attrs["num_hidden_layers"], vocab=attrs["vocab_size"],
            rms_eps=attrs.get("rms_norm_eps", 1e-6),
            rope_theta=float(attrs.get("rope_theta", 10000.0)),
            norm_eps=float(attrs.get("power_norm_eps", 1e-6)), degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, i) -> dict:
    """Layer ``i`` of the served tree as plain named arrays."""
    L = params["layers"]["power"]
    sa, mlp = L["self_attn"], L["mlp"]
    return {
        "ln1": L["input_layernorm"]["weight"][i], "ln2": L["post_attention_layernorm"]["weight"][i],
        "q": sa["q_proj"]["weight"][i], "k": sa["k_proj"]["weight"][i],
        "v": sa["v_proj"]["weight"][i], "o": sa["o_proj"]["weight"][i],
        "q_norm": sa["q_norm"]["weight"][i], "k_norm": sa["k_norm"]["weight"][i],
        "g": sa["g_proj"]["weight"][i], "g_bias": sa["g_proj"]["bias"][i],
        "gate": mlp["gate_proj"]["weight"][i], "up": mlp["up_proj"]["weight"][i],
        "down": mlp["down_proj"]["weight"][i],
    }


def retention(q, k, v, lg, eps: float, fault: Optional[str] = None):
    """The attention form on one sequence: q (S, H, d), k, v (S, G, d), lg (S,
    G), float32. Returns y (S, H, d)."""
    import jax
    import jax.numpy as jnp

    S, H, d = q.shape
    G = k.shape[1]
    reads = jnp.arange(H) % G if fault == "kv_group_misread" else jnp.arange(H) // (H // G)
    block = min(QUERY_BLOCK, S)
    n_blocks = -(-S // block)
    pad = n_blocks * block - S
    pos = jnp.arange(S)

    def one_head(args):
        qh, kappa = args  # (S, d), the KV head it reads
        kh, vh = k[:, kappa], v[:, kappa]
        cum = jnp.cumsum(lg[:, kappa])  # (S,), inclusive: sum_{l <= t} lg_l

        def one_block(args):
            qb, cb, tb = args  # (block, d), (block,), (block,) the queries' positions
            score = (qb @ kh.T) / np.sqrt(d)
            weight = score if fault == "degree_one" else jnp.square(score)
            seen = tb[:, None] >= pos[None, :]
            if fault == "state_dropped_between_chunks":
                seen = seen & (tb[:, None] // FAULT_CHUNK == pos[None, :] // FAULT_CHUNK)
            a = weight * jnp.exp(jnp.where(seen, cb[:, None] - cum[None, :], -jnp.inf))
            num = a @ vh
            if fault == "normaliser_dropped":
                return num
            return num / (jnp.sum(a, axis=-1, keepdims=True) + eps)

        blocks = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (n_blocks, block) + x.shape[1:])
        # a padded query sits at the last position: finite, and dropped below
        tb = jnp.pad(pos, (0, pad), constant_values=S - 1).reshape(n_blocks, block)
        y = jax.lax.map(one_block, (blocks(qh), jnp.take(cum, tb), tb))
        return y.reshape(n_blocks * block, d)[:S]

    y = jax.lax.map(one_head, (jnp.swapaxes(q, 0, 1), reads))  # (H, S, d)
    return jnp.swapaxes(y, 0, 1)


def retention_bf16_state(q, k, v, lg, eps: float):
    """The fault ``state_bf16``: the RECURRENCE over the full square ``k
    (outer) k / sqrt(d)`` (``d^2`` numbers a key: the same sum), its state and
    normaliser rounded to bf16 after every token."""
    import jax
    import jax.numpy as jnp

    S, H, d = q.shape
    G = k.shape[1]
    rnd = lambda a: _rnd(a, jnp.bfloat16)
    square = lambda u: (u[..., :, None] * u[..., None, :] / np.sqrt(d)).reshape(u.shape[:-1] + (d * d,))

    def step(carry, t):
        state, z = carry  # (G, d*d, d), (G, d*d)
        q_t, k_t, v_t, lg_t = t
        pk = square(k_t)
        state = rnd(jnp.exp(lg_t)[:, None, None] * state + pk[:, :, None] * v_t[:, None, :])
        z = rnd(jnp.exp(lg_t)[:, None] * z + pk)
        pq = square(q_t.reshape(G, H // G, d))  # (G, n_rep, d*d)
        num = jnp.einsum("gnD,gDc->gnc", pq, state)
        den = jnp.einsum("gnD,gD->gn", pq, z)
        return (state, z), (num / (den[..., None] + eps)).reshape(H, d)

    zero = (jnp.zeros((G, d * d, d), jnp.float32), jnp.zeros((G, d * d), jnp.float32))
    return jax.lax.scan(step, zero, (q, k, v, lg))[1]


def _layer(h, w, geo: Geometry, rounding=None, fault=None):
    """One decoder layer on one sequence, h: (S, hidden) float32."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    S, H, G, d = h.shape[0], geo.heads, geo.kv_heads, geo.head_dim
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(S, H, d)
    k = _mm(x, w["k"], rounding).reshape(S, G, d)
    v = _mm(x, w["v"], rounding).reshape(S, G, d)
    # normalised a head and rotated in float32, never rounded: they are squared
    q = _rope(_rmsnorm(q, w["q_norm"], geo.rms_eps), pos, geo.rope_theta)
    k = _rope(_rmsnorm(k, w["k_norm"], geo.rms_eps), pos, geo.rope_theta)
    # the gate: exact products of the grid's values, a float32 sum, no rounding
    lg = jax.nn.log_sigmoid(x @ _rnd(f32(w["g"]), rounding) + f32(w["g_bias"])[None, :])
    if fault == "gate_ignored":
        lg = jnp.zeros_like(lg)
    if fault == "state_bf16":
        y = retention_bf16_state(q, k, v, lg, geo.norm_eps)
    else:
        y = retention(q, k, v, lg, geo.norm_eps, fault)
    h = rnd(h + _mm(rnd(y).reshape(S, H * d), w["o"], rounding))
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    act = rnd(rnd(jax.nn.silu(_mm(x, w["gate"], rounding))) * _mm(x, w["up"], rounding))
    return rnd(h + _mm(act, w["down"], rounding))


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    layer = jax.jit(lambda h, w: _layer(h, w, geo, rounding, fault))
    take = jax.jit(layer_weights)
    head = jax.jit(lambda h, norm, w: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), w, rounding))
    return layer, take, head


def reference_logits(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
                     rounding=None, fault: Optional[str] = None) -> np.ndarray:
    """Float32 logits (len(positions), vocab) of one sequence at the given
    positions, from a full causal pass over ``tokens``; with ``rounding`` the
    twin's, with ``fault`` (selftest) one of ``FAULTS`` planted."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    layer, take, head = _programs(geo, rounding, fault)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        for i in range(geo.layers):
            h = layer(h, take(params, i))
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return np.asarray(logits, np.float32)[:, : geo.vocab]


def twin_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                positions: Sequence[int]) -> np.ndarray:
    """The bf16 twin of ``reference_logits``."""
    import jax.numpy as jnp

    return reference_logits(params, geo, tokens, positions, rounding=jnp.bfloat16)
