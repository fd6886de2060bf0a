"""The plain reference of a Granite-4.0-H (``granitemoehybrid``) decoder:
Mamba-2 state-space layers beside GQA attention layers, in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")`` — no kernel, no cache,
no batching, no line of the program's code — and its bf16 TWIN.

The interface is ``dense.py``'s (``geometry`` / ``reference_logits`` /
``twin_logits``); the helpers that round and multiply follow it, with one
difference: a bf16 rounding is a ``lax.reduce_precision`` (see ``_rnd``).

The equations are those of the published implementation
(``transformers`` ``models/granitemoehybrid/modeling_granitemoehybrid.py``,
``torch_forward``), which ``tests/test_granite_hybrid.py`` holds this file to
on the CPU. Every key is the published config's:

    h = embed[tokens] * embedding_multiplier
    per layer l, layer_types[l] in {mamba, attention}:
      h = h + residual_multiplier * Mixer_l(rmsnorm(h, ln1))
      h = h + residual_multiplier * W_down(silu(x' W_gate) * (x' W_up)),  x' = rmsnorm(h, ln2)
    logits = (rmsnorm(h, norm) W_head) / logits_scaling          (W_head = embed^T: tied)

    attention mixer: q,k,v = x Wq, x Wk, x Wv (no bias, NO positional
      embedding: position_embedding_type "nope"), GQA, causal,
      softmax(attention_multiplier * q k^T) v, then Wo.

    Mamba-2 mixer (d_inner = mamba_n_heads * mamba_d_head, one group of B/C,
      conv_dim = d_inner + 2 * mamba_d_state):
      [z, xBC, dt] = split(u W_in, [d_inner, conv_dim, n_heads])
      xBC_t = silu(sum_{k<d_conv} w[k] * xBC_{t-(d_conv-1)+k} + b)     depthwise, causal, zeros before t=0
      [x, B, C] = split(xBC, [d_inner, d_state, d_state]);  x -> (heads, d_head)
      dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)              per head
      S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t              S_{-1} = 0, (heads, d_head, d_state)
      y_t  = S_t . C_t + D x_t
      y    = rmsnorm_w(y * silu(z))                                    over all d_inner (one group)
      out  = y W_out

THE RECURRENCE IS THE SEQUENTIAL ONE: a ``lax.scan`` over tokens carrying
``S``. The program computes a prefill chunk in the chunked ("state-space
dual") form and a decode step with a kernel; this file shares neither.

The twin (``rounding=jnp.bfloat16``) evaluates the same lines with the
roundings a faultless bf16 deployment of this model states. As in
``dense.py`` every matrix product takes operands in bf16, accumulates in
float32 and rounds its result; between products values are computed in
float32 and rounded where the published bf16 model holds a bf16 tensor:

    h                     the embedding row (as stored), again after the multiplier,
                          after each residual product with residual_multiplier, after each add
    rmsnorm               the normalised x, and again after the weight
    z, xBC, dt            the in_proj product (one rounding)
    conv                  the sum with its bias, and again after silu
    dt, A, exp(dt A)      float32 (the published kernels compute the step in float32)
    S                     FLOAT32, never rounded: the recurrence compounds over the
                          request's whole life (configuration file, ``assumed._note``)
    y = S.C + D x         computed in float32 from the float32 state, rounded once
    y * silu(z)           float32; the gated norm rounds as rmsnorm does
    out                   the out_proj product
    attention, MLP, head  as ``dense.py`` (scores float32, softmax rounded before v);
                          logits rounded, then divided by logits_scaling (a power of two)

``rounding=None`` rounds nowhere: the float32 reference. Any other dtype
(float8_e4m3fn: the selftest's control) rounds at the same places to it.
``geo.degree`` is 1: the program refuses this model at tp > 1.

The only thing this file knows of the program is the layout of its parameter
tree: ``layers.mamba`` and ``layers.attention`` each stacked over THEIR
layers in model order, matrices stored (in, out), the conv weight
(layer, tap, channel), a fused QKV laid out [q|k|v], and the published
``in_proj`` held as two matrices, ``in_proj`` = its [z | xBC] columns and
``dt_proj`` = its dt columns (put side by side again here).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import numpy as np



def _rnd(a, rounding):
    """``a`` (float32) rounded to ``rounding`` and held in float32 again; the
    float32 reference (``rounding`` None) rounds nowhere. bf16 is rounded
    with ``lax.reduce_precision`` (8 exponent bits, 7 of mantissa, to nearest
    even), which the compiler may not remove: a float32 -> bf16 -> float32
    pair of converts inside one fusion is one it may (XLA keeps "excess
    precision" by default), and this model's twin has many roundings that sit
    between elementwise operations (conv, silu, gate, norms, residuals)."""
    import jax
    import jax.numpy as jnp

    if rounding is None:
        return a
    if rounding == jnp.bfloat16:
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return a.astype(rounding).astype(jnp.float32)


def _mm(a, b, rounding):
    """``a @ b`` as ``dense._mm``: float32 operands at the ambient
    (``highest``) precision for the reference; for the twin operands on
    ``rounding``'s grid (``a`` holds such values already), accumulated in
    float32, the result rounded."""
    import jax.numpy as jnp

    if rounding is None:
        return a @ b.astype(jnp.float32)
    if rounding == jnp.bfloat16:  # the chip's own product: bf16 operands, float32 accumulator
        prod = jnp.matmul(a.astype(rounding), b.astype(rounding), preferred_element_type=jnp.float32)
    else:  # any other grid: its values, multiplied exactly
        prod = a @ _rnd(b.astype(jnp.float32), rounding)
    return _rnd(prod, rounding)


def _rmsnorm(x, w, eps, rounding=None):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = _rnd(x * jnp.reciprocal(jnp.sqrt(var + eps)), rounding)
    return _rnd(normed * w.astype(jnp.float32), rounding)


@dataclass(frozen=True)
class Geometry:
    hidden: int
    layer_types: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    intermediate: int
    vocab: int
    rms_eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    m_heads: int
    m_head_dim: int
    m_state: int
    m_groups: int
    m_conv: int
    degree: int

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.m_groups * self.m_state

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if attrs.get("num_local_experts", 0):
            raise ValueError("this reference has no routed-expert layer (num_local_experts > 0)")
        if attrs.get("position_embedding_type", "nope") != "nope":
            raise ValueError("this reference rotates nothing (position_embedding_type != 'nope')")
        if degree != 1:
            raise ValueError("the hybrid reference is written for tp_degree 1")
        heads = attrs["num_attention_heads"]
        return Geometry(
            hidden=attrs["hidden_size"], layer_types=tuple(attrs["layer_types"]),
            heads=heads, kv_heads=attrs.get("num_key_value_heads", heads),
            head_dim=attrs["hidden_size"] // heads,
            intermediate=attrs["shared_intermediate_size"], vocab=attrs["vocab_size"],
            rms_eps=attrs.get("rms_norm_eps", 1e-5),
            embedding_multiplier=float(attrs.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(attrs.get("residual_multiplier", 1.0)),
            attention_multiplier=float(attrs["attention_multiplier"]),
            logits_scaling=float(attrs.get("logits_scaling", 1.0)),
            m_heads=attrs["mamba_n_heads"], m_head_dim=attrs["mamba_d_head"],
            m_state=attrs["mamba_d_state"], m_groups=attrs.get("mamba_n_groups", 1),
            m_conv=attrs["mamba_d_conv"], degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, kind: str, i, geo: Geometry) -> Dict[str, object]:
    """Layer ``i`` OF ITS KIND from the served tree, as plain named arrays."""
    import jax.numpy as jnp

    L = params["layers"][kind]
    out = {
        "ln1": L["input_layernorm"]["weight"][i],
        "ln2": L["post_attention_layernorm"]["weight"][i],
        "gate": L["mlp"]["gate_proj"]["weight"][i],
        "up": L["mlp"]["up_proj"]["weight"][i],
        "down": L["mlp"]["down_proj"]["weight"][i],
    }
    if kind == "mamba":
        m = L["mixer"]
        out.update(
            w_in=jnp.concatenate([m["in_proj"]["weight"][i], m["dt_proj"]["weight"][i]], axis=1),
            conv_w=m["conv1d"]["weight"][i],
            conv_b=m["conv1d"]["bias"][i], A_log=m["A_log"][i], D=m["D"][i],
            dt_bias=m["dt_bias"][i], gnorm=m["norm"]["weight"][i],
            w_out=m["out_proj"]["weight"][i],
        )
        return out
    sa = L["self_attn"]
    nq, nkv = geo.heads * geo.head_dim, geo.kv_heads * geo.head_dim
    if "qkv_proj" in sa:
        w = sa["qkv_proj"]["weight"][i]
        out["q"], out["k"], out["v"] = w[:, :nq], w[:, nq : nq + nkv], w[:, nq + nkv :]
    else:
        out["q"], out["k"], out["v"] = (sa[n]["weight"][i] for n in ("q_proj", "k_proj", "v_proj"))
    out["o"] = sa["o_proj"]["weight"][i]
    return out


def _mlp(h, w, geo: Geometry, rounding):
    import jax

    rnd = lambda a: _rnd(a, rounding)
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    act = rnd(rnd(jax.nn.silu(_mm(x, w["gate"], rounding))) * _mm(x, w["up"], rounding))
    return rnd(h + rnd(geo.residual_multiplier * _mm(act, w["down"], rounding)))


def _attention_layer(h, w, geo: Geometry, rounding=None):
    """One attention layer on one sequence, h: (S, H) float32. No rotation."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(S, geo.heads, geo.head_dim)
    k = _mm(x, w["k"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    v = _mm(x, w["v"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    group = geo.heads // geo.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, _mm(qh, kh.T, rounding) * geo.attention_multiplier, -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    attn = attn.reshape(S, geo.heads * geo.head_dim)
    h = rnd(h + rnd(geo.residual_multiplier * _mm(attn, w["o"], rounding)))
    return _mlp(h, w, geo, rounding)


def _mamba_mixer(x, w, geo: Geometry, rounding=None):
    """The Mamba-2 mixer on one sequence from a zero state, x: (S, H) the
    normalised input. The recurrence is a scan over tokens."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    S = x.shape[0]
    H, P, N, G, K = geo.m_heads, geo.m_head_dim, geo.m_state, geo.m_groups, geo.m_conv
    d_inner = geo.d_inner
    proj = _mm(x, w["w_in"], rounding)
    z, xBC, dt = proj[:, :d_inner], proj[:, d_inner : d_inner + geo.conv_dim], proj[:, d_inner + geo.conv_dim :]
    padded = jnp.concatenate([jnp.zeros((K - 1, geo.conv_dim), jnp.float32), xBC], axis=0)
    conv_w = f32(w["conv_w"])  # (K, conv_dim)
    conv = sum(conv_w[k][None, :] * padded[k : k + S] for k in range(K)) + f32(w["conv_b"])[None, :]
    xBC = rnd(jax.nn.silu(rnd(conv)))
    xs = xBC[:, :d_inner].reshape(S, H, P)
    Bm = jnp.repeat(xBC[:, d_inner : d_inner + G * N].reshape(S, G, N), H // G, axis=1)  # (S, H, N)
    Cm = jnp.repeat(xBC[:, d_inner + G * N :].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + f32(w["dt_bias"])[None, :])  # (S, H)
    A = -jnp.exp(f32(w["A_log"]))  # (H,)
    D = f32(w["D"])

    def step(state, t):
        x_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t * A)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y_t = jnp.sum(state * C_t[:, None, :], axis=-1) + D[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, Bm, Cm, dt))
    y = rnd(y.reshape(S, d_inner))
    gated = y * jax.nn.silu(z)
    return _mm(_rmsnorm(gated, w["gnorm"], geo.rms_eps, rounding), w["w_out"], rounding)


def _mamba_layer(h, w, geo: Geometry, rounding=None, mixer=_mamba_mixer):
    rnd = lambda a: _rnd(a, rounding)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    h = rnd(h + rnd(geo.residual_multiplier * mixer(x, w, geo, rounding)))
    return _mlp(h, w, geo, rounding)


def reference_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                     positions: Sequence[int], rounding=None, mixer=_mamba_mixer) -> np.ndarray:
    """Float32 logits (len(positions), vocab) of one sequence at the given
    positions, from a full causal forward pass over ``tokens`` from a zero
    state; with ``rounding`` (a dtype) the twin's. ``mixer``: the selftest
    puts a faulted Mamba-2 mixer in the sound one's place."""
    import jax
    import jax.numpy as jnp

    fns = {
        "mamba": jax.jit(lambda h, w: _mamba_layer(h, w, geo, rounding, mixer)),
        "attention": jax.jit(lambda h, w: _attention_layer(h, w, geo, rounding)),
    }
    take = {kind: jax.jit(lambda p, i, kind=kind: layer_weights(p, kind, i, geo)) for kind in fns}
    head = jax.jit(lambda h, norm, w: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), w, rounding))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        h = _rnd(h * geo.embedding_multiplier, rounding)
        seen = {"mamba": 0, "attention": 0}
        for kind in geo.layer_types:
            h = fns[kind](h, take[kind](params, seen[kind]))
            seen[kind] += 1
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return np.asarray(logits, np.float32)[:, : geo.vocab] / np.float32(geo.logits_scaling)


def twin_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                positions: Sequence[int]) -> np.ndarray:
    """The bf16 twin of ``reference_logits`` (module docstring)."""
    import jax.numpy as jnp

    return reference_logits(params, geo, tokens, positions, rounding=jnp.bfloat16)
