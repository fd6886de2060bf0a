"""The plain reference: a dense decoder forward pass in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, with no kernel, no
cache, no batching and no line of the program's code.

It follows the published Qwen3 block (HF ``modeling_qwen3.py``), which is
also the Llama/Mistral block when ``qk_norm`` is off and Qwen2's when
``qkv_bias`` is on:

    h  = embed[tokens]
    per layer:
      x  = rmsnorm(h, ln1)
      q,k,v = x Wq, x Wk, x Wv            (no bias in Qwen3)
      q,k = rmsnorm over head_dim (q_norm, k_norm), per head, BEFORE RoPE
      q,k = rope(q), rope(k)              rotate-half, theta from the config
      a  = softmax(q k^T / sqrt(D) + causal) v    (GQA: kv head = q head // group)
      h  = h + a Wo
      h  = h + (silu(x' Wg) * (x' Wu)) Wd,  x' = rmsnorm(h, ln2)
    logits = rmsnorm(h, norm) W_head       (W_head = embed^T when tied)

The weights are the SAME bf16 arrays the served model holds, cast to float32
one layer at a time. The only thing this file knows about the program is
the layout of its parameter tree (``PlainView``): matrices are stored
(in, out), stacked over layers, and a fused QKV matrix is laid out
rank-interleaved, [q_0|k_0|v_0|q_1|k_1|v_1|...] for model-parallel ranks
0..g-1. Arrays sharded over a mesh are used as they are: ``jax.jit``
partitions the plain program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

import numpy as np


@dataclass(frozen=True)
class Geometry:
    """The sizes the reference needs, straight from the model's config."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    rms_eps: float
    rope_theta: float
    qk_norm: bool
    tied: bool
    degree: int  # model-parallel degree of the served weights (QKV interleave)

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        heads = attrs["num_attention_heads"]
        return Geometry(
            hidden=attrs["hidden_size"], heads=heads,
            kv_heads=attrs.get("num_key_value_heads", heads),
            head_dim=attrs.get("head_dim") or attrs["hidden_size"] // heads,
            layers=attrs["num_hidden_layers"], vocab=attrs["vocab_size"],
            rms_eps=attrs.get("rms_norm_eps", 1e-6),
            rope_theta=attrs.get("rope_theta", 10000.0),
            qk_norm=attrs["model_type"] == "qwen3",
            tied=bool(attrs.get("tie_word_embeddings", False)),
            degree=degree,
        )


def layer_weights(params: dict, i, geo: Geometry) -> Dict[str, object]:
    """Layer ``i`` of the served tree as plain named matrices (still bf16)."""
    L = params["layers"]
    sa = L["self_attn"]
    out = {
        "ln1": L["input_layernorm"]["weight"][i],
        "ln2": L["post_attention_layernorm"]["weight"][i],
        "o": sa["o_proj"]["weight"][i],
        "gate": L["mlp"]["gate_proj"]["weight"][i],
        "up": L["mlp"]["up_proj"]["weight"][i],
        "down": L["mlp"]["down_proj"]["weight"][i],
    }
    nq, nkv = geo.heads * geo.head_dim, geo.kv_heads * geo.head_dim
    if "qkv_proj" in sa:
        g = geo.degree
        w = sa["qkv_proj"]["weight"][i].reshape(geo.hidden, g, (nq + 2 * nkv) // g)
        out["q"] = w[:, :, : nq // g].reshape(geo.hidden, nq)
        out["k"] = w[:, :, nq // g : (nq + nkv) // g].reshape(geo.hidden, nkv)
        out["v"] = w[:, :, (nq + nkv) // g :].reshape(geo.hidden, nkv)
    else:
        out["q"], out["k"], out["v"] = (sa[n]["weight"][i] for n in ("q_proj", "k_proj", "v_proj"))
    if geo.qk_norm:
        out["q_norm"] = sa["q_norm"]["weight"][i]
        out["k_norm"] = sa["k_norm"]["weight"][i]
    return out


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * w


def _rope(x, positions, theta):
    """x: (S, heads, D). HF rotate-half: pairs are (i, i + D/2)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, D/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return x * cos + rot * sin


def _layer(h, w, geo: Geometry):
    """One decoder layer on one sequence, h: (S, H) float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, f32(w["ln1"]), geo.rms_eps)
    q = (x @ f32(w["q"])).reshape(S, geo.heads, geo.head_dim)
    k = (x @ f32(w["k"])).reshape(S, geo.kv_heads, geo.head_dim)
    v = (x @ f32(w["v"])).reshape(S, geo.kv_heads, geo.head_dim)
    if geo.qk_norm:
        q = _rmsnorm(q, f32(w["q_norm"]), geo.rms_eps)
        k = _rmsnorm(k, f32(w["k_norm"]), geo.rms_eps)
    q, k = _rope(q, pos, geo.rope_theta), _rope(k, pos, geo.rope_theta)
    group = geo.heads // geo.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):  # (S, D) each; head after head, so that (S, S) scores are held once
        qh, kh, vh = qkv
        scores = jnp.where(causal, qh @ kh.T / np.sqrt(geo.head_dim), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    h = h + attn.reshape(S, geo.heads * geo.head_dim) @ f32(w["o"])
    x = _rmsnorm(h, f32(w["ln2"]), geo.rms_eps)
    return h + (jax.nn.silu(x @ f32(w["gate"])) * (x @ f32(w["up"]))) @ f32(w["down"])


def reference_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                     positions: Sequence[int]) -> np.ndarray:
    """Float32 logits (len(positions), vocab) of one sequence at the given
    positions, from a full causal forward pass over ``tokens``."""
    import jax
    import jax.numpy as jnp

    layer = jax.jit(lambda h, w: _layer(h, w, geo))
    take = jax.jit(lambda p, i: layer_weights(p, i, geo))
    head = jax.jit(
        lambda h, norm, w: _rmsnorm(h, norm.astype(jnp.float32), geo.rms_eps)
        @ w.astype(jnp.float32)
    )
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32)
        for i in range(geo.layers):
            h = layer(h, take(params, i))
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return np.asarray(logits, np.float32)[:, : geo.vocab]


def compare(got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """max|a-b| and max|b|: the comparison is err <= tol * scale."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return {
        "max_abs_err": float(np.abs(got - ref).max()),
        "scale": float(np.abs(ref).max()),
        "finite": bool(np.isfinite(got).all()),
    }
