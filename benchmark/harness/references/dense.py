"""The plain reference of a dense decoder: a forward pass in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, with no kernel, no
cache, no batching and no line of the program's code — and its bf16 TWIN,
the same equations with the roundings a bf16 deployment states, which is
the noise floor ``correct.py`` measures the served model against.

The interface of a reference module (``correct.check_model`` loads
``benchmark.harness.references.<name>`` by the configuration's
``reference`` key, ``dense`` where it has none):

    geometry(attrs, degree)                     the sizes, from the model's config
    reference_logits(params, geo, tokens, positions)   float32, (len(positions), vocab)
    twin_logits(params, geo, tokens, positions)        the same, rounded as served

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

The twin (``rounding=jnp.bfloat16``) evaluates these lines and no others.
Every matrix product takes operands in bf16, accumulates in float32 and
rounds its result to bf16; everything between products is computed in
float32 and rounded to bf16 where HF's bf16 model holds a bf16 tensor:

    h                       the embedding row (bf16 as stored); after each residual add
    rmsnorm                 the normalised x, and again after the weight (HF: w * x.to(bf16))
    q, k, v                 each product; q, k again after their norm; after RoPE
                            (cos and sin themselves rounded, each of the two products, the sum)
    scores                  the product q k^T; scale, mask and softmax stay float32
    softmax(...)            rounded before it multiplies v (HF: .to(q.dtype))
    a                       the product with v
    a Wo, (...) Wd          the two ROW-PARALLEL products: with ``degree`` > 1 each is
                            ``degree`` partial products over equal, contiguous slices of the
                            contraction (the heads, the intermediate width, as a tensor-parallel
                            deployment divides them), each rounded to bf16, summed in float32
                            and rounded once — the all-reduce of bf16 partial sums. With
                            ``degree`` 1 it is the one product.
    silu(g), silu(g) * u    each rounded
    logits                  the head's product, rounded to bf16, returned as float32

``rounding=None`` rounds nowhere and takes no partial products: that is the
float32 reference, bit for bit. Any other dtype (float8_e4m3fn: the control
of the selftest) rounds at the same places to that type.

The weights are the SAME bf16 arrays the served model holds, cast to float32
one layer at a time. The only thing this file knows about the program is
the layout of its parameter tree (``layer_weights``): matrices are stored
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
    degree: int  # model-parallel degree of the served weights (QKV interleave; the twin's partial sums)

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


geometry = Geometry.from_config


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


def _rnd(a, rounding):
    """``a`` (float32) rounded to ``rounding`` and held in float32 again; the
    float32 reference (``rounding`` None) rounds nowhere."""
    import jax.numpy as jnp

    return a if rounding is None else a.astype(rounding).astype(jnp.float32)


def _mm(a, b, rounding):
    """``a @ b``. Float32 reference: float32 operands at the ambient
    (``highest``) precision. Twin: operands in ``rounding`` (``a`` holds such
    values already), accumulated in float32, the result rounded."""
    import jax.numpy as jnp

    if rounding is None:
        return a @ b.astype(jnp.float32)
    if rounding == jnp.bfloat16:  # the chip's own product: bf16 operands, float32 accumulator
        prod = jnp.matmul(a.astype(rounding), b.astype(rounding), preferred_element_type=jnp.float32)
    else:  # any other grid: its values, multiplied exactly
        prod = a @ _rnd(b.astype(jnp.float32), rounding)
    return _rnd(prod, rounding)


def _row_parallel(a, w, rounding, partials):
    """``a @ w`` of a row-parallel matrix: ``partials`` products over equal
    contiguous slices of the contraction, each rounded, summed in float32
    and rounded once. One product when ``partials`` is 1 or nothing rounds."""
    if rounding is None or partials == 1:
        return _mm(a, w, rounding)
    step = a.shape[-1] // partials
    parts = [_mm(a[..., g * step : (g + 1) * step], w[g * step : (g + 1) * step], rounding)
             for g in range(partials)]
    return _rnd(sum(parts), rounding)


def _rmsnorm(x, w, eps, rounding=None):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = _rnd(x * jnp.reciprocal(jnp.sqrt(var + eps)), rounding)
    return _rnd(normed * w.astype(jnp.float32), rounding)


def _rope(x, positions, theta, rounding=None):
    """x: (S, heads, D). HF rotate-half: pairs are (i, i + D/2)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, D/2)
    cos = _rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :], rounding)
    sin = _rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :], rounding)
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return _rnd(_rnd(x * cos, rounding) + _rnd(rot * sin, rounding), rounding)


def _layer(h, w, geo: Geometry, rounding=None):
    """One decoder layer on one sequence, h: (S, H) float32 (the twin's
    values lie on ``rounding``'s grid)."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(S, geo.heads, geo.head_dim)
    k = _mm(x, w["k"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    v = _mm(x, w["v"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    if geo.qk_norm:
        q = _rmsnorm(q, w["q_norm"], geo.rms_eps, rounding)
        k = _rmsnorm(k, w["k_norm"], geo.rms_eps, rounding)
    q, k = _rope(q, pos, geo.rope_theta, rounding), _rope(k, pos, geo.rope_theta, rounding)
    group = geo.heads // geo.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):  # (S, D) each; head after head, so that (S, S) scores are held once
        qh, kh, vh = qkv
        scores = jnp.where(causal, _mm(qh, kh.T, rounding) / np.sqrt(geo.head_dim), -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    attn = attn.reshape(S, geo.heads * geo.head_dim)
    h = rnd(h + _row_parallel(attn, w["o"], rounding, geo.degree))
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    act = rnd(rnd(jax.nn.silu(_mm(x, w["gate"], rounding))) * _mm(x, w["up"], rounding))
    return rnd(h + _row_parallel(act, w["down"], rounding, geo.degree))


def reference_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                     positions: Sequence[int], rounding=None) -> np.ndarray:
    """Float32 logits (len(positions), vocab) of one sequence at the given
    positions, from a full causal forward pass over ``tokens``; with
    ``rounding`` (a dtype) the twin's, rounded as the module's docstring lists."""
    import jax
    import jax.numpy as jnp

    layer = jax.jit(lambda h, w: _layer(h, w, geo, rounding))
    take = jax.jit(lambda p, i: layer_weights(p, i, geo))
    head = jax.jit(lambda h, norm, w: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), w, rounding))
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
    """The bf16 twin of ``reference_logits``: what a faultless bf16
    deployment of these equations at ``geo.degree`` gives."""
    import jax.numpy as jnp

    return reference_logits(params, geo, tokens, positions, rounding=jnp.bfloat16)
