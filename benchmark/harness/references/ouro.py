"""The plain reference of Ouro (``model_type: "ouro"``, a looped language
model): a forward pass in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the two loops as Python loops,
full causal attention over the whole sequence, with no kernel, no cache and
no line of the program's code, and its bf16 TWIN. The interface is
``dense.py``'s (``geometry`` / ``layer_weights`` / ``reference_logits`` /
``twin_logits``); neither ``CHOICES`` nor ``PASSES``.

The equations, as ISSUE 51 states the published ``modeling_ouro.py``
(``OuroDecoderLayer.forward``, ``OuroModel.forward``, ``OuroAttention``'s
``past_key_value.update(..., current_ut * num_hidden_layers + layer_idx)``);
T = ``total_ut_steps``, L = ``num_hidden_layers``, ``N(x; g)`` = RMSNorm:

    h = E[ids]
    for t in 0..T-1:                                   # the same weights at every t
      for l in 0..L-1:
        a = N(h; input_layernorm_l)
        q, k, v = a Wq_l, a Wk_l, a Wv_l               # no bias, no q/k norm
        q, k = rope(q, k; p, theta)                    # rotate-half; the token's own position at every t
        o = softmax(q k^T / sqrt(D) + causal) v        # the k, v OF THIS LOOP: one stream a (loop, layer)
        h = h + N(o Wo_l; input_layernorm_2_l)         # a norm on the attention OUTPUT, before the add
        m = N(h; post_attention_layernorm_l)
        h = h + N((silu(m Wg_l) * (m Wu_l)) Wd_l; post_attention_layernorm_2_l)
      h = N(h; model.norm)                             # after EVERY loop; its output starts the next
      e_t = sigmoid(h w_gate + b_gate)                 # early_exit_gate, Linear(H, 1) with bias
    logits = h W_head                                  # from the last loop; the head is not tied

At the published ``early_exit_threshold`` of 1 every position leaves at the
last loop, so the logits are the last loop's for every position and the
gates decide nothing; ``reference_gates`` returns them for the test that
holds the program's to them.

The twin rounds where ``dense.py``'s twin rounds (its docstring lists the
places: every product, every norm twice, RoPE, the softmax before ``v``,
``silu`` and its product, every residual add) and besides: each output norm
(twice, as any norm), the between-loop norm, and the gate's product, its sum
with the bias and the sigmoid. It rounds with ``lax.reduce_precision``
(``granite_hybrid._rnd``), which the compiler may not remove. With
``dense._rnd``'s pair of converts, which it may, the chip read the served
program at 0.92 - 1.66 x the twin's error over 24 rows of 12 seeds (median
1.19; root mean squares 0.98 - 1.46), two rows over ``K``; with
``reduce_precision`` 0.65 - 1.21 over 32 rows of 16 seeds (median 0.93; root
mean squares 0.65 - 1.16) and the fp8 control 3.5 - 5.8 (my chip runs, PR 51):
the first twin had lost roundings, the program had not gained error.
``rounding=None`` rounds nowhere.

Two keywords of ``reference_logits`` are the CONTROLS' and no caller but a
test sets them: ``loops`` (run that many loops, not T) and
``between_loop_norm`` (False: ``model.norm`` after the last loop only). A
reference with such a fault, in the program's place, must fail ``correct``
(``benchmark/selftest/test_correct_ouro.py``, ``tests/test_ouro_reference.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

# the roundings the compiler may not remove (``lax.reduce_precision``): a float32 -> bf16 ->
# float32 pair of converts inside one fusion is one it may, and this twin has 192 layer passes of them
from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd


def _row_parallel(a, w, rounding, partials):
    """``a @ w`` of a row-parallel matrix, as ``dense._row_parallel``:
    ``partials`` products over equal contiguous slices of the contraction,
    each rounded, summed in float32 and rounded once. One product when
    ``partials`` is 1 or nothing rounds."""
    if rounding is None or partials == 1:
        return _mm(a, w, rounding)
    step = a.shape[-1] // partials
    parts = [_mm(a[..., g * step : (g + 1) * step], w[g * step : (g + 1) * step], rounding)
             for g in range(partials)]
    return _rnd(sum(parts), rounding)


def _rope(x, positions, theta, rounding=None):
    """x: (S, heads, D). HF rotate-half: pairs are (i, i + D/2); as
    ``dense._rope`` (cos and sin rounded, each product, the sum)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, D/2)
    cos = _rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :], rounding)
    sin = _rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :], rounding)
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return _rnd(_rnd(x * cos, rounding) + _rnd(rot * sin, rounding), rounding)


@dataclass(frozen=True)
class Geometry:
    """The sizes the reference needs, straight from the model's config."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    loops: int
    vocab: int
    rms_eps: float
    rope_theta: float
    degree: int  # model-parallel degree of the served weights (QKV interleave; the twin's partial sums)

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        heads = attrs["num_attention_heads"]
        return Geometry(
            hidden=attrs["hidden_size"], heads=heads,
            kv_heads=attrs.get("num_key_value_heads", heads),
            head_dim=attrs.get("head_dim") or attrs["hidden_size"] // heads,
            layers=attrs["num_hidden_layers"], loops=attrs["total_ut_steps"],
            vocab=attrs["vocab_size"], rms_eps=attrs.get("rms_norm_eps", 1e-6),
            rope_theta=attrs.get("rope_theta", 10000.0), degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, i, geo: Geometry) -> Dict[str, object]:
    """Layer ``i`` of the served tree as plain named matrices (still bf16):
    ``dense.layer_weights``'s, and the two output norms."""
    L = params["layers"]
    sa = L["self_attn"]
    out = {
        "ln1": L["input_layernorm"]["weight"][i],
        "ln1_out": L["input_layernorm_2"]["weight"][i],
        "ln2": L["post_attention_layernorm"]["weight"][i],
        "ln2_out": L["post_attention_layernorm_2"]["weight"][i],
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
    return out


def _layer(h, w, geo: Geometry, rounding=None):
    """One pass of one layer over one sequence, h: (S, H) float32. Returns
    (h, k, v): the keys (after RoPE) and values this pass attends, (S,
    kv_heads, D): what the pass leaves in its stream of the cache."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(S, geo.heads, geo.head_dim)
    k = _mm(x, w["k"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    v = _mm(x, w["v"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    q, k = _rope(q, pos, geo.rope_theta, rounding), _rope(k, pos, geo.rope_theta, rounding)
    group = geo.heads // geo.kv_heads
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):  # (S, D) each; head after head, so that (S, S) scores are held once
        qh, kh, vh = qkv
        scores = jnp.where(causal, _mm(qh, kh.T, rounding) / np.sqrt(geo.head_dim), -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (
        heads_first(q), heads_first(jnp.repeat(k, group, axis=1)),
        heads_first(jnp.repeat(v, group, axis=1)))))
    attn = attn.reshape(S, geo.heads * geo.head_dim)
    out = _row_parallel(attn, w["o"], rounding, geo.degree)
    h = rnd(h + _rmsnorm(out, w["ln1_out"], geo.rms_eps, rounding))
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    act = rnd(rnd(jax.nn.silu(_mm(x, w["gate"], rounding))) * _mm(x, w["up"], rounding))
    out = _row_parallel(act, w["down"], rounding, geo.degree)
    return rnd(h + _rmsnorm(out, w["ln2_out"], geo.rms_eps, rounding)), k, v


def _forward(params: dict, geo: Geometry, tokens: Sequence[int], rounding=None,
             loops: Optional[int] = None, between_loop_norm: bool = True, streams: bool = False):
    """(hidden after the last loop's norm (S, H), gates (loops, S), the
    (k, v) of every layer pass in cache order ``t * L + l`` where ``streams``,
    else None). Call under ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp

    layer = jax.jit(lambda h, w: _layer(h, w, geo, rounding))
    take = jax.jit(lambda p, i: layer_weights(p, i, geo))
    norm = jax.jit(lambda h, w: _rmsnorm(h, w, geo.rms_eps, rounding))

    @jax.jit
    def gate(h, g):
        logit = _rnd(_mm(h, g["weight"], rounding)[:, 0] + g["bias"].astype(jnp.float32)[0], rounding)
        return _rnd(jax.nn.sigmoid(logit), rounding)

    loops = geo.loops if loops is None else loops
    ids = jnp.asarray(np.asarray(tokens, np.int32))
    h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
    gates, kept = [], [] if streams else None
    for t in range(loops):
        for i in range(geo.layers):
            h, k, v = layer(h, take(params, i))
            if streams:
                kept.append((np.asarray(k), np.asarray(v)))
        if between_loop_norm or t == loops - 1:
            h = norm(h, params["norm"]["weight"])
        gates.append(gate(h, params["early_exit_gate"]))
    return h, jnp.stack(gates), kept


def reference_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                     positions: Sequence[int], rounding=None, loops: Optional[int] = None,
                     between_loop_norm: bool = True) -> np.ndarray:
    """Float32 logits (len(positions), vocab) of one sequence at the given
    positions, from a full causal forward pass over ``tokens`` through every
    loop; with ``rounding`` (a dtype) the twin's. ``loops`` and
    ``between_loop_norm`` are the controls' (module docstring)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        h, _, _ = _forward(params, geo, tokens, rounding, loops, between_loop_norm)
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = jax.jit(lambda r, w: _mm(r, w, rounding))(rows, params["lm_head"]["weight"])
    return np.asarray(logits, np.float32)[:, : geo.vocab]


def twin_logits(params: dict, geo: Geometry, tokens: Sequence[int],
                positions: Sequence[int]) -> np.ndarray:
    """The bf16 twin of ``reference_logits``: what a faultless bf16
    deployment of these equations gives."""
    import jax.numpy as jnp

    return reference_logits(params, geo, tokens, positions, rounding=jnp.bfloat16)


def reference_gates(params: dict, geo: Geometry, tokens: Sequence[int], rounding=None) -> np.ndarray:
    """(loops, len(tokens)) float32: the exit gate ``e_t`` of every position
    after every loop."""
    import jax

    with jax.default_matmul_precision("highest"):
        return np.asarray(_forward(params, geo, tokens, rounding)[1], np.float32)


def reference_streams(params: dict, geo: Geometry, tokens: Sequence[int]):
    """Per layer pass in cache order (index ``t * L + l``: loop t of layer
    l) the float32 ``(k, v)``, each (len(tokens), kv_heads, head_dim): what a
    cache holds for this sequence, stream by stream."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _forward(params, geo, tokens, streams=True)[2]
