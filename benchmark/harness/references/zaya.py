"""The plain reference of a ZAYA1 (``model_type: "zaya"``) decoder: attention
in a compressed latent with conv mixing (CCA) and a top-1 expert layer
behind an MLP router that carries state from layer to layer — in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
cache, no kernel, no batching, the convs as explicit shifts, no line of the
program's code — and its bf16 TWIN.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"):

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The equations. What the catalog row's ``config`` pins: every width, the
head counts, the two kernel sizes, the rotary share and base, 16 experts,
top-1, SiLU gate, router hidden 256. What comes from the family's published
description (Compressed Convolutional Attention, arXiv:2510.04476; ZAYA1
report, arXiv:2511.17127) and is listed under ``assumed`` in the
configuration file: the forms marked (a) below. ``x = rmsnorm(h, ln1)``,
``H_q`` query heads, ``H_kv`` key/value heads of ``d``, ``G = H_q / H_kv``,
``t`` a position, everything with index -1 is zero:

    h = embed[tokens]
    per layer l:
      attention sublayer,  h = h + W_o attn
        q~_t = W_q x_t (H_q x d),  k~_t = W_k x_t (H_kv x d),  u_t = [q~_t ; k~_t]
        a_t[c] = w0[0,c] u_{t-1}[c] + w0[1,c] u_t[c] + b0[c]          depthwise, kernel cca_time0 = 2
        c_t[g] = a_{t-1}[g] W1[0,g] + a_t[g] W1[1,g] + b1[g]          (a) grouped by head, d -> d, kernel cca_time1 = 2
        q_t = c_t[q] + (q~_t + repeat_G(k~_t)) / 2                      (a) q-k mean
        k_t = c_t[k] + (mean_G(q~_t) + k~_t) / 2
        v_t = [W_v1 x_t ; W_v2 x_{t-1}]  -> H_kv heads of d             (a) value shift
        q_t = sqrt(d) q_t / |q_t|,  k_t = exp(tau_head) sqrt(d) k_t / |k_t|   (a) per head; |.| = sqrt(sum^2 + 1e-12)
        rotary (HF rotate-half) on the first d * partial_rotary_factor dimensions of a head
        causal grouped-query attention, softmax(q k^T / sqrt(d)) v, in the latent
      expert sublayer,  h = h + p_e Expert_e(x'),  x' = rmsnorm(h, ln2)
        r_l = x' W_d + b_d;  r_l = r_l + gamma_l * r_{l-1}  (l > 0; the value before the norm)   (a)
        z = rmsnorm(r_l, w_r);  logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3                 (a) gelu exact (erf)
        p = softmax(logits);  e = argmax(p + b_bal)  or, replaying, the served selection
        Expert_e(x') = (silu(x' W_gate,e) * (x' W_up,e)) W_down,e;   p_e is NOT renormalised (top-1)
    logits = rmsnorm(h, norm) W_head                                    (W_head = embed^T: tied)

The selection score (``choice_margins``) is ``p + b_bal``.

DEPARTURES from the family's description, both because the catalog row's
``config`` has no key for them (the sibling rows' Megatron-style keys name
them): no skip choice beside the experts (``zaya_use_mod``), no learned
scales on the residual merge (``scale_residual_merge``). Each is a comment
at its line below.

The twin (``rounding=jnp.bfloat16``) evaluates the same lines with the
roundings a faultless bf16 deployment states; a rounding is a
``lax.reduce_precision`` (``granite_hybrid._rnd``: the compiler may not
remove it). Every matrix product takes bf16 operands, accumulates in float32
and rounds its result; between products values are float32 and rounded
where a bf16 model holds a bf16 tensor:

    h                after the embedding (as stored), after each residual add
    rmsnorm          the normalised x, and again after the weight
    q~, k~, v1, v2   each product
    a                the sum with its bias (it is carried from token to token in bf16)
    c                both taps' products summed in float32 and rounded; again after the bias
    q, k             after the q-k mean; after the normalisation (float32 inside); rotary as dense.py's
    attention        scores float32, softmax rounded before v, the product rounded; W_o's product rounded
    router           FLOAT32 throughout, from the bf16 x': x' W_d is a product of bf16 operands
                     accumulated in float32 and NOT rounded, and r, z, the MLP, p and p + b_bal are
                     float32 (the sibling rows' ``zaya_high_prec``; the program's router runs the same way)
    expert           each product, silu(g), silu(g) * u, p_e (rounded) times the expert's output, the add

``rounding=None`` rounds nowhere; any other dtype (float8_e4m3fn: the
control) rounds at the same places to it. ``geo.degree`` is 1: the program
refuses this model at tp > 1.

The only thing this file knows of the program is the layout of its parameter
tree: every leaf of ``layers`` stacked over the layers, matrices stored
(in, out), ``conv0.weight`` (layer, tap, channel), ``conv1.weight`` (layer,
tap, group, in, out), the expert stacks (layer, expert, in, out).

``forward`` takes, for the selftest alone, ``fault``: one of ``FAULTS``, the
equations with one part left out (in the program's place, to see the rule
fail it).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, layers, 1) expert indices

#: what ``fault`` may plant (selftest): the conv carry zeroed at every
#: multiple of ``FAULT_BOUNDARY`` tokens (a chunk boundary; the hand-over from
#: prefill to decode), the value shift dropped (W_v2 x_t for W_v2 x_{t-1}),
#: p_e not applied, the router's carry from the layer before dropped, rotary
#: on all of a head's dimensions
FAULTS = ("conv_carry_zeroed", "value_shift_dropped", "affinity_not_applied",
          "router_carry_dropped", "rotary_on_all_dims")
FAULT_BOUNDARY = 128


@dataclass(frozen=True)
class Geometry:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    layers: int
    vocab: int
    rms_eps: float
    experts: int
    router_hidden: int
    degree: int

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the zaya reference is written for tp_degree 1")
        if attrs.get("num_experts_per_tok", 1) != 1:
            raise ValueError("the zaya reference is top-1 (num_experts_per_tok 1)")
        if (attrs.get("cca_time0", 2), attrs.get("cca_time1", 2)) != (2, 2):
            raise ValueError("the zaya reference has both conv kernels of size 2")
        rope = (attrs.get("rope_parameters") or {}).get("hybrid", {})
        head_dim = attrs["head_dim"]
        return Geometry(
            hidden=attrs["hidden_size"], heads=attrs["num_attention_heads"],
            kv_heads=attrs["num_key_value_heads"], head_dim=head_dim,
            rotary_dim=int(head_dim * attrs.get("partial_rotary_factor", 1.0)),
            rope_theta=float(rope.get("rope_theta", attrs.get("rope_theta", 10000.0))),
            layers=attrs["num_hidden_layers"], vocab=attrs["vocab_size"],
            rms_eps=attrs.get("rms_norm_eps", 1e-5), experts=attrs["num_experts"],
            router_hidden=attrs["router_hidden_size"], degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, i) -> dict:
    """Layer ``i`` of the served tree as plain named arrays (still as stored)."""
    L = params["layers"]
    sa, router, experts = L["self_attn"], L["mlp"]["router"], L["mlp"]["experts"]
    return {
        "ln1": L["input_layernorm"]["weight"][i], "ln2": L["post_attention_layernorm"]["weight"][i],
        "q": sa["q_proj"]["weight"][i], "k": sa["k_proj"]["weight"][i],
        "v1": sa["v1_proj"]["weight"][i], "v2": sa["v2_proj"]["weight"][i],
        "o": sa["o_proj"]["weight"][i],
        "w0": sa["conv0"]["weight"][i], "b0": sa["conv0"]["bias"][i],
        "w1": sa["conv1"]["weight"][i], "b1": sa["conv1"]["bias"][i], "tau": sa["key_temp"][i],
        "wd": router["down_proj"]["weight"][i], "bd": router["down_proj"]["bias"][i],
        "gamma": router["gamma"][i], "wr": router["norm"]["weight"][i],
        "fc1": router["fc1"]["weight"][i], "fb1": router["fc1"]["bias"][i],
        "fc2": router["fc2"]["weight"][i], "fb2": router["fc2"]["bias"][i],
        "fc3": router["fc3"]["weight"][i], "bal": router["balance_bias"][i],
        "gate": experts["gate_proj"]["weight"][i], "up": experts["up_proj"]["weight"][i],
        "down": experts["down_proj"]["weight"][i],
    }


def _shift(a, fault=None):
    """``a_{t-1}`` of (S, ...): the explicit shift, zeros before t = 0."""
    import jax.numpy as jnp

    prev = jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)
    if fault == "conv_carry_zeroed":
        prev = prev.at[FAULT_BOUNDARY::FAULT_BOUNDARY].set(0.0)
    return prev


def _rotary(x, positions, geo: Geometry, rounding, fault=None):
    """x: (S, heads, d). HF rotate-half on the first ``rotary_dim`` dimensions
    of a head (pairs (i, i + rotary_dim / 2)); the rest pass through."""
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    n = x.shape[-1] if fault == "rotary_on_all_dims" else geo.rotary_dim
    inv_freq = 1.0 / (geo.rope_theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, n/2)
    cos = rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :])
    sin = rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :])
    xr = x[..., :n]
    rot = jnp.concatenate([-xr[..., n // 2 :], xr[..., : n // 2]], axis=-1)
    return jnp.concatenate([rnd(rnd(xr * cos) + rnd(rot * sin)), x[..., n:]], axis=-1)


def _attention(h, w, geo: Geometry, rounding, fault):
    """The attention sublayer on one sequence, h: (S, H) float32."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    S, Hq, Hkv, d = h.shape[0], geo.heads, geo.kv_heads, geo.head_dim
    G = Hq // Hkv
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    qt, kt = _mm(x, w["q"], rounding), _mm(x, w["k"], rounding)  # (S, Hq d), (S, Hkv d)
    u = jnp.concatenate([qt, kt], axis=-1)
    w0, w1 = f32(w["w0"]), w["w1"]
    a = rnd(w0[0][None, :] * _shift(u, fault) + w0[1][None, :] * u + f32(w["b0"])[None, :])
    a_prev = _shift(a, fault).reshape(S, Hq + Hkv, d)
    a_now = a.reshape(S, Hq + Hkv, d)

    def tap(av, wt):  # (S, groups, d) x (groups, d, d): a product per group, summed unrounded
        # av holds values of the rounding's grid, so at the ambient ("highest")
        # precision these are the twin's exact products, accumulated in float32
        return jnp.einsum("sgi,gio->sgo", av, _rnd(f32(wt), rounding))

    c = rnd(rnd(tap(a_prev, w1[0]) + tap(a_now, w1[1])) + f32(w["b1"]).reshape(1, Hq + Hkv, d))
    qt, kt = qt.reshape(S, Hq, d), kt.reshape(S, Hkv, d)
    q = rnd(c[:, :Hq] + (qt + jnp.repeat(kt, G, axis=1)) / 2)
    k = rnd(c[:, Hq:] + (qt.reshape(S, Hkv, G, d).mean(axis=2) + kt) / 2)
    x_prev = x if fault == "value_shift_dropped" else _shift(x)
    v = jnp.concatenate([_mm(x, w["v1"], rounding), _mm(x_prev, w["v2"], rounding)], axis=-1)
    v = v.reshape(S, Hkv, d)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-12)
    q = rnd(np.sqrt(d) * unit(q))
    k = rnd(jnp.exp(f32(w["tau"]))[None, :, None] * np.sqrt(d) * unit(k))
    q, k = _rotary(q, pos, geo, rounding, fault), _rotary(k, pos, geo, rounding, fault)
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, _mm(qh, kh.T, rounding) / np.sqrt(d), -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    # departure: no learned scale on this residual merge (scale_residual_merge: no key in the row's config)
    return rnd(h + _mm(attn.reshape(S, Hq * d), w["o"], rounding))


def _experts(h, r_prev, w, geo: Geometry, rounding, follow, fault):
    """The expert sublayer: (h after it, r of this layer (S, R), selection
    scores p + b_bal (S, E), the selection (S, 1))."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    gelu = lambda t: jax.nn.gelu(t, approximate=False)
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    # the router, float32 throughout (module docstring): the product of bf16 operands is not rounded
    r = x @ _rnd(f32(w["wd"]), rounding)  # x lies on the rounding's grid: exact products, float32 sum
    r = r + f32(w["bd"])[None, :]
    if fault != "router_carry_dropped":
        r = r + f32(w["gamma"])[None, :] * r_prev  # r_prev is zero at layer 0
    z = _rmsnorm(r, w["wr"], geo.rms_eps)
    t = gelu(z @ f32(w["fc1"]) + f32(w["fb1"])[None, :])
    t = gelu(t @ f32(w["fc2"]) + f32(w["fb2"])[None, :])
    p = jax.nn.softmax(t @ f32(w["fc3"]), axis=-1)
    score = p + f32(w["bal"])[None, :]
    # departure: no skip choice beside the experts (zaya_use_mod: no key in the row's config)
    chosen = jnp.argmax(score, axis=-1)[:, None].astype(jnp.int32) if follow is None else follow
    aff = jnp.take_along_axis(p, chosen, axis=1)  # (S, 1), not renormalised

    def expert(gud):  # one expert for every token; the chosen one's rows are picked below
        gate, up, down = gud
        act = rnd(rnd(jax.nn.silu(_mm(x, gate, rounding))) * _mm(x, up, rounding))
        return _mm(act, down, rounding)

    every = jax.lax.map(expert, (w["gate"], w["up"], w["down"]))  # (E, S, H), expert after expert
    out = every[chosen[:, 0], jnp.arange(h.shape[0])]  # (S, H)
    if fault != "affinity_not_applied":
        out = rnd(out * rnd(aff))
    # departure: no learned scale on this residual merge either
    return rnd(h + out), r, score, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    def layer(h, r_prev, w, follow=None):
        h = _attention(h, w, geo, rounding, fault)
        return _experts(h, r_prev, w, geo, rounding, follow, fault)

    head = jax.jit(lambda h, norm, wgt: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), wgt, rounding))
    return jax.jit(layer), jax.jit(layer_weights), head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None):
    """(logits (len(positions), vocab) float32, selection scores (L, S, E)
    float64, selection (L, S, 1)) of one sequence from a full causal pass:
    the selection is ``choices[NAME]`` (S, L, 1) where given, else each
    layer's own argmax."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    layer, take, head = _programs(geo, rounding, fault)
    follow = None if choices is None else jnp.asarray(np.asarray(choices[NAME], np.int32))
    scores, chosen = [], []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        r = jnp.zeros((len(tokens), geo.router_hidden), jnp.float32)
        for i in range(geo.layers):
            h, r, s, c = layer(h, r, take(params, i), None if follow is None else follow[:, i])
            scores.append(s)
            chosen.append(c)
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return (np.asarray(logits, np.float32)[:, : geo.vocab],
            np.asarray(jnp.stack(scores), np.float64), np.asarray(jnp.stack(chosen)))


def reference_logits(params, geo, tokens, positions, choices=None, rounding=None,
                     fault=None) -> np.ndarray:
    return forward(params, geo, tokens, positions, choices, rounding, fault)[0]


def twin_logits(params, geo, tokens, positions, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return forward(params, geo, tokens, positions, choices, jnp.bfloat16)[0]


def choice_margins(params, geo, tokens, choices):
    """Per layer, on the replayed path: (regret, score_floor, differing)."""
    import jax.numpy as jnp

    _, s32, _ = forward(params, geo, tokens, [0], choices)
    _, s16, _ = forward(params, geo, tokens, [0], choices, jnp.bfloat16)
    sel = np.transpose(np.asarray(choices[NAME]), (1, 0, 2))  # (L, S, 1)
    taken = np.take_along_axis(s32, sel, axis=2)[..., 0]
    short = s32.max(axis=2) - taken  # (L, S): how far the served choice lies under the best
    return short.max(axis=1), np.abs(s16 - s32).max(axis=(1, 2)), (short > 0).sum(axis=1)
