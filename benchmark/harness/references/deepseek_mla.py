"""The plain reference of a DeepSeek-V3-style (``model_type: "deepseek_v3"``)
decoder: multi-head latent attention (MLA) in its EXPANDED form, a dense
gated MLP in the first ``first_k_dense_replace`` layers and, after them, a
sigmoid-scored top-k expert layer with a selection bias and shared experts
— in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
cache, no kernel, no batching, no absorption, no line of the program's code
— and its bf16 TWIN.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"):

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The equations (``x = rmsnorm(h, ln1)``, ``H`` heads, ``t`` a position; every
key is the published config's; ``tests/test_deepseek_reference.py`` holds
this file to the installed ``transformers`` ``deepseek_v3`` module at a small
size, so the equations are the family's):

    h = embed[tokens]
    per layer l:
      attention,  h = h + W_o [a_1 .. a_H]
        q_t = W_q x_t                       H heads of qk_nope_head_dim + qk_rope_head_dim
                                            (q_lora_rank set: W_qb rmsnorm(W_qa x_t))
        [c_raw_t ; k_r_t] = W_kva x_t       kv_lora_rank + qk_rope_head_dim
        c_t = rmsnorm(c_raw_t, w_c)         a learned weight of kv_lora_rank
        k_r_t  rotated at rope_theta: ONE rotary key, shared by all heads
        q_rope_h,t  rotated as k_r_t is     (the last qk_rope_head_dim of a head's q)
        [k_nope_h,t ; v_h,t] = W_kvb,h c_t  qk_nope_head_dim + v_head_dim a head
        a_h,t = sum_j softmax_j((q_nope_h,t . k_nope_h,j + q_rope_h,t . k_r_j) / sqrt(d_q)) v_h,j
                                            j <= t;  d_q = qk_nope_head_dim + qk_rope_head_dim
      layer l < first_k_dense_replace:  h = h + W_down (silu(W_gate x') * W_up x'),  x' = rmsnorm(h, ln2)
      layer l >= first_k_dense_replace:
        s = sigmoid(x' W_r)                 n_routed_experts scores, float32
        chosen = the num_experts_per_tok largest of s + b    (b = e_score_correction_bias:
                                            for the choice ONLY; n_group 1: no group limit)
                 or, replaying, the served selection
        w_e = s_e / sum_{chosen} s  x routed_scaling_factor   (norm_topk_prob)
        h = h + sum_{e chosen} w_e W_down,e (silu(W_gate,e x') * W_up,e x')
              + W_down,s (silu(W_gate,s x') * W_up,s x')      the shared experts, one MLP of
                                            n_shared_experts x moe_intermediate_size, unweighted
    logits = rmsnorm(h, norm) W_head

The selection score (``choice_margins``) is ``s + b``.

Rotary: the tree stores the rotary dimensions of ``W_q`` and ``W_kva``
de-interleaved (the program's checkpoint conversion permutes the family's
interleaved pairs ``(2i, 2i + 1)`` to ``(i, i + d/2)``), so the rotation here
is rotate-half on the tree's order; under seeded weights the two orders are
one relabelling of rows.

The twin (``rounding=jnp.bfloat16``) evaluates the same lines with the
roundings a faultless bf16 deployment states; a rounding is a
``lax.reduce_precision`` (``granite_hybrid._rnd``: the compiler may not
remove it). Every matrix product takes bf16 operands, accumulates in float32
and rounds its result; between products values are float32 and rounded
where a bf16 model holds a bf16 tensor:

    h                after the embedding (as stored), after each residual add
    rmsnorm          the normalised x, and again after the weight (the latent's too)
    q, [c_raw; k_r]  each product
    rotary           cos and sin rounded; x cos and rot(x) sin each rounded, and their sum
    k_nope, v        each head's product with W_kvb
    attention        the two score products summed in float32 (one product over the joined
                     192 dimensions), softmax rounded before v, the product rounded; W_o's product
    dense / shared   each product, silu(g), silu(g) * u
    router           FLOAT32 throughout, from the bf16 x': x' W_r is a product of bf16 operands
                     accumulated in float32 and NOT rounded; s, s + b, the weights are float32
    experts          each product, silu(g), silu(g) * u; w_e (rounded) times the expert's output
                     (rounded); the sum over the chosen in float32, rounded; the sum with the
                     shared MLP's output rounded

``rounding=None`` rounds nowhere; any other dtype (float8_e4m3fn: the
control) rounds at the same places to it. ``geo.degree`` is 1.

The only thing this file knows of the program is the layout of its parameter
tree: ``layers`` a list of the dense group and the expert group, every leaf
stacked over its group's layers, matrices stored (in, out), ``W_kvb`` as its
two halves ``k_absorb`` (layer, head, nope, latent) and ``v_absorb`` (layer,
head, latent, v), the expert stacks (layer, expert, in, out).

``forward`` takes, for the selftest alone, ``fault``: one of ``FAULTS``, the
equations with one part wrong (in the program's place, to see the rule fail
it).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, expert layers, k) expert indices

#: what ``fault`` may plant (selftest): the rotary on a head's nope dimensions
#: too, the latent's norm left out, ``b`` added to the weights and not only
#: to the choice, the routed scaling factor left out, the shared MLP left out,
#: the value read from the LAST ``kv_lora_rank`` lanes of the joined key
#: ``[c ; k_r]`` and not from its first (what a kernel that takes its value
#: tile from the wrong end computes)
FAULTS = ("rotary_on_nope", "latent_norm_dropped", "bias_in_weights", "scaling_dropped",
          "shared_dropped", "value_from_last_lanes")


@dataclass(frozen=True)
class Geometry:
    hidden: int
    heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    layers: int
    first_dense: int
    vocab: int
    rms_eps: float
    experts: int
    top_k: int
    shared: int
    norm_topk: bool
    scaling: float
    degree: int

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the deepseek_mla reference is written for tp_degree 1")
        if attrs.get("n_group", 1) != 1 or attrs.get("topk_group", 1) != 1:
            raise ValueError("the deepseek_mla reference has no group-limited routing (n_group 1)")
        if attrs.get("rope_scaling"):
            raise ValueError("the deepseek_mla reference has plain rotary (rope_scaling null)")
        if attrs.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("the deepseek_mla reference scores with a sigmoid")
        return Geometry(
            hidden=attrs["hidden_size"], heads=attrs["num_attention_heads"],
            q_lora_rank=attrs.get("q_lora_rank"), kv_lora_rank=attrs["kv_lora_rank"],
            nope=attrs["qk_nope_head_dim"], rope=attrs["qk_rope_head_dim"],
            v_dim=attrs["v_head_dim"], rope_theta=float(attrs.get("rope_theta", 10000.0)),
            layers=attrs["num_hidden_layers"],
            first_dense=min(attrs.get("first_k_dense_replace", 0), attrs["num_hidden_layers"]),
            vocab=attrs["vocab_size"], rms_eps=attrs.get("rms_norm_eps", 1e-6),
            experts=attrs["n_routed_experts"], top_k=attrs["num_experts_per_tok"],
            shared=attrs.get("n_shared_experts", 0) or 0,
            norm_topk=bool(attrs.get("norm_topk_prob", True)),
            scaling=float(attrs.get("routed_scaling_factor", 1.0)), degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, group: int, i) -> dict:
    """Layer ``i`` of group ``group`` of the served tree as plain named
    arrays (still as stored)."""
    L = params["layers"][group]
    sa, mlp = L["self_attn"], L["mlp"]
    w = {
        "ln1": L["input_layernorm"]["weight"][i], "ln2": L["post_attention_layernorm"]["weight"][i],
        "kva": sa["kv_a_proj"]["weight"][i], "wc": sa["kv_a_layernorm"]["weight"][i],
        "uk": sa["k_absorb"]["weight"][i], "uv": sa["v_absorb"]["weight"][i],
        "o": sa["o_proj"]["weight"][i],
    }
    if "q_proj" in sa:
        w["q"] = sa["q_proj"]["weight"][i]
    else:
        w.update(qa=sa["q_a_proj"]["weight"][i], wq=sa["q_a_layernorm"]["weight"][i],
                 qb=sa["q_b_proj"]["weight"][i])
    if "router" in mlp:
        w.update(router=mlp["router"]["weight"][i], bias=mlp["router"]["e_score_correction_bias"][i],
                 gate=mlp["experts"]["gate_proj"]["weight"][i],
                 up=mlp["experts"]["up_proj"]["weight"][i],
                 down=mlp["experts"]["down_proj"]["weight"][i])
        if "shared_experts" in mlp:
            mlp = mlp["shared_experts"]
            w.update(sgate=mlp["gate_proj"]["weight"][i], sup=mlp["up_proj"]["weight"][i],
                     sdown=mlp["down_proj"]["weight"][i])
    else:
        w.update(dgate=mlp["gate_proj"]["weight"][i], dup=mlp["up_proj"]["weight"][i],
                 ddown=mlp["down_proj"]["weight"][i])
    return w


def _rotary(x, positions, geo: Geometry, rounding):
    """x: (S, heads, n). Rotate-half on all ``n`` dimensions at ``rope_theta``
    (pairs ``(i, i + n / 2)``: the tree's order, module docstring)."""
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    n = x.shape[-1]
    inv_freq = 1.0 / (geo.rope_theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, n/2)
    cos = rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :])
    sin = rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :])
    rot = jnp.concatenate([-x[..., n // 2 :], x[..., : n // 2]], axis=-1)
    return rnd(rnd(x * cos) + rnd(rot * sin))


def _gated(x, gate, up, down, rounding):
    import jax

    act = _rnd(_rnd(jax.nn.silu(_mm(x, gate, rounding)), rounding) * _mm(x, up, rounding), rounding)
    return _mm(act, down, rounding)


def _attention(h, w, geo: Geometry, rounding, fault):
    """The attention sublayer on one sequence, expanded form; h: (S, hidden) float32."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S, H, r = h.shape[0], geo.heads, geo.kv_lora_rank
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    if "q" in w:
        q = _mm(x, w["q"], rounding)
    else:
        q = _mm(_rmsnorm(_mm(x, w["qa"], rounding), w["wq"], geo.rms_eps, rounding), w["qb"], rounding)
    q = q.reshape(S, H, geo.nope + geo.rope)
    q_nope, q_rope = q[..., : geo.nope], _rotary(q[..., geo.nope :], pos, geo, rounding)
    ckv = _mm(x, w["kva"], rounding)
    c = ckv[:, :r]
    if fault != "latent_norm_dropped":
        c = _rmsnorm(c, w["wc"], geo.rms_eps, rounding)
    k_r = _rotary(ckv[:, None, r:], pos, geo, rounding)[:, 0]  # (S, rope): one key, every head's
    if fault == "rotary_on_nope":
        q_nope = _rotary(q_nope, pos, geo, rounding)
    # the fault: the value tile taken from the END of the joined key [c ; k_r]
    c_v = jnp.concatenate([c, k_r], -1)[:, geo.rope :] if fault == "value_from_last_lanes" else c
    causal = pos[:, None] >= pos[None, :]
    scale = 1.0 / np.sqrt(geo.nope + geo.rope)

    def one_head(args):
        qn, qr, uk, uv = args  # (S, nope), (S, rope), (nope, r), (r, v)
        k_nope = _mm(c, uk.T, rounding)  # (S, nope): this head's keys from the latent
        if fault == "rotary_on_nope":
            k_nope = _rotary(k_nope[:, None], pos, geo, rounding)[:, 0]
        v = _mm(c_v, uv, rounding)  # (S, v)
        if rounding is None:
            scores = qn @ k_nope.T + qr @ k_r.T
        else:  # one product over the joined dimensions: float32 sum, one rounding
            scores = _mm(jnp.concatenate([qn, qr], -1), jnp.concatenate([k_nope, k_r], -1).T, rounding)
        scores = jnp.where(causal, scores * scale, -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), v, rounding)

    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))
    attn = heads_first(jax.lax.map(
        one_head, (heads_first(q_nope), heads_first(q_rope), w["uk"], w["uv"])))
    return rnd(h + _mm(attn.reshape(S, H * geo.v_dim), w["o"], rounding))


def _dense(h, w, geo: Geometry, rounding):
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    return _rnd(h + _gated(x, w["dgate"], w["dup"], w["ddown"], rounding), rounding)


def _experts(h, w, geo: Geometry, rounding, follow, fault):
    """The expert sublayer: (h after it, selection scores s + b (S, E), the
    selection (S, k))."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    # the router, float32 from the rounded x': exact products of grid values, float32 sum
    s = jax.nn.sigmoid(x @ _rnd(f32(w["router"]), rounding))
    score = s + f32(w["bias"])[None, :]
    chosen = jax.lax.top_k(score, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    taken = jnp.take_along_axis(score if fault == "bias_in_weights" else s, chosen, axis=1)  # (S, k)
    if geo.norm_topk:
        taken = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20)
    if fault != "scaling_dropped":
        taken = taken * geo.scaling
    # (S, E) weights, zero outside the selection
    weights = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(taken)

    def expert(acc, gudw):  # one expert for every token, weighted by its column; expert after expert
        gate, up, down, col = gudw
        return acc + rnd(rnd(col)[:, None] * _gated(x, gate, up, down, rounding)), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h), (w["gate"], w["up"], w["down"], weights.T))
    out = rnd(routed)
    if "sgate" in w and fault != "shared_dropped":
        out = rnd(out + _gated(x, w["sgate"], w["sup"], w["sdown"], rounding))
    return rnd(h + out), score, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    def dense_layer(h, w):
        return _dense(_attention(h, w, geo, rounding, fault), w, geo, rounding)

    def expert_layer(h, w, follow=None):
        return _experts(_attention(h, w, geo, rounding, fault), w, geo, rounding, follow, fault)

    head = jax.jit(lambda h, norm, wgt: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), wgt, rounding))
    take = jax.jit(layer_weights, static_argnums=1)
    return jax.jit(dense_layer), jax.jit(expert_layer), take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None):
    """(logits (len(positions), vocab) float32, selection scores (L_moe, S, E)
    float64, selection (L_moe, S, k)) of one sequence from a full causal
    pass: the selection is ``choices[NAME]`` (S, L_moe, k) where given, else
    each layer's own top-k."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    dense_layer, expert_layer, take, head = _programs(geo, rounding, fault)
    follow = None if choices is None else jnp.asarray(np.asarray(choices[NAME], np.int32))
    scores, chosen = [], []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        groups = len(params["layers"])
        for i in range(geo.layers):
            if i < geo.first_dense:
                h = dense_layer(h, take(params, 0, i))
                continue
            m = i - geo.first_dense
            h, s, c = expert_layer(h, take(params, groups - 1, m),
                                   None if follow is None else follow[:, m])
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
    """Per expert layer, on the replayed path: (regret, score_floor,
    differing): how far the weakest expert taken lies under the strongest left
    out, in the float32 router's ``s + b``; the floor max |twin's score -
    float32's|; the tokens whose selection is not float32's own."""
    import jax.numpy as jnp

    _, s32, _ = forward(params, geo, tokens, [0], choices)
    _, s16, _ = forward(params, geo, tokens, [0], choices, jnp.bfloat16)
    sel = np.transpose(np.asarray(choices[NAME]), (1, 0, 2))  # (L_moe, S, k)
    taken = np.take_along_axis(s32, sel, axis=2)
    rest = s32.copy()
    np.put_along_axis(rest, sel, -np.inf, axis=2)
    short = np.maximum(rest.max(axis=2) - taken.min(axis=2), 0.0)
    return short.max(axis=1), np.abs(s16 - s32).max(axis=(1, 2)), (short > 0).sum(axis=1)
