"""The plain reference of a Kimi-Linear (``model_type: "kimi_linear"``)
decoder: Kimi Delta Attention (KDA) layers as the token-by-token RECURRENCE
beside latent-attention (MLA) layers in their EXPANDED form without rotation,
a dense gated MLP in the first ``first_k_dense_replace`` layers and
DeepSeek-V3's expert layer under a held share after them — in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, no chunked form, no
cache, no kernel, no batching, no absorption, no line of the program's code —
and its bf16 TWIN.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"; ``deepseek_mla.py``):

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The equations are those of the Kimi Linear report (arXiv:2510.26692) and the
published ``modeling_kimi.py``. ``transformers`` 4.57.6, installed here, has
no such module, so each part is held to what IS installed
(``tests/test_kimi_linear_reference.py``): the recurrence to ``qwen3_next``'s
``torch_recurrent_gated_delta_rule`` (the same delta rule with a scalar decay
a head: this file's with ``g`` constant over a head's channels), the MLA
layer to this repo's ``deepseek_mla`` reference with the rotation made the
identity, the router and experts to ``deepseek_mla``'s (held to the installed
``deepseek_v3``). Layers are numbered from 1 as ``linear_attn_config`` does
(``x = rmsnorm(h, w)``, eps ``rms_norm_eps``):

    h = embed[tokens]
    per layer l:  h = h + Mixer_l(rmsnorm(h, ln1));  h = h + MLP_l(rmsnorm(h, ln2))
    logits = rmsnorm(h, norm) W_head

    KDA (l in kda_layers; H = num_heads heads of D = head_dim, d_k = d_v = D):
      [q; k; v]_t = silu(sum_{i<4} w[i] * ([W_q; W_k; W_v] x)_{t-3+i})   depthwise, causal, no bias,
                                                                          zeros before t = 0
      q_h = q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(D);   k_h = k_h / sqrt(|k_h|^2 + 1e-6)
      g_t = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)               (H, D): one a KEY channel
      b_t = sigmoid(x W_b)                                                 (H,)
      S' = diag(exp(g_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t;  S_{-1} = 0
      o_h = o_h / sqrt(mean(o_h^2) + eps) * w_o * sigmoid((x W_ga) W_gb)_h   w_o (D,) shared by heads
      out = [o_1 .. o_H] W_o
    MLA (l in full_attn_layers): ``deepseek_mla``'s attention with q direct
      (q_lora_rank null) and NO rotation of the qk_rope_head_dim dimensions of
      q and of the one shared key (mla_use_nope); scale 1 / sqrt(nope + rope)
    MLP: l <= first_k_dense_replace: W_down (silu(W_gate x) * W_up x)
         else: s = sigmoid(x W_r) float32 over the PUBLISHED experts; chosen = the
         num_experts_per_token largest of s + b (or, replaying, the served selection);
         w_e = s_e / sum_chosen s x routed_scaling_factor; out = sum_{e chosen AND HELD} w_e
         Expert_e(x) + Shared(x)

A HELD SHARE: ``num_experts`` experts are held as rank ``expert_share.first``
of ``expert_share.of`` equal shares; the weights are normalised over the
token's choices BEFORE the held are kept; nothing stands in for the others.
``geo.first`` is the first expert held; the selftest moves it.

The twin (``rounding=jnp.bfloat16``) rounds where a faultless bf16 deployment
holds a bf16 tensor: every product takes bf16 operands, accumulates in
float32 and rounds its result; the conv's output and its silu; ``S`` is
FLOAT32, never rounded, and so are q and k after their normalisation, g, b;
``o`` rounded as it leaves the recurrence, the head norm as rmsnorm rounds,
its product with the gate's sigmoid; the MLA layer and the MLPs as
``deepseek_mla`` rounds them. ``rounding=None`` rounds nowhere; any other
dtype (float8_e4m3fn: the control) rounds at the same places to it.

Computed a layer at a time, one expert and one attention head at a time.

The only thing this file knows of the program is the layout of its parameter
tree: ``layers.kda`` / ``layers.mla`` / ``layers.dense`` / ``layers.moe`` each
stacked over THEIR blocks in model order, matrices stored (in, out), q, k and
v projections side by side in ``qkv_proj`` and their convs in ``conv1d``
(layer, tap, channel), ``W_kvb`` as ``k_absorb`` / ``v_absorb``, the routed
stacks (layer, HELD expert, in, out).

``forward`` takes, for the selftest alone, ``fault``: one of ``FAULTS``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import numpy as np

from benchmark.harness.references.deepseek_mla import _gated, _rotary
from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, expert layers, k) expert indices

#: what ``fault`` may plant (selftest): the decay one number a head (the mean
#: of its channels': what a state kernel with a scalar decay computes); the
#: state not read before its write (``u = b v``: gated linear attention, no
#: delta); the state S not carried from one chunk of 128 positions to the
#: next; q and k not normalised a head; the rope dimensions of an MLA layer
#: rotated (theta 10000, the rotation the config carries and ``mla_use_nope``
#: turns off); the output gate's sigmoid left out; the shared expert left out
FAULTS = ("decay_scalar_per_head", "state_not_read", "state_dropped_between_chunks",
          "qk_not_normalised", "mla_rotated", "gate_dropped", "shared_dropped")
FAULT_CHUNK = 128

KDA, MLA, DENSE, MOE = "kda", "mla", "dense", "moe"


@dataclass(frozen=True)
class Geometry:
    hidden: int
    mixers: Tuple[str, ...]  # per layer: kda or mla
    first_dense: int
    heads: int  # MLA
    kv_lora_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    k_heads: int  # KDA
    k_dim: int
    k_conv: int
    vocab: int
    rms_eps: float
    experts: int  # the published count: the router's width
    held: int
    first: int
    top_k: int
    norm_topk: bool
    scaling: float
    degree: int

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the kimi_linear reference is written for tp_degree 1")
        if attrs.get("num_expert_group", 1) != 1 or attrs.get("topk_group", 1) != 1:
            raise ValueError("the kimi_linear reference has no group-limited routing")
        if not attrs.get("mla_use_nope", False) or attrs.get("q_lora_rank"):
            raise ValueError("the kimi_linear reference has MLA with q direct and no rotation")
        lin = attrs["linear_attn_config"]
        kinds = {int(l): KDA for l in lin["kda_layers"]}
        kinds.update({int(l): MLA for l in lin["full_attn_layers"]})
        L = attrs["num_hidden_layers"]
        share = attrs.get("expert_share") or {"first": 0, "of": 1}
        held = attrs["num_experts"]
        return Geometry(
            hidden=attrs["hidden_size"], mixers=tuple(kinds[l] for l in range(1, L + 1)),
            first_dense=min(attrs.get("first_k_dense_replace", 0), L),
            heads=attrs["num_attention_heads"], kv_lora_rank=attrs["kv_lora_rank"],
            nope=attrs["qk_nope_head_dim"], rope=attrs["qk_rope_head_dim"],
            v_dim=attrs["v_head_dim"], rope_theta=float(attrs.get("rope_theta", 10000.0)),
            k_heads=lin["num_heads"], k_dim=lin["head_dim"],
            k_conv=lin.get("short_conv_kernel_size", 4), vocab=attrs["vocab_size"],
            rms_eps=attrs.get("rms_norm_eps", 1e-5),
            experts=held * int(share["of"]), held=held, first=held * int(share["first"]),
            top_k=attrs["num_experts_per_token"],
            norm_topk=bool(attrs.get("moe_renormalize", True)),
            scaling=float(attrs.get("routed_scaling_factor", 1.0)), degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, kind: str, i) -> dict:
    """Block ``i`` OF ITS KIND from the served tree, as plain named arrays."""
    L = params["layers"][kind]
    w = {"ln": L["input_layernorm"]["weight"][i]}
    if kind == KDA:
        m = L["mixer"]
        w.update({n: m[n + "_proj"]["weight"][i] for n in ("qkv", "f_a", "f_b", "b", "g_a", "g_b", "o")})
        w.update(conv=m["conv1d"]["weight"][i], A_log=m["A_log"][i], dt_bias=m["dt_bias"][i],
                 o_norm=m["o_norm"]["weight"][i])
    elif kind == MLA:
        sa = L["self_attn"]
        w.update(q=sa["q_proj"]["weight"][i], kva=sa["kv_a_proj"]["weight"][i],
                 wc=sa["kv_a_layernorm"]["weight"][i], uk=sa["k_absorb"]["weight"][i],
                 uv=sa["v_absorb"]["weight"][i], o=sa["o_proj"]["weight"][i])
    else:
        mlp = L["mlp"]
        if kind == MOE:
            w.update(router=mlp["router"]["weight"][i], bias=mlp["router"]["e_score_correction_bias"][i])
            if "shared_experts" in mlp:
                sh = mlp["shared_experts"]
                w.update(sgate=sh["gate_proj"]["weight"][i], sup=sh["up_proj"]["weight"][i],
                         sdown=sh["down_proj"]["weight"][i])
            mlp = mlp["experts"]
        w.update(gate=mlp["gate_proj"]["weight"][i], up=mlp["up_proj"]["weight"][i],
                 down=mlp["down_proj"]["weight"][i])
    return w


def delta_rule(q, k, v, g, beta, keep=None, read=True):
    """The recurrence on one sequence from a zero state, a scan over tokens:
    q, k, v, g (S, H, D), beta (S, H), float32; ``keep`` (S,) 0 where the state
    is dropped BEFORE the token (the fault); ``read`` False: no delta (the
    fault). Returns o (S, H, D)."""
    import jax
    import jax.numpy as jnp

    S, H, D = q.shape

    def step(state, t):  # state (H, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t, keep_t = t
        s = jnp.exp(g_t)[:, :, None] * (state * keep_t)
        read_t = jnp.sum(s * k_t[:, :, None], axis=1) if read else 0.0
        state = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read_t))[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    keep = jnp.ones((S,), jnp.float32) if keep is None else keep
    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32), (q, k, v, g, beta, keep))
    return o


def kda_mixer(x, w, geo: Geometry, rounding=None, fault=None):
    """The KDA mixer on one sequence; x: (S, hidden) the normalised input."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    S, H, D, K = x.shape[0], geo.k_heads, geo.k_dim, geo.k_conv
    proj = _mm(x, w["qkv"], rounding)  # (S, 3 H D)
    padded = jnp.concatenate([jnp.zeros((K - 1, proj.shape[1]), jnp.float32), proj], axis=0)
    taps = f32(w["conv"])  # (K, 3 H D)
    conv = sum(taps[i][None, :] * padded[i : i + S] for i in range(K))
    qkv = rnd(jax.nn.silu(rnd(conv))).reshape(S, 3, H, D)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    if fault != "qk_not_normalised":
        unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q), unit(k)
    q = q / np.sqrt(D)
    decay = _mm(_mm(x, w["f_a"], rounding), w["f_b"], rounding) + f32(w["dt_bias"])[None, :]
    g = -jnp.exp(f32(w["A_log"]))[None, :, None] * jax.nn.softplus(decay).reshape(S, H, D)
    if fault == "decay_scalar_per_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(x, w["b"], rounding))  # (S, H)
    keep = None
    if fault == "state_dropped_between_chunks":
        keep = (jnp.arange(S) % FAULT_CHUNK != 0).astype(jnp.float32)
    o = rnd(delta_rule(q, k, v, g, beta, keep, read=fault != "state_not_read"))
    o = _rmsnorm(o, w["o_norm"], geo.rms_eps, rounding)  # over each head's D; w (D,)
    if fault != "gate_dropped":
        gate = _mm(_mm(x, w["g_a"], rounding), w["g_b"], rounding).reshape(S, H, D)
        o = rnd(o * jax.nn.sigmoid(gate))
    return _mm(o.reshape(S, H * D), w["o"], rounding)


def mla_mixer(x, w, geo: Geometry, rounding=None, fault=None):
    """Latent attention on one sequence, expanded form, no rotation; x: (S,
    hidden) the normalised input."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S, H, r = x.shape[0], geo.heads, geo.kv_lora_rank
    pos = jnp.arange(S)
    q = _mm(x, w["q"], rounding).reshape(S, H, geo.nope + geo.rope)
    q_nope, q_rope = q[..., : geo.nope], q[..., geo.nope :]
    ckv = _mm(x, w["kva"], rounding)
    c = _rmsnorm(ckv[:, :r], w["wc"], geo.rms_eps, rounding)
    k_r = ckv[:, r:]  # (S, rope): one key, every head's
    if fault == "mla_rotated":
        q_rope = _rotary(q_rope, pos, geo, rounding)
        k_r = _rotary(k_r[:, None], pos, geo, rounding)[:, 0]
    causal = pos[:, None] >= pos[None, :]
    scale = 1.0 / np.sqrt(geo.nope + geo.rope)

    def one_head(args):
        qn, qr, uk, uv = args  # (S, nope), (S, rope), (nope, r), (r, v)
        k_nope = _mm(c, uk.T, rounding)
        v = _mm(c, uv, rounding)
        if rounding is None:
            scores = qn @ k_nope.T + qr @ k_r.T
        else:  # one product over the joined dimensions: float32 sum, one rounding
            scores = _mm(jnp.concatenate([qn, qr], -1), jnp.concatenate([k_nope, k_r], -1).T, rounding)
        scores = jnp.where(causal, scores * scale, -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), v, rounding)

    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))
    attn = heads_first(jax.lax.map(
        one_head, (heads_first(q_nope), heads_first(q_rope), w["uk"], w["uv"])))
    return _mm(attn.reshape(S, H * geo.v_dim), w["o"], rounding)


def experts_mlp(x, w, geo: Geometry, rounding=None, follow=None, fault=None):
    """(the expert layer's output, selection scores s + b (S, E), the
    selection (S, k)); x: (S, hidden) normalised. The sum runs over the HELD
    experts, one at a time."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    # the router, float32 from the rounded x: exact products of grid values, float32 sum
    s = jax.nn.sigmoid(x @ _rnd(f32(w["router"]), rounding))
    score = s + f32(w["bias"])[None, :]
    chosen = jax.lax.top_k(score, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    taken = jnp.take_along_axis(s, chosen, axis=1)
    if geo.norm_topk:
        taken = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20)
    # (S, E) weights over the published width, zero outside the selection; then the held columns
    weights = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(taken * geo.scaling)
    held = weights[:, geo.first : geo.first + geo.held]

    def expert(acc, gudw):  # one expert for every token, weighted by its column
        gate, up, down, col = gudw
        return acc + rnd(rnd(col)[:, None] * _gated(x, gate, up, down, rounding)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), (w["gate"], w["up"], w["down"], held.T))
    out = rnd(routed)
    if "sgate" in w and fault != "shared_dropped":
        out = rnd(out + _gated(x, w["sgate"], w["sup"], w["sdown"], rounding))
    return out, score, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    rnd = lambda a: _rnd(a, rounding)
    norm = lambda h, w: _rmsnorm(h, w["ln"], geo.rms_eps, rounding)

    def kda(h, w):
        return rnd(h + kda_mixer(norm(h, w), w, geo, rounding, fault))

    def mla(h, w):
        return rnd(h + mla_mixer(norm(h, w), w, geo, rounding, fault))

    def dense(h, w):
        return rnd(h + _gated(norm(h, w), w["gate"], w["up"], w["down"], rounding))

    def moe(h, w, follow=None):
        out, score, chosen = experts_mlp(norm(h, w), w, geo, rounding, follow, fault)
        return rnd(h + out), score, chosen

    head = jax.jit(lambda h, nw, wgt: _mm(_rmsnorm(h, nw, geo.rms_eps, rounding), wgt, rounding))
    take = jax.jit(layer_weights, static_argnums=1)
    blocks = {KDA: jax.jit(kda), MLA: jax.jit(mla), DENSE: jax.jit(dense), MOE: jax.jit(moe)}
    return blocks, take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None,
            first: Optional[int] = None):
    """(logits (len(positions), vocab) float32, selection scores (L_moe, S, E)
    float64, selection (L_moe, S, k)) of one sequence from a full causal pass
    from a zero state: the selection is ``choices[NAME]`` (S, L_moe, k) where
    given, else each layer's own top-k. ``first`` (selftest): another first
    held expert than the configuration's."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    if first is not None:
        geo = dataclasses.replace(geo, first=int(first))
    blocks, take, head = _programs(geo, rounding, fault)
    follow = None if choices is None else jnp.asarray(np.asarray(choices[NAME], np.int32))
    scores, chosen = [], []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        seen = {kind: 0 for kind in (KDA, MLA, DENSE, MOE)}
        for l, mixer in enumerate(geo.mixers):
            h = blocks[mixer](h, take(params, mixer, seen[mixer]))
            seen[mixer] += 1
            if l < geo.first_dense:
                h = blocks[DENSE](h, take(params, DENSE, seen[DENSE]))
                seen[DENSE] += 1
                continue
            m = seen[MOE]
            h, s, c = blocks[MOE](h, take(params, MOE, m), None if follow is None else follow[:, m])
            scores.append(s)
            chosen.append(c)
            seen[MOE] += 1
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    S = len(tokens)
    return (np.asarray(logits, np.float32)[:, : geo.vocab],
            np.asarray(jnp.stack(scores), np.float64) if scores else np.zeros((0, S, geo.experts)),
            np.asarray(jnp.stack(chosen)) if chosen else np.zeros((0, S, geo.top_k), np.int32))


def reference_logits(params, geo, tokens, positions, choices=None, rounding=None,
                     fault=None) -> np.ndarray:
    return forward(params, geo, tokens, positions, choices, rounding, fault)[0]


def twin_logits(params, geo, tokens, positions, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return forward(params, geo, tokens, positions, choices, jnp.bfloat16)[0]


def choice_margins(params, geo, tokens, choices) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
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
