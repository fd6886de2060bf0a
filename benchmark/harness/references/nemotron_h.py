"""The plain reference of a Nemotron-H (``model_type: "nemotron_h"``) decoder:
one stack of single-part blocks of three kinds — a Mamba-2 mixer with groups
of B/C, top-k two-matrix relu^2 experts beside a shared expert, GQA attention
without rotation — in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no batching,
no line of the program's code — and its bf16 TWIN.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"; ``deepseek_mla.py``):

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The equations are those of the published ``nemotron_h`` modeling
(``modeling_nemotron_h.py`` beside the checkpoint). ``transformers`` 4.57.6,
installed here, has no such module, so each part is held to what IS
installed, one test a part (``tests/test_nemotron_h_reference.py``):

* the conv and the recurrence of a block ``M`` up to the gated norm:
  ``transformers.models.mamba2`` ``Mamba2Mixer.torch_forward`` at
  ``n_groups`` 8 (the same [z | xBC | dt] split, depthwise causal conv with
  bias, silu, softplus(dt + dt_bias), ``A = -exp(A_log)``, head ``h`` reading
  group ``h // (heads / n_groups)``, the ``D`` skip);
* the gated norm BY GROUP: by its equation. The installed ``MambaRMSNormGated``
  norms all of ``d_inner`` as one group whatever ``n_groups``: a departure of
  THEIRS from the ``nemotron_h`` modeling (whose ``group_size = d_inner //
  n_groups``), so the test above runs this file's ONE-group variant (the
  fault ``gated_norm_one_group``) against the installed mixer's output, and a
  second test holds the grouped form to ``v / sqrt(mean_g(v^2) + eps) * w``;
* the router: the installed ``deepseek_v3`` gate (``DeepseekV3TopkRouter``:
  sigmoid scores, the selection bias for the choice only, weights the
  uncorrected scores of the chosen renormalised, times the scaling factor).

Every key is the published config's (``x = rmsnorm(h, w_l)``, eps
``layer_norm_epsilon``; block ``l`` is ``hybrid_override_pattern[l]``):

    h = embed[tokens]
    per block l:   h = h + Mixer_l(x)
    logits = rmsnorm(h, norm_f) W_head

    M (heads H = mamba_num_heads of P = mamba_head_dim, d_inner = H P, NOT
      expand x hidden; N = ssm_state_size; G = n_groups; K = conv_kernel):
      [z, xBC, dt] = split(x W_in, [d_inner, d_inner + 2 G N, H])
      xBC_t = silu(sum_{k<K} w[k] * xBC_{t-(K-1)+k} + b)        depthwise, causal, zeros before t = 0
      [x, B, C] = split(xBC, [d_inner, G N, G N]);  x -> (H, P);  B, C -> (G, N)
      dt_t = softplus(dt_t + dt_bias)   (no clamp: the row has no time_step_limit);  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,g(h)     g(h) = h // (H / G);  S_{-1} = 0
      y_t = S_t . C_t,g(h) + D x_t
      v = y * silu(z);  each group of d_inner / G channels: v / sqrt(mean(v^2) + eps);  times norm.weight
      out = v W_out
    E: s = sigmoid(x W_r)                     n_routed_experts scores, float32
      chosen = the num_experts_per_tok largest of s + b   (b = e_score_correction_bias, for
               the choice ONLY; n_group 1: no group limit), or, replaying, the served selection
      w_e = s_e / sum_{chosen} s  x routed_scaling_factor       (norm_topk_prob)
      out = sum_{e chosen AND HELD} w_e W_down,e relu(W_up,e x)^2  +  W_down,s relu(W_up,s x)^2
    *: q, k, v = x W_q, x W_k, x W_v (no bias, NO rotation), GQA, causal,
      softmax(q k^T / sqrt(head_dim)) v, then W_o

A HELD SHARE: the configuration may hold ``n_routed_experts`` experts as rank
``expert_share.first`` of ``expert_share.of`` equal shares of the published
count. The router is the published width, the weights are normalised over
the token's choices BEFORE the held are kept, and the sum runs over the held
experts alone: nothing stands in for the others. ``geo.first`` is the first
expert held; the selftest moves it to see a wrong share fail.

The selection score (``choice_margins``) is ``s + b`` over the published width.

The twin (``rounding=jnp.bfloat16``) rounds where a faultless bf16 deployment
holds a bf16 tensor (``granite_hybrid.py`` for the mixer and attention,
``deepseek_mla.py`` for the router and experts): every product takes bf16
operands, accumulates in float32 and rounds its result; ``S`` is FLOAT32,
never rounded; dt, A, exp(dt A) float32; the router float32 throughout from
the rounded x; ``relu(u)^2`` rounded once; the gated norm rounds as rmsnorm
does, a group at a time. ``rounding=None`` rounds nowhere; any other dtype
(float8_e4m3fn: the control) rounds at the same places to it.

Computed a block at a time, one expert at a time and one attention head at a
time, so that a 6144-token prompt fits beside the probe application.

The only thing this file knows of the program is the layout of its parameter
tree: ``layers.mamba`` / ``layers.attention`` / ``layers.moe`` each stacked
over THEIR blocks in model order, matrices stored (in, out) but the routed
experts' ``up_proj``, (expert, out, in) as published; the published
``in_proj`` held as ``in_proj`` = its [z | xBC] columns and ``dt_proj`` = its
dt columns; the conv weight (layer, tap, channel); the routed stacks hold the
HELD experts only.

``forward`` takes, for the selftest alone, ``fault``: one of ``FAULTS``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import numpy as np

from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, expert blocks, k) expert indices

#: what ``fault`` may plant (selftest), one in each part: every head of a
#: state-space block reading group 0's B and C; the gated norm over d_inner as
#: one group; the state S not carried from one chunk of 128 positions to the
#: next; the routed experts' relu not squared; the selection bias ``b`` added
#: to the weights and not only to the choice; the shared expert left out; q
#: and k of an attention block rotated (theta 10000, the rotation the config
#: carries and the modeling does not apply)
FAULTS = ("groups_read_as_one", "gated_norm_one_group", "state_dropped_between_chunks",
          "relu_not_squared", "bias_in_weights", "shared_dropped", "rotary_applied")
FAULT_CHUNK = 128

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclass(frozen=True)
class Geometry:
    hidden: int
    pattern: str
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rms_eps: float
    m_heads: int
    m_head_dim: int
    m_state: int
    m_groups: int
    m_conv: int
    experts: int  # the published count: the router's width
    held: int
    first: int
    top_k: int
    norm_topk: bool
    scaling: float
    rope_theta: float
    degree: int

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.m_groups * self.m_state

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the nemotron_h reference is written for tp_degree 1")
        if set(attrs["hybrid_override_pattern"]) - set(KINDS):
            raise ValueError("the nemotron_h reference has blocks M, E and * only")
        if attrs.get("n_group", 1) != 1 or attrs.get("topk_group", 1) != 1:
            raise ValueError("the nemotron_h reference has no group-limited routing (n_group 1)")
        if attrs.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError("the nemotron_h reference's experts are relu^2")
        share = attrs.get("expert_share") or {"first": 0, "of": 1}
        held = attrs["n_routed_experts"]
        return Geometry(
            hidden=attrs["hidden_size"], pattern=attrs["hybrid_override_pattern"],
            heads=attrs["num_attention_heads"], kv_heads=attrs["num_key_value_heads"],
            head_dim=attrs["head_dim"], vocab=attrs["vocab_size"],
            rms_eps=attrs.get("layer_norm_epsilon", attrs.get("norm_eps", 1e-5)),
            m_heads=attrs["mamba_num_heads"], m_head_dim=attrs["mamba_head_dim"],
            m_state=attrs["ssm_state_size"], m_groups=attrs["n_groups"], m_conv=attrs["conv_kernel"],
            experts=held * int(share["of"]), held=held, first=held * int(share["first"]),
            top_k=attrs["num_experts_per_tok"], norm_topk=bool(attrs.get("norm_topk_prob", True)),
            scaling=float(attrs.get("routed_scaling_factor", 1.0)),
            rope_theta=float(attrs.get("rope_theta", 10000.0)), degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, kind: str, i) -> dict:
    """Block ``i`` OF ITS KIND from the served tree, as plain named arrays."""
    import jax.numpy as jnp

    L = params["layers"][kind]
    w = {"ln": L["input_layernorm"]["weight"][i]}
    if kind == "mamba":
        m = L["mixer"]
        w.update(
            w_in=jnp.concatenate([m["in_proj"]["weight"][i], m["dt_proj"]["weight"][i]], axis=1),
            conv_w=m["conv1d"]["weight"][i], conv_b=m["conv1d"]["bias"][i], A_log=m["A_log"][i],
            D=m["D"][i], dt_bias=m["dt_bias"][i], gnorm=m["norm"]["weight"][i],
            w_out=m["out_proj"]["weight"][i],
        )
    elif kind == "attention":
        sa = L["self_attn"]
        w.update({n[0]: sa[n]["weight"][i] for n in ("q_proj", "k_proj", "v_proj", "o_proj")})
    else:
        mlp = L["mlp"]
        w.update(router=mlp["router"]["weight"][i], bias=mlp["router"]["e_score_correction_bias"][i],
                 up=mlp["experts"]["up_proj"]["weight"][i], down=mlp["experts"]["down_proj"]["weight"][i])
        if "shared_experts" in mlp:
            w.update(sup=mlp["shared_experts"]["up_proj"]["weight"][i],
                     sdown=mlp["shared_experts"]["down_proj"]["weight"][i])
    return w


def grouped_gated_norm(y, z, weight, groups: int, eps: float, rounding=None):
    """``v = y * silu(z)``, each of the ``groups`` equal parts of the last axis
    divided by its own ``sqrt(mean(v^2) + eps)``, times ``weight``."""
    import jax

    v = y * jax.nn.silu(z)
    parts = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
    w = weight.reshape(groups, -1)
    return _rmsnorm(parts, w, eps, rounding).reshape(v.shape)


def mamba_mixer(x, w, geo: Geometry, rounding=None, fault=None):
    """The Mamba-2 mixer on one sequence from a zero state, x: (S, hidden) the
    normalised input. The recurrence is a scan over tokens. Returns (the
    mixer's output, y before the gated norm (S, d_inner), z): the last two for
    the test that holds the recurrence to the installed ``mamba2``."""
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
    Bg = xBC[:, d_inner : d_inner + G * N].reshape(S, G, N)
    Cg = xBC[:, d_inner + G * N :].reshape(S, G, N)
    if fault == "groups_read_as_one":
        Bg, Cg = jnp.repeat(Bg[:, :1], G, axis=1), jnp.repeat(Cg[:, :1], G, axis=1)
    Bm, Cm = jnp.repeat(Bg, H // G, axis=1), jnp.repeat(Cg, H // G, axis=1)  # (S, H, N): head h reads group h // (H / G)
    dt = jax.nn.softplus(dt + f32(w["dt_bias"])[None, :])  # (S, H)
    A = -jnp.exp(f32(w["A_log"]))  # (H,)
    D = f32(w["D"])

    def step(state, t):
        x_t, B_t, C_t, dt_t, keep = t
        state = jnp.exp(dt_t * A)[:, None, None] * (state * keep) + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y_t = jnp.sum(state * C_t[:, None, :], axis=-1) + D[:, None] * x_t
        return state, y_t

    keep = jnp.ones((S,), jnp.float32)
    if fault == "state_dropped_between_chunks":
        keep = (jnp.arange(S) % FAULT_CHUNK != 0).astype(jnp.float32)
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, Bm, Cm, dt, keep))
    y = rnd(y.reshape(S, d_inner))
    groups = 1 if fault == "gated_norm_one_group" else G
    gated = grouped_gated_norm(y, z, f32(w["gnorm"]), groups, geo.rms_eps, rounding)
    return _mm(gated, w["w_out"], rounding), y, z


def _rotate(x, geo: Geometry, rounding):
    """(the fault) rotate-half at ``rope_theta`` over a head; x: (S, heads, d)."""
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    n = x.shape[-1]
    inv_freq = 1.0 / (geo.rope_theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :])
    sin = rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :])
    rot = jnp.concatenate([-x[..., n // 2 :], x[..., : n // 2]], axis=-1)
    return rnd(rnd(x * cos) + rnd(rot * sin))


def attention_mixer(x, w, geo: Geometry, rounding=None, fault=None):
    """GQA attention on one sequence, no rotation; x: (S, hidden) normalised."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S = x.shape[0]
    pos = jnp.arange(S)
    q = _mm(x, w["q"], rounding).reshape(S, geo.heads, geo.head_dim)
    k = _mm(x, w["k"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    v = _mm(x, w["v"], rounding).reshape(S, geo.kv_heads, geo.head_dim)
    if fault == "rotary_applied":
        q, k = _rotate(q, geo, rounding), _rotate(k, geo, rounding)
    group = geo.heads // geo.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    causal = pos[:, None] >= pos[None, :]
    scale = 1.0 / np.sqrt(geo.head_dim)

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, _mm(qh, kh.T, rounding) * scale, -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    return _mm(attn.reshape(S, geo.heads * geo.head_dim), w["o"], rounding)


def _two_matrix(x, up_t, down, rounding, squared=True):
    """``relu(x up)^2 down``; ``up_t`` the (in, out) matrix."""
    import jax

    u = jax.nn.relu(_mm(x, up_t, rounding))
    return _mm(_rnd(u * u, rounding) if squared else u, down, rounding)


def router(x, w_router, bias, geo: Geometry, rounding=None, follow=None, fault=None):
    """(selection scores s + b (S, E), the selection (S, k), its weights
    (S, k)): float32 throughout from the (rounded) x."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    # exact products of grid values, float32 sum, not rounded
    s = jax.nn.sigmoid(x @ _rnd(f32(w_router), rounding))
    score = s + f32(bias)[None, :]
    chosen = jax.lax.top_k(score, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    taken = jnp.take_along_axis(score if fault == "bias_in_weights" else s, chosen, axis=1)
    if geo.norm_topk:
        taken = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20)
    return score, chosen, taken * geo.scaling


def experts_mixer(x, w, geo: Geometry, rounding=None, follow=None, fault=None):
    """(the expert block's output, selection scores, the selection); x: (S,
    hidden) normalised. The sum runs over the HELD experts, one at a time."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    score, chosen, taken = router(x, w["router"], w["bias"], geo, rounding, follow, fault)
    # (S, E) weights over the published width, zero outside the selection; then the held columns
    weights = jnp.zeros_like(score).at[jnp.arange(x.shape[0])[:, None], chosen].set(taken)
    held = weights[:, geo.first : geo.first + geo.held]
    squared = fault != "relu_not_squared"

    def expert(acc, udw):  # one expert for every token, weighted by its column
        up, down, col = udw
        y = _two_matrix(x, up.T, down, rounding, squared)
        return acc + rnd(rnd(col)[:, None] * y), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), (w["up"], w["down"], held.T))
    out = rnd(routed)
    if "sup" in w and fault != "shared_dropped":
        out = rnd(out + _two_matrix(x, w["sup"], w["sdown"], rounding))
    return out, score, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    rnd = lambda a: _rnd(a, rounding)
    norm = lambda h, w: _rmsnorm(h, w["ln"], geo.rms_eps, rounding)

    def mamba(h, w):
        return rnd(h + mamba_mixer(norm(h, w), w, geo, rounding, fault)[0])

    def attention(h, w):
        return rnd(h + attention_mixer(norm(h, w), w, geo, rounding, fault))

    def moe(h, w, follow=None):
        out, score, chosen = experts_mixer(norm(h, w), w, geo, rounding, follow, fault)
        return rnd(h + out), score, chosen

    head = jax.jit(lambda h, nw, wgt: _mm(_rmsnorm(h, nw, geo.rms_eps, rounding), wgt, rounding))
    take = jax.jit(layer_weights, static_argnums=1)
    return {"mamba": jax.jit(mamba), "attention": jax.jit(attention), "moe": jax.jit(moe)}, take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None,
            first: Optional[int] = None):
    """(logits (len(positions), vocab) float32, selection scores (L_moe, S, E)
    float64, selection (L_moe, S, k)) of one sequence from a full causal
    pass from a zero state: the selection is ``choices[NAME]`` (S, L_moe, k)
    where given, else each block's own top-k. ``first`` (selftest): another
    first held expert than the configuration's."""
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
        seen = {kind: 0 for kind in KINDS.values()}
        for letter in geo.pattern:
            kind = KINDS[letter]
            w = take(params, kind, seen[kind])
            if kind == "moe":
                h, s, c = blocks[kind](h, w, None if follow is None else follow[:, seen[kind]])
                scores.append(s)
                chosen.append(c)
            else:
                h = blocks[kind](h, w)
            seen[kind] += 1
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    E, k = geo.experts, geo.top_k
    S = len(tokens)
    return (np.asarray(logits, np.float32)[:, : geo.vocab],
            np.asarray(jnp.stack(scores), np.float64) if scores else np.zeros((0, S, E)),
            np.asarray(jnp.stack(chosen)) if chosen else np.zeros((0, S, k), np.int32))


def reference_logits(params, geo, tokens, positions, choices=None, rounding=None,
                     fault=None) -> np.ndarray:
    return forward(params, geo, tokens, positions, choices, rounding, fault)[0]


def twin_logits(params, geo, tokens, positions, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return forward(params, geo, tokens, positions, choices, jnp.bfloat16)[0]


def choice_margins(params, geo, tokens, choices) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per expert block, on the replayed path: (regret, score_floor,
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
