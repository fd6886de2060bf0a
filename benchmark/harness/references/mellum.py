"""The plain reference of a Mellum 2 (``model_type: "mellum"``) decoder: the
Qwen3-MoE block (per-head q/k RMSNorm, rotate-half rotary on the whole head,
a top-k softmax-router expert sublayer) in a stack that MIXES two kinds of
attention layer, each with its own mask and its own rotary table — in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no paging, no batching, no line of the program's code — and
its bf16 TWIN.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"):

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None, fault=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The equations (every key is the published config's; ``t`` a position, ``W`` =
``sliding_window``, ``kind(l)`` = ``layer_types[l]``):

    h = embed[tokens]
    per layer l, on x = rmsnorm(h, ln1):
      q, k, v = x Wq, x Wk, x Wv            heads of head_dim; kv heads shared by groups of q heads
      q, k = rmsnorm over head_dim, per head (a learned weight of head_dim), then
      rotate-half rotary on all of a head's dimensions with the table of kind(l):
        sliding_attention   inv_freq_i = theta ** (-2i / d)                    cos, sin as they are
        full_attention      YaRN: inv_freq_i blended between theta ** (-2i / d) and that / factor
                            by a ramp over i from the dimension that turns ``beta_fast`` times in
                            ``original_max_position_embeddings`` to the one that turns
                            ``beta_slow`` times; cos and sin multiplied by ``attention_factor``
      a_t = sum_j softmax_j(q_t . k_j / sqrt(d)) v_j over the j that t sees:
        sliding_attention   t - W < j <= t        (a DENSE mask of the whole sequence here)
        full_attention      j <= t
      h = h + a Wo;  x' = rmsnorm(h, ln2)
      p = softmax(x' W_r) over the experts, float32;  e_1..e_k the k largest (or, replaying, the
      served selection);  w_j = p[e_j] / sum_j p[e_j]  (``norm_topk_prob``)
      h = h + sum_j w_j W_down,e_j (silu(W_gate,e_j x') * W_up,e_j x')
    logits = rmsnorm(h, norm) W_head

YaRN is written out here from the config's keys (``_yarn_inv_freq``), not
imported from the program.

Size: one sequence of up to ~16k tokens. The attention runs head after head
and, inside a head, block of ``Q_BLOCK`` queries after block, so that the
float32 scores held at once are (Q_BLOCK, S); the experts run expert after
expert, each over every token, weighted by its column of the (S, E) weights
(zero outside a token's selection) and summed; one layer's weights are
sliced at a time.

The twin (``rounding=jnp.bfloat16``) rounds where ``sdar_moe``'s does (a
rounding is arithmetic the compiler may not remove, ``sdar_moe._rnd``; the
fp8-e4m3 grid of the control too): h after the embedding and after each
residual add; the normalised x and again after the weight; every product
(bf16 operands, float32 accumulator, the result rounded); the per-head norms;
cos and sin (``attention_factor`` applied first), x cos and rot(x) sin each,
and their sum; the softmax before v; the router FLOAT32 from the rounded x'
(exact products of grid values, not rounded); each expert's products,
silu(g), silu(g) * u; the weight (rounded) times the expert's output
(rounded); the sum over the chosen; the residual add. ``geo.degree`` is 1.

The only thing this file knows of the program is the layout of its parameter
tree: ``layers`` a LIST, one tree a run of like layers in model order
(``[W, W, W], [F], [W, W, W], [F]``), every leaf stacked over its run's
layers, matrices stored (in, out), a fused QKV laid out [q|k|v], the expert
stacks (layer, expert, in, out).

``forward`` takes, for the selftest and the chip's controls, ``fault``: one
of ``FAULTS``, the equations with one part wrong (in the program's place, to
see the rule fail it).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import numpy as np

from benchmark.harness.references import dense
from benchmark.harness.references.sdar_moe import _mm, _rmsnorm, _rnd

CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, layers, k) expert indices

#: what ``fault`` may plant: the window ignored in the window layers (they
#: attend their whole causal context); the default rotary table, cos and sin
#: unscaled, in the full layers; the window one key too wide; the selected
#: affinities not renormalised
FAULTS = ("window_ignored", "default_rope_in_full", "window_off_by_one", "not_renormalised")

WINDOW, FULL = "sliding_attention", "full_attention"

#: queries whose scores a head holds at once
Q_BLOCK = 512


@dataclass(frozen=True)
class Rope:
    """One ``rope_parameters`` section, hashable."""

    rope_type: str
    theta: float
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @staticmethod
    def of(section: dict) -> "Rope":
        return Rope(
            rope_type=section.get("rope_type", "default"), theta=float(section["rope_theta"]),
            factor=float(section.get("factor", 1.0)),
            original=int(section.get("original_max_position_embeddings", 0)),
            beta_fast=float(section.get("beta_fast", 32.0)), beta_slow=float(section.get("beta_slow", 1.0)),
            attention_factor=section.get("attention_factor"),
        )


@dataclass(frozen=True)
class Geometry:
    dense: dense.Geometry
    kinds: Tuple[str, ...]
    window: int
    ropes: Tuple[Tuple[str, Rope], ...]
    experts: int
    top_k: int
    normalize: bool

    def rope(self, kind: str) -> Rope:
        return dict(self.ropes)[kind]


def geometry(attrs: dict, degree: int) -> Geometry:
    if degree != 1:
        raise ValueError("the mellum reference is written for tp_degree 1")
    kinds = tuple(attrs["layer_types"])
    if len(kinds) != attrs["num_hidden_layers"] or set(kinds) - {WINDOW, FULL}:
        raise ValueError(f"layer_types {kinds}: one of {WINDOW!r}, {FULL!r} a layer")
    base = dataclasses.replace(dense.Geometry.from_config(attrs, degree), qk_norm=True)
    return Geometry(
        base, kinds=kinds, window=int(attrs["sliding_window"]),
        ropes=tuple((kind, Rope.of(attrs["rope_parameters"][kind])) for kind in sorted(set(kinds))),
        experts=attrs["num_experts"], top_k=attrs["num_experts_per_tok"],
        normalize=bool(attrs.get("norm_topk_prob", True)),
    )


def _yarn_inv_freq(rope: Rope, d: int) -> np.ndarray:
    """YaRN's blend, float64: dimension pair ``i`` turns ``original * inv_i /
    2 pi`` times over the original context; pairs that turn more than
    ``beta_fast`` times keep their frequency (extrapolation), pairs that turn
    fewer than ``beta_slow`` times are slowed by ``factor`` (interpolation),
    and a linear ramp over the pair index joins the two."""
    i = np.arange(0, d, 2, dtype=np.float64)
    extrapolated = rope.theta ** (-i / d)
    interpolated = extrapolated / rope.factor

    def pair_that_turns(rotations):  # the (real) pair index that turns so often over the original context
        return d * math.log(rope.original / (rotations * 2 * math.pi)) / (2 * math.log(rope.theta))

    low = max(math.floor(pair_that_turns(rope.beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(rope.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def rotary_table(rope: Rope, d: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (d / 2,) float32, the factor on cos and sin)."""
    if rope.rope_type == "default":
        return (rope.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32), 1.0
    if rope.rope_type != "yarn":
        raise ValueError(f"rope_type {rope.rope_type!r}: the default table and YaRN's are written")
    factor = rope.attention_factor
    if factor is None:
        factor = 0.1 * math.log(rope.factor) + 1.0 if rope.factor > 1 else 1.0
    return _yarn_inv_freq(rope, d).astype(np.float32), float(factor)


def runs_of(kinds: Sequence[str]):
    """``kinds`` cut into runs of like layers: [(kind, length), ...]."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def layer_weights(params: dict, i: int, geo: Geometry) -> dict:
    """Layer ``i`` of the served tree as plain named matrices (as stored): the
    attention half through ``dense.layer_weights`` (the expert stacks stand
    where it looks for the dense MLP, so ``gate`` / ``up`` / ``down`` come out
    (E, in, out))."""
    # the run of like layers that holds layer i, and i's index in it
    for (_, count), layers in zip(runs_of(geo.kinds), params["layers"]):
        if i < count:
            break
        i -= count
    layers = dict(layers)
    mlp = layers["mlp"]
    layers["mlp"] = mlp["experts"]
    out = dense.layer_weights({"layers": layers}, i, geo.dense)
    out["router"] = mlp["router"]["weight"][i]
    return out


def _rotary(x, positions, inv_freq, factor, rounding):
    """x: (S, heads, d). HF rotate-half: pairs are (i, i + d/2)."""
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = rnd(factor * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :])
    sin = rnd(factor * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :])
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return rnd(rnd(x * cos) + rnd(rot * sin))


def _attention(h, w, geo: Geometry, kind: str, rounding, fault):
    """The attention sublayer of a layer of ``kind`` on one sequence; h: (S, hidden) float32."""
    import jax
    import jax.numpy as jnp

    g = geo.dense
    rnd = lambda a: _rnd(a, rounding)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rmsnorm(h, w["ln1"], g.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(S, g.heads, g.head_dim)
    k = _mm(x, w["k"], rounding).reshape(S, g.kv_heads, g.head_dim)
    v = _mm(x, w["v"], rounding).reshape(S, g.kv_heads, g.head_dim)
    q = _rmsnorm(q, w["q_norm"], g.rms_eps, rounding)
    k = _rmsnorm(k, w["k_norm"], g.rms_eps, rounding)
    rope = geo.rope(kind)
    if kind == FULL and fault == "default_rope_in_full":
        rope = dataclasses.replace(rope, rope_type="default")
    inv_freq, factor = rotary_table(rope, g.head_dim)
    q, k = (_rotary(a, pos, inv_freq, factor, rounding) for a in (q, k))
    window = None
    if kind == WINDOW and fault != "window_ignored":
        window = geo.window + (1 if fault == "window_off_by_one" else 0)
    # queries in blocks: (blocks, Q_BLOCK, heads, d), the last block padded
    blocks = -(-S // Q_BLOCK)
    pad = blocks * Q_BLOCK - S
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(blocks, Q_BLOCK, g.heads, g.head_dim)
    q_pos = jnp.pad(pos, (0, pad), constant_values=S - 1).reshape(blocks, Q_BLOCK)
    group = g.heads // g.kv_heads
    scale = 1.0 / np.sqrt(g.head_dim)

    def one_head(args):
        qh, kh, vh = args  # (blocks, Q_BLOCK, d), (S, d), (S, d)

        def one_block(qp):
            qb, at = qp  # (Q_BLOCK, d), (Q_BLOCK,)
            seen = pos[None, :] <= at[:, None]  # the DENSE mask of these queries
            if window is not None:
                seen = seen & (pos[None, :] > at[:, None] - window)
            scores = jnp.where(seen, _mm(qb, kh.T, rounding) * scale, -jnp.inf)
            return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

        return jax.lax.map(one_block, (qh, q_pos))

    heads_first = lambda a: jnp.moveaxis(a, -2, 0)
    attn = jax.lax.map(one_head, (
        heads_first(q_blocks), heads_first(jnp.repeat(k, group, axis=1)),
        heads_first(jnp.repeat(v, group, axis=1))))  # (heads, blocks, Q_BLOCK, d)
    attn = jnp.moveaxis(attn, 0, 2).reshape(blocks * Q_BLOCK, g.heads * g.head_dim)[:S]
    return rnd(h + _mm(attn, w["o"], rounding))


def _experts(h, w, geo: Geometry, rounding, follow, fault):
    """The expert sublayer: (h after it, the router's scores (S, E), the selection (S, k))."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    x = _rmsnorm(h, w["ln2"], geo.dense.rms_eps, rounding)
    # the router, float32: x lies on the rounding's grid, so at the ambient ("highest") precision
    # these are the twin's exact products, accumulated in float32 and not rounded
    scores = jax.nn.softmax(x @ _rnd(w["router"].astype(jnp.float32), rounding), axis=-1)
    chosen = jax.lax.top_k(scores, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    taken = jnp.take_along_axis(scores, chosen, axis=1)
    if geo.normalize and fault != "not_renormalised":
        taken = taken / jnp.sum(taken, axis=-1, keepdims=True)
    # (S, E) weights, zero outside the selection
    weights = jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], chosen].set(taken)

    def expert(acc, gudw):  # one expert for every token, weighted by its column; expert after expert
        gate, up, down, col = gudw
        act = rnd(rnd(jax.nn.silu(_mm(x, gate, rounding))) * _mm(x, up, rounding))
        return acc + rnd(rnd(col)[:, None] * _mm(act, down, rounding)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), (w["gate"], w["up"], w["down"], weights.T))
    return rnd(h + rnd(routed)), scores, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    def layer(h, w, follow, kind):
        return _experts(_attention(h, w, geo, kind, rounding, fault), w, geo, rounding, follow, fault)

    g = geo.dense
    head = jax.jit(lambda h, norm, wgt: _mm(_rmsnorm(h, norm, g.rms_eps, rounding), wgt, rounding))
    take = jax.jit(lambda p, i: layer_weights(p, i, geo), static_argnums=1)
    return jax.jit(layer, static_argnames=("kind",)), take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None):
    """(logits (len(positions), vocab) float32, the routers' scores (L, S, E)
    float64, the selection (L, S, k)) of one sequence from a full pass: the
    selection is ``choices[NAME]`` (S, L, k) where given, else each layer's
    own top-k."""
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
        for i, kind in enumerate(geo.kinds):
            h, s, c = layer(h, take(params, i), None if follow is None else follow[:, i], kind=kind)
            # one layer's sliced weights at a time: wait before the next is sliced
            scores.append(np.asarray(s, np.float64))
            chosen.append(np.asarray(c))
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return np.asarray(logits, np.float32)[:, : geo.dense.vocab], np.stack(scores), np.stack(chosen)


def reference_logits(params, geo, tokens, positions, choices=None, rounding=None,
                     fault=None) -> np.ndarray:
    return forward(params, geo, tokens, positions, choices, rounding, fault)[0]


def twin_logits(params, geo, tokens, positions, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return forward(params, geo, tokens, positions, choices, jnp.bfloat16)[0]


def choice_margins(params, geo, tokens, choices):
    """Per expert layer, on the replayed path: (regret, score_floor,
    differing): how far the weakest expert taken lies under the strongest
    left out, in the float32 router's scores; the floor max |twin's score -
    float32's|; the tokens whose selection is not float32's own."""
    import jax.numpy as jnp

    _, s32, _ = forward(params, geo, tokens, [0], choices)
    _, s16, _ = forward(params, geo, tokens, [0], choices, jnp.bfloat16)
    sel = np.transpose(np.asarray(choices[NAME]), (1, 0, 2))  # (L, S, k)
    taken = np.take_along_axis(s32, sel, axis=2)
    rest = s32.copy()
    np.put_along_axis(rest, sel, -np.inf, axis=2)
    short = np.maximum(rest.max(axis=2) - taken.min(axis=2), 0.0)
    return short.max(axis=1), np.abs(s16 - s32).max(axis=(1, 2)), (short > 0).sum(axis=1)
