"""A plain reference of a decoder whose MLP is a top-k softmax-router expert
layer (the Mixtral block: the Llama attention of ``references/dense.py``,
then per token ``sum_k a_k * expert_{e_k}(x)``), kept as a SELFTEST FIXTURE:
the benchmark has no expert configuration, and ``harness/references/`` holds
only references of configurations it has. The selftests inject it by
monkeypatching ``correct.load_reference``.

It has the interface of a reference module that replays choices
(``harness/correct.py``, "A model that chooses"), so it is also the worked
example of one:

    CHOICES                      set True by the tests that replay
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

The expert sublayer, per token, after ``x = rmsnorm(h, ln2)``:

    scores = softmax(x W_router)          float32 in the twin too (the program's router_dtype)
    e_1..e_k = top-k of scores            or, replaying, the served selection
    a_k    = scores[e_k]                  divided by their sum where the config normalises
    h      = h + sum_k a_k * (silu(x Wg[e_k]) * (x Wu[e_k])) Wd[e_k]

The selection score is ``scores``. The twin rounds where ``dense.py``'s does
and besides: the affinity, each expert's three products, ``silu(g)``,
``silu(g) * u``, each weighted expert output, their sum. Every expert is
computed for every token and the chosen ones picked: plain, and cheap at
the sizes a selftest runs.

Beyond the interface, for the selftests' stand-in served model and its
faults: ``forward`` takes ``placement="served"`` (the same equations with
the roundings placed as another sound bf16 implementation might: one
rounding in a norm, one in the gated product, the affinity unrounded) and
``fault`` (``wrong_expert``: layer 1 computes with expert e + 1 while
reporting e; ``no_affinity``: the expert output is not weighted), and
``swap = (mask (S, L) bool, expert (S, L))``: where the mask is set, the
layer's first choice is replaced by the given expert, computed with and
reported (a router that sometimes picks at random).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from benchmark.harness.references import dense

CHOICES = False
NAME = "experts"  # the key of the choices dict: (tokens, layers, k) expert indices


@dataclass(frozen=True)
class Geometry:
    dense: dense.Geometry
    experts: int
    top_k: int
    normalize: bool


def geometry(attrs: dict, degree: int) -> Geometry:
    return Geometry(dense.Geometry.from_config(attrs, degree),
                    experts=attrs.get("num_local_experts") or attrs["num_experts"],
                    top_k=attrs.get("num_experts_per_tok", 2),
                    normalize=bool(attrs.get("norm_topk_prob", True)))


def layer_weights(params: dict, i, geo: Geometry) -> dict:
    """Layer ``i`` as plain named matrices: the attention half through
    ``dense.layer_weights`` (the expert stacks stand where it looks for the
    dense MLP, so ``gate``/``up``/``down`` come out (E, in, out))."""
    layers = dict(params["layers"])
    mlp = layers["mlp"]
    layers["mlp"] = mlp["experts"]
    out = dense.layer_weights({"layers": layers}, i, geo.dense)
    out["router"] = mlp["router"]["weight"][i]
    return out


def _attention(h, w, g: dense.Geometry, rounding):
    """``dense._layer``'s attention half: h + attention(rmsnorm(h)) Wo."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: dense._rnd(a, rounding)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = dense._rmsnorm(h, w["ln1"], g.rms_eps, rounding)
    q = dense._mm(x, w["q"], rounding).reshape(S, g.heads, g.head_dim)
    k = dense._mm(x, w["k"], rounding).reshape(S, g.kv_heads, g.head_dim)
    v = dense._mm(x, w["v"], rounding).reshape(S, g.kv_heads, g.head_dim)
    q, k = dense._rope(q, pos, g.rope_theta, rounding), dense._rope(k, pos, g.rope_theta, rounding)
    k = jnp.repeat(k, g.heads // g.kv_heads, axis=1)
    v = jnp.repeat(v, g.heads // g.kv_heads, axis=1)
    causal = pos[:, None] >= pos[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, dense._mm(qh, kh.T, rounding) / np.sqrt(g.head_dim), -jnp.inf)
        return dense._mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))
    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v))))
    return rnd(h + dense._row_parallel(attn.reshape(S, g.heads * g.head_dim), w["o"], rounding, g.degree))


def _experts(h, w, geo: Geometry, rounding, follow, swap, placement, fault):
    """(h after the expert sublayer, scores (S, E), selection (S, k))."""
    import jax
    import jax.numpy as jnp

    g = geo.dense
    rnd = lambda a: dense._rnd(a, rounding)
    served = placement == "served"
    if served:  # one rounding in the norm
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        x = rnd(h * jnp.reciprocal(jnp.sqrt(var + g.rms_eps)) * w["ln2"].astype(jnp.float32))
    else:
        x = dense._rmsnorm(h, w["ln2"], g.rms_eps, rounding)
    scores = jax.nn.softmax(x @ w["router"].astype(jnp.float32), axis=-1)
    chosen = jax.lax.top_k(scores, geo.top_k)[1] if follow is None else follow
    if swap is not None:
        chosen = chosen.at[:, 0].set(jnp.where(swap[0], swap[1], chosen[:, 0]))
    aff = jnp.take_along_axis(scores, chosen, axis=1)
    if geo.normalize:
        aff = aff / jnp.sum(aff, axis=-1, keepdims=True)

    def expert(gate, up, down):
        a, b = dense._mm(x, gate, rounding), dense._mm(x, up, rounding)
        act = rnd(jax.nn.silu(a) * b) if served else rnd(rnd(jax.nn.silu(a)) * b)
        return dense._mm(act, down, rounding)

    every = jax.vmap(expert)(w["gate"], w["up"], w["down"])  # (E, S, H)
    used = (chosen + 1) % geo.experts if fault == "wrong_expert" else chosen
    picked = every[used, jnp.arange(h.shape[0])[:, None]]  # (S, k, H)
    if fault != "no_affinity":
        picked = rnd(picked * (aff if served else rnd(aff))[..., None])
    return rnd(h + rnd(jnp.sum(picked, axis=1))), scores, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, placement, fault):
    """The jitted layer (sound, and with the fault where one is planted) and head."""
    import jax

    def layer(fault):
        def fn(h, w, follow=None, swap=None):
            h = _attention(h, w, geo.dense, rounding)
            return _experts(h, w, geo, rounding, follow, swap, placement, fault)
        return jax.jit(fn)

    take = jax.jit(lambda p, i: layer_weights(p, i, geo))
    head = jax.jit(lambda h, norm, wgt: dense._mm(
        dense._rmsnorm(h, norm, geo.dense.rms_eps, rounding), wgt, rounding))
    return layer(None), layer(fault), take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, placement: str = "reference",
            fault: Optional[str] = None, swap=None):
    """(logits (len(positions), vocab) float32, scores (L, S, E), selection
    (L, S, k)) of one sequence: the selection is ``choices[NAME]`` laid
    (S, L, k) where given, else each layer's own top-k."""
    import jax
    import jax.numpy as jnp

    sound, faulty, take, head = _programs(geo, rounding, placement, fault)
    follow = None if choices is None else jnp.asarray(np.asarray(choices[NAME], np.int32))
    scores, chosen = [], []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = dense._rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        for i in range(geo.dense.layers):
            # wrong_expert: one layer's fault; no_affinity: every layer's
            layer = faulty if fault and (i == 1 or fault == "no_affinity") else sound
            h, s, c = layer(h, take(params, i), None if follow is None else follow[:, i],
                            None if swap is None else (jnp.asarray(swap[0][:, i]), jnp.asarray(swap[1][:, i])))
            scores.append(s)
            chosen.append(c)
        rows = jnp.take(h, jnp.asarray(np.asarray(positions, np.int32)), axis=0)
        logits = head(rows, params["norm"]["weight"], params["lm_head"]["weight"])
    return (np.asarray(logits, np.float32)[:, : geo.dense.vocab],
            np.asarray(jnp.stack(scores), np.float64), np.asarray(jnp.stack(chosen)))


def reference_logits(params, geo, tokens, positions, choices=None, rounding=None) -> np.ndarray:
    return forward(params, geo, tokens, positions, choices, rounding)[0]


def twin_logits(params, geo, tokens, positions, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return forward(params, geo, tokens, positions, choices, jnp.bfloat16)[0]


def choice_margins(params, geo, tokens, choices):
    """Per layer, on the replayed path: (regret, score_floor, differing)."""
    import jax.numpy as jnp

    _, s32, _ = forward(params, geo, tokens, [0], choices)
    _, s16, _ = forward(params, geo, tokens, [0], choices, jnp.bfloat16)
    sel = np.transpose(np.asarray(choices[NAME]), (1, 0, 2))  # (L, S, k)
    taken = np.take_along_axis(s32, sel, axis=2)
    rest = s32.copy()
    np.put_along_axis(rest, sel, -np.inf, axis=2)
    # how far the weakest expert taken lies below the strongest one left out
    short = np.maximum(rest.max(axis=2) - taken.min(axis=2), 0.0)  # (L, S)
    return short.max(axis=1), np.abs(s16 - s32).max(axis=(1, 2)), (short > 0).sum(axis=1)
