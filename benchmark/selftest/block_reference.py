"""A plain reference of a decoder whose step fills a BLOCK of positions (the
attention of ``references/dense.py`` under a block-causal mask, generated
block by block from mask tokens), kept as a SELFTEST FIXTURE: the benchmark
has no such configuration, and ``harness/references/`` holds only references
of configurations it has. The selftests inject it by monkeypatching
``correct.load_reference``. Its MLP is the dense one, or, where the
configuration has experts, ``expert_reference.py``'s top-k expert sublayer.

It has the interface of a reference module that plans passes
(``harness/correct.py``, "A model whose step is a block"), so it is also the
worked example of one:

    PASSES = True
    CHOICES                      set True by the tests that replay the expert variant
    geometry(attrs, degree)
    probe_budget(geo)            two blocks
    probe_passes(geo, prompt, following, revealed_at=None) -> (prefill_len, passes)
    reference_logits(params, geo, prompt, passes, choices=None)   (1 + reads, vocab)
    twin_logits(params, geo, prompt, passes, choices=None)
    choice_margins(params, geo, prompt, passes, choices)          expert layers, then the reveal

The model. With block length B, position i sees position j iff
``j // B <= i // B``: causal between blocks, both ways inside one, in the
prompt too. Generation, block by block: the prompt's whole blocks are
prefilled (``prefill_len = len(prompt) // B * B``) and what is left of it
opens the first generated block; a block's other positions start as the
mask token. A DENOISE pass runs the B positions against the blocks before
them and predicts, at every masked position, a token (the argmax AT that
position) and a confidence (its softmax probability); the schedule reveals
the ``ceil(B / steps)`` most confident (all that is left, in a block's last
denoise pass). When no mask is left a COMMIT pass runs the block once more,
and only that pass's K and V stay for later blocks.

The plan. A pass is ``{"ids", "positions", "read", "chosen", "kind"}``
(``kind`` is this module's own key, and ``by`` on a denoise pass: whether
its reveal followed a confidence or the seed's order). A denoise pass reads every position
still masked, ``chosen`` the token revealed there by THIS pass or -1; a
commit pass reads its last position (so that the last block's commit is
held too), ``chosen`` -1. Only blocks whose tokens are all known (prompt
and ``following``) are planned. Without ``revealed_at`` (the long prompt)
the order is made from the tokens: a block's generated positions by token
id, ``ceil(B / steps)`` a pass.

The reference runs every pass as ONE full forward over the committed tokens
and the pass's ids, with no cache: plain, and cheap at the sizes a selftest
runs (a reference at a cell's size shares the committed tokens' work
between passes). ``run_rows`` is the layer stack on a cache of K and V that
it writes before it attends; the reference hands it an empty cache as wide
as the sequence, the selftests' stand-in served model a cache that lives
from pass to pass (``placement="served"``: roundings placed as another sound
bf16 implementation might; ``fault="causal_in_block"``: the mask of an
autoregressive model; ``write=False``: a pass whose K and V are not kept).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional, Sequence

import numpy as np

from benchmark.harness.references import dense
from benchmark.selftest import expert_reference

PASSES = True
CHOICES = False
NAME = expert_reference.NAME  # the key of the choices dict: (tokens, layers, k) expert indices


@dataclass(frozen=True)
class Geometry:
    base: object  # dense.Geometry, or expert_reference.Geometry where the MLP is an expert layer
    block: int
    steps: int
    mask_id: int

    @property
    def dense(self) -> dense.Geometry:
        return getattr(self.base, "dense", self.base)

    @property
    def experts(self) -> int:
        return getattr(self.base, "experts", 0)

    @property
    def per_pass(self) -> int:
        return -(-self.block // self.steps)


def geometry(attrs: dict, degree: int) -> Geometry:
    moe = attrs.get("num_local_experts") or attrs.get("num_experts")
    base = expert_reference.geometry(attrs, degree) if moe else dense.geometry(attrs, degree)
    return Geometry(base, block=attrs["block_length"], steps=attrs["denoise_steps"],
                    mask_id=attrs["mask_token_id"])


def probe_budget(geo: Geometry) -> int:
    return 2 * geo.block


def seeded_order(geo: Geometry, left: int, following: Sequence[int]) -> List[int]:
    """``revealed_at`` made from the tokens alone: the generated positions of
    a block (the first has ``left`` prompt tokens before them) in the order
    of their token ids, ``per_pass`` a pass."""
    out, start, room = [], 0, geo.block - left
    while start < len(following):
        tokens = list(following[start : start + room])
        rank = np.argsort(np.argsort(tokens, kind="stable"), kind="stable")
        out += [int(r) // geo.per_pass for r in rank]
        start, room = start + room, geo.block
    return out


def probe_passes(geo: Geometry, prompt, following, revealed_at=None):
    B = geo.block
    prefill_len = len(prompt) // B * B
    left = len(prompt) - prefill_len
    by = "seed" if revealed_at is None else "confidence"
    if revealed_at is None:
        revealed_at = seeded_order(geo, left, following)
    known = [int(t) for t in prompt[prefill_len:]] + [int(t) for t in following]
    when = [-1] * left + [int(k) for k in revealed_at]  # -1: there before the first pass
    passes = []
    for start in range(0, len(known) - B + 1, B):
        final, at = known[start : start + B], when[start : start + B]
        positions = [prefill_len + start + j for j in range(B)]
        for k in range(max(at) + 1):
            read = [j for j in range(B) if at[j] >= k]
            passes.append({"ids": [final[j] if at[j] < k else geo.mask_id for j in range(B)],
                           "positions": positions, "read": read,
                           "chosen": [final[j] if at[j] == k else -1 for j in read],
                           "kind": "denoise", "by": by})
        passes.append({"ids": final, "positions": positions, "read": [B - 1], "chosen": [-1],
                       "kind": "commit"})
    return prefill_len, passes


def _layer(h, w, geo: Geometry, rounding, positions, ck, cv, visible, follow, placement, write):
    """One layer on the rows ``h`` (n, H) at ``positions``: their K and V are
    written into the cache (W, kv heads, D) first (unless ``write`` is off),
    then they attend over the cache where ``visible`` (n, W). Returns
    (h, ck, cv, scores, selection), the last two None for a dense MLP."""
    import jax
    import jax.numpy as jnp

    g = geo.dense
    rnd = lambda a: dense._rnd(a, rounding)
    served = placement == "served"
    n = h.shape[0]
    x = dense._rmsnorm(h, w["ln1"], g.rms_eps, rounding)
    q = dense._mm(x, w["q"], rounding).reshape(n, g.heads, g.head_dim)
    k = dense._mm(x, w["k"], rounding).reshape(n, g.kv_heads, g.head_dim)
    v = dense._mm(x, w["v"], rounding).reshape(n, g.kv_heads, g.head_dim)
    if g.qk_norm:
        q = dense._rmsnorm(q, w["q_norm"], g.rms_eps, rounding)
        k = dense._rmsnorm(k, w["k_norm"], g.rms_eps, rounding)
    q = dense._rope(q, positions, g.rope_theta, rounding)
    k = dense._rope(k, positions, g.rope_theta, rounding)
    if write:
        ck, cv = ck.at[positions].set(k), cv.at[positions].set(v)
    group = g.heads // g.kv_heads
    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(visible, dense._mm(qh, kh.T, rounding) / np.sqrt(g.head_dim), -jnp.inf)
        return dense._mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(jnp.repeat(ck, group, axis=1)),
                                              heads_first(jnp.repeat(cv, group, axis=1)))))
    h = rnd(h + dense._row_parallel(attn.reshape(n, g.heads * g.head_dim), w["o"], rounding, g.degree))
    if geo.experts:
        h, scores, chosen = expert_reference._experts(h, w, geo.base, rounding, follow, None, placement, None)
        return h, ck, cv, scores, chosen
    if served:  # one rounding in the norm, one in the gated product
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        x = rnd(h * jnp.reciprocal(jnp.sqrt(var + g.rms_eps)) * w["ln2"].astype(jnp.float32))
        act = rnd(jax.nn.silu(dense._mm(x, w["gate"], rounding)) * dense._mm(x, w["up"], rounding))
    else:
        x = dense._rmsnorm(h, w["ln2"], g.rms_eps, rounding)
        act = rnd(rnd(jax.nn.silu(dense._mm(x, w["gate"], rounding))) * dense._mm(x, w["up"], rounding))
    return rnd(h + dense._row_parallel(act, w["down"], rounding, g.degree)), ck, cv, None, None


@lru_cache(maxsize=None)
def _program(geo: Geometry, rounding, placement: str, fault: Optional[str]):
    """The jitted layer stack and head (``run_rows``'s docstring)."""
    import jax
    import jax.numpy as jnp

    g = geo.dense
    weights = (lambda p, i: expert_reference.layer_weights(p, i, geo.base)) if geo.experts else (
        lambda p, i: dense.layer_weights(p, i, geo.base))

    def fn(params, ids, positions, last, ck, cv, follow, rows, write):
        cols = jnp.arange(ck.shape[1])
        if fault == "causal_in_block":
            visible = cols[None, :] <= positions[:, None]
        else:
            visible = (cols[None, :] // geo.block <= positions[:, None] // geo.block) & (cols[None, :] <= last)
        h = dense._rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        new_k, new_v, scores, chosen = [], [], [], []
        for i in range(g.layers):
            h, k, v, s, c = _layer(h, weights(params, i), geo, rounding, positions, ck[i], cv[i], visible,
                                   None if follow is None else follow[:, i], placement, write)
            new_k.append(k), new_v.append(v), scores.append(s), chosen.append(c)
        top = dense._rmsnorm(jnp.take(h, rows, axis=0), params["norm"]["weight"], g.rms_eps, rounding)
        logits = dense._mm(top, params["lm_head"]["weight"], rounding)[:, : g.vocab]
        moe = (jnp.stack(scores), jnp.stack(chosen)) if geo.experts else (None, None)
        return logits, jnp.stack(new_k), jnp.stack(new_v), moe[0], moe[1]

    return jax.jit(fn, static_argnames=("write",))


def empty_cache(geo: Geometry, width: int):
    import jax.numpy as jnp

    g = geo.dense
    return tuple(jnp.zeros((g.layers, width, g.kv_heads, g.head_dim), jnp.float32) for _ in range(2))


def run_rows(params, geo: Geometry, ids, positions, last: int, cache, rows, follow=None,
             rounding=None, placement: str = "reference", fault: Optional[str] = None,
             write: bool = True):
    """The layer stack on ``ids`` at ``positions`` against ``cache`` (K, V:
    (L, W, kv heads, D)): position j of the cache is seen from i iff
    ``j // B <= i // B`` and ``j <= last``. Returns (logits at ``rows`` of
    the pass, float32; the cache after the pass; scores (L, n, E) and
    selection (L, n, k) of an expert MLP, else None, None)."""
    import jax
    import jax.numpy as jnp

    as_ints = lambda a: jnp.asarray(np.asarray(a, np.int32))
    with jax.default_matmul_precision("highest"):
        logits, ck, cv, scores, chosen = _program(geo, rounding, placement, fault)(
            params, as_ints(ids), as_ints(positions), jnp.int32(last), cache[0], cache[1],
            None if follow is None else as_ints(follow), as_ints(rows), write=write)
    return np.asarray(logits, np.float32), (ck, cv), scores, chosen


def replay(params, geo: Geometry, prompt, passes, choices=None, rounding=None) -> dict:
    """Every planned pass as one full forward over the committed tokens and
    the pass's ids. ``logits`` (1 + reads, V) as ``correct.judge`` compares
    them; ``block_logits`` per pass (q, V); with an expert MLP ``scores``
    (L, tokens, E), the tokens in the order of the choices' first axis (the
    prompt's prefilled tokens, then every pass's)."""
    prefill_len = passes[0]["positions"][0]
    committed = [int(t) for t in prompt[:prefill_len]]
    sel = None if choices is None else np.asarray(choices[NAME], np.int32)
    committed_sel, offset = (None if sel is None else sel[:prefill_len]), prefill_len
    logits, block_logits, scores = [], [], []
    for k, p in enumerate(passes):
        q, n = len(p["ids"]), len(committed)
        assert list(p["positions"]) == list(range(n, n + q)), "a pass fills the block after the committed ones"
        tokens = committed + [int(t) for t in p["ids"]]
        pad = -len(tokens) % 64  # few shapes; the padding lies in later blocks and past ``last``
        follow = None
        if sel is not None:
            mine = sel[offset : offset + q]
            follow = np.concatenate([committed_sel, mine, np.zeros((pad,) + mine.shape[1:], np.int32)])
        rows = ([prefill_len - 1] if k == 0 else []) + list(range(n, n + q))
        out, _, s, _ = run_rows(params, geo, tokens + [0] * pad, range(len(tokens) + pad), len(tokens) - 1,
                                empty_cache(geo, len(tokens) + pad), rows, follow, rounding)
        if k == 0:
            logits.append(out[0])
            out = out[1:]
            if s is not None:
                scores.append(np.asarray(s[:, :prefill_len], np.float64))
        block_logits.append(out)
        logits.extend(out[p["read"]])
        if s is not None:
            scores.append(np.asarray(s[:, n : n + q], np.float64))
        if p["kind"] == "commit":
            committed = tokens
            if sel is not None:
                committed_sel = np.concatenate([committed_sel, mine])
        offset += q
    return {"logits": np.stack(logits), "block_logits": block_logits,
            "scores": np.concatenate(scores, axis=1) if scores else None}


def reference_logits(params, geo, prompt, passes, choices=None, rounding=None) -> np.ndarray:
    return replay(params, geo, prompt, passes, choices, rounding)["logits"]


def twin_logits(params, geo, prompt, passes, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return replay(params, geo, prompt, passes, choices, jnp.bfloat16)["logits"]


def confidence(logits: np.ndarray) -> np.ndarray:
    """The largest softmax probability of each row, in float64."""
    z = np.asarray(logits, np.float64)
    z = np.exp(z - z.max(axis=-1, keepdims=True))
    return (z / z.sum(axis=-1, keepdims=True)).max(axis=-1)


def choice_margins(params, geo, prompt, passes, choices):
    """(regret, score_floor, differing), one entry per expert layer on the
    replayed path (``expert_reference.choice_margins``'s) and, appended, one
    for the REVEAL: over the denoise passes of a session (an order made from
    the seed follows no confidence and has no regret), how far the float32
    confidence of the least confident position revealed lies under the most
    confident one left masked; the floor is max |twin's confidence -
    float32's| over every masked position of every denoise pass."""
    import jax.numpy as jnp

    r32 = replay(params, geo, prompt, passes, choices)
    r16 = replay(params, geo, prompt, passes, choices, jnp.bfloat16)
    regret, floor, differing = [], [], []
    if geo.experts:
        s32, s16 = r32["scores"], r16["scores"]
        sel = np.transpose(np.asarray(choices[NAME]), (1, 0, 2))  # (L, tokens, k)
        taken = np.take_along_axis(s32, sel, axis=2)
        rest = s32.copy()
        np.put_along_axis(rest, sel, -np.inf, axis=2)
        short = np.maximum(rest.max(axis=2) - taken.min(axis=2), 0.0)
        regret, floor = list(short.max(axis=1)), list(np.abs(s16 - s32).max(axis=(1, 2)))
        differing = list((short > 0).sum(axis=1))
    short, off = [0.0], [0.0]
    for p, l32, l16 in zip(passes, r32["block_logits"], r16["block_logits"]):
        if p["kind"] != "denoise":
            continue
        c32, c16 = confidence(l32[p["read"]]), confidence(l16[p["read"]])
        off.append(float(np.abs(c16 - c32).max()))
        revealed = np.asarray(p["chosen"]) >= 0
        if p["by"] == "confidence" and revealed.any() and not revealed.all():
            short.append(max(0.0, float(c32[~revealed].max() - c32[revealed].min())))
    return (np.asarray(regret + [max(short)]), np.asarray(floor + [max(off)]),
            np.asarray(differing + [sum(s > 0 for s in short)]))
