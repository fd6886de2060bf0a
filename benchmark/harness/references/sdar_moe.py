"""The plain reference of an SDAR-MoE (``model_type: "sdar_moe"``) decoder:
the Qwen3-MoE block (``references/dense.py``'s attention with per-head q/k
RMSNorm, then a top-k softmax-router expert sublayer) under a BLOCK-CAUSAL
mask, generated block by block from mask tokens — in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernel, no paging, no
batching, no line of the program's code — and its bf16 TWIN.

The interface is that of a reference that plans passes and replays choices
(``harness/correct.py``, "A model whose step is a block" and "A model that
chooses"):

    PASSES = True, CHOICES = True
    geometry(attrs, degree)
    probe_budget(geo)                                   two blocks
    probe_passes(geo, prompt, following, revealed_at=None) -> (prefill_len, passes)
    reference_logits(params, geo, prompt, passes, choices=None, rounding=None)   (1 + reads, vocab)
    twin_logits(params, geo, prompt, passes, choices=None)
    choice_margins(params, geo, prompt, passes, choices)  the expert layers, then the reveal

The model. With block length B, position i sees position j iff
``j // B <= i // B``: causal between blocks, both ways inside one, in the
prompt too. Per layer, on ``x = rmsnorm(h, ln1)``:

    q, k, v = x Wq, x Wk, x Wv;  q, k = rmsnorm over head_dim per head (learned weight), then
    rotate-half rotary on all of a head's dimensions;  a = softmax(q k^T / sqrt(D) + mask) v (GQA)
    h = h + a Wo;  x' = rmsnorm(h, ln2)
    p = softmax(x' W_r) over the experts, float32;  e_1..e_k the k largest (or, replaying, the
    served selection);  a_j = p[e_j] / sum_j p[e_j] (``norm_topk_prob``)
    h = h + sum_j a_j W_down,e_j (silu(W_gate,e_j x') * W_up,e_j x')
    logits = rmsnorm(h, norm) W_head

Generation, block by block: the prompt's whole blocks are prefilled
(``prefill_len = len(prompt) // B * B``), what is left of it opens the
first generated block, a block's other positions start as the mask token. A
DENOISE pass runs the B positions against the blocks before them and
predicts, AT every masked position, a token (the argmax over the
vocabulary without the mask token: a pass never predicts a mask) and a
confidence (that token's softmax probability there); the
``ceil(B / steps)`` most confident are revealed (ties by position; all that
is left in a block's last denoise pass). When no mask is left a COMMIT pass runs the block once more and only that pass's K
and V stay for later blocks.

The plan (``probe_passes``) is ``selftest/block_reference.py``'s: a pass is
``{"ids", "positions", "read", "chosen", "kind"}`` (and ``by`` on a denoise
pass: whether its order followed a confidence or the seed's tokens); a
denoise pass reads every position still masked, a commit pass its last.

Unlike that fixture this module SHARES the committed tokens' K and V between
a row's passes: one cache per layer of the row's K and V (float32 values, on
the twin's grid for the twin), which every pass writes at its positions
before it attends, so the commit pass's stay and a pass costs B positions
and not a full forward. It slices one layer's weights at a time and waits
for the layer before it slices the next (a layer's three expert stacks are
1.2 GB at the cell's size; ``PERF.md`` section 7 has what happens otherwise).
Every expert is computed for every token of a pass, expert after expert,
and the chosen ones are picked: plain.

The twin (``rounding=jnp.bfloat16``) rounds where ``dense.py``'s does (a
rounding is a ``lax.reduce_precision``, as ``granite_hybrid._rnd``'s: the
compiler may not remove it) and besides: the router is FLOAT32 from the
bf16 x' (a product of bf16 operands accumulated in float32 and not
rounded; softmax, top-k and the renormalisation float32), the affinity
rounded once, each expert's three products, ``silu(g)``, ``silu(g) * u``,
each weighted expert output, their sum, the residual add. ``geo.degree`` is
1: the program refuses this model at tp > 1.

``replay`` and ``generate`` take, for the selftest alone, ``fault``: one of
``FAULTS``, the equations with one part wrong (in the program's place, to
see the rule fail it).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional, Sequence

import numpy as np

from benchmark.harness.references import dense

PASSES = True
CHOICES = True
NAME = "experts"  # the key of the choices dict: (tokens, layers, k) expert indices

#: what ``fault`` may plant (selftest): in-block attention causal where it is
#: both ways; the commit pass's K and V not kept (a denoise pass's stay); a
#: pass's logits read one position early; the selected affinities not
#: renormalised; (``generate`` alone) the reveal taking the LEAST confident
FAULTS = ("causal_in_block", "commit_skipped", "read_early", "not_renormalised", "least_confident")


def _rnd(a, rounding):
    """``a`` (float32) rounded to ``rounding``'s grid and held in float32
    again; the float32 reference (``rounding`` None) rounds nowhere. Every
    rounding is arithmetic the compiler may not remove and no conversion to
    a narrow type: bf16 is ``lax.reduce_precision`` (8 exponent bits, 7 of
    mantissa, to nearest even), as ``granite_hybrid._rnd``; fp8-e4m3 (the
    control) keeps 3 bits of mantissa the same way and, under its least
    normal number 2**-6, rounds to its subnormals' step 2**-9 (a float32 ->
    float8_e4m3fn -> float32 pair of converts inside this module's expert
    loop halted the chip with an out-of-range vector load: my chip run, PR
    39; the two agree value for value below the type's largest number)."""
    import jax
    import jax.numpy as jnp

    if rounding is None:
        return a
    if rounding == jnp.bfloat16:
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    if rounding == jnp.float8_e4m3fn:
        normal = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)
        return jnp.where(jnp.abs(a) < 2.0 ** -6, jnp.round(a * 512.0) / 512.0, normal)
    return a.astype(rounding).astype(jnp.float32)


def _mm(a, b, rounding):
    """``a @ b`` as ``dense._mm``: float32 operands at the ambient
    (``highest``) precision for the reference; for the twin operands on
    ``rounding``'s grid (``a`` holds such values already), accumulated in
    float32, the result rounded."""
    import jax.numpy as jnp

    if rounding is None:
        return a @ b.astype(jnp.float32)
    if rounding == jnp.bfloat16:  # the chip's own product: bf16 operands, float32 accumulator
        prod = jnp.matmul(a.astype(rounding), b.astype(rounding), preferred_element_type=jnp.float32)
    else:  # any other grid: its values, multiplied exactly
        prod = a @ _rnd(b.astype(jnp.float32), rounding)
    return _rnd(prod, rounding)


def _rmsnorm(x, w, eps, rounding=None):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = _rnd(x * jnp.reciprocal(jnp.sqrt(var + eps)), rounding)
    return _rnd(normed * w.astype(jnp.float32), rounding)


@dataclass(frozen=True)
class Geometry:
    dense: dense.Geometry
    experts: int
    top_k: int
    normalize: bool
    block: int
    steps: int
    mask_id: int

    @property
    def per_pass(self) -> int:
        return -(-self.block // self.steps)


def geometry(attrs: dict, degree: int) -> Geometry:
    if degree != 1:
        raise ValueError("the sdar_moe reference is written for tp_degree 1")
    base = dataclasses.replace(dense.Geometry.from_config(attrs, degree), qk_norm=True)
    return Geometry(base, experts=attrs["num_experts"], top_k=attrs["num_experts_per_tok"],
                    normalize=bool(attrs.get("norm_topk_prob", True)), block=attrs["block_length"],
                    steps=attrs["denoise_steps"], mask_id=attrs["mask_token_id"])


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


def layer_weights(params: dict, i, geo: Geometry) -> dict:
    """Layer ``i`` of the served tree as plain named matrices (as stored): the
    attention half through ``dense.layer_weights`` (the expert stacks stand
    where it looks for the dense MLP, so ``gate`` / ``up`` / ``down`` come out
    (E, in, out))."""
    layers = dict(params["layers"])
    mlp = layers["mlp"]
    layers["mlp"] = mlp["experts"]
    out = dense.layer_weights({"layers": layers}, i, geo.dense)
    out["router"] = mlp["router"]["weight"][i]
    return out


def _rope(x, positions, theta, rounding):
    """x: (n, heads, D). HF rotate-half: pairs are (i, i + D/2)."""
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = rnd(jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :])
    sin = rnd(jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :])
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return rnd(rnd(x * cos) + rnd(rot * sin))


def _layer(h, w, geo: Geometry, rounding, positions, last, ck, cv, follow, fault, write):
    """One layer on the rows ``h`` (n, H) at ``positions``: their K and V go
    into the row's cache (W, kv heads, D) first (unless ``write`` is off),
    then they attend over the cache: position j is seen from i iff
    ``j // B <= i // B`` and ``j <= last``. Returns (h, ck, cv, the router's
    scores (n, E), the selection (n, k))."""
    import jax
    import jax.numpy as jnp

    g = geo.dense
    rnd = lambda a: _rnd(a, rounding)
    n = h.shape[0]
    x = _rmsnorm(h, w["ln1"], g.rms_eps, rounding)
    q = _mm(x, w["q"], rounding).reshape(n, g.heads, g.head_dim)
    k = _mm(x, w["k"], rounding).reshape(n, g.kv_heads, g.head_dim)
    v = _mm(x, w["v"], rounding).reshape(n, g.kv_heads, g.head_dim)
    q = _rmsnorm(q, w["q_norm"], g.rms_eps, rounding)
    k = _rmsnorm(k, w["k_norm"], g.rms_eps, rounding)
    q, k = _rope(q, positions, g.rope_theta, rounding), _rope(k, positions, g.rope_theta, rounding)
    if write:
        ck, cv = ck.at[positions].set(k), cv.at[positions].set(v)
    cols = jnp.arange(ck.shape[0])
    if fault == "causal_in_block":
        visible = cols[None, :] <= positions[:, None]
    else:
        visible = (cols[None, :] // geo.block <= positions[:, None] // geo.block) & (cols[None, :] <= last)
    group = g.heads // g.kv_heads
    heads_first = lambda a: jnp.transpose(a, (1, 0, 2))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(visible, _mm(qh, kh.T, rounding) / np.sqrt(g.head_dim), -jnp.inf)
        return _mm(rnd(jax.nn.softmax(scores, axis=-1)), vh, rounding)

    attn = heads_first(jax.lax.map(one_head, (heads_first(q), heads_first(jnp.repeat(ck, group, axis=1)),
                                              heads_first(jnp.repeat(cv, group, axis=1)))))
    h = rnd(h + _mm(attn.reshape(n, g.heads * g.head_dim), w["o"], rounding))
    # the expert sublayer
    x = _rmsnorm(h, w["ln2"], g.rms_eps, rounding)
    # the router, float32: x lies on the rounding's grid, so at the ambient ("highest") precision
    # these are the twin's exact products, accumulated in float32 and not rounded
    scores = jax.nn.softmax(x @ _rnd(w["router"].astype(jnp.float32), rounding), axis=-1)
    chosen = jax.lax.top_k(scores, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    aff = jnp.take_along_axis(scores, chosen, axis=1)
    if geo.normalize and fault != "not_renormalised":
        aff = aff / jnp.sum(aff, axis=-1, keepdims=True)

    def expert(gud):  # one expert for every row of the pass; the chosen ones are picked below
        gate, up, down = gud
        act = rnd(rnd(jax.nn.silu(_mm(x, gate, rounding))) * _mm(x, up, rounding))
        return _mm(act, down, rounding)

    every = jax.lax.map(expert, (w["gate"], w["up"], w["down"]))  # (E, n, H), expert after expert
    picked = every[chosen, jnp.arange(n)[:, None]]  # (n, k, H)
    picked = rnd(picked * rnd(aff)[..., None])
    return rnd(h + rnd(jnp.sum(picked, axis=1))), ck, cv, scores, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    def layer(h, w, positions, last, ck, cv, follow, write):
        return _layer(h, w, geo, rounding, positions, last, ck, cv, follow, fault, write)

    g = geo.dense
    head = jax.jit(lambda h, norm, wgt: _mm(_rmsnorm(h, norm, g.rms_eps, rounding), wgt, rounding))
    return (jax.jit(layer, static_argnames=("write",)), jax.jit(lambda p, i: layer_weights(p, i, geo)), head)


class Row:
    """One row's K and V, layer by layer, and the stack that runs a pass
    against them."""

    def __init__(self, params, geo: Geometry, width: int, rounding=None, fault=None):
        import jax.numpy as jnp

        g = geo.dense
        self.params, self.geo, self.rounding, self.fault = params, geo, rounding, fault
        self.layer, self.take, self.head = _programs(geo, rounding, fault)
        width = -(-width // 64) * 64  # few shapes
        zero = lambda: jnp.zeros((width, g.kv_heads, g.head_dim), jnp.float32)
        self.cache = [(zero(), zero()) for _ in range(g.layers)]

    def run(self, ids, positions, rows, follow=None, write: bool = True):
        """(logits (len(rows), V) float32 at ``rows`` of the pass, scores
        (L, n, E) float64, selection (L, n, k)). ``follow`` (n, L, k): the
        selection to take."""
        import jax
        import jax.numpy as jnp

        g = self.geo.dense
        as_ints = lambda a: jnp.asarray(np.asarray(a, np.int32))
        scores, chosen = [], []
        with jax.default_matmul_precision("highest"):
            at = as_ints(positions)
            h = _rnd(jnp.take(self.params["embed_tokens"]["weight"], as_ints(ids), axis=0)
                     .astype(jnp.float32), self.rounding)
            for i in range(g.layers):
                ck, cv = self.cache[i]
                h, ck, cv, s, c = self.layer(
                    h, self.take(self.params, i), at, jnp.int32(max(positions)), ck, cv,
                    None if follow is None else as_ints(follow[:, i]), write=write)
                self.cache[i] = (ck, cv)
                # one layer's sliced weights at a time: wait before the next is sliced
                scores.append(np.asarray(s, np.float64))
                chosen.append(np.asarray(c))
            logits = self.head(jnp.take(h, as_ints(rows), axis=0), self.params["norm"]["weight"],
                               self.params["lm_head"]["weight"])
        return np.asarray(logits, np.float32)[:, : g.vocab], np.stack(scores), np.stack(chosen)

    def run_pass(self, p: dict, follow=None):
        """A planned pass: (block logits (B, V), scores, selection)."""
        write = not (self.fault == "commit_skipped" and p["kind"] == "commit")
        logits, s, c = self.run(p["ids"], p["positions"], range(len(p["ids"])), follow, write)
        if self.fault == "read_early":  # position i answers with what position i - 1 predicts
            logits = np.concatenate([logits[:1], logits[:-1]])
        return logits, s, c


def replay(params, geo: Geometry, prompt, passes, choices=None, rounding=None, fault=None) -> dict:
    """The prompt's whole blocks in one pass, then every planned pass against
    the row's cache. ``logits`` (1 + reads, V) as ``correct.judge`` compares
    them; ``block_logits`` per pass (B, V); ``scores`` (L, tokens, E) and
    ``chosen`` (L, tokens, k), the tokens in the order of the choices' first
    axis (the prefilled tokens, then every pass's)."""
    prefill_len = passes[0]["positions"][0]
    sel = None if choices is None else np.asarray(choices[NAME], np.int32)
    row = Row(params, geo, passes[-1]["positions"][-1] + 1, rounding, fault)
    first, s, c = row.run(prompt[:prefill_len], range(prefill_len), [prefill_len - 1],
                          None if sel is None else sel[:prefill_len])
    logits, block_logits, scores, chosen, offset = [first[0]], [], [s], [c], prefill_len
    for p in passes:
        q = len(p["ids"])
        out, s, c = row.run_pass(p, None if sel is None else sel[offset : offset + q])
        block_logits.append(out)
        logits.extend(out[p["read"]])
        scores.append(s)
        chosen.append(c)
        offset += q
    return {"logits": np.stack(logits), "block_logits": block_logits,
            "scores": np.concatenate(scores, axis=1), "chosen": np.concatenate(chosen, axis=1)}


def reference_logits(params, geo, prompt, passes, choices=None, rounding=None) -> np.ndarray:
    return replay(params, geo, prompt, passes, choices, rounding)["logits"]


def twin_logits(params, geo, prompt, passes, choices=None) -> np.ndarray:
    import jax.numpy as jnp

    return replay(params, geo, prompt, passes, choices, jnp.bfloat16)["logits"]


def predict(logits: np.ndarray, mask_id: int):
    """(token, confidence) of each row: the argmax over the vocabulary
    without the mask token, and its softmax probability there, in float64."""
    z = np.array(logits, np.float64)
    z[..., mask_id] = -np.inf
    z = np.exp(z - z.max(axis=-1, keepdims=True))
    return z.argmax(axis=-1), (z / z.sum(axis=-1, keepdims=True)).max(axis=-1)


def confidence(logits: np.ndarray, mask_id: int) -> np.ndarray:
    return predict(logits, mask_id)[1]


def choice_margins(params, geo, prompt, passes, choices):
    """(regret, score_floor, differing), one entry per expert layer on the
    replayed path (how far the weakest expert taken lies under the strongest
    left out, in the float32 router's scores; the floor max |twin's score -
    float32's|) and, appended, one for the REVEAL: over the denoise passes of
    a session (an order made from the seed follows no confidence and has no
    regret), how far the float32 confidence of the least confident position
    revealed lies under the most confident one left masked; the floor is max
    |twin's confidence - float32's| over every masked position of every
    denoise pass."""
    import jax.numpy as jnp

    r32 = replay(params, geo, prompt, passes, choices)
    r16 = replay(params, geo, prompt, passes, choices, jnp.bfloat16)
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
        c32, c16 = (confidence(l[p["read"]], geo.mask_id) for l in (l32, l16))
        off.append(float(np.abs(c16 - c32).max()))
        revealed = np.asarray(p["chosen"]) >= 0
        if p["by"] == "confidence" and revealed.any() and not revealed.all():
            short.append(max(0.0, float(c32[~revealed].max() - c32[revealed].min())))
    return (np.asarray(regret + [max(short)]), np.asarray(floor + [max(off)]),
            np.asarray(differing + [sum(s > 0 for s in short)]))


def generate(params, geo: Geometry, prompt, budget: int, rounding=None, fault=None):
    """The reference's own generation: (generated tokens, ``revealed_at``,
    per denoise pass the float64 confidences of its masked positions as
    ``(block start, pass ordinal, {position in block: confidence})``),
    greedy, block by block, its own top-k routes."""
    B = geo.block
    prefill_len = len(prompt) // B * B
    blocks = -(-(budget + len(prompt) - prefill_len) // B)
    row = Row(params, geo, prefill_len + blocks * B, rounding, fault)
    if prefill_len:
        row.run(prompt[:prefill_len], range(prefill_len), [prefill_len - 1])
    left = [int(t) for t in prompt[prefill_len:]]
    gen, when, seen, start = [], [], [], prefill_len
    sign = 1 if fault == "least_confident" else -1
    while len(gen) < budget:
        ids = left + [geo.mask_id] * (B - len(left))
        positions, at = list(range(start, start + B)), {}
        masked, k = list(range(len(left), B)), 0
        while masked:
            logits, _, _ = row.run_pass({"ids": ids, "positions": positions, "kind": "denoise"})
            best, conf = predict(logits, geo.mask_id)
            seen.append((start, k, {j: float(conf[j]) for j in masked}))
            for j in sorted(masked, key=lambda j: (sign * conf[j], j))[: geo.per_pass]:
                ids[j], at[j] = int(best[j]), k
                masked.remove(j)
            k += 1
        row.run_pass({"ids": ids, "positions": positions, "kind": "commit"})
        gen += ids[len(left):]
        when += [at[j] for j in range(len(left), B)]
        left, start = [], start + B
    return gen[:budget], when[:budget], seen
