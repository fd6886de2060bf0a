"""The plain reference of a GLM-5-style (``model_type: "glm_moe_dsa"``)
decoder: DeepSeek-V3's stack (``deepseek_mla.py``: multi-head latent
attention in its EXPANDED form, leading dense layers, a sigmoid-scored top-k
expert layer with a selection bias and a shared expert) with DeepSeek-V3.2's
learned sparse attention: in every layer an INDEXER scores each earlier
token for each query and the attention runs over the ``index_topk`` tokens
of largest score alone -- in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, full causal scores, a top-k, a
masked softmax, no cache, no kernel, no batching, no absorption, no line of
the program's code -- and its bf16 TWIN. A file of its own: ``deepseek_mla.py``
is what another configuration's cell reads.

The interface is that of a reference that replays choices
(``harness/correct.py``, "A model that chooses"), with TWO kinds of choice:

    CHOICES = True
    geometry(attrs, degree)
    reference_logits(params, geo, tokens, positions, choices=None, rounding=None)
    twin_logits(params, geo, tokens, positions, choices=None)
    choice_margins(params, geo, tokens, choices) -> (regret, score_floor, differing)

``choices`` is ``{"experts": (S, L_moe, k) expert indices, "selection": (S,
L, index_topk) positions in the row, -1 padded}``; a name that is absent is
chosen here (each layer's own top-k).

The equations (the published config's keys; the forms are those of
DeepSeek-V3.2-Exp's published ``inference/model.py``, ``Indexer`` and ``MLA``,
and of the ``glm_moe_dsa`` modelling file; the installed ``transformers`` has
``deepseek_v3`` and no ``glm_moe_dsa``, so the parts this file shares with
``deepseek_mla.py`` are held to the installed module through it
(``tests/test_glm_dsa_reference.py``) and the indexer stands as written here).
``N`` = RMSNorm at ``rms_norm_eps``; ``t`` a position, keys ``s <= t``:

    h = embed[tokens]
    per layer l:
      a    = N(h; ln1)
      cq   = N(a W_qa; w_q)                      q_lora_rank: read by q AND by the indexer
      q    = cq W_qb -> heads x [q_nope | q_rope];   q_rope rotated at rope_theta
      [c_raw | k_r] = a W_kva;  c = N(c_raw; w_c);  k_r rotated: ONE rotary key, every head's
      the indexer:
        qI   = cq W_Iq -> index_n_heads x index_head_dim, the FIRST qk_rope_head_dim of each rotated
        kI   = LayerNorm(a W_Ik; weight, bias, eps 1e-6), its first qk_rope_head_dim rotated
        w    = (a W_Iw) x index_n_heads^-1/2 x index_head_dim^-1/2          float32
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                      s <= t
        S_t  = the index_topk keys s <= t of largest I[t, s] (every s <= t while t + 1 <=
               index_topk; of equal scores the lower position first), or, replaying, the
               served selection
      [k_nope_n | v_n][s] = W_kvb,n c[s]         qk_nope_head_dim + v_head_dim a head
      o[t, n] = sum_{s in S_t} softmax_{s in S_t}((q_nope . k_nope + q_rope . k_r) / sqrt(d_q)) v_n[s]
      h = h + W_o [o_1 .. o_H]
      m = N(h; ln2)
      l <  first_k_dense_replace:  h = h + W_down (silu(W_gate m) * W_up m)
      l >= first_k_dense_replace:  sc = sigmoid(m W_r) float32 over the PUBLISHED experts;
            chosen = the num_experts_per_tok largest of sc + b (b for the choice only), or the
            served; weights sc_e / sum_chosen sc x routed_scaling_factor; the sum runs over
            the experts HELD here (below); + the shared expert, unweighted
    logits = N(h; norm) W_head

A held share (``expert_share = {"first": r, "of": n}`` beside the published
keys): ``n_routed_experts`` is the count held, rank ``r`` of ``n`` equal
shares of the published count; the router keeps the published width, the
weights are normalised over the token's choices BEFORE the held are kept,
the shared expert is counted here once.

Rotary: the tree stores rotary dimensions de-interleaved (``deepseek_mla.py``'s
docstring); the rotation is rotate-half on the tree's order, for the
indexer's first ``qk_rope_head_dim`` dimensions too. Not in this file, as not
in the program: the published indexer's Hadamard rotation of ``qI`` and ``kI``
(orthogonal: ``qI . kI`` is what it was) and its fp8 store of ``kI``.

The twin (``rounding=jnp.bfloat16``): ``deepseek_mla.py``'s roundings, and
for the indexer: ``qI``, ``kI`` (the normalised value, and again after weight
and bias) and each rotation rounded as q and k are; ``w`` FLOAT32 from the
rounded ``a`` (a product of bf16 operands accumulated in float32 and not
rounded); ``qI . kI`` a product of bf16 operands in float32, NOT rounded
(the program keeps the scores in float32 from the product to the
selection); relu, the weighting and the sum over heads in float32.

Long prompts: queries are taken ``QUERY_BLOCK`` at a time (the scores of a
block against every key, one head after another), so a 16k-token prompt's
float32 scores never stand whole.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from benchmark.harness.references.deepseek_mla import _dense, _gated, _rotary
from benchmark.harness.references.granite_hybrid import _mm, _rmsnorm, _rnd

CHOICES = True
EXPERTS = "experts"  # (tokens, expert layers, k) expert indices
SELECTION = "selection"  # (tokens, layers, index_topk) positions, -1 padded

QUERY_BLOCK = 512

#: what ``fault`` may plant (selftest and tier-1 controls): attend every live
#: token (no selection), half the ``index_topk``, the ReLU left out, the
#: indexer's rotation left out, the indexer fed ``a W_qa`` without its norm,
#: a selection that is not the indexer's (the LOWEST scores taken)
FAULTS = ("attend_all", "topk_halved", "relu_dropped", "index_rotary_dropped",
          "index_q_unnormed", "lowest_selected")


@dataclass(frozen=True)
class Geometry:
    hidden: int
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    layers: int
    first_dense: int
    vocab: int
    rms_eps: float
    experts: int  # the published count: the router's width
    held: int
    first: int  # the first expert held here
    top_k: int
    shared: int
    norm_topk: bool
    scaling: float
    index_heads: int
    index_dim: int
    index_topk: int
    degree: int

    @staticmethod
    def from_config(attrs: dict, degree: int) -> "Geometry":
        if degree != 1:
            raise ValueError("the glm_dsa reference is written for tp_degree 1")
        if attrs.get("n_group", 1) != 1 or attrs.get("topk_group", 1) != 1:
            raise ValueError("the glm_dsa reference has no group-limited routing (n_group 1)")
        nested = attrs.get("rope_parameters") or {}
        if attrs.get("rope_scaling") or nested.get("rope_type", "default") != "default":
            raise ValueError("the glm_dsa reference has plain rotary (rope_type default)")
        if attrs.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("the glm_dsa reference scores with a sigmoid")
        if not attrs.get("q_lora_rank"):
            raise ValueError("the glm_dsa reference's indexer reads the q latent (q_lora_rank)")
        share = attrs.get("expert_share") or {"first": 0, "of": 1}
        held = attrs["n_routed_experts"]
        return Geometry(
            hidden=attrs["hidden_size"], heads=attrs["num_attention_heads"],
            q_lora_rank=attrs["q_lora_rank"], kv_lora_rank=attrs["kv_lora_rank"],
            nope=attrs["qk_nope_head_dim"], rope=attrs["qk_rope_head_dim"],
            v_dim=attrs["v_head_dim"],
            rope_theta=float(attrs.get("rope_theta", nested.get("rope_theta", 10000.0))),
            layers=attrs["num_hidden_layers"],
            first_dense=min(attrs.get("first_k_dense_replace", 0), attrs["num_hidden_layers"]),
            vocab=attrs["vocab_size"], rms_eps=attrs.get("rms_norm_eps", 1e-6),
            experts=held * int(share["of"]), held=held, first=held * int(share["first"]),
            top_k=attrs["num_experts_per_tok"], shared=attrs.get("n_shared_experts", 0) or 0,
            norm_topk=bool(attrs.get("norm_topk_prob", True)),
            scaling=float(attrs.get("routed_scaling_factor", 1.0)),
            index_heads=attrs["index_n_heads"], index_dim=attrs["index_head_dim"],
            index_topk=attrs["index_topk"], degree=degree,
        )


geometry = Geometry.from_config


def layer_weights(params: dict, group: int, i) -> dict:
    """Layer ``i`` of group ``group`` of the served tree as plain named
    arrays (still as stored)."""
    L = params["layers"][group]
    sa, mlp = L["self_attn"], L["mlp"]
    ix = sa["indexer"]
    w = {
        "ln1": L["input_layernorm"]["weight"][i], "ln2": L["post_attention_layernorm"]["weight"][i],
        "qa": sa["q_a_proj"]["weight"][i], "wq": sa["q_a_layernorm"]["weight"][i],
        "qb": sa["q_b_proj"]["weight"][i],
        "kva": sa["kv_a_proj"]["weight"][i], "wc": sa["kv_a_layernorm"]["weight"][i],
        "uk": sa["k_absorb"]["weight"][i], "uv": sa["v_absorb"]["weight"][i],
        "o": sa["o_proj"]["weight"][i],
        "iq": ix["wq_b"]["weight"][i], "ik": ix["wk"]["weight"][i],
        "ikw": ix["k_norm"]["weight"][i], "ikb": ix["k_norm"]["bias"][i],
        "iw": ix["weights_proj"]["weight"][i],
    }
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


def _rotary_first(x, positions, geo: Geometry, rounding):
    """x (S, heads, n): its first ``geo.rope`` dimensions rotated."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [_rotary(x[..., : geo.rope], positions, geo, rounding), x[..., geo.rope :]], axis=-1)


def _layernorm(x, weight, bias, rounding, eps=1e-6):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    normed = _rnd((x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)), rounding)
    return _rnd(normed * weight.astype(jnp.float32) + bias.astype(jnp.float32), rounding)


def _product(a, b, rounding):
    """``a @ b`` in float32, NOT rounded: for the twin a product of operands
    on ``rounding``'s grid accumulated in float32."""
    import jax.numpy as jnp

    if rounding == jnp.bfloat16:
        return jnp.matmul(a.astype(rounding), b.astype(rounding), preferred_element_type=jnp.float32)
    return a @ _rnd(b.astype(jnp.float32), rounding)


def _query_blocks(S: int):
    return [(lo, min(lo + QUERY_BLOCK, S)) for lo in range(0, S, QUERY_BLOCK)]


def _index_parts(x, cq, w, geo: Geometry, rounding, fault):
    """The indexer's query ``(S, heads, D)``, transposed key ``(D, S)`` and
    head weights ``(S, heads)`` float32 of one sequence."""
    import jax.numpy as jnp

    S = x.shape[0]
    pos = jnp.arange(S)
    q_i = _mm(cq, w["iq"], rounding).reshape(S, geo.index_heads, geo.index_dim)
    k_i = _layernorm(_mm(x, w["ik"], rounding), w["ikw"], w["ikb"], rounding)[:, None, :]
    if fault != "index_rotary_dropped":
        q_i, k_i = (_rotary_first(t, pos, geo, rounding) for t in (q_i, k_i))
    head_w = _product(x, w["iw"], rounding) * (geo.index_heads ** -0.5 * geo.index_dim ** -0.5)
    return q_i, k_i[:, 0].T, head_w


def _index_block(parts, lo: int, hi: int, rounding, fault):
    """``I[lo:hi, :]`` float32: a block of queries against every key (the
    caller keeps ``s <= t``), one index head after another."""
    import jax
    import jax.numpy as jnp

    q_i, k_t, head_w = parts
    act = (lambda s: s) if fault == "relu_dropped" else jax.nn.relu

    def head(acc, qw):  # one index head for the block's queries
        q, wt = qw  # (n, D), (n,)
        return acc + wt[:, None] * act(_product(q, k_t, rounding)), None

    acc, _ = jax.lax.scan(
        head, jnp.zeros((hi - lo, k_t.shape[1]), jnp.float32),
        (jnp.transpose(q_i[lo:hi], (1, 0, 2)), head_w[lo:hi].T))
    return acc


def _index_scores(x, cq, w, geo: Geometry, rounding, fault):
    """``I (S, S)`` float32: every query against every key."""
    import jax.numpy as jnp

    parts = _index_parts(x, cq, w, geo, rounding, fault)
    return jnp.concatenate(
        [_index_block(parts, lo, hi, rounding, fault) for lo, hi in _query_blocks(x.shape[0])], axis=0)


def _layer_inputs(h, w, geo: Geometry, rounding):
    """(a, a W_qa, cq) of a layer: its normed input, the raw q latent and
    the normed one, read by q and by the indexer."""
    x = _rmsnorm(h, w["ln1"], geo.rms_eps, rounding)
    q_raw = _mm(x, w["qa"], rounding)
    return x, q_raw, _rmsnorm(q_raw, w["wq"], geo.rms_eps, rounding)


def _own_selection(scores, k: int, fault):
    """(S, S) bool: per query the ``k`` causal keys of largest score (every
    causal key while there are no more than ``k``)."""
    import jax
    import jax.numpy as jnp

    S = scores.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    if fault == "attend_all" or S <= k:
        return causal
    if fault == "topk_halved":
        k = k // 2
    ranked = jnp.where(causal, -scores if fault == "lowest_selected" else scores, -jnp.inf)
    idx = jax.lax.top_k(ranked, k)[1]  # of equal scores the lower position first
    picked = jnp.zeros((S, S), bool).at[pos[:, None], idx].set(True)
    return picked & causal


def _served_selection(sel, S: int):
    """(S, index_topk) positions, -1 padded -> (S, S) bool."""
    import jax.numpy as jnp

    rows = jnp.arange(S)[:, None]
    cols = jnp.where(sel >= 0, sel, S)  # -1: dropped
    return jnp.zeros((S, S + 1), bool).at[rows, cols].set(True)[:, :S]


def _attention(h, w, geo: Geometry, rounding, fault, follow):
    """The attention sublayer on one sequence, expanded form, over the
    selected keys: (h after it, the selection (S, S) bool); ``follow``: the
    served selection (S, index_topk), or None: the indexer's own (the index
    scores are computed only then)."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    S, H, r = h.shape[0], geo.heads, geo.kv_lora_rank
    pos = jnp.arange(S)
    x, q_raw, cq = _layer_inputs(h, w, geo, rounding)
    q = _mm(cq, w["qb"], rounding).reshape(S, H, geo.nope + geo.rope)
    q_nope, q_rope = q[..., : geo.nope], _rotary(q[..., geo.nope :], pos, geo, rounding)
    ckv = _mm(x, w["kva"], rounding)
    c = _rmsnorm(ckv[:, :r], w["wc"], geo.rms_eps, rounding)
    k_r = _rotary(ckv[:, None, r:], pos, geo, rounding)[:, 0]  # (S, rope): one key, every head's

    if follow is None:
        scores = _index_scores(
            x, q_raw if fault == "index_q_unnormed" else cq, w, geo, rounding, fault)
        selected = _own_selection(scores, geo.index_topk, fault)
    else:
        selected = _served_selection(follow, S)
    scale = 1.0 / np.sqrt(geo.nope + geo.rope)
    blocks = _query_blocks(S)

    def one_head(args):
        qn, qr, uk, uv = args  # (S, nope), (S, rope), (nope, r), (r, v)
        k_nope = _mm(c, uk.T, rounding)  # (S, nope): this head's keys from the latent
        v = _mm(c, uv, rounding)  # (S, v)
        keys = jnp.concatenate([k_nope, k_r], -1).T
        out = []
        for lo, hi in blocks:
            qs = jnp.concatenate([qn[lo:hi], qr[lo:hi]], -1)
            if rounding is None:
                sc = qs @ keys
            else:  # one product over the joined dimensions: float32 sum, one rounding
                sc = _mm(qs, keys, rounding)
            sc = jnp.where(selected[lo:hi], sc * scale, -jnp.inf)
            out.append(_mm(rnd(jax.nn.softmax(sc, axis=-1)), v, rounding))
        return jnp.concatenate(out, axis=0)

    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))
    attn = heads_first(jax.lax.map(
        one_head, (heads_first(q_nope), heads_first(q_rope), w["uk"], w["uv"])))
    return rnd(h + _mm(attn.reshape(S, H * geo.v_dim), w["o"], rounding)), selected


def _experts(h, w, geo: Geometry, rounding, follow):
    """The expert sublayer: (h after it, selection scores s + b (S, E), the
    selection (S, k)); the sum runs over the experts held here."""
    import jax
    import jax.numpy as jnp

    rnd = lambda a: _rnd(a, rounding)
    f32 = lambda a: a.astype(jnp.float32)
    x = _rmsnorm(h, w["ln2"], geo.rms_eps, rounding)
    # the router, float32 from the rounded x': exact products of grid values, float32 sum
    s = jax.nn.sigmoid(x @ _rnd(f32(w["router"]), rounding))
    score = s + f32(w["bias"])[None, :]
    chosen = jax.lax.top_k(score, geo.top_k)[1].astype(jnp.int32) if follow is None else follow
    taken = jnp.take_along_axis(s, chosen, axis=1)  # (S, k)
    if geo.norm_topk:
        taken = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20)
    taken = taken * geo.scaling
    # (S, E) weights over the published width, zero outside the selection; then the held columns
    weights = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(taken)
    held = weights[:, geo.first : geo.first + geo.held]

    def expert(acc, gudw):  # one held expert for every token, weighted by its column
        gate, up, down, col = gudw
        return acc + rnd(rnd(col)[:, None] * _gated(x, gate, up, down, rounding)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), (w["gate"], w["up"], w["down"], held.T))
    out = rnd(routed)
    if "sgate" in w:
        out = rnd(out + _gated(x, w["sgate"], w["sup"], w["sdown"], rounding))
    return rnd(h + out), score, chosen


@lru_cache(maxsize=None)
def _programs(geo: Geometry, rounding, fault):
    import jax

    def dense_layer(h, w, follow_keys=None):
        h, selected = _attention(h, w, geo, rounding, fault, follow_keys)
        return _dense(h, w, geo, rounding), selected

    def expert_layer(h, w, follow_keys=None, follow_experts=None):
        h, selected = _attention(h, w, geo, rounding, fault, follow_keys)
        return (*_experts(h, w, geo, rounding, follow_experts), selected)

    head = jax.jit(lambda h, norm, wgt: _mm(_rmsnorm(h, norm, geo.rms_eps, rounding), wgt, rounding))
    take = jax.jit(layer_weights, static_argnums=1)
    return jax.jit(dense_layer), jax.jit(expert_layer), take, head


def forward(params: dict, geo: Geometry, tokens: Sequence[int], positions: Sequence[int],
            choices: Optional[dict] = None, rounding=None, fault: Optional[str] = None,
            per_layer=None):
    """(logits (len(positions), vocab) float32, router scores (L_moe, S, E)
    float64, experts taken (L_moe, S, k)) of one sequence from a full causal
    pass. ``choices``: what is followed (module docstring). ``per_layer(l, h
    (S, hidden) entering layer l, its weights, its selection (S, S) bool)``
    is called with each layer's device arrays (``choice_margins`` and
    ``own_selection`` read them there)."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    dense_layer, expert_layer, take, head = _programs(geo, rounding, fault)
    choices = choices or {}
    ints = lambda a: jnp.asarray(np.asarray(a, np.int32))
    follow_e = ints(choices[EXPERTS]) if EXPERTS in choices else None
    follow_k = ints(choices[SELECTION]) if SELECTION in choices else None
    scores, chosen = [], []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.asarray(tokens, np.int32))
        h = _rnd(jnp.take(params["embed_tokens"]["weight"], ids, axis=0).astype(jnp.float32), rounding)
        groups = len(params["layers"])
        for i in range(geo.layers):
            keys = None if follow_k is None else follow_k[:, i]
            entering = h
            if i < geo.first_dense:
                w = take(params, 0, i)
                h, selected = dense_layer(h, w, keys)
            else:
                m = i - geo.first_dense
                w = take(params, groups - 1, m)
                h, s, c, selected = expert_layer(
                    h, w, keys, None if follow_e is None else follow_e[:, m])
                scores.append(s)
                chosen.append(c)
            if per_layer is not None:
                per_layer(i, entering, w, selected)
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


def own_selection(params, geo, tokens, rounding=None) -> np.ndarray:
    """The reference's OWN selection, every layer's, as the program returns
    one: (S, L, index_topk) positions ascending, -1 padded (tests)."""
    S, k = len(tokens), geo.index_topk
    out = np.full((S, geo.layers, k), -1, np.int32)

    def keep(l, _h, _w, selected):
        sel = np.asarray(selected)
        for t in range(S):
            at = np.flatnonzero(sel[t])
            out[t, l, : len(at)] = at

    forward(params, geo, tokens, [0], None, rounding, per_layer=keep)
    return out


@lru_cache(maxsize=None)
def _key_margins(geo: Geometry, S: int):
    """The jitted reduction of one layer's selection on the replayed path:
    from the float32 pass's and the twin's hidden states entering the layer,
    (regret, score_floor, differing) of its keys, a block of queries at a
    time: neither pass's (S, S) scores stand whole."""
    import jax
    import jax.numpy as jnp

    def margins(h32, h16, w, sel):
        selected = _served_selection(sel, S)
        pos = jnp.arange(S)
        parts = []
        for h, rounding in ((h32, None), (h16, jnp.bfloat16)):
            x, _, cq = _layer_inputs(h, w, geo, rounding)
            parts.append(_index_parts(x, cq, w, geo, rounding, None))
        regret, floor, differing = jnp.float32(0.0), jnp.float32(0.0), jnp.int32(0)
        for lo, hi in _query_blocks(S):
            i32 = _index_block(parts[0], lo, hi, None, None)
            i16 = _index_block(parts[1], lo, hi, jnp.bfloat16, None)
            causal = pos[lo:hi, None] >= pos[None, :]
            taken = selected[lo:hi] & causal
            left = causal & ~selected[lo:hi]
            worst = jnp.min(jnp.where(taken, i32, jnp.inf), axis=1)
            best = jnp.max(jnp.where(left, i32, -jnp.inf), axis=1)
            short = jnp.where(jnp.any(left, axis=1) & jnp.any(taken, axis=1),
                              jnp.maximum(best - worst, 0.0), 0.0)
            # a set of the wrong size is no top-k at all, whatever it holds
            size = jnp.minimum(pos[lo:hi] + 1, geo.index_topk)
            short = jnp.where(jnp.sum(taken, axis=1) == size, short, jnp.inf)
            regret = jnp.maximum(regret, jnp.max(short))
            floor = jnp.maximum(floor, jnp.max(jnp.where(causal, jnp.abs(i16 - i32), 0.0)))
            differing = differing + jnp.sum(short > 0)
        return regret, floor, differing

    return jax.jit(margins)


def choice_margins(params, geo, tokens, choices):
    """On the replayed path: (regret, score_floor, differing), the expert
    layers first (``deepseek_mla.choice_margins``'s numbers: the router's ``s
    + b``), then one entry a LAYER for the selection of keys: ``regret`` the
    most, over queries, by which the best float32 index score LEFT OUT exceeds
    the worst float32 score TAKEN (0 where a query takes every causal key; inf
    where a query's set is not min(t + 1, index_topk) causal keys: a halved
    top-k takes the best of them and would else pass);
    ``score_floor`` max |twin's I - float32's I| over causal pairs;
    ``differing`` the queries whose set is not float32's own."""
    import jax
    import jax.numpy as jnp

    S = len(tokens)
    entering = {}

    def keep32(l, h, _w, _selected):
        entering[l] = np.asarray(h)  # on the host: L x S x hidden floats do not stand on the device

    key_margins = []
    follow_k = jnp.asarray(np.asarray(choices[SELECTION], np.int32))

    def reduce16(l, h16, w, _selected):
        with jax.default_matmul_precision("highest"):
            out = _key_margins(geo, S)(jnp.asarray(entering.pop(l)), h16, w, follow_k[:, l])
        key_margins.append(tuple(float(x) for x in out))

    _, s32, _ = forward(params, geo, tokens, [0], choices, per_layer=keep32)
    _, s16, _ = forward(params, geo, tokens, [0], choices, jnp.bfloat16, per_layer=reduce16)
    regret, floor, differing = [], [], []
    if s32.shape[0]:
        sel = np.transpose(np.asarray(choices[EXPERTS]), (1, 0, 2))  # (L_moe, S, k)
        taken = np.take_along_axis(s32, sel, axis=2)
        rest = s32.copy()
        np.put_along_axis(rest, sel, -np.inf, axis=2)
        short = np.maximum(rest.max(axis=2) - taken.min(axis=2), 0.0)
        regret += short.max(axis=1).tolist()
        floor += np.abs(s16 - s32).max(axis=(1, 2)).tolist()
        differing += (short > 0).sum(axis=1).tolist()
    for r, f, d in key_margins:
        regret.append(r)
        floor.append(f)
        differing.append(int(d))
    return np.asarray(regret), np.asarray(floor), np.asarray(differing)
