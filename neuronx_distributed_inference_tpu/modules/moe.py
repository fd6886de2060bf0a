"""Mixture-of-Experts module (router + expert MLPs) with expert parallelism.

TPU-native re-design of the reference MoE stack
(reference: modules/moe.py / moe_v2.py:23 ``initialize_moe_module``; nxd
``ExpertMLPsV2`` + ``RouterTopK``; MoENeuronConfig, config.py:665-713).

Design:
- Router: fp32 linear -> softmax -> top-k -> (optionally) renormalized
  affinities (HF Mixtral/Qwen3-MoE semantics).
- Expert compute has THREE strategies (moe_layer picks per shape):
  decode / EP-sharded experts run DENSE over all experts — the reference's
  decode strategy (``moe_token_gen_all_experts`` kernel, §2.10): a
  (E, T, I) batched einsum keeps the MXU busy at tiny T. Large-T prefill
  runs the DROPLESS sorted-token grouped path (T·k rows of work instead of
  E·T: the Pallas grouped matmul of ops/grouped_matmul.py on the experts'
  stack IN PLACE where it can serve the entry, ``jax.lax.ragged_dot``
  elsewhere). :func:`expert_path` is the rule.
- Expert parallelism: expert dim sharded over the ``ep`` mesh axis, expert
  ffn dim over ``(cp, tp)`` — the combine over experts becomes a psum over
  ``ep``, emitted by GSPMD (reference moe_tp×moe_ep process groups,
  moe_v2.py:134-160).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Set, Tuple

import jax
import jax.numpy as jnp

#: the sets open while a step program is traced (:func:`noting_masked_sorts`)
_MASKED_SORTS: List[Set[Tuple[int, int]]] = []


@contextmanager
def noting_masked_sorts():
    """While a step program is traced inside: the set of the ``(B, S)`` of
    every :func:`moe_layer` whose grouped sort was handed the pass's real
    positions (``valid``). The runner keeps it beside the program, and the
    serving session counts a pass's padded rows as left out of the sort
    (``nxdi_moe_sorted_rows_total{kind=padding}``) only for a shape in it: a
    builder that does not hand its expert layer the mask reads 0."""
    shapes: Set[Tuple[int, int]] = set()
    _MASKED_SORTS.append(shapes)
    try:
        yield shapes
    finally:
        _MASKED_SORTS.remove(shapes)


@dataclass(frozen=True)
class MoESpec:
    num_experts: int
    top_k: int
    normalize_top_k_affinities: bool = True  # renorm selected affinities to sum 1
    router_dtype: str = "float32"
    act: str = "silu"
    # scale expert INPUTS by affinity instead of outputs (reference
    # early_expert_affinity_modulation, config.py:665-713)
    early_affinity_modulation: bool = False
    router_bias: bool = False
    # DeepSeek-V3 routing (reference modeling_deepseek.py MoEGate):
    # sigmoid scoring with an aux-free correction bias and group-limited
    # top-k over n_group expert groups
    scoring_func: str = "softmax"  # or "sigmoid"
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # GPT-OSS expert MLP variants (reference modeling_gpt_oss.py):
    # act(x) = x * sigmoid(act_scale * x) clamped, up + act_bias
    act_scale: float = 1.0
    act_bias: float = 0.0
    swiglu_limit: Optional[float] = None
    # p-norm renormalization of the selected weights (DBRX
    # moe_normalize_expert_weights); None = plain sum when
    # normalize_top_k_affinities
    norm_weights_p: Optional[float] = None
    # expert-parallel degree: > 1 keeps the dense all-experts path (the
    # grouped paths are token-sorted on one shard; EP dispatch rides the
    # dense einsum's GSPMD partitioning)
    ep_degree: int = 1
    # SEQUENCE length at/above which prefill takes a sparse dispatch path;
    # decode (S = 1..spec_len, any batch) stays dense (reference
    # moe_token_gen_all_experts)
    sparse_dispatch_threshold: int = 64
    # full model-parallel degree (see AttnSpec.model_parallel: pallas_call
    # has no GSPMD rule, so the grouped-matmul kernel requires one shard)
    model_parallel: int = 1
    # hybrid CTE/TKG expert sharding (reference HybridShardingConfig,
    # models/config.py:694 + moe_v2.py:135-144): decode keeps the persistent
    # ep x tp expert layout; prefill-sized calls constrain the expert weights
    # to FULL tensor parallel (moe_cte_ep=1) — GSPMD reshards them inside the
    # prefill program, amortized over the prompt (per-phase weight layouts
    # are a Neuron notion; on TPU one physical layout + an in-program
    # constraint is the equivalent lever)
    hybrid_cte_full_tp: bool = False
    # a HELD SHARE of the routed experts (one rank of an expert-parallel
    # group, served without its exchange): the layer holds the
    # ``held_experts`` experts from ``first_expert`` on. The router keeps its
    # ``num_experts`` columns and its ``top_k`` choices, normalised over the
    # token's choices as published; the layer computes the part of the result
    # its held experts give (plus the shared expert) and nothing stands in
    # for the others. None = every expert (``held == num_experts``)
    held_experts: Optional[int] = None
    first_expert: int = 0

    @property
    def held(self) -> int:
        return self.num_experts if self.held_experts is None else self.held_experts

    @property
    def holds_share(self) -> bool:
        return self.held < self.num_experts


def held_share(config) -> Tuple[int, int]:
    """``(published experts, first expert held)`` of a model configuration
    that may state a held share beside its published keys: ``expert_share =
    {"first": r, "of": n}`` makes ``n_routed_experts`` the count HELD here,
    rank ``r`` of ``n`` equal shares of the published ``n_routed_experts *
    n`` (``n_routed_experts_published``, if given, must say the same). No
    ``expert_share``: every expert is held."""
    share = getattr(config, "expert_share", None) or {"first": 0, "of": 1}
    first, of = int(share["first"]), int(share["of"])
    published = config.n_routed_experts * of
    said = getattr(config, "n_routed_experts_published", published)
    if not 0 <= first < of or said != published:
        raise ValueError(
            f"expert_share {first} of {of}, n_routed_experts={config.n_routed_experts} held: rank "
            f"'first' of 'of' equal shares of {said} published experts"
        )
    return published, first * config.n_routed_experts


class ExpertLayerError(NotImplementedError):
    """An option that would run an expert layer wrongly rather than not at
    all was set for it (:func:`validate_expert_layer`)."""


def two_matrix(experts: dict) -> bool:
    """An expert of TWO matrices, ``down(act(up x))``, no gate (the published
    ``nemotron_h`` relu2 experts), told by what the parameter tree holds. Both
    are held ``(E, width, hidden)``: ``up_proj`` as published ``(out, in)``,
    ``down_proj`` ``(in, out)``, so that the hidden size lies on the lanes
    whatever the expert width (1856 is 14.5 lane tiles: on the minor axis the
    stack would be padded in device memory and copied for a kernel)."""
    return "gate_proj" not in experts


def expert_projs(experts: dict) -> Tuple[str, ...]:
    return tuple(name for name in _EXPERT_PROJS if name in experts)


def validate_expert_layer(spec: MoESpec, experts: dict, quantized: bool = False) -> None:
    """Refuse at config time what the two-matrix form or a held share cannot
    serve (``experts``: one layer's expert entries or their shapes). One line
    each: none is a silent wrong answer."""
    refusals = []
    if two_matrix(experts):
        what = "two-matrix experts"
        refusals += [
            (quantized, what, "quantised experts: the two products take plain weights"),
            (spec.early_affinity_modulation, what, "early_affinity_modulation"),
        ]
    if spec.holds_share:
        if not 0 <= spec.first_expert <= spec.num_experts - spec.held or spec.held < 1:
            raise ValueError(
                f"held experts [{spec.first_expert}, {spec.first_expert + spec.held}) "
                f"are not a share of {spec.num_experts}")
        what = "a held share of the experts"
        refusals += [
            (spec.ep_degree > 1, what,
             "ep_degree > 1: a held share is one rank's; the exchange is not built"),
            (spec.hybrid_cte_full_tp, what, "hybrid_sharding_config: there is no ep axis to fold"),
        ]
    for flag, what, why in refusals:
        if flag:
            raise ExpertLayerError(f"an expert layer with {what} cannot run with {why}")


def router_top_k(
    router_logits: jax.Array,  # (T, E) fp32
    spec: MoESpec,
    correction_bias: Optional[jax.Array] = None,  # (E,) DeepSeek-V3 e_score_correction_bias
) -> Tuple[jax.Array, jax.Array]:
    """Full (T, E) affinity matrix, zero outside the top-k, plus the (T, E)
    bool SELECTION mask derived from the top-k indices — selection must not
    be inferred from affinity nonzero-ness (an underflowed-to-zero weight of
    a selected expert would silently drop it; matters for biased experts)
    (reference RouterTopK semantics; sigmoid/group-limited variant =
    DeepSeek-V3 MoEGate noaux_tc, modeling_deepseek.py)."""
    T, E = router_logits.shape
    if spec.scoring_func in ("softmax_topk", "sigmoid_topk"):
        # top-k over raw LOGITS, then weight the selected values:
        # softmax_topk = GPT-OSS (reference GptOssTopKRouter),
        # sigmoid_topk = Llama4, no renormalization (reference Llama4Router)
        top_vals, top_idx = jax.lax.top_k(router_logits, spec.top_k)
        weigh = (
            jax.nn.sigmoid
            if spec.scoring_func == "sigmoid_topk"
            else lambda v: jax.nn.softmax(v, axis=-1)
        )
        weights = weigh(top_vals) * spec.routed_scaling_factor
        onehot = jax.nn.one_hot(top_idx, E, dtype=router_logits.dtype)
        return jnp.einsum("tke,tk->te", onehot, weights), onehot.sum(axis=1) > 0
    if spec.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(router_logits)
    else:
        scores = jax.nn.softmax(router_logits, axis=-1)
    choice = scores if correction_bias is None else scores + correction_bias[None, :]

    if spec.n_group > 1:
        # group-limited routing: keep the topk_group groups ranked by the sum
        # of each group's top-2 choice scores, mask the rest
        g = spec.n_group
        grouped = choice.reshape(T, g, E // g)
        top2 = jax.lax.top_k(grouped, min(2, E // g))[0].sum(axis=-1)  # (T, g)
        _, keep_idx = jax.lax.top_k(top2, spec.topk_group)  # (T, topk_group)
        group_mask = jnp.zeros((T, g), bool).at[
            jnp.arange(T)[:, None], keep_idx
        ].set(True)
        choice = jnp.where(
            jnp.repeat(group_mask, E // g, axis=1), choice, -jnp.inf
        )

    top_vals, top_idx = jax.lax.top_k(choice, spec.top_k)  # (T, k) ranked by choice
    # combine weights use the UNCORRECTED scores of the selected experts
    weights = jnp.take_along_axis(scores, top_idx, axis=1)
    if spec.norm_weights_p is not None:
        # DBRX p-norm renormalization (reference DbrxRouter
        # moe_normalize_expert_weights)
        p = spec.norm_weights_p
        norm = jnp.sum(jnp.abs(weights) ** p, axis=-1, keepdims=True) ** (1.0 / p)
        weights = weights / (norm + 1e-20)
    elif spec.normalize_top_k_affinities:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * spec.routed_scaling_factor
    onehot = jax.nn.one_hot(top_idx, E, dtype=scores.dtype)  # (T, k, E)
    return jnp.einsum("tke,tk->te", onehot, weights), onehot.sum(axis=1) > 0


def _glu_fn(spec: MoESpec):
    from neuronx_distributed_inference_tpu.models.base import act_fn as get_act

    def glu(gate, up):
        if spec.act_scale != 1.0 or spec.act_bias != 0.0 or spec.swiglu_limit is not None:
            # GPT-OSS swiglu: x·sigmoid(act_scale·x), clamped, up offset by
            # act_bias (reference modeling_gpt_oss.py + mx_layout_transform
            # hidden_act_scaling_factor=1.702, hidden_act_bias=1)
            if spec.swiglu_limit is not None:
                gate = jnp.clip(gate, max=spec.swiglu_limit)
                up = jnp.clip(up, -spec.swiglu_limit, spec.swiglu_limit)
            return gate * jax.nn.sigmoid(spec.act_scale * gate) * (up + spec.act_bias)
        act = get_act(spec.act)
        return act(gate) * up

    return glu


def _has_blockwise_scales(params: dict) -> bool:
    from neuronx_distributed_inference_tpu.ops.quant_matmul import is_int4_entry

    for name in expert_projs(params):
        entry = params[name]
        if is_int4_entry(entry):
            # packed int4 experts dequantize at the matmul site in every
            # dispatch path (see _expert_entry) — they don't need the
            # blockwise-einsum restriction
            continue
        s = entry.get("scale")
        if s is not None and s.ndim == entry["weight"].ndim:
            return True
    return False


def _expert_entry(entry: dict, x_in: jax.Array) -> dict:
    """Resolve a packed-int4 expert entry to a plain weight for the einsum
    paths: experts stay int4-resident in HBM (0.5 byte/param streamed) and
    XLA fuses the group-structured dequant into the expert matmul — the
    (E, in, out) weight never round-trips through HBM in compute dtype.
    Dense-linear projections take the Pallas fused-dequant kernel instead
    (ops/quant_matmul via quant.linear); the expert einsums are gather-
    shaped, so they use this runtime-dequant form."""
    from neuronx_distributed_inference_tpu.ops.quant_matmul import (
        maybe_dequantize_int4,
    )

    return maybe_dequantize_int4(entry, x_in.shape[-1], x_in.dtype)


def _sorted_dispatch(affinities: jax.Array, k: int, first: int = 0, held: Optional[int] = None,
                     valid: Optional[jax.Array] = None):
    """(T, E) affinity matrix -> token-replica rows sorted by expert:
    (row_token (T*k,), row_expert, row_weight, group_sizes (E,)). Under a
    held share (``held`` experts from ``first``) the experts are numbered
    among the held and ``group_sizes`` is over them. A (row, choice) pair of
    an expert outside the held, or of a token that is not ``valid`` ((T,)
    bool: a padded position of a chunk pass), carries the number of groups,
    sorts last and belongs to no group: the sizes may sum to less than T*k."""
    T, E = affinities.shape
    w_topk, e_topk = jax.lax.top_k(affinities, k)  # (T, k)
    flat_e = e_topk.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = w_topk.reshape(T * k)
    if held is not None and held < E:
        E = held
        flat_e = flat_e - first
        flat_e = jnp.where((flat_e >= 0) & (flat_e < held), flat_e, held)
    if valid is not None:
        flat_e = jnp.where(jnp.repeat(valid, k), flat_e, E)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1, mode="drop")
    return st, se, sw, group_sizes


class LayerOfStack(NamedTuple):
    """An expert weight as the layer scan holds it: the stack of every
    layer's ``(L, E, in, out)`` and this layer's index into it. The grouped
    matmul kernel reads the layer's experts from the stack where they lie;
    a scan that handed the kernel its own slice would copy it first
    (:func:`hoist_expert_stacks`)."""

    stack: jax.Array
    layer: jax.Array


_EXPERT_PROJS = ("gate_proj", "up_proj", "down_proj")


def _grouped_mm(entry: dict, x_rows: jax.Array, row_expert: jax.Array,
                group_sizes: jax.Array, kernel: bool = False,
                out_in: bool = False) -> jax.Array:
    """Ragged grouped matmul over expert-sorted rows — the Megablox-style GMM
    (reference BlockwiseMatmulConfig / nxd ExpertMLPsV2 blockwise path).
    x_rows (R, in) sorted by expert; weight (E, in, out) -> (R, out).
    ``kernel``: through ops/grouped_matmul.py (a plain entry, whose weight may
    be a :class:`LayerOfStack`); else ``jax.lax.ragged_dot``. ``out_in``: the
    weight is held (E, out, in) (:func:`two_matrix`)."""
    if kernel:
        from neuronx_distributed_inference_tpu.ops.grouped_matmul import grouped_matmul
        from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret

        w = entry["weight"]
        stack, layer = w if isinstance(w, LayerOfStack) else (w, None)
        y = grouped_matmul(x_rows, stack, group_sizes, layer, out_in=out_in,
                           interpret=kernel_interpret())
    else:
        entry = _expert_entry(entry, x_rows)
        w = entry["weight"]
        if out_in:
            w = jnp.swapaxes(w, -1, -2)
        y = jax.lax.ragged_dot(x_rows, w.astype(x_rows.dtype), group_sizes)
    s = entry.get("scale")
    if s is not None:
        y = y * s.astype(y.dtype)[row_expert]
    if "bias" in entry:
        y = y + entry["bias"].astype(y.dtype)[row_expert]
    return y


def expert_mlps_grouped(
    params: dict,
    x: jax.Array,  # (T, H)
    affinities: jax.Array,  # (T, E)
    spec: MoESpec,
    kernel: bool = False,
    valid: Optional[jax.Array] = None,  # (T,) bool: False = a padded position
) -> jax.Array:
    """Dropless sorted-token grouped dispatch: T·k rows of expert work
    instead of the dense path's E·T (a ~E/k FLOP reduction at prefill;
    VERDICT r2 weak #1). Reference: nxd ExpertMLPsV2 blockwise matmuls.
    ``kernel``: the products through the grouped-matmul kernel (three, or the
    two of a :func:`two_matrix` expert). Under a held share the rows of an
    expert held elsewhere, and with ``valid`` the rows of a padded position,
    are in no group: they cost no visit and add nothing."""
    st, se, sw, group_sizes = _sorted_dispatch(
        affinities, spec.top_k, spec.first_expert, spec.held_experts, valid)
    xs = x[st]  # (R, H) gathered token rows
    sww = sw.astype(x.dtype)[:, None]
    if spec.early_affinity_modulation:
        xs = xs * sww
    if two_matrix(params):
        from neuronx_distributed_inference_tpu.models.base import act_fn

        u = _grouped_mm(params["up_proj"], xs, se, group_sizes, kernel, out_in=True)
        y = _grouped_mm(params["down_proj"], act_fn(spec.act)(u), se, group_sizes, kernel)
    else:
        glu = _glu_fn(spec)
        g = _grouped_mm(params["gate_proj"], xs, se, group_sizes, kernel)
        u = _grouped_mm(params["up_proj"], xs, se, group_sizes, kernel)
        y = _grouped_mm(params["down_proj"], glu(g, u), se, group_sizes, kernel)  # (R, H)
    if not spec.early_affinity_modulation:
        y = y * sww
    if spec.holds_share or valid is not None:
        # a row in no group was never computed: whatever stands there is not a number of ours
        y = jnp.where((se < spec.held)[:, None], y, jnp.zeros((), y.dtype))
    return jnp.zeros_like(x).at[st].add(y)


#: rows from which the dense strategy of a HELD share hands the experts'
#: first two products the rows broadcast over the experts (``eth,ehi->eti``)
#: in place of ``th,ehi->eti``. Read from the compiled decode program of
#: kimi-linear-48b-a3b for a described v5e (128 rows, 16 held experts of
#: 2304 x 1024, 15 layers; tests/test_chip_compile.py): at 128 rows the
#: compiler makes the weights the stationary operand of the unbatched form
#: and re-lays the WHOLE (layers, experts, in, out) gate and up stacks with
#: the hidden size minor, 2 x 1.05 GiB copied on every step before the layer
#: loop; the batched form reads the stacks as stored (temporaries 2.22 ->
#: 0.11 GiB). Under that many rows, and for a model that holds every expert,
#: the form is what it was (the cells the benchmark had decode 32-64 rows
#: under a share; sdar-30b-a3b's block step of 192 rows holds no share and
#: was not read: PERF.md section 7).
_BATCHED_ROWS = 128


def expert_mlps_dense(
    params: dict,
    x: jax.Array,  # (T, H)
    affinities: jax.Array,  # (T, E)
    spec: MoESpec,
    selected: Optional[jax.Array] = None,  # (T, E) bool top-k selection
) -> jax.Array:
    """All-experts dense compute + affinity-weighted combine
    (reference moe_token_gen_all_experts kernel strategy, §2.10).

    Expert weights: gate/up (E, H, I), down (E, I, H) — sharded E over ``ep``
    and I over ``(cp, tp)``.
    """

    def expert_mm(entry, x_in, eq):
        """Expert batched matmul with optional dequant scale + bias (E, out).

        Blockwise scales (scale.ndim == weight.ndim; reference
        blockwise_matmul_block_size) apply per input block before the sum —
        the exact dequantized matmul, MXU-shaped."""
        entry = _expert_entry(entry, x_in)
        w = entry["weight"]
        s = entry.get("scale")
        if s is not None and s.ndim == w.ndim:
            G = s.shape[-2]
            bs = w.shape[-2] // G
            wb = w.reshape(w.shape[0], G, bs, w.shape[-1]).astype(x_in.dtype)
            xb = x_in.reshape(*x_in.shape[:-1], G, bs)
            if x_in.ndim == 2:  # (T, in)
                y = jnp.einsum("tgb,egbo->egto", xb, wb)
            else:  # (E, T, in)
                y = jnp.einsum("etgb,egbo->egto", xb, wb)
            y = jnp.einsum("egto,ego->eto", y, s.astype(x_in.dtype))
        else:
            y = jnp.einsum(eq, x_in, w.astype(x_in.dtype))
            if s is not None:
                y = y * s.astype(y.dtype)[:, None, :]
        if "bias" in entry:
            y = y + entry["bias"].astype(y.dtype)[:, None, :]
        return y

    if spec.holds_share:  # the held columns: a choice held elsewhere adds nothing here
        held = slice(spec.first_expert, spec.first_expert + spec.held)
        affinities = affinities[:, held]
        selected = None if selected is None else selected[:, held]
    aff = affinities.astype(x.dtype)
    if two_matrix(params):
        from neuronx_distributed_inference_tpu.models.base import act_fn

        u = expert_mm(params["up_proj"], x, "th,eih->eti")
        y = expert_mm(params["down_proj"], act_fn(spec.act)(u), "eti,eih->eth")  # (E, T, H)
        return jnp.einsum("te,eth->th", aff, y)
    glu = _glu_fn(spec)
    if spec.early_affinity_modulation:
        # scale expert inputs, combine unweighted over the SELECTED experts
        # (reference early_expert_affinity_modulation). The selection mask
        # matters with biased experts: a non-selected expert sees zero input
        # but its biases would otherwise leak glu(bias) into every token.
        xe = jnp.einsum("te,th->eth", aff, x)
        g = expert_mm(params["gate_proj"], xe, "eth,ehi->eti")
        u = expert_mm(params["up_proj"], xe, "eth,ehi->eti")
        y = expert_mm(params["down_proj"], glu(g, u), "eti,eih->eth")
        # combine over the SELECTED experts (top-k indices, not affinity
        # nonzero-ness — an underflowed weight must not drop its expert)
        sel = (
            selected if selected is not None else (affinities != 0)
        ).astype(x.dtype)  # (T, E)
        return jnp.einsum("te,eth->th", sel, y)
    xe, into = x, "th,ehi->eti"
    if spec.holds_share and x.shape[0] >= _BATCHED_ROWS:
        # the rows laid over the held experts: a batched product that reads the
        # stacks as they are stored (module note at _BATCHED_ROWS)
        xe, into = jnp.broadcast_to(x[None], (spec.held,) + x.shape), "eth,ehi->eti"
    g = expert_mm(params["gate_proj"], xe, into)
    u = expert_mm(params["up_proj"], xe, into)
    y = expert_mm(params["down_proj"], glu(g, u), "eti,eih->eth")  # (E, T, H)
    return jnp.einsum("te,eth->th", aff, y)


def linear_router(params: dict, x: jax.Array, spec: MoESpec) -> Tuple[jax.Array, jax.Array]:
    """The default router: one linear map of the token to expert logits
    (``params["router"]``: weight (H, E), optional bias and DeepSeek-V3
    correction bias), then :func:`router_top_k`. x (T, H) -> ((T, E) float32
    affinities, zero outside the top-k; (T, E) bool selection)."""
    from neuronx_distributed_inference_tpu.config import to_dtype

    rdt = to_dtype(spec.router_dtype)
    router_logits = x.astype(rdt) @ params["router"]["weight"].astype(rdt)
    if spec.router_bias:
        router_logits = router_logits + params["router"]["bias"].astype(rdt)
    correction = params["router"].get("e_score_correction_bias")
    if correction is not None:
        correction = correction.astype(jnp.float32)
    return router_top_k(
        router_logits.astype(jnp.float32), spec, correction_bias=correction
    )


def carried_mlp_router(
    params: dict,  # one layer's router leaves (models/zaya.py param_shapes)
    x: jax.Array,  # (T, H) the normalised hidden state
    carry: jax.Array,  # (T, R) float32: the layer before's r, zeros at the first layer
    eps: float,
):
    """A router that is a small network with a carry from layer to layer
    (ZAYA1): ``r = x W_d + b_d + gamma * carry``; ``z = rmsnorm(r)``;
    ``logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3``; ``p = softmax``;
    top-1 of ``p + b_bal``, weighted by ``p`` of the chosen expert, not
    renormalised. Float32 throughout, every product at full precision (the
    network is R x R: nothing beside an expert's matrices): a top-1 choice
    among near-tied scores is the last thing to round.

    Returns ((T, E) float32 affinities, zero outside the choice; (T, E) bool
    selection; r (T, R) float32, the next layer's carry: the value BEFORE
    the norm; the chosen expert (T,) int32)."""
    from neuronx_distributed_inference_tpu.modules.norm import rms_norm

    with jax.named_scope("layer.moe.router"):
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        dense = lambda a, name: (
            jnp.matmul(a, params[name]["weight"].astype(f32), precision=hi)
            + params[name]["bias"].astype(f32)
        )
        gelu = lambda a: jax.nn.gelu(a, approximate=False)
        down = params["down_proj"]
        # x and W_d as they are stored: a product of bf16 operands is exact in
        # the float32 it accumulates in, whatever the precision asked for
        r = jnp.matmul(x, down["weight"], preferred_element_type=f32,
                       precision=hi if x.dtype == f32 else None)
        r = r + down["bias"].astype(f32) + params["gamma"].astype(f32) * carry
        z = rms_norm(r, params["norm"]["weight"], eps)
        t = gelu(dense(gelu(dense(z, "fc1")), "fc2"))
        p = jax.nn.softmax(
            jnp.matmul(t, params["fc3"]["weight"].astype(f32), precision=hi), axis=-1
        )
        choice = jnp.argmax(p + params["balance_bias"].astype(f32), axis=-1).astype(jnp.int32)
        selected = jax.nn.one_hot(choice, p.shape[-1], dtype=bool)
        return jnp.where(selected, p, 0.0), selected, r, choice


def expert_path(spec: MoESpec, experts: dict, q_len: int, rows: int, dtype) -> str:
    """The strategy :func:`moe_layer` takes for a pass of ``rows`` token
    positions, ``q_len`` of them a row, from the shapes and the entry alone
    (a host-side function: the serving session counts its passes by it,
    ``nxdi_moe_grouped_rows_total``). ``experts``: one layer's expert entries
    or the stack of every layer's. One of

    - ``"dense"``: every expert over every row, one batched product. Decode
      (``q_len`` under ``sparse_dispatch_threshold``, however large the
      batch: the reference's moe_token_gen_all_experts), experts divided
      over ``ep`` (the dispatch rides the einsum's GSPMD partitioning),
      blockwise-quantised experts, ``top_k == num_experts``, and every
      prefill-sized pass that the rule below does not send elsewhere;
    - ``"kernel"``: dropless grouped, its three products through
      ops/grouped_matmul.py. Where the kernel can serve the entry
      (kernel_mode.use_grouped_matmul: plain experts on one shard, on the
      chip) the line against dense is kernel_mode.grouped_beats_dense, drawn
      from ``rows``, ``num_experts`` and ``top_k`` and the chip's operations
      a weight byte. Read alone on a v5e (PERF.md section 6, PR 45: ms a
      layer, the three products and their dispatch):

      ========================  =====  =====  ==========  ======  ========  ======
      experts / top_k, widths   rows   dense  ragged_dot  kernel  in place  stream
      ========================  =====  =====  ==========  ======  ========  ======
      16 / 1, 2048 x 2048       1024   2.55   2.73        2.08    **0.87**  0.49
      (zaya1-8b)                256    0.68   2.06        1.82    0.60
      128 / 8, 2048 x 768       1024   8.28   9.37        6.68    **3.01**  1.47
      (sdar-30b-a3b)            256    2.32   8.28        5.59    1.93
      64 / 6, 2048 x 1408       1024   7.30   10.16       6.12    **2.77**  1.35
      (kimi-vl-a3b)             256    1.89   8.91        5.10    1.78
      ========================  =====  =====  ==========  ======  ========  ======

      ``ragged_dot`` and ``kernel``: inside a layer scan, on the scan's own
      slice of the stack (a 134-400 MB copy a product); ``in place``: the
      kernel on the stacked weights with the layer's index; ``stream``: the
      layer's expert weights once at 819 GB/s. At 1024 rows (8 x 128) dense
      is bound by arithmetic and grouped in place takes a third of it; at
      256 (8 x 32, under ``sparse_dispatch_threshold``) the two are level
      and the pass stays dense.

    - ``"ragged_dot"``: dropless grouped through ``jax.lax.ragged_dot``, for
      an entry or a mesh the kernel does not serve (quantised experts, the
      hybrid prefill re-constraint, off the chip). That lowering runs at
      2.9-5.6 x the experts' stream (PERF.md section 6, PR 45), so it is
      taken only where it saves 16 x the arithmetic, ``num_experts >= 16 *
      top_k``, as before the kernel.
    """
    if q_len < spec.sparse_dispatch_threshold:
        return "dense"
    # hybrid prefill is logically ep=1 (experts replicated over ep after the
    # constraint), so the token-sorted sparse paths apply
    ep_ok = spec.ep_degree == 1 or spec.hybrid_cte_full_tp
    if not ep_ok or spec.top_k >= spec.num_experts or _has_blockwise_scales(experts):
        return "dense"
    from neuronx_distributed_inference_tpu.ops.kernel_mode import (
        grouped_beats_dense,
        use_grouped_matmul,
    )

    # under a held share: the experts held, and the rows EXPECTED here
    share = spec.held / spec.num_experts
    if use_grouped_matmul(spec, experts, dtype):
        return "kernel" if grouped_beats_dense(spec.held, spec.top_k, rows, share) else "dense"
    return "ragged_dot" if spec.num_experts >= 16 * spec.top_k else "dense"


def stacked_experts(layers) -> dict:
    """The expert entries of a model's stacked layer parameters
    (``params["layers"]``: one stack, or a list of groups' stacks, or a dict
    of kinds' stacks, of which one holds the expert layers), every leaf led
    by its layer axis."""
    if isinstance(layers, dict) and "mlp" not in layers:
        layers = list(layers.values())
    groups = layers if isinstance(layers, (list, tuple)) else [layers]
    return next(g["mlp"]["experts"] for g in groups if "experts" in g.get("mlp", {}))


def _map_expert_projs(layers: dict, fn) -> dict:
    """``layers`` with ``fn(name, entry)`` in place of each expert projection."""
    experts = layers["mlp"]["experts"]
    mapped = {name: fn(name, experts[name]) for name in expert_projs(experts)}
    return dict(layers, mlp=dict(layers["mlp"], experts=dict(experts, **mapped)))


def hoist_expert_stacks(layers: dict, spec: MoESpec, q_len: int, rows: int, dtype):
    """For a scan over ``layers`` (every leaf led by the layer axis): where
    the pass takes the grouped-matmul kernel, ``(layers without the three
    expert weights, the three stacks)``, else ``(layers, None)``. The stacks
    stay out of the scanned operands, loop-invariant, and
    :func:`place_expert_stacks` hands each layer its index into them: the
    scan's own slice of a stack would reach the kernel, a custom call, as a
    copy of ``(E, in, out)`` a product a layer."""
    experts = layers.get("mlp", {}).get("experts") if isinstance(layers, dict) else None
    if experts is None or expert_path(spec, experts, q_len, rows, dtype) != "kernel":
        return layers, None
    stacks = {name: experts[name]["weight"] for name in expert_projs(experts)}
    scanned = _map_expert_projs(
        layers, lambda _, entry: {k: v for k, v in entry.items() if k != "weight"}
    )
    return scanned, stacks


def place_expert_stacks(layer_params: dict, stacks: Optional[dict], layer: jax.Array) -> dict:
    """One scanned layer's parameters with the hoisted expert weights back in
    place as :class:`LayerOfStack` (``layer``: its index into the stacks)."""
    if stacks is None:
        return layer_params
    return _map_expert_projs(
        layer_params,
        lambda name, entry: dict(entry, weight=LayerOfStack(stacks[name], layer)),
    )


class ExpertMlp:
    """A decoder layer's ``mlp_fn`` that is an expert layer. It carries the
    :class:`MoESpec`, so that the layer scan (models/base.run_decoder_layers)
    can ask :func:`hoist_expert_stacks` which passes read the stacks in place."""

    def __init__(self, spec: MoESpec, shared_mlp_fn=None):
        self.spec, self.shared_mlp_fn = spec, shared_mlp_fn

    def __call__(self, mlp_params, hidden, model_spec, valid=None):
        return moe_layer(
            mlp_params, hidden, self.spec, shared_mlp_fn=self.shared_mlp_fn,
            return_choices=model_spec.output_choices, valid=valid,
        )


def moe_layer(
    params: dict,
    hidden: jax.Array,  # (B, S, H)
    spec: MoESpec,
    shared_mlp_fn=None,
    router=linear_router,
    return_choices: bool = False,
    valid: Optional[jax.Array] = None,  # (B, S) bool
) -> jax.Array:
    """Full MoE block (reference initialize_moe_module product, moe_v2.py:23).
    With ``return_choices`` (a builder passes ``ModelSpec.output_choices``):
    (the block's output, the experts each position selected, int32
    (B, S, top_k), most affine first).

    ``router(params, x (T, H), spec) -> (affinities (T, E) float32, selected
    (T, E) bool)`` is the builder's: the linear router unless a model brings
    its own (models/zaya.py: a down-projection, a carry from the layer
    before and an MLP). The expert strategy by shape below is shared.
    ``valid``: the positions that are real (False: a padded position of a
    chunk pass, whose output nobody reads); a grouped strategy then leaves
    their rows out of the sort. None (every caller but the hybrid stack's
    single-part expert block): every position is routed, as before."""
    B, S, H = hidden.shape
    x = hidden.reshape(B * S, H)
    with jax.named_scope("layer.moe.router"):
        affinities, selected = router(params, x, spec)  # (T, E) fp32, (T, E) bool
    path = expert_path(spec, params["experts"], S, B * S, x.dtype)
    prefill_sized = S >= spec.sparse_dispatch_threshold
    expert_params = params["experts"]
    if spec.hybrid_cte_full_tp and prefill_sized:
        # hybrid sharding, prefill side: constrain the expert weights to full
        # tensor parallel (ep folded into the ffn axes) — GSPMD inserts the
        # reshard inside this (CTE-sized) program only; decode keeps the
        # stored ep x tp layout untouched (reference HybridShardingConfig
        # moe_cte_tp/ep, moe_v2.py:135-144)
        from jax.sharding import PartitionSpec as P

        from neuronx_distributed_inference_tpu.parallel.sharding import constrain

        full = ("ep", "cp", "tp")

        def _cte_constrain(entry, in_axis_last):
            out = dict(entry)
            w = entry["weight"]
            spec_w = (
                P(None, None, full) if in_axis_last else P(None, full, None)
            )
            out["weight"] = constrain(w, spec_w)
            return out

        expert_params = dict(expert_params)
        expert_params["gate_proj"] = _cte_constrain(expert_params["gate_proj"], True)
        expert_params["up_proj"] = _cte_constrain(expert_params["up_proj"], True)
        expert_params["down_proj"] = _cte_constrain(expert_params["down_proj"], False)

    with jax.named_scope("layer.moe.experts"):
        if path in ("kernel", "ragged_dot"):
            if valid is not None:
                for shapes in _MASKED_SORTS:
                    shapes.add((B, S))
            out = expert_mlps_grouped(expert_params, x, affinities, spec, path == "kernel",
                                      None if valid is None else valid.reshape(B * S))
        else:
            out = expert_mlps_dense(expert_params, x, affinities, spec, selected)
    if shared_mlp_fn is not None:
        with jax.named_scope("layer.shared_mlp"):
            out = out + shared_mlp_fn(params["shared_experts"], x)
    out = out.reshape(B, S, H).astype(hidden.dtype)
    if not return_choices:
        return out
    # the selection itself, not affinity nonzero-ness (router_top_k)
    picked = jax.lax.top_k(jnp.where(selected, 1.0 + affinities, 0.0), spec.top_k)[1]
    return out, picked.reshape(B, S, spec.top_k).astype(jnp.int32)


def shared_expert_shapes(L: int, H: int, I: int, fused: bool) -> dict:
    """Shape tree for the shared expert under either layout (builders call
    this so fused_shared_experts stays one switch)."""
    if fused:
        return {"gate_up_proj": {"weight": (L, H, 2 * I)}, "down_proj": {"weight": (L, I, H)}}
    return {
        "gate_proj": {"weight": (L, H, I)},
        "up_proj": {"weight": (L, H, I)},
        "down_proj": {"weight": (L, I, H)},
    }


def shared_expert_pspecs(fused: bool, tensor_axes):
    from jax.sharding import PartitionSpec as P

    if fused:
        return {
            "gate_up_proj": {"weight": P(None, None, tensor_axes)},
            "down_proj": {"weight": P(None, tensor_axes, None)},
        }
    return {
        "gate_proj": {"weight": P(None, None, tensor_axes)},
        "up_proj": {"weight": P(None, None, tensor_axes)},
        "down_proj": {"weight": P(None, tensor_axes, None)},
    }


def fuse_shared_expert_params(node: dict) -> dict:
    """Separate gate/up -> fused gate_up (checkpoint conversion; reference
    llama4 fused_shared_experts key concat, modeling_llama4_text.py:722)."""
    return {
        "gate_up_proj": {
            "weight": jnp.concatenate(
                [node["gate_proj"]["weight"], node["up_proj"]["weight"]], axis=-1
            )
        },
        "down_proj": node["down_proj"],
    }


def shared_expert_mlp(params: dict, x: jax.Array, act_name: str = "silu") -> jax.Array:
    """Shared-expert GLU MLP supporting BOTH weight layouts: separate
    gate/up projections, or the FUSED gate_up projection
    (config fused_shared_experts; reference SharedExperts
    fused_gate_up_projection, moe_v2.py:90-101) — one column-parallel matmul
    split in halves after."""
    from neuronx_distributed_inference_tpu.models.base import act_fn
    from neuronx_distributed_inference_tpu.ops.quant import linear

    act = act_fn(act_name)
    if "gate_proj" not in params and "gate_up_proj" not in params:
        # a two-matrix shared expert (the form of its routed ones: two_matrix)
        return linear(params["down_proj"], act(linear(params["up_proj"], x)))
    if "gate_up_proj" in params:
        gu = linear(params["gate_up_proj"], x)
        g, u = jnp.split(gu, 2, axis=-1)
    else:
        g = linear(params["gate_proj"], x)
        u = linear(params["up_proj"], x)
    return linear(params["down_proj"], act(g) * u)
