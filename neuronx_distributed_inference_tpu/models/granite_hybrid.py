"""Granite-4.0-H (``granitemoehybrid``) model plugin: Mamba-2 state-space
layers beside GQA attention layers in one stack.

Published implementation: ``transformers``
``models/granitemoehybrid/modeling_granitemoehybrid.py``. Per layer ``l`` of
``layer_types`` (granite-4.0-h-micro: 40 layers, attention at 5, 15, 25, 35 —
a period of ten = 5 mamba, 1 attention, 4 mamba):

    h = h + residual_multiplier * Mixer_l(rmsnorm(h))
    h = h + residual_multiplier * MLP(rmsnorm(h))        # the "shared" SwiGLU MLP
    logits = rmsnorm(h) @ embed.T / logits_scaling,  h0 = embed[ids] * embedding_multiplier

The attention mixer is the shared ``decoder_layer`` with no positional
embedding (``position_embedding_type: "nope"``) and softmax scale
``attention_multiplier``; the Mamba-2 mixer is modules/ssm.py.

What differs from every other plugin is WHAT A LAYER KEEPS: an attention
layer pages K/V over the block pool, a state-space layer keeps a constant
per-slot state (``cache_layers`` / ``init_slot_state``; the application
builds ``HybridBlockCache``). The stack is therefore run by
:class:`HybridStack` (a ``models/base.LayerStack``): one ``lax.scan`` over
the periods of the layer pattern, inside it one scan per run of like
layers, each layer's weights taken from its kind's stacked tree by a
computed index — three compiled layer bodies for 40 layers.

Served on the paged, chunked, continuously batched path only
(``ServingSession``); what cannot serve a constant-size state is refused at
config time (config.validate_slot_state_serving).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    to_dtype,
    validate_slot_state_serving,
)
from neuronx_distributed_inference_tpu.models.base import (
    EXPERT_CHOICES,
    PHASE_TOKEN_GENERATION,
    LayerStack,
    ModelSpec,
    _decoder_layer_mlp,
    build_mask,
    decoder_layer,
    paged_block_inputs,
    residual_add,
    slot_state_rows,
)
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    PAGED_KV,
    SLOT_STATE,
    HybridBlockCache,
)
from neuronx_distributed_inference_tpu.modules.norm import rms_norm
from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret
from neuronx_distributed_inference_tpu.ops.quant import linear

MAMBA, ATTENTION, MOE = "mamba", "attention", "moe"
#: further kinds of single-part block (models/kimi_linear.py): a KDA mixer
#: (modules/kda.py), a latent-attention mixer (models/deepseek.mla_decoder_layer
#: without its MLP) and a dense gated MLP alone
KDA, MLA, DENSE = "kda", "mla", "dense"
#: a layer of two parts whose mixer is power retention (models/brumby.py,
#: modules/power_retention.py): Qwen3's layer with the attention swapped
POWER = "power"
#: the kinds whose blocks page K/V (or latents) over the block pool
_PAGING = (ATTENTION, MLA)


class GraniteHybridInferenceConfig(InferenceConfig):
    _REQUIRED_ATTRS = (
        "hidden_size", "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "vocab_size", "shared_intermediate_size", "layer_types", "attention_multiplier",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
    )

    def validate_config(self):
        super().validate_config()
        if getattr(self, "num_local_experts", 0):
            raise NotImplementedError(
                "granitemoehybrid with num_local_experts > 0: the routed-expert layer "
                "is not implemented (only the dense shared MLP is)"
            )
        pe = getattr(self, "position_embedding_type", "nope")
        if pe != "nope":
            raise NotImplementedError(
                f"granitemoehybrid with position_embedding_type={pe!r}: only 'nope' "
                "(no rotation of q and k) is implemented"
            )
        kinds = tuple(self.layer_types)
        if len(kinds) != self.num_hidden_layers or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(
                "layer_types must name 'mamba' or 'attention' for each of "
                f"num_hidden_layers={self.num_hidden_layers} layers, got {kinds}"
            )
        if MAMBA in kinds:
            validate_slot_state_serving(self.tpu_config)


def _runs(kinds: Tuple[str, ...], seen: Dict[str, int]) -> List[Tuple[str, int, int]]:
    """``kinds`` cut into runs of like layers: (kind, rank of the run's first
    layer among the layers of that kind, counted on in ``seen``, length)."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((kind, seen.get(kind, 0), 1))
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def _counts(kinds) -> Dict[str, int]:
    return {k: kinds.count(k) for k in set(kinds)}


def layer_runs(kinds: Tuple[str, ...]) -> Tuple[int, List[Tuple[str, int, int]]]:
    """(number of periods, runs of one period): the shortest prefix that
    repeats to give ``kinds``, cut into runs of like layers — (kind, rank of
    the run's first layer among the period's layers of that kind, length)."""
    n = len(kinds)
    period = next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))
    return n // period, _runs(kinds[:period], {})


def layer_plan(kinds: Tuple[str, ...]) -> List[Tuple[int, List[Tuple[str, int, int]], Dict[str, int]]]:
    """The stack as segments ``(repeats, runs of one unit, layers of each
    kind a unit)``, in model order: each segment is one ``lax.scan`` over its
    ``repeats`` of the unit (inline where 1), a run's first layer of repeat
    ``p`` has rank ``first + p * a unit's layers of its kind`` among ALL the
    stack's layers of its kind. A list that is a whole repeat is one segment
    (:func:`layer_runs`: granite-4.0-h-micro, 4 periods of 10). Any other is
    cut greedily: at each layer the unit that, repeated at least twice,
    covers most (the shorter of two that cover alike), else the layer joins
    an inline segment. ``MEMEM*EMEMEM*`` is (ME) x 2, M *, (EM) x 3, *: seven
    compiled bodies for thirteen blocks, and for ANY depth of such a pattern
    a number set by its units, not by its length."""
    kinds = tuple(kinds)
    periods, runs = layer_runs(kinds)
    if periods > 1:
        return [(periods, runs, _counts(kinds[: len(kinds) // periods]))]
    plan, seen, inline, i, n = [], {}, [], 0, len(kinds)

    def flush():
        if inline:
            plan.append((1, _runs(tuple(inline), seen), _counts(inline)))
            inline.clear()

    while i < n:
        best = (0, 0, 0)  # (covered, -unit, repeats)
        for u in range(1, (n - i) // 2 + 1):
            unit, r = kinds[i : i + u], 1
            while kinds[i + r * u : i + (r + 1) * u] == unit:
                r += 1
            if r > 1:
                best = max(best, (u * r, -u, r))
        covered, neg_u, r = best
        if not covered:
            inline.append(kinds[i])
            i += 1
            continue
        flush()
        unit = kinds[i : i - neg_u]
        plan.append((r, _runs(unit, dict(seen)), _counts(unit)))
        for k in unit:
            seen[k] = seen.get(k, 0) + r
        i += covered
    flush()
    return plan


def _take(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def mamba_layer(lp, hidden, state: ssm.RecurrentState, li, valid, reset,
                spec: ModelSpec, sspec: ssm.SSMSpec, mlp_fn, slots=None):
    """One state-space layer: hidden (R, Q, H); ``state`` the stacked
    per-slot state of ALL such layers, advanced at index ``li`` for the
    ``valid`` (R, Q) positions; ``reset`` (R,) rows start from zero.
    ``slots`` (R,): the slot each row's state is taken from at entry and
    written back to at exit (the chunk program; an index past the last slot
    = an empty row, whose write is dropped); None = row r owns slot r (the
    decode program, one row per slot)."""
    R, Q, _ = hidden.shape
    Hn, Pd, N, G = sspec.num_heads, sspec.head_dim, sspec.state_size, sspec.n_groups
    d_inner, conv_dim = sspec.d_inner, sspec.conv_dim
    f32 = jnp.float32
    m = lp["mixer"]
    with jax.named_scope("layer.norm"):
        x = rms_norm(hidden, lp["input_layernorm"]["weight"], spec.rms_eps)
    with jax.named_scope("layer.ssm"):
        # the published in_proj, 2048 -> [z | xBC | dt] = 4096 + 4352 + 64, held as
        # two matrices: 8512 columns are not a whole number of 128-lane tiles, and
        # the compiler then copies the STACKED weight (1.26 GB) into another layout
        # on every step; 8448 and 64 it takes as they are
        proj = linear(m["in_proj"], x)
        z, xBC = proj[..., :d_inner], proj[..., d_inner:]
        dt = jax.nn.softplus(linear(m["dt_proj"], x).astype(f32) + m["dt_bias"].astype(f32))
        A = -jnp.exp(m["A_log"].astype(f32))

        xBC, conv = ssm.conv_with_carry(
            state.conv, li, xBC, m["conv1d"]["weight"], m["conv1d"]["bias"], valid, reset, slots)
        xBC = xBC.astype(hidden.dtype)
        xs = xBC[..., :d_inner].reshape(R, Q, Hn, Pd)
        Bm = xBC[..., d_inner : d_inner + G * N].reshape(R, Q, G, N)
        Cm = xBC[..., d_inner + G * N :].reshape(R, Q, G, N)

        if Q == 1 and slots is None:
            from neuronx_distributed_inference_tpu.ops.ssm_state_update import ssm_state_update

            y, new_ssm = ssm_state_update(
                state.ssm, li, xs[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A,
                valid[:, 0], reset, interpret=kernel_interpret(),
            )
            y = y[:, None]
        else:
            s = ssm.rows_state(state.ssm, li, reset, slots)
            y, s = ssm.mamba2_chunk(xs, Bm, Cm, dt, A, s, valid, chunk_size=sspec.chunk_size)
            new_ssm = ssm.put_rows_state(state.ssm, s, li, slots)
        y = (y + m["D"].astype(f32)[None, None, :, None] * xs.astype(f32)).astype(hidden.dtype)
        gated = ssm.gated_rms_norm(y.reshape(R, Q, d_inner), z, m["norm"]["weight"], sspec.rms_eps,
                                   groups=sspec.norm_groups)
        hidden = residual_add(hidden, linear(m["out_proj"], gated), spec)
    if "mlp" in lp:  # a layer of two parts (granite); a single-part block ends here
        hidden = _decoder_layer_mlp(lp, hidden, spec, mlp_fn)
    return hidden, ssm.RecurrentState(conv=conv, ssm=new_ssm)


def moe_block(lp, hidden, spec: ModelSpec, expert_mlp, valid=None):
    """A block that is an expert layer alone: norm, router, experts (and the
    shared expert), residual. ``valid`` (B, S): the real positions (a padded
    position of a chunk pass is routed to no expert). Returns (hidden, the
    experts each position chose (B, S, k) under ``spec.output_choices``, else
    None)."""
    with jax.named_scope("layer.norm"):
        x = rms_norm(hidden, lp["input_layernorm"]["weight"], spec.rms_eps)
    with jax.named_scope("layer.mlp"):
        out = expert_mlp(lp["mlp"], x, spec, valid=valid)
        out, picked = out if spec.output_choices else (out, None)
        return residual_add(hidden, out, spec), picked


class HybridStack(LayerStack):
    """Runs a stack of ``layer_types`` over ``HybridBlockCache``: blocks of
    kind MAMBA (a state-space mixer), ATTENTION (paged K/V) and MOE (an
    expert layer alone; ``expert_mlp`` a modules/moe.ExpertMlp). A MAMBA or
    ATTENTION block whose parameters hold an ``mlp`` is a layer of two parts
    (granite: the mixer, then the MLP, in one body); one that holds none is
    the mixer alone. Each kind's weights stay stacked over ITS layers and a
    block reads its own with a computed index (:func:`layer_plan`).

    Three more kinds of single-part block, for a model whose every layer is
    a mixer block and then an MLP block (models/kimi_linear.py): KDA (a
    delta-rule mixer over the per-slot state, ``kspec`` a modules/kda.KDASpec),
    MLA (latent attention over a pool of latents, ``mla`` a
    models/deepseek.MLASpec) and DENSE (the gated ``mlp_fn`` alone).

    POWER (models/brumby.py) is a layer of two parts as granite's are: a
    power-retention mixer over the per-slot state (``pspec`` a
    modules/power_retention.PowerSpec; Qwen3's projections, QK-norm and
    rotation before it), then the MLP. A stack with no block of a paging
    kind reads neither the block table nor the pool: nothing of either is
    in its two programs."""

    def __init__(self, layer_types: Tuple[str, ...], sspec: ssm.SSMSpec = None, expert_mlp=None,
                 kspec=None, mla=None, pspec=None):
        self.layer_types = tuple(layer_types)
        self.sspec = sspec
        self.expert_mlp = expert_mlp
        self.kspec, self.mla, self.pspec = kspec, mla, pspec
        self.plan = layer_plan(self.layer_types)

    def __call__(self, params, hidden, cache, inputs, *, spec, phase, mlp_fn):
        if phase != PHASE_TOKEN_GENERATION or inputs.block_table is None:
            raise NotImplementedError(
                "a stack with per-slot state runs on the paged serving path only "
                "(chunk and decode programs of the token-generation runner)"
            )
        if not isinstance(cache, HybridBlockCache):
            raise TypeError(f"expected a HybridBlockCache, got {type(cache).__name__}")
        positions = inputs.position_ids
        valid, reset, slots = slot_state_rows(inputs, cache.state.num_slots)
        paging = any(kind in _PAGING for kind in self.layer_types)
        block_inputs = paged_block_inputs(inputs, cache.block_size) if paging else None
        mask = build_mask(inputs, spec, phase) if paging else None
        layers = params["layers"]
        # the carry: hidden, pool, state and, where the step returns its
        # choices, every expert layer's (L_moe, B, S, k)
        n_moe = self.layer_types.count(MOE)
        choices = spec.output_choices and n_moe > 0

        def attention(carry, li):
            h, k, v, *rest = carry
            lp = _take(layers[ATTENTION], li)
            h, k, v = decoder_layer(
                lp, h, None, None, k, v, li, mask,
                inputs.seq_ids, positions, spec, phase, mlp_fn if "mlp" in lp else None,
                block_inputs=block_inputs,
            )
            return (h, k, v, *rest), None

        def mamba(carry, li):
            h, k, v, st, *rest = carry
            h, st = mamba_layer(
                _take(layers[MAMBA], li), h, st, li, valid, reset, spec, self.sspec, mlp_fn,
                slots=slots,
            )
            return (h, k, v, st, *rest), None

        def kda(carry, li):
            from neuronx_distributed_inference_tpu.modules.kda import kda_mixer

            h, k, v, st, *rest = carry
            lp = _take(layers[KDA], li)
            with jax.named_scope("layer.norm"):
                x = rms_norm(h, lp["input_layernorm"]["weight"], spec.rms_eps)
            with jax.named_scope("layer.kda"):
                out, st = kda_mixer(lp["mixer"], x, st, li, valid, reset, self.kspec, slots=slots)
                h = residual_add(h, out, spec)
            return (h, k, v, st, *rest), None

        if POWER in self.layer_types:  # the rotary tables, once for every layer
            from neuronx_distributed_inference_tpu.modules.rope import rope_cos_sin

            cos, sin = rope_cos_sin(positions, params["rope"]["inv_freq"], spec.attention_scaling)

        def power(carry, li):
            from neuronx_distributed_inference_tpu.modules.power_retention import power_mixer

            h, k, v, st, *rest = carry
            lp = _take(layers[POWER], li)
            with jax.named_scope("layer.norm"):
                x = rms_norm(h, lp["input_layernorm"]["weight"], spec.rms_eps)
            with jax.named_scope("layer.power"):
                out, st = power_mixer(
                    lp["self_attn"], x, cos, sin, st, li, valid, reset, self.pspec,
                    spec.attn.rms_norm_eps, slots=slots,
                )
                h = residual_add(h, out, spec)
            h = _decoder_layer_mlp(lp, h, spec, mlp_fn)
            return (h, k, v, st, *rest), None

        def mla(carry, li):
            from neuronx_distributed_inference_tpu.models.deepseek import mla_decoder_layer

            h, k, v, *rest = carry
            h, k, v = mla_decoder_layer(
                _take(layers[MLA], li), h, None, None, k, v, li, mask, inputs.seq_ids,
                positions, spec, phase, None, mla=self.mla, block_inputs=block_inputs,
            )
            return (h, k, v, *rest), None

        def dense(carry, li):
            h, *rest = carry
            lp = _take(layers[DENSE], li)
            with jax.named_scope("layer.norm"):
                x = rms_norm(h, lp["input_layernorm"]["weight"], spec.rms_eps)
            with jax.named_scope("layer.mlp"):
                h = residual_add(h, mlp_fn(lp["mlp"], x, spec), spec)
            return (h, *rest), None

        bodies = {MAMBA: mamba, ATTENTION: attention, KDA: kda, MLA: mla, DENSE: dense,
                  POWER: power}
        if n_moe:
            from neuronx_distributed_inference_tpu.modules.moe import (
                hoist_expert_stacks,
                place_expert_stacks,
            )

            B, S, _ = hidden.shape
            # a pass that takes the grouped-matmul kernel reads the experts
            # from the stacks in place (never a layer's slice of them)
            moe_layers, expert_stacks = hoist_expert_stacks(
                layers[MOE], self.expert_mlp.spec, S, B * S, hidden.dtype
            )

            def moe(carry, li):
                h, *rest = carry
                lp = place_expert_stacks(_take(moe_layers, li), expert_stacks, li)
                h, picked = moe_block(lp, h, spec, self.expert_mlp, valid)
                if choices:
                    rest[-1] = jax.lax.dynamic_update_index_in_dim(rest[-1], picked, li, 0)
                return (h, *rest), None

            bodies[MOE] = moe

        def unit(runs, per_unit):
            def run(carry, p):
                for kind, first, count in runs:
                    base = p * per_unit[kind] + first
                    if count == 1:
                        carry, _ = bodies[kind](carry, base)
                    else:
                        carry, _ = jax.lax.scan(
                            bodies[kind], carry, base + jnp.arange(count, dtype=jnp.int32)
                        )
                return carry, None

            return run

        carry = (hidden, cache.k, cache.v, cache.state)
        if choices:
            top_k = self.expert_mlp.spec.top_k
            carry += (jnp.zeros((n_moe,) + hidden.shape[:2] + (top_k,), jnp.int32),)
        for repeats, runs, per_unit in self.plan:
            if repeats == 1:
                carry, _ = unit(runs, per_unit)(carry, jnp.int32(0))
            else:
                carry, _ = jax.lax.scan(
                    unit(runs, per_unit), carry, jnp.arange(repeats, dtype=jnp.int32)
                )
        hidden, k, v, state, *rest = carry
        new_cache = HybridBlockCache(k=k, v=v, state=state)
        if not choices:
            return hidden, new_cache
        # (L_moe, B, S, k) -> (B, S, L_moe, k)
        return hidden, new_cache, {EXPERT_CHOICES: jnp.transpose(rest[0], (1, 2, 0, 3))}


@register_model("granitemoehybrid")
class GraniteHybridModelBuilder(DecoderModelBuilder):
    """Granite-4.0-H: Mamba-2 + GQA attention, dense shared MLP."""

    config_cls = GraniteHybridInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        tc = config.tpu_config
        self.layer_types = tuple(config.layer_types)
        self.n_mamba = self.layer_types.count(MAMBA)
        self.n_attention = self.layer_types.count(ATTENTION)
        if self.n_mamba and not (tc.is_block_kv_layout and tc.is_chunked_prefill):
            raise NotImplementedError(
                "granitemoehybrid is served on the paged, chunked path only: set "
                "is_block_kv_layout, is_chunked_prefill and is_continuous_batching"
            )

    def ssm_spec(self) -> ssm.SSMSpec:
        cfg = self.config
        return ssm.SSMSpec(
            num_heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
            state_size=cfg.mamba_d_state, n_groups=getattr(cfg, "mamba_n_groups", 1),
            conv_kernel=cfg.mamba_d_conv, chunk_size=getattr(cfg, "mamba_chunk_size", 256),
            rms_eps=getattr(cfg, "rms_norm_eps", 1e-5),
        )

    def attn_spec(self):
        return dataclasses.replace(
            super().attn_spec(), scale=float(self.config.attention_multiplier), use_rope=False
        )

    def model_spec(self) -> ModelSpec:
        cfg = self.config
        return dataclasses.replace(
            super().model_spec(),
            intermediate_size=cfg.shared_intermediate_size,
            embedding_multiplier=float(getattr(cfg, "embedding_multiplier", 1.0)),
            residual_multiplier=float(getattr(cfg, "residual_multiplier", 1.0)),
            logits_scaling=float(getattr(cfg, "logits_scaling", 1.0)),
        )

    def layer_fn(self):
        return HybridStack(self.layer_types, self.ssm_spec())

    # ---- what each layer keeps -------------------------------------------

    def cache_layers(self):
        return tuple(SLOT_STATE if k == MAMBA else PAGED_KV for k in self.layer_types)

    def init_slot_state(self, num_slots: int):
        if not self.n_mamba:
            return None
        state = ssm.init_recurrent_state(
            self.ssm_spec(), self.n_mamba, num_slots, to_dtype(self.config.tpu_config.dtype)
        )
        return state, ssm.recurrent_state_pspecs()

    # ---- params ------------------------------------------------------------

    def _common_shapes(self, L: int) -> Dict:
        H, I = self.config.hidden_size, self.config.shared_intermediate_size
        return {
            "input_layernorm": {"weight": (L, H)},
            "post_attention_layernorm": {"weight": (L, H)},
            "mlp": {
                "gate_proj": {"weight": (L, H, I)},
                "up_proj": {"weight": (L, H, I)},
                "down_proj": {"weight": (L, I, H)},
            },
        }

    def param_shapes(self) -> Dict:
        cfg = self.config
        H, D = cfg.hidden_size, self.head_dim
        Hq, Hkv = self.gqa.q_heads, self.gqa.kv_heads
        s = self.ssm_spec()
        Lm, La = self.n_mamba, self.n_attention
        if cfg.tpu_config.fused_qkv:
            attn = {"qkv_proj": {"weight": (La, H, (Hq + 2 * Hkv) * D)}}
        else:
            attn = {
                "q_proj": {"weight": (La, H, Hq * D)},
                "k_proj": {"weight": (La, H, Hkv * D)},
                "v_proj": {"weight": (La, H, Hkv * D)},
            }
        attn["o_proj"] = {"weight": (La, Hq * D, H)}
        shapes = {
            "embed_tokens": {"weight": (self.padded_vocab, H)},
            "layers": {
                MAMBA: {
                    **self._common_shapes(Lm),
                    "mixer": {
                        "in_proj": {"weight": (Lm, H, s.d_inner + s.conv_dim)},
                        "dt_proj": {"weight": (Lm, H, s.num_heads)},
                        "conv1d": {"weight": (Lm, s.conv_kernel, s.conv_dim),
                                   "bias": (Lm, s.conv_dim)},
                        "A_log": (Lm, s.num_heads),
                        "D": (Lm, s.num_heads),
                        "dt_bias": (Lm, s.num_heads),
                        "norm": {"weight": (Lm, s.d_inner)},
                        "out_proj": {"weight": (Lm, s.d_inner, H)},
                    },
                },
                ATTENTION: {**self._common_shapes(La), "self_attn": attn},
            },
            "norm": {"weight": (H,)},
        }
        if not getattr(cfg, "tie_word_embeddings", False):
            shapes["lm_head"] = {"weight": (H, self.padded_vocab)}
        return shapes

    def param_pspecs(self) -> Dict:
        # tp_degree 1 (config.validate_slot_state_serving): everything replicated
        specs = jax.tree.map(
            lambda _: P(), self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)
        )
        specs["lm_head"] = {"weight": P()}
        return specs

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        """Random init for tests: matrices N(0, 0.02), norm weights 1, and the
        PUBLISHED initialisation of the recurrence (``A_log = log(1..heads)``,
        ``dt_bias`` the inverse softplus of dt log-uniform in 1e-3..1e-1,
        ``D = 1``): slow-decay heads, under which a lost carry shows."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        params = self.random_tree(self.param_shapes(), key, dtype, on_host, std=0.02)
        ones = lambda a: jnp.ones_like(jnp.asarray(a))
        for kind in (MAMBA, ATTENTION):
            for name in ("input_layernorm", "post_attention_layernorm"):
                params["layers"][kind][name]["weight"] = ones(params["layers"][kind][name]["weight"])
        params["norm"]["weight"] = ones(params["norm"]["weight"])
        mixer = params["layers"][MAMBA]["mixer"]
        Lm, Hn = self.n_mamba, self.config.mamba_n_heads
        rng = np.random.default_rng(self.config.tpu_config.seed)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (Lm, Hn)))
        mixer["norm"]["weight"] = ones(mixer["norm"]["weight"])
        mixer["A_log"] = jnp.asarray(np.tile(np.log(np.arange(1, Hn + 1.0)), (Lm, 1)), dtype)
        mixer["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)
        mixer["D"] = jnp.ones((Lm, Hn), dtype)
        if getattr(self.config, "tie_word_embeddings", False):
            params["lm_head"] = {"weight": jnp.asarray(params["embed_tokens"]["weight"]).T}
        return params

    HF_MAMBA = "mamba."
    HF_MLP = "shared_mlp."

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """HF ``GraniteMoeHybridForCausalLM`` checkpoint -> the stacked tree."""
        cfg = self.config
        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        I = cfg.shared_intermediate_size

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")
            return np.asarray(sd[name])

        idx = {kind: [i for i, k in enumerate(self.layer_types) if k == kind]
               for kind in (MAMBA, ATTENTION)}

        def stack(kind, fn):
            return jnp.asarray(
                np.stack([fn(self.HF_LAYER_PREFIX.format(i=i)) for i in idx[kind]]), dtype
            )

        def common(kind):
            return {
                "input_layernorm": {"weight": stack(kind, lambda p: get(p + "input_layernorm.weight"))},
                "post_attention_layernorm": {
                    "weight": stack(kind, lambda p: get(p + "post_attention_layernorm.weight"))},
                "mlp": {
                    "gate_proj": {"weight": stack(
                        kind, lambda p: get(p + self.HF_MLP + "input_linear.weight")[:I].T)},
                    "up_proj": {"weight": stack(
                        kind, lambda p: get(p + self.HF_MLP + "input_linear.weight")[I:].T)},
                    "down_proj": {"weight": stack(
                        kind, lambda p: get(p + self.HF_MLP + "output_linear.weight").T)},
                },
            }

        mm = lambda name: (lambda p: get(p + self.HF_MAMBA + name))
        embed = get(self.HF_EMBED)
        vpad = self.padded_vocab - embed.shape[0]
        if vpad:
            embed = np.pad(embed, ((0, vpad), (0, 0)))
        params = {
            "embed_tokens": {"weight": jnp.asarray(embed, dtype)},
            "layers": {
                MAMBA: {
                    **common(MAMBA),
                    "mixer": {
                        # HF in_proj rows are [z | xBC | dt]
                        "in_proj": {"weight": stack(
                            MAMBA, lambda p: mm("in_proj.weight")(p)[:-cfg.mamba_n_heads].T)},
                        "dt_proj": {"weight": stack(
                            MAMBA, lambda p: mm("in_proj.weight")(p)[-cfg.mamba_n_heads:].T)},
                        # HF depthwise conv weight (conv_dim, 1, K) -> (K, conv_dim)
                        "conv1d": {
                            "weight": stack(MAMBA, lambda p: mm("conv1d.weight")(p)[:, 0, :].T),
                            "bias": stack(MAMBA, mm("conv1d.bias")),
                        },
                        "A_log": stack(MAMBA, mm("A_log")),
                        "D": stack(MAMBA, mm("D")),
                        "dt_bias": stack(MAMBA, mm("dt_bias")),
                        "norm": {"weight": stack(MAMBA, mm("norm.weight"))},
                        "out_proj": {"weight": stack(MAMBA, lambda p: mm("out_proj.weight")(p).T)},
                    },
                },
                ATTENTION: {
                    **common(ATTENTION),
                    "self_attn": {
                        n: {"weight": stack(ATTENTION, lambda p, n=n: get(p + f"self_attn.{n}.weight").T)}
                        for n in ("q_proj", "k_proj", "v_proj", "o_proj")
                    },
                },
            },
            "norm": {"weight": jnp.asarray(get(self.HF_NORM), dtype)},
        }
        if cfg.tpu_config.fused_qkv:
            sa = params["layers"][ATTENTION]["self_attn"]
            sa["qkv_proj"] = {"weight": jnp.concatenate(
                [sa.pop(n)["weight"] for n in ("q_proj", "k_proj", "v_proj")], axis=-1)}
        if getattr(cfg, "tie_word_embeddings", False):
            params["lm_head"] = {"weight": params["embed_tokens"]["weight"].T}
        else:
            lm = get(self.HF_LM_HEAD).T
            if vpad:
                lm = np.pad(lm, ((0, 0), (0, vpad)))
            params["lm_head"] = {"weight": jnp.asarray(lm, dtype)}
        return params
