"""NVIDIA Nemotron-H (``model_type: "nemotron_h"``; Nemotron-3-Nano-30B-A3B)
model plugin: ONE stack of three kinds of single-part block.

Published: the ``nemotron_h`` modeling (``modeling_nemotron_h.py`` beside the
checkpoint; ``transformers`` 4.57.6 here has no such module, so the forms the
config does not pin are written out in ``benchmark/harness/references/
nemotron_h.py``, which tier-1 holds this file to and whose parts tier-1
holds to the installed ``mamba2`` and ``deepseek_v3`` modules). Block ``l``
of kind ``hybrid_override_pattern[l]`` is one norm, one part, one residual:

    h = h + Mixer_l(rmsnorm_l(h)),      logits = rmsnorm_f(h) @ lm_head,   h0 = embed[ids]

* ``M``: a Mamba-2 mixer (modules/ssm.py; models/granite_hybrid.mamba_layer)
  with ``n_groups`` groups of B/C (head ``h`` reads group ``h // (heads /
  n_groups)``), ``d_inner = mamba_num_heads * mamba_head_dim`` (NOT ``expand
  * hidden_size``) and a gated norm that divides each GROUP of ``d_inner /
  n_groups`` channels by its own root mean square.
* ``E``: top-k of ``n_routed_experts`` experts of TWO matrices,
  ``down(relu(up x)^2)``, behind a sigmoid router with a selection bias
  (modules/moe.router_top_k: the DeepSeek-V3 gate, weights renormalised over
  the chosen, times ``routed_scaling_factor``), beside one shared expert of
  the same form.
* ``*``: GQA attention with NO rotation of q and k (order reaches it through
  the state-space blocks), scale ``1 / sqrt(head_dim)``.
* ``-`` (a dense two-matrix MLP of ``intermediate_size``): not built; no
  published pattern of this size holds one. Refused at config time.

WHAT A BLOCK KEEPS: ``M`` a constant per-slot state, ``*`` paged K/V, ``E``
nothing: ``HybridBlockCache``, run by models/granite_hybrid.HybridStack over
:func:`~.granite_hybrid.layer_plan`'s segments (each kind's weights stacked
over its own blocks, read with a computed index).

A HELD SHARE of the experts (one rank of an expert-parallel group, served
without its exchange) is the model configuration's own to state, in one key
beside the published ones: ``expert_share = {"first": r, "of": n}`` makes
``n_routed_experts`` the count HELD here, rank ``r`` of ``n`` equal shares of
the published ``n_routed_experts * n`` (``n_routed_experts_published``, if
given, must say the same). The router keeps the published width and the six
choices; modules/moe.MoESpec has the rest.

Served on the paged, chunked, continuously batched path only; what a
per-slot state does not support is refused at config time
(config.validate_slot_state_serving), as is what two-matrix experts or a
held share do not (modules/moe.validate_expert_layer).

Checkpoint names (``convert_hf_state_dict``) are the published modeling's
(``backbone.layers.N.mixer.*``); no checkpoint can be read here, so that
path is held to the tree's shapes only.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    to_dtype,
    validate_slot_state_serving,
)
from neuronx_distributed_inference_tpu.models.base import ModelSpec
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.granite_hybrid import (
    ATTENTION,
    MAMBA,
    MOE,
    HybridStack,
)
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.modules.block_kvcache import PAGED_KV, SLOT_STATE
from neuronx_distributed_inference_tpu.modules.moe import (
    ExpertMlp,
    MoESpec,
    held_share,
    shared_expert_mlp,
    validate_expert_layer,
)

#: the pattern's letters
KINDS = {"M": MAMBA, "E": MOE, "*": ATTENTION}
#: what a block of each kind keeps between steps (None: nothing)
KEEPS = {MAMBA: SLOT_STATE, ATTENTION: PAGED_KV, MOE: None}


class NemotronHInferenceConfig(InferenceConfig):
    _REQUIRED_ATTRS = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_hidden_layers", "vocab_size", "hybrid_override_pattern",
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
        "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    )

    def add_derived_config(self):
        super().add_derived_config()
        self.rms_norm_eps = getattr(self, "layer_norm_epsilon", getattr(self, "norm_eps", 1e-5))
        self.hidden_act = getattr(self, "mlp_hidden_act", "relu2")
        #: the router's width, and the first expert held here
        self.published_experts, self.first_expert = held_share(self)

    def validate_config(self):
        super().validate_config()
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set(KINDS) - {"-"}:
            raise ValueError(
                "hybrid_override_pattern must name M, E, * or - for each of "
                f"num_hidden_layers={self.num_hidden_layers} blocks, got {pattern!r}"
            )
        unwritten = (
            ("-" in pattern, "a '-' block (a dense MLP of intermediate_size alone)"),
            (getattr(self, "mamba_hidden_act", "silu") != "silu", "mamba_hidden_act other than silu"),
            (getattr(self, "mlp_hidden_act", "relu2") != "relu2", "mlp_hidden_act other than relu2"),
            (getattr(self, "n_group", 1) != 1 or getattr(self, "topk_group", 1) != 1,
             "group-limited routing (n_group, topk_group != 1)"),
            (getattr(self, "attention_bias", False) or getattr(self, "mlp_bias", False)
             or getattr(self, "use_bias", False) or getattr(self, "mamba_proj_bias", False),
             "a bias on a projection (attention_bias, mlp_bias, use_bias, mamba_proj_bias)"),
            (not getattr(self, "use_conv_bias", True), "use_conv_bias false"),
            (getattr(self, "sliding_window", None), "sliding_window"),
            (getattr(self, "time_step_limit", None), "time_step_limit (a clamp on dt)"),
            (getattr(self, "n_shared_experts", 1) > 1, "more than one shared expert"),
            (getattr(self, "tie_word_embeddings", False), "tie_word_embeddings"),
            (self.mamba_num_heads % self.n_groups, "n_groups that does not divide mamba_num_heads"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"nemotron_h with {what} is not implemented")
        if "M" in pattern:
            validate_slot_state_serving(self.tpu_config)


@register_model("nemotron_h")
class NemotronHModelBuilder(DecoderModelBuilder):
    """Nemotron-H: Mamba-2, two-matrix relu2 experts and NoPE GQA blocks."""

    config_cls = NemotronHInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        tc = config.tpu_config
        self.layer_types = tuple(KINDS[c] for c in config.hybrid_override_pattern)
        self.counts = {kind: self.layer_types.count(kind) for kind in KINDS.values()}
        if self.counts[MAMBA] and not (tc.is_block_kv_layout and tc.is_chunked_prefill):
            raise NotImplementedError(
                "nemotron_h is served on the paged, chunked path only: set "
                "is_block_kv_layout, is_chunked_prefill and is_continuous_batching"
            )
        if tc.fused_qkv:
            raise NotImplementedError("nemotron_h with fused_qkv is not implemented")
        if self.counts[MOE]:
            validate_expert_layer(
                self.moe_spec(), self.param_shapes()["layers"][MOE]["mlp"]["experts"],
                quantized=bool(tc.quantized),
            )

    # ---- specs -------------------------------------------------------------

    def ssm_spec(self) -> ssm.SSMSpec:
        cfg = self.config
        return ssm.SSMSpec(
            num_heads=cfg.mamba_num_heads, head_dim=cfg.mamba_head_dim,
            state_size=cfg.ssm_state_size, n_groups=cfg.n_groups,
            conv_kernel=cfg.conv_kernel, chunk_size=getattr(cfg, "chunk_size", 128),
            rms_eps=cfg.rms_norm_eps, norm_groups=cfg.n_groups,
        )

    def attn_spec(self):
        return dataclasses.replace(super().attn_spec(), use_rope=False)

    def model_spec(self) -> ModelSpec:
        return dataclasses.replace(
            super().model_spec(), intermediate_size=self.config.moe_intermediate_size
        )

    def moe_spec(self) -> MoESpec:
        cfg = self.config
        tc = cfg.tpu_config
        return MoESpec(
            num_experts=cfg.published_experts,
            top_k=cfg.num_experts_per_tok,
            normalize_top_k_affinities=bool(getattr(cfg, "norm_topk_prob", True)),
            act=cfg.hidden_act,
            scoring_func="sigmoid",
            routed_scaling_factor=float(getattr(cfg, "routed_scaling_factor", 1.0)),
            ep_degree=tc.ep_degree,
            hybrid_cte_full_tp=bool(getattr(tc, "hybrid_sharding_config", None)),
            model_parallel=self.degree,
            held_experts=(
                cfg.n_routed_experts if cfg.n_routed_experts < cfg.published_experts else None),
            first_expert=cfg.first_expert,
        )

    def expert_layers(self):
        """(expert blocks, experts each holds HERE, experts per token)."""
        if not self.counts[MOE]:
            return None
        spec = self.moe_spec()
        return self.counts[MOE], spec.held, spec.top_k

    def layer_fn(self):
        shared = None
        if getattr(self.config, "n_shared_experts", 1):
            act = self.config.hidden_act
            shared = lambda p, x: shared_expert_mlp(p, x, act)
        expert_mlp = ExpertMlp(self.moe_spec(), shared) if self.counts[MOE] else None
        return HybridStack(self.layer_types, self.ssm_spec(), expert_mlp)

    # ---- what each block keeps --------------------------------------------

    def cache_layers(self):
        return tuple(KEEPS[k] for k in self.layer_types)

    def init_slot_state(self, num_slots: int):
        if not self.counts[MAMBA]:
            return None
        state = ssm.init_recurrent_state(
            self.ssm_spec(), self.counts[MAMBA], num_slots, to_dtype(self.config.tpu_config.dtype)
        )
        return state, ssm.recurrent_state_pspecs()

    # ---- params ------------------------------------------------------------

    def param_shapes(self) -> Dict:
        cfg = self.config
        H, D = cfg.hidden_size, self.head_dim
        Hq, Hkv = self.gqa.q_heads, self.gqa.kv_heads
        s = self.ssm_spec()
        Lm, La, Le = (self.counts[k] for k in (MAMBA, ATTENTION, MOE))
        E, I = self.moe_spec().held, cfg.moe_intermediate_size
        Is = getattr(cfg, "moe_shared_expert_intermediate_size", I)
        layers = {}
        if Lm:
            layers[MAMBA] = {
                "input_layernorm": {"weight": (Lm, H)},
                "mixer": {
                    # the published in_proj [z | xBC | dt] held as two matrices
                    # (models/granite_hybrid.mamba_layer: whole lane tiles)
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
            }
        if La:
            layers[ATTENTION] = {
                "input_layernorm": {"weight": (La, H)},
                "self_attn": {
                    "q_proj": {"weight": (La, H, Hq * D)},
                    "k_proj": {"weight": (La, H, Hkv * D)},
                    "v_proj": {"weight": (La, H, Hkv * D)},
                    "o_proj": {"weight": (La, Hq * D, H)},
                },
            }
        if Le:
            mlp = {
                # the router keeps its published width whatever share is held
                "router": {"weight": (Le, H, cfg.published_experts),
                           "e_score_correction_bias": (Le, cfg.published_experts)},
                # two-matrix experts, both (E, width, hidden) (modules/moe.two_matrix)
                "experts": {"up_proj": {"weight": (Le, E, I, H)},
                            "down_proj": {"weight": (Le, E, I, H)}},
            }
            if getattr(cfg, "n_shared_experts", 1):
                mlp["shared_experts"] = {"up_proj": {"weight": (Le, H, Is)},
                                         "down_proj": {"weight": (Le, Is, H)}}
            layers[MOE] = {"input_layernorm": {"weight": (Le, H)}, "mlp": mlp}
        return {
            "embed_tokens": {"weight": (self.padded_vocab, H)},
            "layers": layers,
            "norm": {"weight": (H,)},
            "lm_head": {"weight": (H, self.padded_vocab)},
        }

    def param_pspecs(self) -> Dict:
        # tp_degree 1 (config.validate_slot_state_serving): everything replicated
        return jax.tree.map(
            lambda _: P(), self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)
        )

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        """Random init for tests: matrices N(0, 0.02) but the experts (0.05,
        so that an expert's output shows in the residual) and the selection
        bias (0.1: it changes which experts are chosen), norm weights 1, and
        the PUBLISHED initialisation of the recurrence (granite_hybrid)."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        std = {"experts": 0.05, "e_score_correction_bias": 0.1, "router": 0.5}
        params = self.random_tree_by_name(self.param_shapes(), std, key, dtype)
        if self.counts[MAMBA]:
            mixer = params["layers"][MAMBA]["mixer"]
            Lm, Hn = self.counts[MAMBA], self.config.mamba_num_heads
            rng = np.random.default_rng(self.config.tpu_config.seed)
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (Lm, Hn)))
            mixer["A_log"] = jnp.asarray(np.tile(np.log(np.arange(1, Hn + 1.0)), (Lm, 1)), dtype)
            mixer["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)
            mixer["D"] = jnp.ones((Lm, Hn), dtype)
        return params

    HF_LAYER_PREFIX = "backbone.layers.{i}."
    HF_EMBED = "backbone.embeddings.weight"
    HF_NORM = "backbone.norm_f.weight"

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """The published ``NemotronHForCausalLM`` names -> the stacked tree
        (a held share takes experts ``[first_expert, first_expert + held)``)."""
        cfg = self.config
        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        spec = self.moe_spec()

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")
            return np.asarray(sd[name])

        idx = {kind: [i for i, k in enumerate(self.layer_types) if k == kind] for kind in self.counts}

        def stack(kind, fn):
            return jnp.asarray(
                np.stack([fn(self.HF_LAYER_PREFIX.format(i=i)) for i in idx[kind]]), dtype
            )

        mx = lambda name, t=False: (
            lambda p: get(p + "mixer." + name).T if t else get(p + "mixer." + name))
        norm = lambda kind: {"weight": stack(kind, lambda p: get(p + "norm.weight"))}
        held = range(spec.first_expert, spec.first_expert + spec.held)
        Hn = cfg.mamba_num_heads
        layers = {}
        if idx[MAMBA]:
            layers[MAMBA] = {
                "input_layernorm": norm(MAMBA),
                "mixer": {
                    # HF in_proj rows are [z | xBC | dt]
                    "in_proj": {"weight": stack(MAMBA, lambda p: mx("in_proj.weight")(p)[:-Hn].T)},
                    "dt_proj": {"weight": stack(MAMBA, lambda p: mx("in_proj.weight")(p)[-Hn:].T)},
                    # HF depthwise conv weight (conv_dim, 1, K) -> (K, conv_dim)
                    "conv1d": {
                        "weight": stack(MAMBA, lambda p: mx("conv1d.weight")(p)[:, 0, :].T),
                        "bias": stack(MAMBA, mx("conv1d.bias")),
                    },
                    "A_log": stack(MAMBA, mx("A_log")),
                    "D": stack(MAMBA, mx("D")),
                    "dt_bias": stack(MAMBA, mx("dt_bias")),
                    "norm": {"weight": stack(MAMBA, mx("norm.weight"))},
                    "out_proj": {"weight": stack(MAMBA, mx("out_proj.weight", True))},
                },
            }
        if idx[ATTENTION]:
            layers[ATTENTION] = {
                "input_layernorm": norm(ATTENTION),
                "self_attn": {
                    n: {"weight": stack(ATTENTION, mx(n + ".weight", True))}
                    for n in ("q_proj", "k_proj", "v_proj", "o_proj")
                },
            }
        if idx[MOE]:
            experts = lambda name, t: (lambda p: np.stack(
                [mx(f"experts.{e}.{name}.weight", t)(p) for e in held]))
            mlp = {
                "router": {
                    "weight": stack(MOE, mx("gate.weight", True)),
                    "e_score_correction_bias": stack(MOE, mx("gate.e_score_correction_bias")),
                },
                "experts": {
                    # up as published (out, in); down (out, in) -> (in, out)
                    "up_proj": {"weight": stack(MOE, experts("up_proj", False))},
                    "down_proj": {"weight": stack(MOE, experts("down_proj", True))},
                },
            }
            if getattr(cfg, "n_shared_experts", 1):
                mlp["shared_experts"] = {
                    n: {"weight": stack(MOE, mx(f"shared_experts.{n}.weight", True))}
                    for n in ("up_proj", "down_proj")
                }
            layers[MOE] = {"input_layernorm": norm(MOE), "mlp": mlp}
        embed, lm = get(self.HF_EMBED), get(self.HF_LM_HEAD).T
        vpad = self.padded_vocab - embed.shape[0]
        if vpad:
            embed, lm = np.pad(embed, ((0, vpad), (0, 0))), np.pad(lm, ((0, 0), (0, vpad)))
        return {
            "embed_tokens": {"weight": jnp.asarray(embed, dtype)},
            "layers": layers,
            "norm": {"weight": jnp.asarray(get(self.HF_NORM), dtype)},
            "lm_head": {"weight": jnp.asarray(lm, dtype)},
        }
