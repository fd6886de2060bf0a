"""Kimi-Linear (``model_type: "kimi_linear"``; Kimi-Linear-48B-A3B) model
plugin: linear attention (KDA) and latent attention (MLA) mixed in ONE stack,
every layer a mixer and then an MLP.

Published: the Kimi Linear report (arXiv:2510.26692) and ``modeling_kimi.py``
beside the checkpoint; the installed ``transformers`` has no such module, so
the forms the config does not pin are written out in
``benchmark/harness/references/kimi_linear.py``, which tier-1 holds this file
to (tests/test_kimi_linear_reference.py) and whose recurrence tier-1 holds to
the installed ``qwen3_next`` gated delta rule. Layer ``l`` (numbered from 1,
as ``linear_attn_config`` numbers them):

    h = h + Mixer_l(rmsnorm(h)),   h = h + MLP_l(rmsnorm(h)),
    logits = rmsnorm_f(h) @ lm_head,   h0 = embed[ids]

* ``l`` in ``kda_layers``: Kimi Delta Attention (modules/kda.py): q, k, v
  through a depthwise causal conv of 4 and silu, q and k normalised a head,
  a decay a KEY CHANNEL from a low-rank projection, a step size a head, the
  delta-rule recurrence over a float32 matrix state a head, a gated norm a
  head. No position enters but through the recurrence.
* ``l`` in ``full_attn_layers``: DeepSeek-V3's latent attention
  (models/deepseek.mla_decoder_layer), ``q_lora_rank`` null, and NO rotation
  of the ``qk_rope_head_dim`` dimensions (``mla_use_nope``).
* MLP: a dense SwiGLU in the first ``first_k_dense_replace`` layers, then the
  DeepSeek-V3 expert layer (sigmoid scores, a choice bias, renormalised top-k
  times ``routed_scaling_factor``, shared experts; modules/moe.py).

WHAT A LAYER KEEPS: a KDA mixer a constant per-slot state
(modules/kda.DeltaState), an MLA mixer one latent and one key a token in the
pool (``cache_streams``: DeepSeek-V3's), an MLP nothing: ``HybridBlockCache``
with a LATENT pool beside a per-slot state. The stack is
models/granite_hybrid.HybridStack over single-part blocks: a layer is two of
them (KDA or MLA, then DENSE or MOE), each kind's weights stacked over its
own blocks.

A HELD SHARE of the experts is the configuration's own to state
(modules/moe.held_share): ``expert_share = {"first": r, "of": n}`` makes
``num_experts`` the count HELD here (``num_experts_published``, if given,
must be ``num_experts * n``); the router keeps the published width.

Served on the paged, chunked, continuously batched path only; what a
per-slot state or a latent pool does not support is refused at config time
(config.validate_slot_state_serving, config.validate_latent_attention).

Checkpoint names (``convert_hf_state_dict``) are the published modeling's;
no checkpoint can be read here, so that path is held to the tree's shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import to_dtype, validate_slot_state_serving
from neuronx_distributed_inference_tpu.models.base import ModelSpec, gated_mlp
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.deepseek import (
    DeepseekV3InferenceConfig,
    DeepseekV3ModelBuilder,
)
from neuronx_distributed_inference_tpu.models.granite_hybrid import (
    DENSE,
    KDA,
    MLA,
    MOE,
    HybridStack,
)
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import kda
from neuronx_distributed_inference_tpu.modules.block_kvcache import PAGED_KV, SLOT_STATE
from neuronx_distributed_inference_tpu.modules.moe import ExpertMlp, held_share, shared_expert_mlp

#: what a block of each kind keeps between steps (None: nothing)
KEEPS = {KDA: SLOT_STATE, MLA: PAGED_KV, DENSE: None, MOE: None}


class KimiLinearInferenceConfig(DeepseekV3InferenceConfig):
    _REQUIRED_ATTRS = DeepseekV3InferenceConfig._REQUIRED_ATTRS + (
        "linear_attn_config", "intermediate_size", "moe_intermediate_size",
        "num_experts", "num_experts_per_token",
    )

    def add_derived_config(self):
        super().add_derived_config()
        # the expert layer under DeepSeek-V3's key names, which models/deepseek.py
        # and modules/moe.held_share read
        for ours, theirs, default in (
            ("n_routed_experts", "num_experts", None),
            ("n_routed_experts_published", "num_experts_published", None),
            ("num_experts_per_tok", "num_experts_per_token", None),
            ("n_shared_experts", "num_shared_experts", 0),
            ("norm_topk_prob", "moe_renormalize", True),
            ("scoring_func", "moe_router_activation_func", "sigmoid"),
            ("n_group", "num_expert_group", 1),
        ):
            value = getattr(self, theirs, default)
            if value is not None:
                setattr(self, ours, value)
        lin = getattr(self, "linear_attn_config", None) or {}
        #: the mixer of each layer, in model order (the config numbers from 1)
        kinds = {int(l): KDA for l in lin.get("kda_layers", ())}
        kinds.update({int(l): MLA for l in lin.get("full_attn_layers", ())})
        self.mixer_kinds = tuple(
            kinds.get(l) for l in range(1, getattr(self, "num_hidden_layers", 0) + 1)
        )

    def validate_config(self):
        super().validate_config()
        lin = self.linear_attn_config
        listed = sorted(list(lin.get("kda_layers", ())) + list(lin.get("full_attn_layers", ())))
        if listed != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "linear_attn_config.kda_layers and full_attn_layers must name each of the layers "
                f"1..{self.num_hidden_layers} once, got {listed}"
            )
        unwritten = (
            (not getattr(self, "mla_use_nope", False),
             "mla_use_nope false (rotated latent attention in this stack)"),
            (self.n_group != 1 or getattr(self, "topk_group", 1) != 1,
             "group-limited routing (num_expert_group, topk_group != 1)"),
            (getattr(self, "moe_layer_freq", 1) != 1, "moe_layer_freq other than 1"),
            (self.scoring_func != "sigmoid", "moe_router_activation_func other than sigmoid"),
            (getattr(self, "hidden_act", "silu") != "silu", "hidden_act other than silu"),
            (getattr(self, "tie_word_embeddings", False), "tie_word_embeddings"),
            (getattr(self, "num_nextn_predict_layers", 0), "num_nextn_predict_layers"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"kimi_linear with {what} is not implemented")
        held_share(self)  # a share that is none is refused here, not at the first step
        if KDA in self.mixer_kinds:
            validate_slot_state_serving(
                self.tpu_config, what="linear-attention (KDA) layers", state="delta-rule state"
            )


@register_model("kimi_linear")
class KimiLinearModelBuilder(DeepseekV3ModelBuilder):
    """Kimi-Linear: KDA and NoPE MLA mixers, a dense MLP then expert MLPs."""

    config_cls = KimiLinearInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        tc = config.tpu_config
        mlps = tuple(DENSE if l < self.first_dense else MOE for l in range(config.num_hidden_layers))
        #: the stack as single-part blocks: each layer's mixer, then its MLP
        self.layer_types = tuple(b for pair in zip(config.mixer_kinds, mlps) for b in pair)
        self.counts = {kind: self.layer_types.count(kind) for kind in KEEPS}
        if not (tc.is_block_kv_layout and tc.is_chunked_prefill):
            raise NotImplementedError(
                "kimi_linear is served on the paged, chunked path only: set "
                "is_block_kv_layout, is_chunked_prefill and is_continuous_batching"
            )

    # ---- specs -------------------------------------------------------------

    def kda_spec(self) -> kda.KDASpec:
        cfg = self.config
        lin = cfg.linear_attn_config
        return kda.KDASpec(
            num_heads=lin["num_heads"], head_dim=lin["head_dim"],
            conv_kernel=lin.get("short_conv_kernel_size", 4), gate_rank=lin["head_dim"],
            rms_eps=getattr(cfg, "rms_norm_eps", 1e-5),
        )

    def mla_spec(self):
        return dataclasses.replace(super().mla_spec(), use_rope=False)

    def model_spec(self) -> ModelSpec:
        # one stack of blocks (HybridStack), not DeepSeek-V3's layer groups
        return DecoderModelBuilder.model_spec(self)

    def expert_layers(self):
        if not self.counts[MOE]:
            return None
        return self.counts[MOE], self.num_experts, self.moe_spec().top_k

    def mlp_fn(self):
        return gated_mlp

    def layer_fn(self):
        shared = None
        if getattr(self.config, "n_shared_experts", 0):
            shared = lambda p, x: shared_expert_mlp(p, x, "silu")
        expert_mlp = ExpertMlp(self.moe_spec(), shared) if self.counts[MOE] else None
        return HybridStack(
            self.layer_types, expert_mlp=expert_mlp, kspec=self.kda_spec(), mla=self.mla_spec()
        )

    # ---- what each block keeps --------------------------------------------

    def cache_layers(self):
        return tuple(KEEPS[k] for k in self.layer_types)

    def init_slot_state(self, num_slots: int):
        if not self.counts[KDA]:
            return None
        state = kda.init_delta_state(
            self.kda_spec(), self.counts[KDA], num_slots, to_dtype(self.config.tpu_config.dtype)
        )
        return state, kda.delta_state_pspecs()

    # ---- params ------------------------------------------------------------

    def param_shapes(self) -> Dict:
        cfg = self.config
        H = cfg.hidden_size
        s = self.kda_spec()
        Lk, La, Ld, Le = (self.counts[k] for k in (KDA, MLA, DENSE, MOE))
        norm = lambda L: {"weight": (L, H)}
        layers = {}
        if Lk:
            layers[KDA] = {
                "input_layernorm": norm(Lk),
                "mixer": {
                    # the published q_proj, k_proj, v_proj side by side, and their three
                    # depthwise convs as one over [q | k | v] (no bias)
                    "qkv_proj": {"weight": (Lk, H, s.conv_dim)},
                    "conv1d": {"weight": (Lk, s.conv_kernel, s.conv_dim)},
                    "f_a_proj": {"weight": (Lk, H, s.gate_rank)},
                    "f_b_proj": {"weight": (Lk, s.gate_rank, s.d_inner)},
                    "A_log": (Lk, s.num_heads),
                    "dt_bias": (Lk, s.d_inner),
                    "b_proj": {"weight": (Lk, H, s.num_heads)},
                    "g_a_proj": {"weight": (Lk, H, s.gate_rank)},
                    "g_b_proj": {"weight": (Lk, s.gate_rank, s.d_inner)},
                    "o_norm": {"weight": (Lk, s.head_dim)},
                    "o_proj": {"weight": (Lk, s.d_inner, H)},
                },
            }
        if La:
            layers[MLA] = {"input_layernorm": norm(La), "self_attn": self._attn_shapes(La)}
        if Ld:
            layers[DENSE] = {"input_layernorm": norm(Ld), "mlp": self._dense_mlp_shapes(Ld)}
        if Le:
            layers[MOE] = {"input_layernorm": norm(Le), "mlp": self._moe_mlp_shapes(Le)}
        return {
            "embed_tokens": {"weight": (self.padded_vocab, H)},
            "layers": layers,
            "norm": {"weight": (H,)},
            "lm_head": {"weight": (H, self.padded_vocab)},
        }

    def param_pspecs(self) -> Dict:
        # every degree is 1 (config.validate_slot_state_serving): everything replicated
        return jax.tree.map(
            lambda _: P(), self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)
        )

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        """Random init for tests: matrices N(0, 0.02) but the experts (0.05),
        the selection bias (0.1: it changes which experts are chosen), the
        router (0.5) and the conv taps (0.5: a conv output of unit size), norm
        weights 1, and the PUBLISHED initialisation of the decay (``A_log =
        log U(1, 16)``, ``dt_bias`` the inverse softplus of dt log-uniform in
        1e-3..1e-1): slow-decay heads, under which a lost carry shows."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        std = {"experts": 0.05, "e_score_correction_bias": 0.1, "router": 0.5, "conv1d": 0.5}
        params = self.random_tree_by_name(self.param_shapes(), std, key, dtype)
        if self.counts[KDA]:
            mixer = params["layers"][KDA]["mixer"]
            rng = np.random.default_rng(self.config.tpu_config.seed)
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), mixer["dt_bias"].shape))
            mixer["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, mixer["A_log"].shape)), dtype)
            mixer["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)
        return params

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """The published ``KimiLinearForCausalLM`` names -> the stacked tree
        (a held share takes experts ``[first_expert, first_expert + held)``)."""
        cfg = self.config
        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        Hq, d_nope, dv, r_kv = self.q_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")
            return np.asarray(sd[name])

        lt = lambda name: get(name).T  # (out, in) -> (in, out)
        mlps = self.layer_types[1::2]
        idx = {
            KDA: [i for i, k in enumerate(cfg.mixer_kinds) if k == KDA],
            MLA: [i for i, k in enumerate(cfg.mixer_kinds) if k == MLA],
            DENSE: [i for i, k in enumerate(mlps) if k == DENSE],
            MOE: [i for i, k in enumerate(mlps) if k == MOE],
        }

        def stack(kind, fn):
            return jnp.asarray(
                np.stack([fn(self.HF_LAYER_PREFIX.format(i=i)) for i in idx[kind]]), dtype
            )

        def tree(kind, fn):
            per = [fn(self.HF_LAYER_PREFIX.format(i=i)) for i in idx[kind]]
            return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs), dtype), *per)

        sa = lambda name, t=False: (
            lambda p: lt(p + "self_attn." + name) if t else get(p + "self_attn." + name))
        qkv = ("q", "k", "v")

        def mla_attn(p):
            p += "self_attn."
            # kv_b (heads x (d_nope + dv), r_kv) -> the absorption tensors
            wkv = get(p + "kv_b_proj.weight").reshape(Hq, d_nope + dv, r_kv)
            return {
                "q_proj": {"weight": lt(p + "q_proj.weight")},
                "kv_a_proj": {"weight": lt(p + "kv_a_proj_with_mqa.weight")},
                "kv_a_layernorm": {"weight": get(p + "kv_a_layernorm.weight")},
                "k_absorb": {"weight": wkv[:, :d_nope, :]},
                "v_absorb": {"weight": np.swapaxes(wkv[:, d_nope:, :], 1, 2)},
                "o_proj": {"weight": lt(p + "o_proj.weight")},
            }

        held = range(self.first_expert, self.first_expert + self.num_experts)

        def moe_mlp(p):
            p += "block_sparse_moe."
            # the published experts name their matrices w1 (gate), w3 (up), w2 (down)
            experts = lambda w: {"weight": np.stack([lt(p + f"experts.{e}.{w}.weight") for e in held])}
            out = {
                "router": {"weight": lt(p + "gate.weight"),
                           "e_score_correction_bias": get(p + "gate.e_score_correction_bias")},
                "experts": {"gate_proj": experts("w1"), "up_proj": experts("w3"),
                            "down_proj": experts("w2")},
            }
            if getattr(cfg, "n_shared_experts", 0):
                out["shared_experts"] = {
                    n: {"weight": lt(p + f"shared_experts.{n}.weight")}
                    for n in ("gate_proj", "up_proj", "down_proj")
                }
            return out

        layers = {}
        if idx[KDA]:
            layers[KDA] = {
                "input_layernorm": {"weight": stack(KDA, lambda p: get(p + "input_layernorm.weight"))},
                "mixer": {
                    "qkv_proj": {"weight": stack(KDA, lambda p: np.concatenate(
                        [lt(p + f"self_attn.{n}_proj.weight") for n in qkv], axis=1))},
                    # HF depthwise conv weights (channels, 1, K) -> (K, [q | k | v] channels)
                    "conv1d": {"weight": stack(KDA, lambda p: np.concatenate(
                        [get(p + f"self_attn.{n}_conv1d.weight")[:, 0, :].T for n in qkv], axis=1))},
                    "A_log": stack(KDA, lambda p: sa("A_log")(p).reshape(-1)),
                    "dt_bias": stack(KDA, sa("dt_bias")),
                    "o_norm": {"weight": stack(KDA, sa("o_norm.weight"))},
                    **{n: {"weight": stack(KDA, sa(n + ".weight", True))}
                       for n in ("f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "g_b_proj", "o_proj")},
                },
            }
        if idx[MLA]:
            layers[MLA] = {
                "input_layernorm": {"weight": stack(MLA, lambda p: get(p + "input_layernorm.weight"))},
                "self_attn": tree(MLA, mla_attn),
            }
        post = lambda kind: {"weight": stack(kind, lambda p: get(p + "post_attention_layernorm.weight"))}
        if idx[DENSE]:
            layers[DENSE] = {
                "input_layernorm": post(DENSE),
                "mlp": {n: {"weight": stack(DENSE, lambda p, n=n: lt(p + f"mlp.{n}.weight"))}
                        for n in ("gate_proj", "up_proj", "down_proj")},
            }
        if idx[MOE]:
            layers[MOE] = {"input_layernorm": post(MOE), "mlp": tree(MOE, moe_mlp)}
        embed, lm = get(self.HF_EMBED), lt(self.HF_LM_HEAD)
        vpad = self.padded_vocab - embed.shape[0]
        if vpad:
            embed, lm = np.pad(embed, ((0, vpad), (0, 0))), np.pad(lm, ((0, 0), (0, vpad)))
        return {
            "embed_tokens": {"weight": jnp.asarray(embed, dtype)},
            "layers": layers,
            "norm": {"weight": jnp.asarray(get(self.HF_NORM), dtype)},
            "lm_head": {"weight": jnp.asarray(lm, dtype)},
        }
