"""Mixtral + Qwen3-MoE model plugins.

Reference: models/mixtral/modeling_mixtral.py (330 LoC, MoE via
initialize_moe_module) and models/qwen3_moe/modeling_qwen3_moe.py (542 LoC).
Both reuse the llama decoder graph with the MoE block as mlp_fn.
"""

from __future__ import annotations

from typing import Dict

import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import InferenceConfig
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules.moe import ExpertMlp, MoESpec
from neuronx_distributed_inference_tpu.parallel.sharding import TENSOR


class MoEInferenceConfig(InferenceConfig):
    _REQUIRED_ATTRS = (
        "hidden_size",
        "num_attention_heads",
        "num_hidden_layers",
        "num_key_value_heads",
        "vocab_size",
    )


class MoEDecoderModelBuilder(DecoderModelBuilder):
    """Shared MoE builder: llama attention + MoE mlp block."""

    config_cls = MoEInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        # base builder shapes read intermediate_size; for checkpoints that
        # only declare moe_intermediate_size, alias it up front
        if getattr(config, "intermediate_size", None) is None:
            config.intermediate_size = self.expert_intermediate

    # HF name templates (mixtral layout); qwen3-moe overrides
    HF_ROUTER = "block_sparse_moe.gate.weight"
    HF_EXPERT_GATE = "block_sparse_moe.experts.{e}.w1.weight"
    HF_EXPERT_DOWN = "block_sparse_moe.experts.{e}.w2.weight"
    HF_EXPERT_UP = "block_sparse_moe.experts.{e}.w3.weight"

    @property
    def num_experts(self) -> int:
        for attr in ("num_local_experts", "num_experts"):
            n = getattr(self.config, attr, None)
            if n is not None:
                return n
        raise ValueError(
            "MoE config needs num_local_experts (mixtral) or num_experts (qwen3-moe)"
        )

    @property
    def expert_intermediate(self) -> int:
        for attr in ("moe_intermediate_size", "intermediate_size"):
            n = getattr(self.config, attr, None)
            if n is not None:
                return n
        raise ValueError("MoE config needs moe_intermediate_size or intermediate_size")

    def _check_all_sparse(self):
        """Stacked-scan layers require a homogeneous decoder; mixed
        dense/sparse checkpoints need layer grouping (planned — reference
        supports it via per-layer module init, moe_v2.py:23)."""
        cfg = self.config
        if getattr(cfg, "mlp_only_layers", None):
            raise NotImplementedError(
                "mixed dense/sparse layers (mlp_only_layers) not yet supported"
            )
        step = getattr(cfg, "decoder_sparse_step", 1)
        if step not in (0, 1):
            raise NotImplementedError(
                f"decoder_sparse_step={step} (mixed dense/sparse) not yet supported"
            )

    def moe_spec(self) -> MoESpec:
        cfg = self.config
        tc = cfg.tpu_config
        return MoESpec(
            num_experts=self.num_experts,
            top_k=getattr(cfg, "num_experts_per_tok", 2),
            normalize_top_k_affinities=bool(getattr(cfg, "norm_topk_prob", True)),
            router_dtype=getattr(tc, "router_dtype", "float32"),
            act=getattr(cfg, "hidden_act", "silu"),
            early_affinity_modulation=bool(
                getattr(tc, "early_expert_affinity_modulation", False)
            ),
            # MoETpuConfig activation knobs honored by every MoE model
            # (reference MoENeuronConfig, config.py:679-680)
            act_scale=float(getattr(tc, "hidden_act_scaling_factor", 1.0)),
            act_bias=float(getattr(tc, "hidden_act_bias", 0.0)),
            ep_degree=tc.ep_degree,
            hybrid_cte_full_tp=bool(getattr(tc, "hybrid_sharding_config", None)),
            model_parallel=self.degree,
        )

    def param_shapes(self) -> Dict:
        shapes = super().param_shapes()
        cfg = self.config
        L, H = cfg.num_hidden_layers, cfg.hidden_size
        E, I = self.num_experts, self.expert_intermediate
        shapes["layers"]["mlp"] = {
            "router": {"weight": (L, H, E)},
            "experts": {
                "gate_proj": {"weight": (L, E, H, I)},
                "up_proj": {"weight": (L, E, H, I)},
                "down_proj": {"weight": (L, E, I, H)},
            },
        }
        return shapes

    def param_pspecs(self) -> Dict:
        specs = super().param_pspecs()
        # experts over ep; expert ffn over (cp, tp) (reference moe_tp×moe_ep
        # groups, moe_v2.py:134-160)
        ffn = ("cp", "tp")
        specs["layers"]["mlp"] = {
            "router": {"weight": P()},
            "experts": {
                "gate_proj": {"weight": P(None, "ep", None, ffn)},
                "up_proj": {"weight": P(None, "ep", None, ffn)},
                "down_proj": {"weight": P(None, "ep", ffn, None)},
            },
        }
        return specs

    def convert_hf_state_dict(self, sd, dtype=None):
        self._check_all_sparse()
        # build dense-MLP-free base first by temporarily mapping expert names
        cfg = self.config
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.config import to_dtype

        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        L, E = cfg.num_hidden_layers, self.num_experts

        # base conversion needs mlp.{gate,up,down}_proj names; synthesize them
        # as zero-size placeholders then replace with real expert stacks
        sd = dict(sd)
        H, I = cfg.hidden_size, self.expert_intermediate
        zero_g = np.zeros((1, H), np.float32)
        for i in range(L):
            p = self.HF_LAYER_PREFIX.format(i=i)
            sd.setdefault(p + "mlp.gate_proj.weight", zero_g)
            sd.setdefault(p + "mlp.up_proj.weight", zero_g)
            sd.setdefault(p + "mlp.down_proj.weight", zero_g.T)
        params = super().convert_hf_state_dict(sd, dtype)

        def stack_experts(tmpl, transpose):
            per_layer = []
            for i in range(L):
                p = self.HF_LAYER_PREFIX.format(i=i)
                per_expert = [
                    np.asarray(sd[p + tmpl.format(e=e)]).T
                    if transpose
                    else np.asarray(sd[p + tmpl.format(e=e)])
                    for e in range(E)
                ]
                per_layer.append(np.stack(per_expert))
            return jnp.asarray(np.stack(per_layer), dtype)

        params["layers"]["mlp"] = {
            "router": {
                "weight": jnp.asarray(
                    np.stack(
                        [
                            np.asarray(
                                sd[self.HF_LAYER_PREFIX.format(i=i) + self.HF_ROUTER]
                            ).T
                            for i in range(L)
                        ]
                    ),
                    dtype,
                )
            },
            "experts": {
                "gate_proj": {"weight": stack_experts(self.HF_EXPERT_GATE, True)},
                "up_proj": {"weight": stack_experts(self.HF_EXPERT_UP, True)},
                "down_proj": {"weight": stack_experts(self.HF_EXPERT_DOWN, True)},
            },
        }
        return params

    def mlp_fn(self):
        return ExpertMlp(self.moe_spec())


@register_model("mixtral")
class MixtralModelBuilder(MoEDecoderModelBuilder):
    """Reference: models/mixtral/modeling_mixtral.py."""


@register_model("qwen3_moe")
class Qwen3MoeModelBuilder(MoEDecoderModelBuilder):
    """Reference: models/qwen3_moe/modeling_qwen3_moe.py — qk norm + MoE."""

    qk_norm = True
    HF_ROUTER = "mlp.gate.weight"
    HF_EXPERT_GATE = "mlp.experts.{e}.gate_proj.weight"
    HF_EXPERT_DOWN = "mlp.experts.{e}.down_proj.weight"
    HF_EXPERT_UP = "mlp.experts.{e}.up_proj.weight"
