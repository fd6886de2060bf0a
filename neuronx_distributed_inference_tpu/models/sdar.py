"""SDAR-MoE (``model_type: "sdar_moe"``): the Qwen3-MoE decoder generating by
diffusion over blocks.

The layer stack is :class:`~.mixtral.Qwen3MoeModelBuilder`'s, key for key
(per-head q/k RMSNorm, rotate-half rotary, softmax router in float32, top-k
renormalised, no shared expert). New are the mask and the generation, which
the builder declares as a :class:`~.base.BlockStepSpec` read from the model's
config attributes ``block_length``, ``denoise_steps`` and ``mask_token_id``:
position ``i`` sees ``j`` iff ``j // block_length <= i // block_length``, and
a sequence grows block by block from mask tokens, the most confident
positions revealed pass by pass, then committed (runtime/block_step.py).

Served on the paged, chunked path only; what is not built and tested is
refused by type at config time (config.validate_block_step_serving).
"""

from __future__ import annotations

import dataclasses

from neuronx_distributed_inference_tpu.config import validate_block_step_serving
from neuronx_distributed_inference_tpu.models.base import BlockStepSpec, ModelSpec
from neuronx_distributed_inference_tpu.models.mixtral import (
    MoEInferenceConfig,
    Qwen3MoeModelBuilder,
)
from neuronx_distributed_inference_tpu.models.registry import register_model


class SdarMoeInferenceConfig(MoEInferenceConfig):
    _REQUIRED_ATTRS = MoEInferenceConfig._REQUIRED_ATTRS + (
        "block_length", "denoise_steps", "mask_token_id",
    )

    def validate_config(self):
        super().validate_config()
        validate_block_step_serving(
            self.tpu_config, self.block_length, self.denoise_steps,
            self.mask_token_id, self.vocab_size,
        )


@register_model("sdar_moe")
class SdarMoeModelBuilder(Qwen3MoeModelBuilder):
    """Qwen3-MoE layers under a block-causal mask, generated block by block."""

    config_cls = SdarMoeInferenceConfig

    def block_step(self) -> BlockStepSpec:
        cfg = self.config
        return BlockStepSpec(cfg.block_length, cfg.denoise_steps, cfg.mask_token_id)

    def model_spec(self) -> ModelSpec:
        return dataclasses.replace(super().model_spec(), block_step=self.block_step())

    def expert_layers(self):
        cfg = self.config
        return cfg.num_hidden_layers, self.num_experts, cfg.num_experts_per_tok
