"""Ouro (``model_type: "ouro"``): a decoder whose WHOLE layer stack runs
``total_ut_steps`` times over one set of weights (a looped language model).

What the published block (HF ``modeling_ouro.py``) adds to the llama block,
and where each part lives here:

    h = E[ids]
    for t in 0..T-1:                              # the same weights at every t
      for l in 0..L-1:
        a = N(h; input_layernorm)
        q, k, v = a Wq, a Wk, a Wv                # no bias, no q/k norm
        q, k = rope(q, k; the token's own position at every t)
        K[t*L + l], V[t*L + l] <- k, v            # ONE STREAM A (LOOP, LAYER)
        o = softmax(q K^T / sqrt(D) + causal) V
        h = h + N(o Wo; input_layernorm_2)        # a norm on the attention OUTPUT
        m = N(h; post_attention_layernorm)
        h = h + N(mlp(m); post_attention_layernorm_2)   # and on the MLP OUTPUT
      h = N(h; model.norm)                        # after EVERY loop
      e_t = sigmoid(h w_gate + b_gate)            # early_exit_gate, Linear(H, 1)
    logits = h W_head                             # from the last loop; untied

- the loop: ``ModelSpec.loop_steps`` and the outer scan of
  ``models/base.py::run_decoder_layers`` (one compiled layer body);
- the two output norms: ``decoder_layer`` / ``_decoder_layer_mlp`` take them
  at trace time from the keys of the layer's params (scope ``layer.post_norm``);
- the cache: ``cache_layers()`` has one entry per layer PASS, T x L, so the
  paged pool (``application.paged_layers``, ``ServingSession.block_bytes``)
  and the contiguous cache (``init_kv_cache``) span T x L streams;
- the exit gate: ``models/base.py::exit_gate``. At the published
  ``early_exit_threshold`` of 1 every position leaves at the last loop, so
  the serving step never reads it; a tensor tap (``exit_gate``) computes it.
  A threshold under 1 (a depth that differs by row) is not built and is
  refused (``config.validate_looped_stack``), as is every option that reads
  one cache entry a layer.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    to_dtype,
    validate_looped_stack,
)
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model

#: the two norms a layer applies to a sub-block's output (HF names)
OUTPUT_NORMS = ("input_layernorm_2", "post_attention_layernorm_2")


class OuroInferenceConfig(InferenceConfig):
    _REQUIRED_ATTRS = (
        "hidden_size",
        "num_attention_heads",
        "num_hidden_layers",
        "num_key_value_heads",
        "vocab_size",
        "intermediate_size",
        "total_ut_steps",
    )

    def validate_config(self):
        super().validate_config()
        unwritten = (
            (getattr(self, "tie_word_embeddings", False), "tie_word_embeddings"),
            (getattr(self, "use_sliding_window", False) and getattr(self, "sliding_window", None),
             "use_sliding_window (a window that differs by layer_types)"),
            (set(getattr(self, "layer_types", None) or ()) - {"full_attention"},
             "layer_types other than full_attention"),
            (getattr(self, "attention_bias", False) or getattr(self, "mlp_bias", False),
             "a bias on a projection (attention_bias, mlp_bias)"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"ouro with {what} is not implemented")
        validate_looped_stack(
            self.tpu_config, int(self.total_ut_steps),
            float(getattr(self, "early_exit_threshold", 1.0)),
        )


@register_model("ouro")
class OuroModelBuilder(DecoderModelBuilder):
    """Ouro: the llama block between two more norms, the stack looped."""

    config_cls = OuroInferenceConfig

    @property
    def loop_steps(self) -> int:
        return int(self.config.total_ut_steps)

    def model_spec(self):
        return dataclasses.replace(super().model_spec(), loop_steps=self.loop_steps)

    def cache_layers(self):
        from neuronx_distributed_inference_tpu.modules.block_kvcache import PAGED_KV

        return (PAGED_KV,) * (self.loop_steps * self.config.num_hidden_layers)

    def param_shapes(self) -> Dict:
        shapes = super().param_shapes()
        L, H = self.config.num_hidden_layers, self.config.hidden_size
        for name in OUTPUT_NORMS:
            shapes["layers"][name] = {"weight": (L, H)}
        shapes["early_exit_gate"] = {"weight": (H, 1), "bias": (1,)}
        return shapes

    def param_pspecs(self) -> Dict:
        specs = super().param_pspecs()
        for name in OUTPUT_NORMS:
            specs["layers"][name] = {"weight": P()}
        specs["early_exit_gate"] = {"weight": P(), "bias": P()}
        return specs

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        params = super().random_params(key, dtype, on_host)
        for name in OUTPUT_NORMS:
            params["layers"][name]["weight"] = jnp.ones_like(params["layers"][name]["weight"])
        return params

    HF_EXIT_GATE = "model.early_exit_gate."

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """HF ``OuroForCausalLM`` checkpoint -> the stacked tree: the llama
        names, two more norms a layer, and the gate's ``Linear(H, 1)``."""
        params = super().convert_hf_state_dict(sd, dtype)
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")
            return np.asarray(sd[name])

        for name in OUTPUT_NORMS:
            params["layers"][name] = {"weight": jnp.asarray(np.stack([
                get(self.HF_LAYER_PREFIX.format(i=i) + name + ".weight")
                for i in range(self.config.num_hidden_layers)
            ]), dtype)}
        params["early_exit_gate"] = {
            "weight": jnp.asarray(get(self.HF_EXIT_GATE + "weight").T, dtype),  # (1, H) -> (H, 1)
            "bias": jnp.asarray(get(self.HF_EXIT_GATE + "bias"), dtype),
        }
        return params
