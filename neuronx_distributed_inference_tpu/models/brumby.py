"""Brumby (``model_type: "brumby"``; Brumby-14B-Base) model plugin: Qwen3's
decoder with every attention layer replaced by POWER RETENTION of degree 2.

Published: ``manifestai/Brumby-14B-Base`` (its ``config.json`` is Qwen3-14B's
key for key), "Scaling Context Requires Rethinking Attention"
(arXiv:2507.04239) and Manifest AI's ``retention`` package. The installed
``transformers`` has no such module and the published modeling file is not in
this repository, so what the config does not pin (the degree, the gate's
projection, the normaliser) is written out in
``benchmark/harness/references/brumby.py``, which tier-1 holds this file to
(tests/test_brumby_reference.py). Per layer:

    h = h + W_o PowerRetention(rmsnorm(h)),   h = h + MLP(rmsnorm(h)),
    logits = rmsnorm_f(h) @ lm_head,   h0 = embed[ids]

with q, k, v projected, q and k normalised a head and rotated as Qwen3 does,
one log decay a KV head a token ``log_sigmoid(W_g x + b_g)`` and the mixer of
modules/power_retention.py (its docstring has the recurrence and the layout
of the state held).

WHAT A LAYER KEEPS: a float32 state ``(D, head_dim)`` and a normaliser
``(D,)`` a KV head a slot, ``D = 8704`` at ``head_dim`` 128: 33.8 MB a layer a
slot, constant in the context length. EVERY layer keeps one and none pages:
``cache_layers()`` is ``SLOT_STATE`` x layers, the application builds a pool
of zero layers (no byte) beside the state, the session admits by free slots
alone, and neither step program reads a block table or writes K/V.

THE STACK is models/granite_hybrid.HybridStack with one more kind of block
(``POWER``: a layer of two parts, as granite's): that stack already owns what
a per-slot state needs of a pass (which positions advance it, which rows
start from zero, whose slot a chunk row is: models/base.slot_state_rows), the
state in the scan's carry and the computed index into a kind's stacked
weights. Qwen3's shared ``decoder_layer`` with the mixer swapped would have
repeated that inside ``run_decoder_layers``' scan, whose carry is the K/V
pool and whose every branch (ring, interleaved, paged, ragged) is about where
K and V go.

Served on the paged, chunked, continuously batched path only; what a per-slot
state does not support is refused at config time
(config.validate_slot_state_serving).

Checkpoint names (``convert_hf_state_dict``) are Qwen3's with the gate's two
tensors beside them (``self_attn.g_proj.weight`` / ``.bias``, an assumption
the configuration file lists); no checkpoint can be read here, so that path
is held to the tree's shapes.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import to_dtype, validate_slot_state_serving
from neuronx_distributed_inference_tpu.models.granite_hybrid import POWER, HybridStack
from neuronx_distributed_inference_tpu.models.qwen import Qwen3ModelBuilder, QwenInferenceConfig
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import power_retention as pr
from neuronx_distributed_inference_tpu.modules.block_kvcache import SLOT_STATE
from neuronx_distributed_inference_tpu.modules.rope import compute_inv_freq


class BrumbyInferenceConfig(QwenInferenceConfig):
    def validate_config(self):
        super().validate_config()
        unwritten = (
            (getattr(self, "power_degree", 2) != 2, "a retention degree other than 2"),
            (getattr(self, "hidden_act", "silu") != "silu", "hidden_act other than silu"),
            (getattr(self, "tie_word_embeddings", False), "tie_word_embeddings"),
            (getattr(self, "attention_bias", False), "attention_bias"),
            (self.tpu_config.fused_qkv, "fused_qkv"),
            (self.tpu_config.lora_config is not None, "lora_config"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"brumby with {what} is not implemented")
        head_dim = getattr(self, "head_dim", None) or self.hidden_size // self.num_attention_heads
        held = getattr(self, "power_state_dim", None)
        if held is not None and held != pr.state_dim(head_dim):
            raise ValueError(
                f"power_state_dim {held}: the layout held at head_dim {head_dim} is "
                f"{pr.state_dim(head_dim)} (modules/power_retention.phi)"
            )
        validate_slot_state_serving(
            self.tpu_config, what="power retention layers", state="power-retention state"
        )


@register_model("brumby")
class BrumbyModelBuilder(Qwen3ModelBuilder):
    """Brumby: Qwen3's projections, QK-norm, rotation and MLP around a
    power-retention mixer; no K/V anywhere."""

    config_cls = BrumbyInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        tc = config.tpu_config
        if not (tc.is_block_kv_layout and tc.is_chunked_prefill):
            raise NotImplementedError(
                "brumby is served on the paged, chunked path only: set "
                "is_block_kv_layout, is_chunked_prefill and is_continuous_batching"
            )

    def power_spec(self) -> pr.PowerSpec:
        cfg = self.config
        return pr.PowerSpec(
            num_heads=cfg.num_attention_heads, num_kv_heads=cfg.num_key_value_heads,
            head_dim=self.head_dim, norm_eps=float(getattr(cfg, "power_norm_eps", 1e-6)),
        )

    def layer_fn(self):
        return HybridStack((POWER,) * self.config.num_hidden_layers, pspec=self.power_spec())

    # ---- what each layer keeps --------------------------------------------

    def cache_layers(self):
        return (SLOT_STATE,) * self.config.num_hidden_layers

    def init_slot_state(self, num_slots: int):
        state = pr.init_power_state(self.power_spec(), self.config.num_hidden_layers, num_slots)
        return state, pr.power_state_pspecs()

    # ---- params: Qwen3's tree under ``layers/power``, the gate beside q, k, v

    def param_shapes(self) -> Dict:
        shapes = super().param_shapes()
        L, H, G = self.config.num_hidden_layers, self.config.hidden_size, self.gqa.kv_heads
        shapes["layers"]["self_attn"]["g_proj"] = {"weight": (L, H, G), "bias": (L, G)}
        shapes["layers"] = {POWER: shapes["layers"]}
        return shapes

    def param_pspecs(self) -> Dict:
        # every degree is 1 (config.validate_slot_state_serving): everything replicated
        return jax.tree.map(
            lambda _: P(), self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)
        )

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        """Random init for tests: matrices N(0, 0.02) but the embedding (0.5:
        a token's own embedding stays a part of its state), norm weights 1,
        the gate's bias 4 + N(0, 0.5): a token's decay is 0.97 - 0.99 and the
        state carries over hundreds of tokens, under which a lost carry shows
        (at a bias of 0 a head forgets in two tokens)."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        params = self.random_tree_by_name(
            self.param_shapes(), {"embed_tokens": 0.5}, key, dtype)
        params["rope"]["inv_freq"] = compute_inv_freq(self.config)
        gate = params["layers"][POWER]["self_attn"]["g_proj"]
        rng = np.random.default_rng(self.config.tpu_config.seed)
        gate["bias"] = jnp.asarray(4.0 + 0.5 * rng.standard_normal(gate["bias"].shape), dtype)
        return params

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """The published names -> the stacked tree: Qwen3's, and a layer's
        ``self_attn.g_proj.weight`` (G, hidden) / ``.bias`` (G,)."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        params = super().convert_hf_state_dict(sd, dtype)

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}; have e.g. {list(sd)[:5]}")
            return np.asarray(sd[name])

        prefixes = [self.HF_LAYER_PREFIX.format(i=i) for i in range(self.config.num_hidden_layers)]
        params["layers"]["self_attn"]["g_proj"] = {
            "weight": jnp.asarray(np.stack([get(p + "self_attn.g_proj.weight").T for p in prefixes]), dtype),
            "bias": jnp.asarray(np.stack([get(p + "self_attn.g_proj.bias") for p in prefixes]), dtype),
        }
        params["layers"] = {POWER: params["layers"]}
        return params
