"""GLM-5 (``model_type: "glm_moe_dsa"``) model plugin: the DeepSeek-V3 stack
(models/deepseek.py: latent attention, a sigmoid router with a selection
bias, shared experts, leading dense layers) with DeepSeek-V3.2's learned
sparse attention in front of every layer's latent attention
(modules/sparse_index.py): an indexer of ``index_n_heads`` heads of
``index_head_dim`` scores every live key of a row, and a query attends the
``index_topk`` keys of largest score, every live key while a row has no more.

Published: the ``glm_moe_dsa`` modeling and DeepSeek-V3.2-Exp's
``inference/model.py`` (``Indexer``, ``MLA``); the installed ``transformers``
has neither, so the forms are written out in
``benchmark/harness/references/glm_dsa.py``, which tier-1 holds this file to
(tests/test_glm_dsa_reference.py). Departures from the published code, both
of an implementation and neither of the mathematics: the indexer's query and
key are not rotated by a Hadamard matrix (orthogonal: ``q_I . k_I`` is what
it was), and the indexer's key is kept in the cache dtype, not in fp8 with a
scale a token.

WHAT A TOKEN LEAVES in a layer of the pool: three streams
(``cache_streams``): the compressed latent, ONE rotary key (packed as
DeepSeek-V3's is) and the indexer's key, one whole lane row of
``index_head_dim``. The third rides ``BlockKVCache.extra``; the stack below
carries it through the layer scans beside the two the base runner knows.

Served on the paged cache only (chunked or whole-prompt prefill, continuous
batching); what the selection does not do yet is refused at config time
(config.validate_sparse_attention beside validate_latent_attention). The
multi-token-prediction module (``num_nextn_predict_layers``) is not built.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import validate_sparse_attention
from neuronx_distributed_inference_tpu.models.base import (
    EXPERT_CHOICES,
    LayerStack,
    build_mask,
    expert_positions,
    paged_block_inputs,
)
from neuronx_distributed_inference_tpu.models.deepseek import (
    DeepseekV3InferenceConfig,
    DeepseekV3ModelBuilder,
    MLASpec,
    mla_decoder_layer,
)
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import moe
from neuronx_distributed_inference_tpu.modules.block_kvcache import BlockKVCache, CacheStream
from neuronx_distributed_inference_tpu.modules.rope import rope_cos_sin
from neuronx_distributed_inference_tpu.modules.sparse_index import (
    SELECTION_CHOICES,
    IndexerSpec,
)


class GlmMoeDsaInferenceConfig(DeepseekV3InferenceConfig):
    _REQUIRED_ATTRS = DeepseekV3InferenceConfig._REQUIRED_ATTRS + (
        "q_lora_rank", "index_n_heads", "index_head_dim", "index_topk",
    )

    def add_derived_config(self):
        super().add_derived_config()
        # the published file nests the rotary base (rope_parameters)
        nested = getattr(self, "rope_parameters", None) or {}
        if not hasattr(self, "rope_theta") and "rope_theta" in nested:
            self.rope_theta = nested["rope_theta"]

    def validate_config(self):
        super().validate_config()
        validate_sparse_attention(self.tpu_config)
        nested = getattr(self, "rope_parameters", None) or {}
        unwritten = (
            (not self.q_lora_rank, "q_lora_rank null: the indexer reads the q latent"),
            (nested.get("rope_type", "default") != "default" or getattr(self, "rope_scaling", None),
             "a scaled rotary (rope_type other than default)"),
            (self.index_head_dim < self.qk_rope_head_dim,
             "index_head_dim under qk_rope_head_dim: an index head rotates its first "
             "qk_rope_head_dim dimensions"),
            (getattr(self, "attention_bias", False), "attention_bias"),
            (getattr(self, "index_topk_freq", 1) != 1 or getattr(self, "indexer_types", None),
             "an indexer shared between layers (index_topk_freq, indexer_types)"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"glm_moe_dsa with {what} is not implemented")


class SparseLatentStack(LayerStack):
    """Runs the dense-then-expert groups of latent-attention layers that
    each keep THREE pool streams: the base runner's scans
    (models/base.run_decoder_layers) with the indexer's key on the carry
    beside ``k`` and ``v`` and, under ``spec.output_choices``, every layer's
    selection beside its experts' choices."""

    def __init__(self, mla: MLASpec, indexer: IndexerSpec):
        self.layer = functools.partial(mla_decoder_layer, mla=mla, indexer=indexer)
        self.topk = indexer.topk

    def __call__(self, params, hidden, cache, inputs, *, spec, phase, mlp_fn):
        if inputs.slot_mapping is None and inputs.block_table is None:
            raise NotImplementedError(
                "learned sparse attention is served on the paged cache only "
                "(config.validate_sparse_attention)"
            )
        positions = inputs.position_ids
        cos, sin = rope_cos_sin(positions, params["rope"]["inv_freq"], spec.attention_scaling)
        block_inputs = paged_block_inputs(inputs, cache.block_size)
        mask = build_mask(inputs, spec, phase)
        B, S, _ = hidden.shape
        expert_valid = expert_positions(inputs, phase)
        total = sum(g.num_layers for g in spec.layer_groups)
        chose_keys = jnp.zeros((total, B, S, self.topk), jnp.int32) if spec.output_choices else None
        chose_experts = []
        carry = (hidden, cache.k, cache.v, cache.extra[0], chose_keys)
        offset = 0
        for group, gspec in zip(params["layers"], spec.layer_groups):
            g_mlp = mlp_fn[gspec.fn_idx]
            expert_stacks = None
            if isinstance(g_mlp, moe.ExpertMlp):
                # a pass that takes the grouped-matmul kernel reads the experts
                # from the stacks in place (modules/moe.hoist_expert_stacks)
                group, expert_stacks = moe.hoist_expert_stacks(
                    group, g_mlp.spec, S, B * S, hidden.dtype
                )
                # a paged chunk pass routes its real positions alone
                g_mlp = functools.partial(g_mlp, valid=expert_valid)

            def body(carry, xs, g_mlp=g_mlp, expert_stacks=expert_stacks, offset=offset):
                h, k, v, ik, keys = carry
                lp, li = xs
                lp = moe.place_expert_stacks(lp, expert_stacks, li - offset)
                picked = []

                def mlp(p, x, s):
                    out = g_mlp(p, x, s)
                    if isinstance(out, tuple):  # an expert layer under output_choices
                        out, experts = out
                        picked.append(experts)
                    return out

                h, k, v, ik, chosen = self.layer(
                    lp, h, cos, sin, k, v, li, mask, inputs.seq_ids, positions, spec, phase,
                    mlp, block_inputs=block_inputs, index_cache=ik,
                )
                if keys is not None:
                    keys = jax.lax.dynamic_update_index_in_dim(keys, chosen, li, 0)
                return (h, k, v, ik, keys), picked[0] if picked else None

            carry, experts = jax.lax.scan(
                body, carry, (group, offset + jnp.arange(gspec.num_layers, dtype=jnp.int32))
            )
            if experts is not None:
                chose_experts.append(experts)  # (layers of the group, B, S, k)
            offset += gspec.num_layers
        hidden, k, v, ik, chose_keys = carry
        new_cache = BlockKVCache(k=k, v=v, extra=(ik,))
        if not spec.output_choices:
            return hidden, new_cache
        aux = {SELECTION_CHOICES: jnp.transpose(chose_keys, (1, 2, 0, 3))}
        if chose_experts:
            aux[EXPERT_CHOICES] = jnp.transpose(
                jnp.concatenate(chose_experts, axis=0), (1, 2, 0, 3)
            ).astype(jnp.int32)
        return hidden, new_cache, aux


@register_model("glm_moe_dsa")
class GlmMoeDsaModelBuilder(DeepseekV3ModelBuilder):
    """GLM-5: DeepSeek-V3's stack behind a learned top-k selection of keys."""

    config_cls = GlmMoeDsaInferenceConfig

    def indexer_spec(self) -> IndexerSpec:
        cfg = self.config
        return IndexerSpec(
            n_heads=cfg.index_n_heads, head_dim=cfg.index_head_dim, topk=cfg.index_topk
        )

    def layer_fn(self):
        return SparseLatentStack(self.mla_spec(), self.indexer_spec())

    def cache_streams(self):
        """The latent and the rotary key as DeepSeek-V3's, and the indexer's
        key: ``index_head_dim`` numbers a token, unpacked (one whole lane row
        at the published 128)."""
        return super().cache_streams() + (
            CacheStream(1, self.config.index_head_dim, name="index_key"),
        )

    # ---- params ----------------------------------------------------------

    def _attn_shapes(self, L: int) -> Dict:
        cfg = self.config
        shapes = super()._attn_shapes(L)
        Hn, D = cfg.index_n_heads, cfg.index_head_dim
        shapes["indexer"] = {
            "wq_b": {"weight": (L, cfg.q_lora_rank, Hn * D)},
            "wk": {"weight": (L, cfg.hidden_size, D)},
            "k_norm": {"weight": (L, D), "bias": (L, D)},
            "weights_proj": {"weight": (L, cfg.hidden_size, Hn)},
        }
        return shapes

    def _attn_pspecs(self) -> Dict:
        # every degree is 1 on the paged path (config.validate_latent_attention)
        specs = super()._attn_pspecs()
        specs["indexer"] = {
            "wq_b": {"weight": P()}, "wk": {"weight": P()},
            "k_norm": {"weight": P(), "bias": P()}, "weights_proj": {"weight": P()},
        }
        return specs

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        params = super().random_params(key, dtype, on_host)
        for g in params["layers"]:
            norm = g["self_attn"]["indexer"]["k_norm"]
            norm["weight"] = jnp.ones_like(norm["weight"])
            norm["bias"] = jnp.zeros_like(norm["bias"])
        return params

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """The published names (``model.layers.N.self_attn.indexer.{wq_b, wk,
        k_norm, weights_proj}``) beside DeepSeek-V3's. ``indexer_rope_interleave``:
        the rotary dimensions of ``wq_b`` (per head) and ``wk`` are permuted
        from interleaved pairs to the tree's half-against-half order, as
        DeepSeek-V3's ``rope_interleave`` rows are."""
        cfg = self.config
        params = super().convert_hf_state_dict(sd, dtype)
        dtype = params["norm"]["weight"].dtype
        Hn, D, d_rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        perm = np.arange(D)
        if getattr(cfg, "indexer_rope_interleave", False):
            perm[:d_rope] = np.concatenate([np.arange(0, d_rope, 2), np.arange(1, d_rope, 2)])

        def layer(i):
            p = f"model.layers.{i}.self_attn.indexer."
            wq = np.asarray(sd[p + "wq_b.weight"]).T  # (q_lora_rank, Hn * D)
            wq = wq.reshape(wq.shape[0], Hn, D)[..., perm].reshape(wq.shape[0], Hn * D)
            return {
                "wq_b": {"weight": wq},
                "wk": {"weight": np.asarray(sd[p + "wk.weight"]).T[:, perm]},
                "k_norm": {"weight": np.asarray(sd[p + "k_norm.weight"])[perm],
                           "bias": np.asarray(sd[p + "k_norm.bias"])[perm]},
                "weights_proj": {"weight": np.asarray(sd[p + "weights_proj.weight"]).T},
            }

        first = 0
        for g, n in zip(params["layers"], (n for n in self._group_sizes() if n)):
            per = [layer(i) for i in range(first, first + n)]
            g["self_attn"]["indexer"] = jax.tree.map(
                lambda *xs: jnp.asarray(np.stack(xs), dtype), *per
            )
            first += n
        return params
