"""ZAYA1 (``model_type: "zaya"``) model plugin: every layer is attention in a
compressed latent with conv mixing (CCA, modules/latent_attention.py), then
a top-1 expert layer behind an MLP router that carries state from layer to
layer (modules/moe.carried_mlp_router).

Published: Zyphra/ZAYA1-8B ``config.json`` (the widths, head counts, kernel
sizes, rotary share and base, 16 experts, top-1, router hidden 256); the
forms the config does not pin are the family's published description
(arXiv:2510.04476, arXiv:2511.17127) and are written out, with what was
assumed, in ``benchmark/harness/references/zaya.py``, which tier-1 holds this
file to. Per layer ``l``:

    h = h + W_o attention(q, k, v),   q, k, v = cca(rmsnorm(h))  in a latent H_q d wide
    h = h + p_e Expert_e(x),          x = rmsnorm(h),  (p, e, r_l) = router(x, r_{l-1})

NOT built, because the row's config has no key for them (the sibling rows'
Megatron-style keys name them): a skip choice beside the experts
(``zaya_use_mod``) and learned scales on the residual merge
(``scale_residual_merge``).

What differs from every other plugin:

* WHAT A LAYER KEEPS: every layer pages K/V over the block pool at the
  ordinary ``(H_kv, d)`` AND keeps a one-token carry per slot
  (``TokenCarry``; granite keeps one or the other per layer). The cache is a
  ``HybridBlockCache`` whose pool spans all layers.
* WHAT THE LAYER SCAN CARRIES: beside the hidden state, the router's
  representation ``r`` (float32, ``router_hidden_size`` wide).
* The rotation covers the first ``partial_rotary_factor`` of a head.
* Under ``TpuConfig.output_choices`` the step returns each token's expert per
  layer (``{"experts": int (B, S, L, 1)}``) as ``forward``'s third value.

Served on the paged, chunked, continuously batched path only; what the
carry does not support yet (prefix reuse, speculation, the ragged step,
tp > 1, a quantised cache) is refused at config time.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    to_dtype,
    validate_slot_state_serving,
)
from neuronx_distributed_inference_tpu.models.base import (
    PHASE_TOKEN_GENERATION,
    LayerStack,
    build_mask,
    expert_positions,
    paged_block_inputs,
    paged_write_attend,
    residual_add,
    slot_state_rows,
)
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules.block_kvcache import HybridBlockCache
from neuronx_distributed_inference_tpu.modules.latent_attention import (
    CCASpec,
    TokenCarry,
    cca_qkv,
    init_token_carry,
)
from neuronx_distributed_inference_tpu.modules.moe import (
    MoESpec,
    carried_mlp_router,
    hoist_expert_stacks,
    moe_layer,
    place_expert_stacks,
)
from neuronx_distributed_inference_tpu.modules.norm import rms_norm
from neuronx_distributed_inference_tpu.modules.rope import (
    apply_rope,
    compute_inv_freq,
    rope_cos_sin,
)
from neuronx_distributed_inference_tpu.ops.quant import linear

#: the key of the choices a step returns under ``output_choices``
CHOICES = "experts"


class ZayaInferenceConfig(InferenceConfig):
    _REQUIRED_ATTRS = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_hidden_layers", "vocab_size", "num_experts", "moe_intermediate_size",
        "router_hidden_size",
    )

    def add_derived_config(self):
        super().add_derived_config()
        # the published config names the rotation per layer type; every layer
        # of this model is "hybrid"
        rope = (getattr(self, "rope_parameters", None) or {}).get("hybrid", {})
        self.rope_theta = rope.get("rope_theta", getattr(self, "rope_theta", 10000.0))
        factor = rope.get("partial_rotary_factor", getattr(self, "partial_rotary_factor", 1.0))
        self.rope_dim = int(self.head_dim * factor)
        self.intermediate_size = self.moe_intermediate_size

    def validate_config(self):
        super().validate_config()
        unwritten = (
            (getattr(self, "num_experts_per_tok", 1) != 1, "num_experts_per_tok != 1"),
            ((getattr(self, "cca_time0", 2), getattr(self, "cca_time1", 2)) != (2, 2),
             "cca_time0 / cca_time1 != 2: the carry holds one token"),
            (set(getattr(self, "layer_types", ["hybrid"])) != {"hybrid"},
             "layer_types other than 'hybrid' (hybrid_sliding: windowed attention)"),
            (getattr(self, "sliding_window", None), "sliding_window"),
            (getattr(self, "attention_bias", False), "attention_bias"),
            (self.num_attention_heads % self.num_key_value_heads or self.num_key_value_heads % 2,
             "key/value heads that do not split into two halves of whole heads"),
        )
        for flag, what in unwritten:
            if flag:
                raise NotImplementedError(f"zaya with {what} is not implemented")
        validate_slot_state_serving(
            self.tpu_config, what="a one-token carry per slot (CCA)", state="carry")


class ZayaStack(LayerStack):
    """Runs the layers over ``HybridBlockCache(k, v, state=TokenCarry)``."""

    def __init__(self, cca: CCASpec, moe: MoESpec):
        self.cca, self.moe = cca, moe

    def __call__(self, params, hidden, cache, inputs, *, spec, phase, mlp_fn):
        if phase != PHASE_TOKEN_GENERATION or inputs.block_table is None:
            raise NotImplementedError(
                "a stack with a per-slot carry runs on the paged serving path only "
                "(chunk and decode programs of the token-generation runner)"
            )
        if not isinstance(cache, HybridBlockCache):
            raise TypeError(f"expected a HybridBlockCache, got {type(cache).__name__}")
        B, S, H = hidden.shape
        positions = inputs.position_ids
        valid, reset, slots = slot_state_rows(inputs, cache.state.num_slots)
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        # a paged chunk pass routes its real positions alone
        expert_valid = expert_positions(inputs, phase)
        block_inputs = paged_block_inputs(inputs, cache.block_size)
        mask = build_mask(inputs, spec, phase)
        cos, sin = rope_cos_sin(positions, params["rope"]["inv_freq"], spec.attention_scaling)
        cca, moe = self.cca, self.moe

        # a pass that takes the grouped-matmul kernel reads the experts from
        # the stacks in place: they stay out of the scanned operands
        layers, expert_stacks = hoist_expert_stacks(
            params["layers"], moe, S, B * S, hidden.dtype
        )

        def layer(carry, xs):
            h, r, k_cache, v_cache, last = carry
            lp, li = xs
            lp = place_expert_stacks(lp, expert_stacks, li)
            sa = lp["self_attn"]
            with jax.named_scope("layer.norm"):
                x = rms_norm(h, lp["input_layernorm"]["weight"], spec.rms_eps)
            if slots is None:
                rows = jax.lax.dynamic_index_in_dim(last, li, 0, keepdims=False)
            else:
                rows = last.at[li, slots].get(mode="fill", fill_value=0)
            rows = jnp.where(reset[:, None], jnp.zeros((), rows.dtype), rows)
            q, k, v, rows = cca_qkv(sa, x, rows, n_valid, cca)
            if slots is None:
                last = jax.lax.dynamic_update_index_in_dim(last, rows, li, 0)
            else:
                last = last.at[li, slots].set(rows, mode="drop", unique_indices=True)
            with jax.named_scope("layer.qkv"):
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            attn, k_cache, v_cache = paged_write_attend(
                q, k, v, k_cache, v_cache, li, mask, block_inputs, positions, spec
            )
            with jax.named_scope("layer.o_proj"):
                h = residual_add(h, linear(sa["o_proj"], attn.reshape(B, S, -1)), spec)

            with jax.named_scope("layer.norm"):
                x = rms_norm(h, lp["post_attention_layernorm"]["weight"], spec.rms_eps)
            aff, selected, r, choice = carried_mlp_router(
                lp["mlp"]["router"], x.reshape(B * S, H), r, spec.rms_eps
            )
            with jax.named_scope("layer.mlp"):
                out = moe_layer(lp["mlp"], x, moe, router=lambda *_: (aff, selected),
                                valid=expert_valid)
                h = residual_add(h, out, spec)
            return (h, r, k_cache, v_cache, last), (
                choice.reshape(B, S) if spec.output_choices else None
            )

        num_layers = cache.k.shape[0]
        r0 = jnp.zeros((B * S, params["layers"]["mlp"]["router"]["gamma"].shape[-1]), jnp.float32)
        (hidden, _, k, v, last), chosen = jax.lax.scan(
            layer,
            (hidden, r0, cache.k, cache.v, cache.state.last),
            (layers, jnp.arange(num_layers, dtype=jnp.int32)),
        )
        new_cache = HybridBlockCache(k=k, v=v, state=TokenCarry(last=last))
        if not spec.output_choices:
            return hidden, new_cache
        # (L, B, S) -> (B, S, L, k = 1)
        return hidden, new_cache, {CHOICES: jnp.transpose(chosen, (1, 2, 0))[..., None]}


@register_model("zaya")
class ZayaModelBuilder(DecoderModelBuilder):
    """ZAYA1: CCA attention + top-1 experts behind a carried MLP router."""

    config_cls = ZayaInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        tc = config.tpu_config
        if not (tc.is_block_kv_layout and tc.is_chunked_prefill):
            raise NotImplementedError(
                "zaya is served on the paged, chunked path only: set "
                "is_block_kv_layout, is_chunked_prefill and is_continuous_batching"
            )

    def cca_spec(self) -> CCASpec:
        return CCASpec(self.gqa.q_heads, self.gqa.kv_heads, self.head_dim)

    def moe_spec(self) -> MoESpec:
        cfg = self.config
        return MoESpec(
            num_experts=cfg.num_experts, top_k=1, normalize_top_k_affinities=False,
            act=getattr(cfg, "hidden_act", "silu"), model_parallel=self.degree,
        )

    def expert_layers(self):
        return self.config.num_hidden_layers, self.config.num_experts, 1

    def layer_fn(self):
        return ZayaStack(self.cca_spec(), self.moe_spec())

    # ---- what each layer keeps: paged K/V (the default) AND the carry -------

    def init_slot_state(self, num_slots: int):
        state = init_token_carry(
            self.cca_spec(), self.config.num_hidden_layers, num_slots,
            to_dtype(self.config.tpu_config.dtype),
        )
        return state, TokenCarry(last=P())

    # ---- params ------------------------------------------------------------

    def param_shapes(self) -> Dict:
        cfg, c = self.config, self.cca_spec()
        L, H, d = cfg.num_hidden_layers, cfg.hidden_size, self.head_dim
        E, I, R = cfg.num_experts, cfg.moe_intermediate_size, cfg.router_hidden_size
        shapes = {
            "embed_tokens": {"weight": (self.padded_vocab, H)},
            "rope": {"inv_freq": (cfg.rope_dim // 2,)},
            "layers": {
                "input_layernorm": {"weight": (L, H)},
                "post_attention_layernorm": {"weight": (L, H)},
                "self_attn": {
                    "q_proj": {"weight": (L, H, c.num_heads * d)},
                    "k_proj": {"weight": (L, H, c.num_kv_heads * d)},
                    "v1_proj": {"weight": (L, H, c.value_half)},
                    "v2_proj": {"weight": (L, H, c.value_half)},
                    "o_proj": {"weight": (L, c.num_heads * d, H)},
                    # depthwise (tap, channel); grouped by head (tap, group, in, out)
                    "conv0": {"weight": (L, 2, c.channels), "bias": (L, c.channels)},
                    "conv1": {"weight": (L, 2, c.groups, d, d), "bias": (L, c.channels)},
                    "key_temp": (L, c.num_kv_heads),
                },
                "mlp": {
                    "router": {
                        "down_proj": {"weight": (L, H, R), "bias": (L, R)},
                        "gamma": (L, R),
                        "norm": {"weight": (L, R)},
                        "fc1": {"weight": (L, R, R), "bias": (L, R)},
                        "fc2": {"weight": (L, R, R), "bias": (L, R)},
                        "fc3": {"weight": (L, R, E)},
                        "balance_bias": (L, E),
                    },
                    "experts": {
                        "gate_proj": {"weight": (L, E, H, I)},
                        "up_proj": {"weight": (L, E, H, I)},
                        "down_proj": {"weight": (L, E, I, H)},
                    },
                },
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
        """Random init for tests: matrices N(0, 0.02) but where that would
        leave a mechanism idle — the router's network (under 0.02 every token
        of a layer takes one expert), the convs (taps of ``nn.Conv1d``'s own
        size, so that the conv term is as large as the mean term), the
        experts (so that an expert's output shows in the residual) — norm
        weights and ``gamma`` 1, ``tau`` and the balancing bias 0."""
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        shapes = self.param_shapes()
        d = self.head_dim
        std = {"conv0": 0.5, "conv1": (2 * d) ** -0.5, "fc1": 1.0, "fc2": 0.1, "fc3": 0.5,
               "down_proj": 0.05, "gate_proj": 0.05, "up_proj": 0.05}
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple)
        )
        key = key if key is not None else jax.random.PRNGKey(self.config.tpu_config.seed)
        leaves = []
        for (path, shape), k in zip(flat, jax.random.split(key, len(flat))):
            names = [p.key for p in path]
            if "norm" in "/".join(names) or names[-1] == "gamma":
                leaf = jnp.ones(shape, dtype)
            elif names[-1] in ("key_temp", "balance_bias"):
                leaf = jnp.zeros(shape, dtype)
            else:
                s = next((v for n, v in std.items() if n in names), 0.02)
                leaf = (s * jax.random.normal(k, shape)).astype(dtype)
            leaves.append(leaf)
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        params["rope"]["inv_freq"] = compute_inv_freq(self.config)
        if getattr(self.config, "tie_word_embeddings", False):
            params["lm_head"] = {"weight": params["embed_tokens"]["weight"].T}
        return params

    def convert_hf_state_dict(self, sd, dtype=None):
        raise NotImplementedError(
            "zaya: no checkpoint conversion (the published parameter names "
            "cannot be read here); served with seeded random weights"
        )
