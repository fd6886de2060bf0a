"""Llama4 text model plugin: chunked/global attention interleave, NoPE layers
with temperature tuning, interleaved MoE with shared experts.

TPU-native re-design of the reference Llama4 text model
(reference: models/llama4/modeling_llama4_text.py — per-layer use_rope /
use_chunked_attention flags, Llama4Router sigmoid-of-top-k routing with
early affinity modulation + shared expert, L2 qk-norm, NoPE attention
temperature tuning; the chunked-attention masks the repo already carries from
model_base.py:231-318).

Layer heterogeneity maps onto LayerGroupSpec runs: the fn_idx selects one of
four (dense|moe) x (rope-chunked|nope-global) layer/mlp function pairs, and
each group's attention_chunk_size drives its mask. Alternating configurations
(Maverick's dense/moe interleave) run as single-layer groups — correct, with
depth-proportional program size; all-MoE configurations (Scout) collapse to
a handful of groups.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import InferenceConfig, to_dtype
from neuronx_distributed_inference_tpu.models.base import (
    PHASE_CONTEXT_ENCODING,
    LayerGroupSpec,
    gated_mlp,
)
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules.attention import (
    attention_decode,
    attention_prefill,
    o_project,
)
from neuronx_distributed_inference_tpu.modules.kvcache import (
    read_cache_at_layer,
    update_cache_at_layer,
)
from neuronx_distributed_inference_tpu.modules.moe import (
    ExpertMlp,
    MoESpec,
    fuse_shared_expert_params,
    shared_expert_mlp,
    shared_expert_pspecs,
    shared_expert_shapes,
)
from neuronx_distributed_inference_tpu.modules.norm import rms_norm
from neuronx_distributed_inference_tpu.modules.rope import apply_rope_interleaved
from neuronx_distributed_inference_tpu.ops.quant import linear
from neuronx_distributed_inference_tpu.parallel.sharding import TENSOR


class Llama4TextInferenceConfig(InferenceConfig):
    """Reference: Llama4InferenceConfig (modeling_llama4_text.py)."""

    _REQUIRED_ATTRS = (
        "hidden_size",
        "num_attention_heads",
        "num_hidden_layers",
        "num_key_value_heads",
        "vocab_size",
    )

    def add_derived_config(self):
        # Llama4ForConditionalGeneration config.json nests every decoder
        # hyperparam inside text_config; flatten BEFORE required-attr
        # validation runs (the nested values are the decoder's hyperparams)
        text_cfg = getattr(self, "text_config", None)
        if isinstance(text_cfg, dict):
            for k, v in text_cfg.items():
                setattr(self, k, v)


def llama4_decoder_layer(
    layer_params: dict,
    hidden,
    cos,
    sin,
    k_cache,
    v_cache,
    layer_idx,
    mask,
    slot_ids,
    positions,
    spec,
    phase,
    mlp_fn,
    use_rope: bool = True,
    qk_norm: bool = True,
    temp_tuning: bool = False,
    floor_scale: float = 8192.0,
    attn_scale: float = 0.1,
    key_valid=None,
    block_inputs=None,
    adapter_ids=None,
    # static prefill flavor from this layer's group (chunked-attention rope
    # layers / global NoPE layers) — forwarded so the flash kernel applies
    # the right fused mask
    window=None,
    chunk=None,
    flavor_select=None,
):
    """One Llama4 decoder layer (reference Llama4TextAttention.forward):
    interleaved-pair rope (rope layers only), weightless L2 qk-norm after
    rope, NoPE attention temperature tuning, standard cached attention."""
    if block_inputs is not None:
        raise NotImplementedError("Llama4 with the paged cache is not implemented")
    aspec = spec.attn
    residual = hidden
    hidden = rms_norm(hidden, layer_params["input_layernorm"]["weight"], spec.rms_eps)
    B, S, _ = hidden.shape
    q = linear(layer_params["self_attn"]["q_proj"], hidden).reshape(
        B, S, aspec.num_heads, aspec.head_dim
    )
    k = linear(layer_params["self_attn"]["k_proj"], hidden).reshape(
        B, S, aspec.num_kv_heads, aspec.head_dim
    )
    v = linear(layer_params["self_attn"]["v_proj"], hidden).reshape(
        B, S, aspec.num_kv_heads, aspec.head_dim
    )
    if use_rope:
        q = apply_rope_interleaved(q, cos, sin)
        k = apply_rope_interleaved(k, cos, sin)
        if qk_norm:
            # weightless L2 norm (reference Llama4TextL2Norm, eps 1e-6)
            def l2(x):
                xf = x.astype(jnp.float32)
                return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)).astype(x.dtype)

            q, k = l2(q), l2(k)
    elif temp_tuning:
        # NoPE layers: scale q by a position-dependent temperature
        # (reference attn_temperature_tuning, arXiv:2501.19399)
        scales = (
            jnp.log1p(jnp.floor((positions.astype(jnp.float32) + 1.0) / floor_scale))
            * attn_scale
            + 1.0
        )
        q = (q.astype(jnp.float32) * scales[:, :, None, None]).astype(q.dtype)

    k_cache, v_cache = update_cache_at_layer(
        k_cache, v_cache, k, v, layer_idx, slot_ids, positions
    )
    if phase == PHASE_CONTEXT_ENCODING:
        attn_out = attention_prefill(
            q, k, v, mask, aspec, key_valid=key_valid, window=window, chunk=chunk
        )
    else:
        bucket = mask.shape[-1]
        k_r, v_r = read_cache_at_layer(k_cache, v_cache, layer_idx, B, bucket)
        attn_out = attention_decode(q, k_r, v_r, mask, aspec)
    hidden = o_project(layer_params["self_attn"], attn_out, aspec)
    hidden = residual + hidden

    residual = hidden
    hidden = rms_norm(
        hidden, layer_params["post_attention_layernorm"]["weight"], spec.rms_eps
    )
    hidden = residual + mlp_fn(layer_params["mlp"], hidden, spec)
    return hidden, k_cache, v_cache


@register_model("llama4_text")
@register_model("llama4")
class Llama4TextModelBuilder(DecoderModelBuilder):
    """Reference: models/llama4/modeling_llama4_text.py NeuronLlama4ForCausalLM
    (text side; the vision side rides the ImageToText scaffolding)."""

    config_cls = Llama4TextInferenceConfig

    def __init__(self, config):
        # conditional-gen configs flatten in Llama4TextInferenceConfig.
        # add_derived_config; handle raw InferenceConfig instances too
        text_cfg = getattr(config, "text_config", None)
        if isinstance(text_cfg, dict) and not hasattr(config, "hidden_size"):
            for k, v in text_cfg.items():
                setattr(config, k, v)
        super().__init__(config)
        cfg = config
        tc = config.tpu_config
        for flag, why in (
            (tc.is_block_kv_layout, "paged cache"),
            (tc.cp_degree > 1, "context parallelism"),
            (tc.attention_dp_degree > 1, "attention-DP"),
            (tc.data_parallel_degree > 1, "whole-model DP"),
            (tc.fused_qkv, "fused_qkv"),
            (tc.enable_fused_speculation, "fused speculation"),
            (tc.lora_config is not None, "LoRA serving"),
        ):
            if flag:
                raise NotImplementedError(f"Llama4 with {why} is not implemented")
        L = cfg.num_hidden_layers
        step = getattr(cfg, "interleave_moe_layer_step", 1)
        moe_layers = getattr(cfg, "moe_layers", None)
        if moe_layers is None:
            moe_layers = [i for i in range(L) if (i + 1) % max(step, 1) == 0]
        self.is_moe = [i in set(moe_layers) for i in range(L)]
        no_rope = getattr(cfg, "no_rope_layers", None) or [
            int((i + 1) % 4 != 0) for i in range(L)
        ]
        self.use_rope = [bool(x) for x in no_rope]  # 1 = rope (HF convention)
        self.chunk = getattr(cfg, "attention_chunk_size", None)
        # contiguous runs of identical (moe?, rope?) configuration
        self.runs = []
        start = 0
        for i in range(1, L + 1):
            if i == L or (self.is_moe[i], self.use_rope[i]) != (
                self.is_moe[start], self.use_rope[start]
            ):
                self.runs.append((start, i))
                start = i

    def _fn_idx(self, i: int) -> int:
        return 2 * int(self.is_moe[i]) + int(not self.use_rope[i])

    def model_spec(self):
        spec = super().model_spec()
        groups = tuple(
            LayerGroupSpec(
                num_layers=e - s,
                # rope layers use chunked attention, NoPE layers are global
                attention_chunk_size=self.chunk if self.use_rope[s] else None,
                fn_idx=self._fn_idx(s),
            )
            for s, e in self.runs
        )
        return dataclasses.replace(
            spec, layer_groups=groups, attention_chunk_size=None, sliding_window=None
        )

    def moe_spec(self) -> MoESpec:
        cfg = self.config
        tc = cfg.tpu_config
        return MoESpec(
            num_experts=cfg.num_local_experts,
            top_k=getattr(cfg, "num_experts_per_tok", 1),
            router_dtype=getattr(tc, "router_dtype", "float32"),
            scoring_func="sigmoid_topk",
            normalize_top_k_affinities=False,
            early_affinity_modulation=True,
            act=getattr(cfg, "hidden_act", "silu"),
            ep_degree=tc.ep_degree,
            hybrid_cte_full_tp=bool(getattr(tc, "hybrid_sharding_config", None)),
            model_parallel=self.degree,
        )

    def mlp_fn(self):
        act = getattr(self.config, "hidden_act", "silu")
        moe_mlp_fn = ExpertMlp(self.moe_spec(), lambda p, x: shared_expert_mlp(p, x, act))
        # fn_idx layout: 0/1 dense (rope/nope), 2/3 moe (rope/nope)
        return [gated_mlp, gated_mlp, moe_mlp_fn, moe_mlp_fn]

    def layer_fn(self):
        import functools

        cfg = self.config
        common = dict(
            qk_norm=bool(getattr(cfg, "use_qk_norm", True)),
            temp_tuning=bool(getattr(cfg, "attn_temperature_tuning", False)),
            floor_scale=float(getattr(cfg, "floor_scale", 8192.0)),
            attn_scale=float(getattr(cfg, "attn_scale", 0.1)),
        )
        rope_layer = functools.partial(llama4_decoder_layer, use_rope=True, **common)
        nope_layer = functools.partial(llama4_decoder_layer, use_rope=False, **common)
        return [rope_layer, nope_layer, rope_layer, nope_layer]

    # ---- params ----------------------------------------------------------

    def _attn_shapes(self, Lg: int) -> Dict:
        cfg = self.config
        H = cfg.hidden_size
        D = self.head_dim
        Hq, Hkv = self.gqa.q_heads, self.gqa.kv_heads
        return {
            "q_proj": {"weight": (Lg, H, Hq * D)},
            "k_proj": {"weight": (Lg, H, Hkv * D)},
            "v_proj": {"weight": (Lg, H, Hkv * D)},
            "o_proj": {"weight": (Lg, Hq * D, H)},
        }

    def _group_shapes(self, s: int, e: int) -> Dict:
        cfg = self.config
        Lg = e - s
        H = cfg.hidden_size
        shapes = {
            "input_layernorm": {"weight": (Lg, H)},
            "post_attention_layernorm": {"weight": (Lg, H)},
            "self_attn": self._attn_shapes(Lg),
        }
        if self.is_moe[s]:
            E = cfg.num_local_experts
            I = getattr(cfg, "intermediate_size")
            shapes["mlp"] = {
                "router": {"weight": (Lg, H, E)},
                "experts": {
                    "gate_proj": {"weight": (Lg, E, H, I)},
                    "up_proj": {"weight": (Lg, E, H, I)},
                    "down_proj": {"weight": (Lg, E, I, H)},
                },
                "shared_experts": shared_expert_shapes(
                    Lg, H, I,
                    bool(getattr(cfg.tpu_config, "fused_shared_experts", False)),
                ),
            }
        else:
            I = getattr(cfg, "intermediate_size_mlp", cfg.intermediate_size)
            shapes["mlp"] = {
                "gate_proj": {"weight": (Lg, H, I)},
                "up_proj": {"weight": (Lg, H, I)},
                "down_proj": {"weight": (Lg, I, H)},
            }
        return shapes

    def param_shapes(self) -> Dict:
        cfg = self.config
        V, H = self.padded_vocab, cfg.hidden_size
        return {
            "embed_tokens": {"weight": (V, H)},
            "rope": {"inv_freq": (self.head_dim // 2,)},
            "layers": [self._group_shapes(s, e) for s, e in self.runs],
            "norm": {"weight": (H,)},
            "lm_head": {"weight": (H, V)},
        }

    def _group_pspecs(self, s: int) -> Dict:
        t = TENSOR
        specs = {
            "input_layernorm": {"weight": P()},
            "post_attention_layernorm": {"weight": P()},
            "self_attn": {
                "q_proj": {"weight": P(None, None, t)},
                "k_proj": {"weight": P(None, None, t)},
                "v_proj": {"weight": P(None, None, t)},
                "o_proj": {"weight": P(None, t, None)},
            },
        }
        if self.is_moe[s]:
            ffn = ("cp", "tp")
            specs["mlp"] = {
                "router": {"weight": P()},
                "experts": {
                    "gate_proj": {"weight": P(None, "ep", None, ffn)},
                    "up_proj": {"weight": P(None, "ep", None, ffn)},
                    "down_proj": {"weight": P(None, "ep", ffn, None)},
                },
                "shared_experts": shared_expert_pspecs(
                    bool(getattr(self.config.tpu_config, "fused_shared_experts", False)),
                    t,
                ),
            }
        else:
            specs["mlp"] = {
                "gate_proj": {"weight": P(None, None, t)},
                "up_proj": {"weight": P(None, None, t)},
                "down_proj": {"weight": P(None, t, None)},
            }
        return specs

    def param_pspecs(self) -> Dict:
        tc = self.config.tpu_config
        return {
            "embed_tokens": {"weight": P(TENSOR, None) if tc.vocab_parallel else P(None, TENSOR)},
            "rope": {"inv_freq": P()},
            "layers": [self._group_pspecs(s) for s, _ in self.runs],
            "norm": {"weight": P()},
            "lm_head": {"weight": P(None, TENSOR)},
        }

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        dtype = dtype or to_dtype(self.config.tpu_config.dtype)
        params = self.random_tree(self.param_shapes(), key, dtype, on_host, std=0.05)
        from neuronx_distributed_inference_tpu.modules.rope import compute_inv_freq

        params["rope"]["inv_freq"] = compute_inv_freq(self.config)
        params["norm"]["weight"] = jnp.ones_like(params["norm"]["weight"])
        for g in params["layers"]:
            for n in ("input_layernorm", "post_attention_layernorm"):
                g[n]["weight"] = jnp.ones_like(g[n]["weight"])
        return params

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        cfg = self.config
        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        D = self.head_dim
        g = self.gqa

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}")
            return np.asarray(sd[name])

        def lt(name):
            return get(name).T

        def layer_params(i):
            p = f"model.layers.{i}."
            out = {
                "input_layernorm": {"weight": get(p + "input_layernorm.weight")},
                "post_attention_layernorm": {
                    "weight": get(p + "post_attention_layernorm.weight")
                },
                "self_attn": {
                    "q_proj": {"weight": np.asarray(g.pad_q(lt(p + "self_attn.q_proj.weight"), D))},
                    "k_proj": {"weight": np.asarray(g.replicate_kv(lt(p + "self_attn.k_proj.weight"), D))},
                    "v_proj": {"weight": np.asarray(g.replicate_kv(lt(p + "self_attn.v_proj.weight"), D))},
                    "o_proj": {"weight": np.asarray(g.pad_o(lt(p + "self_attn.o_proj.weight"), D))},
                },
            }
            if self.is_moe[i]:
                f = p + "feed_forward."
                gate_up = get(f + "experts.gate_up_proj")  # (E, H, 2I) halves
                I = gate_up.shape[-1] // 2
                out["mlp"] = {
                    "router": {"weight": lt(f + "router.weight")},
                    "experts": {
                        "gate_proj": {"weight": gate_up[..., :I]},
                        "up_proj": {"weight": gate_up[..., I:]},
                        "down_proj": {"weight": get(f + "experts.down_proj")},
                    },
                    "shared_experts": {
                        "gate_proj": {"weight": lt(f + "shared_expert.gate_proj.weight")},
                        "up_proj": {"weight": lt(f + "shared_expert.up_proj.weight")},
                        "down_proj": {"weight": lt(f + "shared_expert.down_proj.weight")},
                    },
                }
                if getattr(cfg.tpu_config, "fused_shared_experts", False):
                    out["mlp"]["shared_experts"] = fuse_shared_expert_params(
                        out["mlp"]["shared_experts"]
                    )
            else:
                f = p + "feed_forward."
                out["mlp"] = {
                    "gate_proj": {"weight": lt(f + "gate_proj.weight")},
                    "up_proj": {"weight": lt(f + "up_proj.weight")},
                    "down_proj": {"weight": lt(f + "down_proj.weight")},
                }
            return out

        def stack(s, e):
            per = [layer_params(i) for i in range(s, e)]
            return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs), dtype), *per)

        embed = get("model.embed_tokens.weight")
        vpad = self.padded_vocab - embed.shape[0]
        if vpad:
            embed = np.pad(embed, ((0, vpad), (0, 0)))
        lm = lt("lm_head.weight") if "lm_head.weight" in sd else embed.T
        if vpad and lm.shape[1] != self.padded_vocab:
            lm = np.pad(lm, ((0, 0), (0, vpad)))
        from neuronx_distributed_inference_tpu.modules.rope import compute_inv_freq

        return {
            "embed_tokens": {"weight": jnp.asarray(embed, dtype)},
            "rope": {"inv_freq": compute_inv_freq(cfg)},
            "layers": [stack(s, e) for s, e in self.runs],
            "norm": {"weight": jnp.asarray(get("model.norm.weight"), dtype)},
            "lm_head": {"weight": jnp.asarray(lm, dtype)},
        }
