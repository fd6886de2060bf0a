"""DeepSeek-V3 model plugin: MLA attention + sigmoid/group-limited MoE.

TPU-native re-design of the reference DeepSeek-V3 model
(reference: models/deepseek/modeling_deepseek.py:79-260 DeepseekV3Attention
with weight-matrix absorption; rope_util.py yarn rope; MoEGate sigmoid
scoring + e_score_correction_bias + group-limited top-k; shared experts;
first_k_dense_replace dense layers).

MLA here uses the same WEIGHT-ABSORPTION formulation the reference decodes
with (modeling_deepseek.py:227-232 ``wkv_b`` absorb): the KV cache stores the
compressed latent ``c`` (kv_lora_rank) in the K stream and the rope keys
``k_pe`` (qk_rope_head_dim) in the V stream — per-token cache cost
r_kv + d_rope instead of 2·H·D. Scores are
``q_pe·k_pe + (q_nope·W_absorb_k)·c`` and outputs are
``(probs·c)·W_absorb_v`` — all MXU einsums over static shapes.

Tensor parallel: heads shard over the model axes; the latent cache is
replicated (the standard MLA TP layout). Dense-first layers
(first_k_dense_replace) run as a separate layer group (models/base.py
LayerGroupSpec).

The serving path (paged cache, chunked prefill): the builder declares the
pool's two streams (``cache_streams``: the latent, and the rotary key packed
two tokens a 128-lane row, modules/block_kvcache.CacheStream), a token's
``kv_lora_rank + qk_rope_head_dim`` numbers are written once a layer, and
both step programs attend in the absorbed form over the block table
(ops/latent_attention.py). Options that cannot serve it are refused by type
at config time (config.validate_latent_attention).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    to_dtype,
    validate_latent_attention,
)
from neuronx_distributed_inference_tpu.models.base import (
    PHASE_CONTEXT_ENCODING,
    LayerGroupSpec,
    gated_mlp,
)
from neuronx_distributed_inference_tpu.models.builder import DecoderModelBuilder
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules import sparse_index
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    CacheStream,
    update_latent_cache_at_layer,
    update_stream_at_layer,
)
from neuronx_distributed_inference_tpu.modules.kvcache import (
    kv_batch_size,
    read_cache_at_layer,
    update_cache_at_layer,
)
from neuronx_distributed_inference_tpu.modules.moe import (
    ExpertMlp,
    MoESpec,
    held_share,
    validate_expert_layer,
)
from neuronx_distributed_inference_tpu.modules.norm import rms_norm
from neuronx_distributed_inference_tpu.modules.rope import apply_rope, yarn_mscale
from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret
from neuronx_distributed_inference_tpu.ops.latent_attention import (
    latent_attend,
    native_latent_attention,
)
from neuronx_distributed_inference_tpu.ops.quant import linear
from neuronx_distributed_inference_tpu.parallel.sharding import TENSOR


class DeepseekV3InferenceConfig(InferenceConfig):
    """Reference: DeepseekV3InferenceConfig (modeling_deepseek.py)."""

    _REQUIRED_ATTRS = (
        "hidden_size",
        "num_attention_heads",
        "num_hidden_layers",
        "vocab_size",
        "kv_lora_rank",
        "qk_nope_head_dim",
        "qk_rope_head_dim",
        "v_head_dim",
    )

    def add_derived_config(self):
        # rope tables are built for the rope sub-dimension only
        self.rope_dim = self.qk_rope_head_dim

    def validate_config(self):
        super().validate_config()
        validate_latent_attention(self.tpu_config)


@dataclass(frozen=True)
class MLASpec:
    """Static MLA dims (reference modeling_deepseek.py:115-135)."""

    num_heads: int  # per-model q heads (padded to degree)
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    scale: float
    rms_eps: float
    #: False (``mla_use_nope``, models/kimi_linear.py): the ``qk_rope_head_dim``
    #: dimensions stay in q, in ``kv_a_proj`` and in the pool, and nothing
    #: rotates them (``cos`` and ``sin`` are not read)
    use_rope: bool = True

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_decoder_layer(
    layer_params: dict,
    hidden: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer_idx: jax.Array,
    mask: jax.Array,
    slot_ids: jax.Array,
    positions: jax.Array,
    spec,
    phase: str,
    mlp_fn,
    mla: MLASpec = None,
    key_valid=None,
    block_inputs=None,
    adapter_ids=None,
    indexer=None,
    index_cache=None,
    # prefill flavor hints from run_decoder_layers; this layer's native
    # attention already encodes the flavor in `mask`
    **_flavor_hints,
):
    """One MLA decoder layer (reference DeepseekV3Attention.forward with
    weight absorption, modeling_deepseek.py:205-260).

    Cache streams: K stream holds the compressed latent ``c`` as a single
    "head" of dim kv_lora_rank; V stream holds the shared rope key ``k_pe``
    (one head of dim qk_rope_head_dim).

    With ``indexer`` (a modules/sparse_index.IndexerSpec; models/
    glm_moe_dsa.py's stack alone passes one): the layer also projects the
    indexer's query, key and head weights, writes the key to ``index_cache``
    (the pool's third stream) with the latent, and attends the keys the
    indexer selects; it then returns ``(hidden, k_cache, v_cache,
    index_cache, chosen)``, ``chosen`` the positions each query attended
    under ``spec.output_choices``, else None. Nothing of this is emitted for
    a layer without one.
    """
    sa = layer_params["self_attn"]
    residual = hidden
    with jax.named_scope("layer.norm"):
        hidden = rms_norm(hidden, layer_params["input_layernorm"]["weight"], spec.rms_eps)
    B, S, _ = hidden.shape
    H = mla.num_heads
    rotate = (lambda x: apply_rope(x, cos, sin)) if mla.use_rope else (lambda x: x)

    # --- q path: low-rank (or direct) projection, split nope/rope ---------
    with jax.named_scope("layer.qkv"):
        if mla.q_lora_rank:
            q = linear(sa["q_a_proj"], hidden)
            q_latent = rms_norm(q, sa["q_a_layernorm"]["weight"], mla.rms_eps)
            q = linear(sa["q_b_proj"], q_latent)
        else:
            q = linear(sa["q_proj"], hidden)
        q = q.reshape(B, S, H, mla.q_head_dim)
        q_nope = q[..., : mla.qk_nope_head_dim]
        q_pe = rotate(q[..., mla.qk_nope_head_dim :])

    # --- compressed kv + rope key: what the token leaves behind -----------
    with jax.named_scope("layer.latent_proj"):
        ckv = linear(sa["kv_a_proj"], hidden)  # (B, S, r_kv + d_rope)
        c = rms_norm(ckv[..., : mla.kv_lora_rank], sa["kv_a_layernorm"]["weight"], mla.rms_eps)
        k_pe = rotate(ckv[..., None, mla.kv_lora_rank :].reshape(
            B, S, 1, mla.qk_rope_head_dim
        ))

    with jax.named_scope("layer.absorb"):
        # q_nope absorbed into latent space: (B,S,H,d_nope)·(H,d_nope,r) -> (B,S,H,r)
        q_c = jnp.einsum("bshd,hdr->bshr", q_nope, sa["k_absorb"]["weight"].astype(q.dtype))

    chosen = None
    if indexer is not None:
        if block_inputs is None:
            raise NotImplementedError(
                "learned sparse attention is served on the paged cache only "
                "(config.validate_sparse_attention refuses the contiguous one)"
            )
        with jax.named_scope("layer.indexer"):
            index = sparse_index.index_projections(
                sa["indexer"], hidden, q_latent, cos, sin, indexer
            )

    # --- write-then-attend on the latent cache ----------------------------
    if block_inputs is not None:
        slot_mapping, block_table, kv_limit = block_inputs
        with jax.named_scope("layer.kv_write"):
            k_cache, v_cache = update_latent_cache_at_layer(
                k_cache, v_cache, c, k_pe[:, :, 0], layer_idx, slot_mapping
            )
            if indexer is not None:
                index_cache = update_stream_at_layer(index_cache, index[1], layer_idx, slot_mapping)
        if indexer is not None:
            latent, chosen = sparse_index.sparse_latent_attention(
                indexer, index, q_c, q_pe, c, k_pe[:, :, 0], (k_cache, v_cache, index_cache),
                layer_idx, mask, block_table, kv_limit, positions,
                whole_prompt=phase == PHASE_CONTEXT_ENCODING, scale=mla.scale,
                want_positions=spec.output_choices,
            )
        else:
            with jax.named_scope("layer.attn"):
                if phase == PHASE_CONTEXT_ENCODING:
                    # a whole prompt: the pass's own latents are its whole context
                    latent = native_latent_attention(q_c, q_pe, c, k_pe[:, :, 0], mask, mla.scale)
                else:
                    latent = latent_attend(
                        q_c, q_pe, k_cache, v_cache, layer_idx, mask, block_table, kv_limit,
                        positions, scale=mla.scale, interpret=kernel_interpret(),
                    )
    else:
        with jax.named_scope("layer.kv_write"):
            k_cache, v_cache = update_cache_at_layer(
                k_cache, v_cache, c[:, :, None, :], k_pe, layer_idx, slot_ids, positions
            )
        with jax.named_scope("layer.attn"):
            c_all, pe_all = read_cache_at_layer(k_cache, v_cache, layer_idx, B, mask.shape[-1])
            latent = native_latent_attention(
                q_c, q_pe, c_all[:, :, 0, :], pe_all[:, :, 0, :], mask, mla.scale
            )
    with jax.named_scope("layer.absorb"):
        out = jnp.einsum(
            "bshr,hrd->bshd", latent.astype(hidden.dtype),
            sa["v_absorb"]["weight"].astype(hidden.dtype),
        )

    with jax.named_scope("layer.o_proj"):
        out = linear(sa["o_proj"], out.reshape(B, S, H * mla.v_head_dim))
        hidden = residual + out

    if mlp_fn is None:  # a block that is the attention part alone (HybridStack)
        return hidden, k_cache, v_cache
    residual = hidden
    with jax.named_scope("layer.norm"):
        hidden = rms_norm(hidden, layer_params["post_attention_layernorm"]["weight"], spec.rms_eps)
    with jax.named_scope("layer.mlp"):
        hidden = residual + mlp_fn(layer_params["mlp"], hidden, spec)
    if indexer is not None:
        return hidden, k_cache, v_cache, index_cache, chosen
    return hidden, k_cache, v_cache


@register_model("deepseek_v3")
class DeepseekV3ModelBuilder(DecoderModelBuilder):
    """Reference: models/deepseek/modeling_deepseek.py NeuronDeepseekForCausalLM."""

    config_cls = DeepseekV3InferenceConfig

    def __init__(self, config):
        super().__init__(config)
        cfg = config
        # pad q heads to the model-parallel degree (MLA has no GQA groups)
        self.q_heads = math.ceil(cfg.num_attention_heads / self.degree) * self.degree
        self.first_dense = getattr(cfg, "first_k_dense_replace", 0)
        #: the router's width and the first expert held here: the published
        #: count and 0 unless the configuration states a held share
        #: (modules/moe.held_share; ``num_experts`` is then the count held)
        self.published_experts, self.first_expert = (
            held_share(cfg) if hasattr(cfg, "n_routed_experts") else (None, 0)
        )
        if self.first_dense < cfg.num_hidden_layers and self.published_experts is not None:
            validate_expert_layer(
                self.moe_spec(), self._moe_mlp_shapes(1)["experts"],
                quantized=bool(cfg.tpu_config.quantized),
            )

    @property
    def num_experts(self) -> int:
        return getattr(self.config, "n_routed_experts")

    def mla_spec(self) -> MLASpec:
        cfg = self.config
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        scaling = getattr(cfg, "rope_scaling", None) or {}
        if scaling.get("mscale_all_dim"):
            m = yarn_mscale(scaling.get("factor", 1.0), scaling["mscale_all_dim"])
            scale = scale * m * m
        return MLASpec(
            num_heads=self.q_heads,
            q_lora_rank=getattr(cfg, "q_lora_rank", None),
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
            scale=scale,
            rms_eps=getattr(cfg, "rms_norm_eps", 1e-6),
        )

    def moe_spec(self) -> MoESpec:
        cfg = self.config
        tc = cfg.tpu_config
        return MoESpec(
            num_experts=self.published_experts,
            top_k=getattr(cfg, "num_experts_per_tok", 8),
            normalize_top_k_affinities=bool(getattr(cfg, "norm_topk_prob", True)),
            router_dtype=getattr(tc, "router_dtype", "float32"),
            act=getattr(cfg, "hidden_act", "silu"),
            scoring_func=getattr(cfg, "scoring_func", "sigmoid"),
            routed_scaling_factor=float(getattr(cfg, "routed_scaling_factor", 1.0)),
            n_group=getattr(cfg, "n_group", 1),
            topk_group=getattr(cfg, "topk_group", 1),
            ep_degree=tc.ep_degree,
            hybrid_cte_full_tp=bool(getattr(tc, "hybrid_sharding_config", None)),
            model_parallel=self.degree,
            held_experts=self.num_experts if self.num_experts < self.published_experts else None,
            first_expert=self.first_expert,
        )

    def model_spec(self):
        cfg = self.config
        spec = super().model_spec()
        L = cfg.num_hidden_layers
        groups = []
        if self.first_dense:
            groups.append(LayerGroupSpec(num_layers=self.first_dense, fn_idx=0))
        if L - self.first_dense > 0:
            groups.append(LayerGroupSpec(num_layers=L - self.first_dense, fn_idx=1))
        return dataclasses.replace(spec, layer_groups=tuple(groups))

    def expert_layers(self):
        """(expert layers, experts each holds, experts per token): what the
        session's ``nxdi_moe_*`` counters count."""
        n_moe = self.config.num_hidden_layers - self.first_dense
        if n_moe <= 0:
            return None
        return n_moe, self.num_experts, self.moe_spec().top_k

    def cache_streams(self):
        """What a token leaves in a layer of the pool: the compressed latent
        and ONE rotary key, ``kv_lora_rank + qk_rope_head_dim`` numbers
        whatever the head count. The rotary key is narrower than the chip's
        128 lanes, so as many tokens as fill them share a pool row."""
        cfg = self.config
        d_rope, bs = cfg.qk_rope_head_dim, cfg.tpu_config.pa_block_size
        pack = 128 // d_rope if 128 % d_rope == 0 and bs % (128 // d_rope) == 0 else 1
        return (
            CacheStream(1, cfg.kv_lora_rank, name="latent"),
            CacheStream(1, d_rope, pack=pack, name="rope_key"),
        )

    def mlp_fn(self):
        has_shared = bool(getattr(self.config, "n_shared_experts", 0))

        from neuronx_distributed_inference_tpu.modules.moe import shared_expert_mlp

        act = getattr(self.config, "hidden_act", "silu")
        shared = (lambda p, x: shared_expert_mlp(p, x, act)) if has_shared else None
        return [gated_mlp, ExpertMlp(self.moe_spec(), shared)]

    def layer_fn(self):
        import functools

        return functools.partial(mla_decoder_layer, mla=self.mla_spec())

    # ---- cache: latent stream ------------------------------------------

    def cache_pspecs(self):
        # single-"head" latent streams replicate over the model axes
        # (quantized caches carry an extra scale leaf per stream); the shard
        # auditor reads this declaration, so the replicated-cache exception
        # for MLA is explicit instead of a special case in the analyzer
        from neuronx_distributed_inference_tpu.modules.kvcache import KVCache

        if self.config.tpu_config.kv_quantized:
            from neuronx_distributed_inference_tpu.modules.kvcache import QuantizedKV

            stream = QuantizedKV(data=P(), scale=P())
            return KVCache(k=stream, v=stream)
        return KVCache(k=P(), v=P())

    def init_kv_cache(self, mesh):
        from neuronx_distributed_inference_tpu.modules.kvcache import init_cache
        from neuronx_distributed_inference_tpu.parallel.sharding import shard_pytree

        cfg = self.config
        tc = cfg.tpu_config
        dt = to_dtype(tc.kv_cache_dtype or tc.dtype)
        kv_batch = tc.kv_cache_batch_size or tc.max_batch_size
        cache = init_cache(
            cfg.num_hidden_layers, kv_batch, tc.seq_len,
            1, cfg.kv_lora_rank,  # K stream: compressed latent
            dtype=dt,
            v_heads=1, v_head_dim=cfg.qk_rope_head_dim,  # V stream: rope keys
        )
        return shard_pytree(cache, self.cache_pspecs(), mesh)

    # ---- params ----------------------------------------------------------

    def _group_sizes(self) -> Tuple[int, int]:
        L = self.config.num_hidden_layers
        return self.first_dense, L - self.first_dense

    def _attn_shapes(self, L: int) -> Dict:
        cfg = self.config
        H = cfg.hidden_size
        Hq = self.q_heads
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        r_q = getattr(cfg, "q_lora_rank", None)
        shapes = {
            "kv_a_proj": {"weight": (L, H, cfg.kv_lora_rank + cfg.qk_rope_head_dim)},
            "kv_a_layernorm": {"weight": (L, cfg.kv_lora_rank)},
            "k_absorb": {"weight": (L, Hq, cfg.qk_nope_head_dim, cfg.kv_lora_rank)},
            "v_absorb": {"weight": (L, Hq, cfg.kv_lora_rank, cfg.v_head_dim)},
            "o_proj": {"weight": (L, Hq * cfg.v_head_dim, H)},
        }
        if r_q:
            shapes["q_a_proj"] = {"weight": (L, H, r_q)}
            shapes["q_a_layernorm"] = {"weight": (L, r_q)}
            shapes["q_b_proj"] = {"weight": (L, r_q, Hq * dq)}
        else:
            shapes["q_proj"] = {"weight": (L, H, Hq * dq)}
        return shapes

    def _attn_pspecs(self) -> Dict:
        r_q = getattr(self.config, "q_lora_rank", None)
        specs = {
            "kv_a_proj": {"weight": P()},
            "kv_a_layernorm": {"weight": P()},
            "k_absorb": {"weight": P(None, TENSOR, None, None)},
            "v_absorb": {"weight": P(None, TENSOR, None, None)},
            "o_proj": {"weight": P(None, TENSOR, None)},
        }
        if r_q:
            specs["q_a_proj"] = {"weight": P()}
            specs["q_a_layernorm"] = {"weight": P()}
            specs["q_b_proj"] = {"weight": P(None, None, TENSOR)}
        else:
            specs["q_proj"] = {"weight": P(None, None, TENSOR)}
        return specs

    def _dense_mlp_shapes(self, L: int) -> Dict:
        H, I = self.config.hidden_size, self.config.intermediate_size
        return {
            "gate_proj": {"weight": (L, H, I)},
            "up_proj": {"weight": (L, H, I)},
            "down_proj": {"weight": (L, I, H)},
        }

    def _moe_mlp_shapes(self, L: int) -> Dict:
        cfg = self.config
        H = cfg.hidden_size
        E = self.num_experts
        I = getattr(cfg, "moe_intermediate_size")
        shapes = {
            # the router keeps its published width whatever share is held
            "router": {
                "weight": (L, H, self.published_experts),
                "e_score_correction_bias": (L, self.published_experts),
            },
            "experts": {
                "gate_proj": {"weight": (L, E, H, I)},
                "up_proj": {"weight": (L, E, H, I)},
                "down_proj": {"weight": (L, E, I, H)},
            },
        }
        n_shared = getattr(cfg, "n_shared_experts", 0)
        if n_shared:
            from neuronx_distributed_inference_tpu.modules.moe import (
                shared_expert_shapes,
            )

            Is = I * n_shared
            shapes["shared_experts"] = shared_expert_shapes(
                L, H, Is, bool(getattr(cfg.tpu_config, "fused_shared_experts", False))
            )
        return shapes

    def param_shapes(self) -> Dict:
        cfg = self.config
        H, V = cfg.hidden_size, self.padded_vocab
        nd, nm = self._group_sizes()
        groups = []
        if nd:
            groups.append(
                {
                    "input_layernorm": {"weight": (nd, H)},
                    "post_attention_layernorm": {"weight": (nd, H)},
                    "self_attn": self._attn_shapes(nd),
                    "mlp": self._dense_mlp_shapes(nd),
                }
            )
        if nm:
            groups.append(
                {
                    "input_layernorm": {"weight": (nm, H)},
                    "post_attention_layernorm": {"weight": (nm, H)},
                    "self_attn": self._attn_shapes(nm),
                    "mlp": self._moe_mlp_shapes(nm),
                }
            )
        return {
            "embed_tokens": {"weight": (V, H)},
            "rope": {"inv_freq": (cfg.qk_rope_head_dim // 2,)},
            "layers": groups,
            "norm": {"weight": (H,)},
            "lm_head": {"weight": (H, V)},
        }

    def param_pspecs(self) -> Dict:
        tc = self.config.tpu_config
        nd, _ = self._group_sizes()
        ffn = TENSOR

        def dense_specs():
            return {
                "gate_proj": {"weight": P(None, None, ffn)},
                "up_proj": {"weight": P(None, None, ffn)},
                "down_proj": {"weight": P(None, ffn, None)},
            }

        moe_specs = {
            "router": {"weight": P(), "e_score_correction_bias": P()},
            "experts": {
                "gate_proj": {"weight": P(None, "ep", None, ("cp", "tp"))},
                "up_proj": {"weight": P(None, "ep", None, ("cp", "tp"))},
                "down_proj": {"weight": P(None, "ep", ("cp", "tp"), None)},
            },
        }
        if getattr(self.config, "n_shared_experts", 0):
            from neuronx_distributed_inference_tpu.modules.moe import (
                shared_expert_pspecs,
            )

            moe_specs["shared_experts"] = shared_expert_pspecs(
                bool(getattr(tc, "fused_shared_experts", False)), ffn
            )
        groups = []
        if nd:
            groups.append(
                {
                    "input_layernorm": {"weight": P()},
                    "post_attention_layernorm": {"weight": P()},
                    "self_attn": self._attn_pspecs(),
                    "mlp": dense_specs(),
                }
            )
        if self.config.num_hidden_layers - nd > 0:
            groups.append(
                {
                    "input_layernorm": {"weight": P()},
                    "post_attention_layernorm": {"weight": P()},
                    "self_attn": self._attn_pspecs(),
                    "mlp": moe_specs,
                }
            )
        return {
            "embed_tokens": {"weight": P(TENSOR, None) if tc.vocab_parallel else P(None, TENSOR)},
            "rope": {"inv_freq": P()},
            "layers": groups,
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
            g["self_attn"]["kv_a_layernorm"]["weight"] = jnp.ones_like(
                g["self_attn"]["kv_a_layernorm"]["weight"]
            )
            if "q_a_layernorm" in g["self_attn"]:
                g["self_attn"]["q_a_layernorm"]["weight"] = jnp.ones_like(
                    g["self_attn"]["q_a_layernorm"]["weight"]
                )
            if "router" in g["mlp"]:
                g["mlp"]["router"]["e_score_correction_bias"] = jnp.zeros_like(
                    g["mlp"]["router"]["e_score_correction_bias"]
                )
        return params

    def convert_hf_state_dict(self, sd: Dict[str, np.ndarray], dtype=None) -> Dict:
        """HF DeepSeek-V3 checkpoint -> grouped param pytree.

        kv_b_proj is split into the absorption tensors (reference wkv_b view,
        modeling_deepseek.py:227-232). Padded q heads get zero rows.
        """
        cfg = self.config
        dtype = dtype or to_dtype(cfg.tpu_config.dtype)
        L = cfg.num_hidden_layers
        nd, _ = self._group_sizes()
        H = cfg.hidden_size
        Hq_orig = cfg.num_attention_heads
        Hq = self.q_heads
        d_nope, d_rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        dq = d_nope + d_rope
        r_kv = cfg.kv_lora_rank
        r_q = getattr(cfg, "q_lora_rank", None)

        def get(name):
            if name not in sd:
                raise KeyError(f"missing HF weight {name}")
            return np.asarray(sd[name])

        def lt(name):  # (out, in) -> (in, out)
            return get(name).T

        # interleaved rope weights (HF rope_interleave=True): permute each
        # rope block's columns [r0,i0,r1,i1,...] -> [r...,i...] so the
        # standard rotate-half rope applies (HF apply_rotary_pos_emb_interleave
        # does the same permutation on activations)
        perm = None
        if getattr(cfg, "rope_interleave", False):
            half = d_rope // 2
            perm = np.empty(d_rope, np.int64)
            perm[:half] = np.arange(half) * 2
            perm[half:] = np.arange(half) * 2 + 1

        def fix_q_rope(w):  # (in, Hq_orig*dq)
            if perm is None:
                return w
            w = w.reshape(w.shape[0], Hq_orig, dq).copy()
            w[..., d_nope:] = w[..., d_nope:][..., perm]
            return w.reshape(w.shape[0], -1)

        def fix_kv_rope(w):  # (in, r_kv + d_rope)
            if perm is None:
                return w
            w = w.copy()
            w[..., r_kv:] = w[..., r_kv:][..., perm]
            return w

        def pad_heads(w, per_head):
            # (..., Hq_orig*per_head) -> (..., Hq*per_head) zero tail heads
            if Hq == Hq_orig:
                return w
            pad = (Hq - Hq_orig) * per_head
            return np.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, pad)])

        def attn_params(i):
            p = f"model.layers.{i}.self_attn."
            out = {
                "kv_a_proj": {"weight": fix_kv_rope(lt(p + "kv_a_proj_with_mqa.weight"))},
                "kv_a_layernorm": {"weight": get(p + "kv_a_layernorm.weight")},
            }
            if r_q:
                out["q_a_proj"] = {"weight": lt(p + "q_a_proj.weight")}
                out["q_a_layernorm"] = {"weight": get(p + "q_a_layernorm.weight")}
                out["q_b_proj"] = {
                    "weight": pad_heads(fix_q_rope(lt(p + "q_b_proj.weight")), dq)
                }
            else:
                out["q_proj"] = {"weight": pad_heads(fix_q_rope(lt(p + "q_proj.weight")), dq)}
            # kv_b (Hq_orig*(d_nope+dv), r_kv) -> absorb tensors
            wkv = get(p + "kv_b_proj.weight").reshape(Hq_orig, d_nope + dv, r_kv)
            k_ab = np.zeros((Hq, d_nope, r_kv), wkv.dtype)
            v_ab = np.zeros((Hq, r_kv, dv), wkv.dtype)
            k_ab[:Hq_orig] = wkv[:, :d_nope, :]
            v_ab[:Hq_orig] = np.swapaxes(wkv[:, d_nope:, :], 1, 2)
            out["k_absorb"] = {"weight": k_ab}
            out["v_absorb"] = {"weight": v_ab}
            o = lt(p + "o_proj.weight")  # (Hq_orig*dv, H)
            o_pad = np.zeros((Hq * dv, o.shape[1]), o.dtype)
            o_pad[: Hq_orig * dv] = o
            out["o_proj"] = {"weight": o_pad}
            return out

        def mlp_dense(i):
            p = f"model.layers.{i}.mlp."
            return {
                "gate_proj": {"weight": lt(p + "gate_proj.weight")},
                "up_proj": {"weight": lt(p + "up_proj.weight")},
                "down_proj": {"weight": lt(p + "down_proj.weight")},
            }

        def mlp_moe(i):
            p = f"model.layers.{i}.mlp."
            held = range(self.first_expert, self.first_expert + self.num_experts)
            out = {
                "router": {
                    "weight": lt(p + "gate.weight"),
                    "e_score_correction_bias": get(p + "gate.e_score_correction_bias"),
                },
                "experts": {
                    "gate_proj": {
                        "weight": np.stack(
                            [lt(p + f"experts.{e}.gate_proj.weight") for e in held]
                        )
                    },
                    "up_proj": {
                        "weight": np.stack(
                            [lt(p + f"experts.{e}.up_proj.weight") for e in held]
                        )
                    },
                    "down_proj": {
                        "weight": np.stack(
                            [lt(p + f"experts.{e}.down_proj.weight") for e in held]
                        )
                    },
                },
            }
            if getattr(cfg, "n_shared_experts", 0):
                out["shared_experts"] = {
                    "gate_proj": {"weight": lt(p + "shared_experts.gate_proj.weight")},
                    "up_proj": {"weight": lt(p + "shared_experts.up_proj.weight")},
                    "down_proj": {"weight": lt(p + "shared_experts.down_proj.weight")},
                }
                if getattr(cfg.tpu_config, "fused_shared_experts", False):
                    from neuronx_distributed_inference_tpu.modules.moe import (
                        fuse_shared_expert_params,
                    )

                    out["shared_experts"] = fuse_shared_expert_params(
                        out["shared_experts"]
                    )
            return out

        def stack_group(layer_ids, mlp_fn_):
            per = []
            for i in layer_ids:
                p = f"model.layers.{i}."
                per.append(
                    {
                        "input_layernorm": {"weight": get(p + "input_layernorm.weight")},
                        "post_attention_layernorm": {
                            "weight": get(p + "post_attention_layernorm.weight")
                        },
                        "self_attn": attn_params(i),
                        "mlp": mlp_fn_(i),
                    }
                )
            return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs), dtype), *per)

        embed = get("model.embed_tokens.weight")
        vpad = self.padded_vocab - embed.shape[0]
        if vpad:
            embed = np.pad(embed, ((0, vpad), (0, 0)))
        lm = lt("lm_head.weight") if "lm_head.weight" in sd else embed.T
        if vpad and lm.shape[1] != self.padded_vocab:
            lm = np.pad(lm, ((0, 0), (0, vpad)))

        from neuronx_distributed_inference_tpu.modules.rope import compute_inv_freq

        groups = []
        if nd:
            groups.append(stack_group(range(nd), mlp_dense))
        if L - nd > 0:
            groups.append(stack_group(range(nd, L), mlp_moe))
        return {
            "embed_tokens": {"weight": jnp.asarray(embed, dtype)},
            "rope": {"inv_freq": compute_inv_freq(cfg)},
            "layers": groups,
            "norm": {"weight": jnp.asarray(get("model.norm.weight"), dtype)},
            "lm_head": {"weight": jnp.asarray(lm, dtype)},
        }
