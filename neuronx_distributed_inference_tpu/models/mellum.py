"""Mellum 2 (``model_type: "mellum"``): the Qwen3-MoE decoder with WINDOW
and FULL attention layers mixed in one stack.

The layer is :class:`~.mixtral.Qwen3MoeModelBuilder`'s, key for key (per-head
q/k RMSNorm before a rotate-half rotary on the whole head, softmax router in
float32, top-k renormalised, no shared expert, every layer sparse). New is
that ``layer_types`` gives each layer a KIND, and a kind has

- its mask: ``sliding_attention`` sees key ``j`` from query ``i`` iff
  ``i - sliding_window < j <= i``; ``full_attention`` is causal;
- its rotary table, from ``rope_parameters[<kind>]``: the window layers the
  default table, the full layers YaRN's blend with cos and sin multiplied by
  ``attention_factor``. Both are built once by the builder
  (:meth:`MellumModelBuilder.rope_tables`) and handed to the layers by kind;
- its cache LIFETIME (``cache_layers()``): a full layer pages K/V over the
  allocator's pool (``PAGED_KV``), as long as the context; a window layer
  keeps a ring of blocks a slot (``WINDOW_KV``:
  modules/block_kvcache.WindowRing), ``sliding_window`` + one prefill chunk
  whatever the context, read by the same two paged kernels through a table
  made in the graph from the row's slot.

The stack is run by :class:`WindowFullStack` (a ``models/base.LayerStack``):
one ``lax.scan`` a RUN of like layers (``[W, W, W, F] x 7`` is 14 runs), each
over its own stacked weights as ``run_decoder_layers`` scans a layer group,
so ``params["layers"]`` is a list of the runs' trees (the builder cuts the
Qwen3-MoE builder's one tree into it at load).

Served on the paged, chunked, continuously batched path only
(``ServingSession``); what a cache of two lifetimes cannot do is refused by
type at config time (config.validate_two_lifetime_cache), ``generate()`` on
the contiguous cache among it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.config import to_dtype, validate_two_lifetime_cache
from neuronx_distributed_inference_tpu.models.base import (
    EXPERT_CHOICES,
    PHASE_TOKEN_GENERATION,
    LayerStack,
    build_mask,
    decoder_layer,
    expert_positions,
    paged_block_inputs,
)
from neuronx_distributed_inference_tpu.models.granite_hybrid import _runs
from neuronx_distributed_inference_tpu.models.mixtral import (
    MoEInferenceConfig,
    Qwen3MoeModelBuilder,
)
from neuronx_distributed_inference_tpu.models.registry import register_model
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    PAGED_KV,
    WINDOW_KV,
    HybridBlockCache,
    WindowRing,
    init_window_ring,
    window_ring_blocks,
    window_ring_pspecs,
)
from neuronx_distributed_inference_tpu.modules.moe import (
    hoist_expert_stacks,
    place_expert_stacks,
)
from neuronx_distributed_inference_tpu.modules.rope import (
    default_inv_freq,
    rope_cos_sin,
    yarn_inv_freq,
    yarn_mscale,
)
from neuronx_distributed_inference_tpu.telemetry.device_scopes import ATTN_FULL, ATTN_WINDOW

#: ``layer_types`` entry -> kind of attention layer (telemetry/device_scopes.ATTN_KINDS)
KINDS = {"sliding_attention": ATTN_WINDOW, "full_attention": ATTN_FULL}


class MellumInferenceConfig(MoEInferenceConfig):
    _REQUIRED_ATTRS = MoEInferenceConfig._REQUIRED_ATTRS + (
        "layer_types", "sliding_window", "rope_parameters",
    )

    def validate_config(self):
        super().validate_config()
        kinds = tuple(self.layer_types)
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(KINDS):
            raise ValueError(
                f"layer_types must name one of {sorted(KINDS)} for each of "
                f"num_hidden_layers={self.num_hidden_layers} layers, got {kinds}"
            )
        if set(getattr(self, "mlp_layer_types", None) or ["sparse"]) != {"sparse"}:
            raise NotImplementedError(
                "mellum with an mlp_layer_types entry other than 'sparse': a dense layer "
                "among the expert layers is not built (every published layer is sparse)"
            )
        rope = self.rope_parameters
        for kind in set(kinds):
            rope_type = (rope.get(kind) or {}).get("rope_type")
            if rope_type not in ("default", "yarn"):
                raise NotImplementedError(
                    f"mellum with rope_parameters[{kind!r}].rope_type = {rope_type!r}: the "
                    "default table and YaRN's are built"
                )
        if not int(self.sliding_window) > 0:
            raise ValueError(f"sliding_window {self.sliding_window}: a window layer sees at least itself")
        if "sliding_attention" in kinds:
            validate_two_lifetime_cache(self.tpu_config)


def rope_table(section: dict, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim / 2,), what cos and sin are multiplied
    by) of one ``rope_parameters`` section."""
    theta = float(section.get("rope_theta", 10000.0))
    if section.get("rope_type", "default") == "default":
        return np.asarray(default_inv_freq(head_dim, theta), np.float32), 1.0
    factor = float(section["factor"])
    inv = yarn_inv_freq(
        head_dim, theta, factor=factor,
        beta_fast=float(section.get("beta_fast", 32.0)), beta_slow=float(section.get("beta_slow", 1.0)),
        original_max_position_embeddings=int(section["original_max_position_embeddings"]),
    )
    scaling = section.get("attention_factor")
    return np.asarray(inv, np.float32), float(yarn_mscale(factor) if scaling is None else scaling)


def layer_runs(kinds: Tuple[str, ...]) -> Tuple[Tuple[str, int, int], ...]:
    """``kinds`` cut into runs of like layers, in model order: (kind, rank of
    the run's first layer among the layers of its kind, length)."""
    return tuple(_runs(tuple(kinds), {}))


class WindowFullStack(LayerStack):
    """Runs a stack whose layers are of two KINDS of attention, window and
    full, over ``HybridBlockCache(k, v, state=WindowRing)``: ``k`` / ``v`` the
    allocator's pool over the full layers, the ring the window layers'.

    ``params["layers"]`` is a LIST, one tree a RUN of like layers stacked over
    the run (:func:`layer_runs`), and a run is one ``lax.scan`` with its
    weights as the scan's own operands, as ``run_decoder_layers`` scans a
    group: the form the chip's compiler keeps in place. (One tree over all
    layers, indexed from a scan over periods, it does not: the layout it
    wants for the expert products of a 16- or 32-position chunk pass it then
    gives to the WHOLE stacks, 2 x 1.97 GB of copies a dispatch at the
    published widths, and refuses the program for memory: PERF.md, PR 58.) A
    layer's index into its kind's pool is its rank among the layers of its
    kind."""

    def __init__(self, kinds: Tuple[str, ...], window: int, ropes: Dict[str, tuple], expert_mlp):
        self.kinds, self.window, self.ropes, self.expert_mlp = tuple(kinds), int(window), ropes, expert_mlp
        self.runs = layer_runs(self.kinds)

    def __call__(self, params, hidden, cache, inputs, *, spec, phase, mlp_fn):
        if phase != PHASE_TOKEN_GENERATION or inputs.block_table is None:
            raise NotImplementedError(
                "a stack of window and full attention layers runs on the paged serving path "
                "only (chunk and decode programs of the token-generation runner)"
            )
        ring = getattr(cache, "state", None)
        if ATTN_WINDOW in self.kinds and not isinstance(ring, WindowRing):
            raise TypeError(
                f"expected a HybridBlockCache over a WindowRing, got {type(cache).__name__}"
            )
        if len(params["layers"]) != len(self.runs):
            raise ValueError(
                f"layer_types has {len(self.runs)} runs of like layers, params carry "
                f"{len(params['layers'])} groups"
            )
        positions = inputs.position_ids
        slot_mapping, table, kv_limit = paged_block_inputs(inputs, cache.block_size)
        block_inputs = {ATTN_FULL: (slot_mapping, table, kv_limit)}
        pools = {ATTN_FULL: (cache.k, cache.v)}
        if ATTN_WINDOW in self.kinds:
            # the window layers' table and write slots: arithmetic on the
            # rows' slots, made here (no host array, no gather through a table)
            fed = (
                inputs.slot_mapping >= 0 if inputs.slot_mapping is not None
                else jnp.ones(positions.shape, bool)
            )
            block_inputs[ATTN_WINDOW] = (
                ring.slot_mapping(inputs.seq_ids, positions, fed),
                ring.block_table(inputs.seq_ids, table.shape[1]),
                kv_limit,
            )
            pools[ATTN_WINDOW] = (ring.k, ring.v)
        windows = {ATTN_FULL: None, ATTN_WINDOW: self.window}
        masks = {k: build_mask(inputs, spec, phase, window=windows[k]) for k in set(self.kinds)}
        rotary = {
            k: rope_cos_sin(positions, jnp.asarray(inv), scaling)
            for k, (inv, scaling) in self.ropes.items()
        }
        B, S, _ = hidden.shape
        # a paged chunk pass routes its real positions alone
        expert_valid = expert_positions(inputs, phase)
        choices = spec.output_choices
        chose = None
        if choices:
            top_k = self.expert_mlp.spec.top_k
            chose = jnp.zeros((len(self.kinds), B, S, top_k), jnp.int32)
        carry = (hidden, pools, chose)
        first = 0
        for (kind, first_rank, count), run_params in zip(self.runs, params["layers"]):
            # a pass that takes the grouped-matmul kernel reads the run's
            # experts from its stacks in place (never a layer's slice of them)
            run_params, expert_stacks = hoist_expert_stacks(
                run_params, self.expert_mlp.spec, S, B * S, hidden.dtype
            )

            def layer(carry, xs, kind=kind, expert_stacks=expert_stacks, first=first,
                      first_rank=first_rank):
                h, pools, chose = carry
                lp, j = xs
                lp = place_expert_stacks(lp, expert_stacks, j)
                picked = []

                def mlp(p, x, s):
                    out = self.expert_mlp(p, x, s, expert_valid)
                    if choices:
                        out, mine = out
                        picked.append(mine)
                    return out

                cos, sin = rotary[kind]
                k_c, v_c = pools[kind]
                h, k_c, v_c = decoder_layer(
                    lp, h, cos, sin, k_c, v_c, first_rank + j, masks[kind], inputs.seq_ids,
                    positions, spec, phase, mlp, block_inputs=block_inputs[kind],
                    adapter_ids=inputs.adapter_ids, window=windows[kind], attn_kind=kind,
                )
                if choices:
                    chose = jax.lax.dynamic_update_index_in_dim(chose, picked[0], first + j, 0)
                return (h, dict(pools, **{kind: (k_c, v_c)}), chose), None

            carry, _ = jax.lax.scan(layer, carry, (run_params, jnp.arange(count, dtype=jnp.int32)))
            first += count
        hidden, pools, chose = carry
        k, v = pools[ATTN_FULL]
        if ATTN_WINDOW in self.kinds:
            k_w, v_w = pools[ATTN_WINDOW]
            new_cache = HybridBlockCache(
                k=k, v=v, state=WindowRing(k=k_w, v=v_w, ring_blocks=ring.ring_blocks)
            )
        else:
            new_cache = type(cache)(k=k, v=v)
        if not choices:
            return hidden, new_cache
        # (L, B, S, k) -> (B, S, L, k)
        return hidden, new_cache, {EXPERT_CHOICES: jnp.transpose(chose, (1, 2, 0, 3))}


@register_model("mellum")
class MellumModelBuilder(Qwen3MoeModelBuilder):
    """Qwen3-MoE layers, each a window or a full attention layer by
    ``layer_types``, over a paged cache of two lifetimes."""

    config_cls = MellumInferenceConfig

    def __init__(self, config):
        super().__init__(config)
        self.kinds = tuple(KINDS[t] for t in config.layer_types)
        self.window = int(config.sliding_window)

    # ---- params: the Qwen3-MoE tree, cut into the runs of like layers ------

    #: set while the parent builds its one tree over all layers
    _whole = False

    def _cut(self, layers, take):
        """``layers`` (every leaf led by the layer axis) as the list of the
        runs' trees; ``take(leaf, start, count)`` cuts one leaf."""
        out, start = [], 0
        for _, _, count in layer_runs(self.kinds):
            out.append(jax.tree.map(
                lambda leaf: take(leaf, start, count), layers,
                is_leaf=lambda x: isinstance(x, tuple)))
            start += count
        return out

    def param_shapes(self) -> Dict:
        shapes = super().param_shapes()
        if not self._whole:
            shapes["layers"] = self._cut(shapes["layers"], lambda s, _, count: (count,) + s[1:])
        return shapes

    def param_pspecs(self) -> Dict:
        specs = super().param_pspecs()
        specs["layers"] = [specs["layers"]] * len(layer_runs(self.kinds))
        return specs

    def _whole_then_cut(self, build):
        self._whole = True
        try:
            params = build()
        finally:
            self._whole = False
        params["layers"] = self._cut(params["layers"], lambda a, start, count: a[start : start + count])
        return params

    def random_params(self, key=None, dtype=None, on_host: bool = False) -> Dict:
        return self._whole_then_cut(lambda: super(MellumModelBuilder, self).random_params(key, dtype, on_host))

    def convert_hf_state_dict(self, sd, dtype=None):
        return self._whole_then_cut(lambda: super(MellumModelBuilder, self).convert_hf_state_dict(sd, dtype))

    def rope_tables(self) -> Dict[str, tuple]:
        """kind -> (inverse frequencies, cos/sin factor), built once."""
        rope = self.config.rope_parameters
        return {
            KINDS[name]: rope_table(rope[name], self.head_dim)
            for name in dict.fromkeys(self.config.layer_types)
        }

    def layer_fn(self):
        return WindowFullStack(self.kinds, self.window, self.rope_tables(), self.mlp_fn())

    def expert_layers(self):
        cfg = self.config
        return cfg.num_hidden_layers, self.num_experts, cfg.num_experts_per_tok

    # ---- what each layer keeps -------------------------------------------

    def cache_layers(self):
        return tuple(WINDOW_KV if k == ATTN_WINDOW else PAGED_KV for k in self.kinds)

    def ring_blocks(self) -> int:
        """Blocks of a slot's ring in a window layer: the window and the
        widest pass that writes, a prefill chunk (window_ring_blocks)."""
        tc = self.config.tpu_config
        cpc = tc.chunked_prefill_config
        chunk = cpc.kernel_q_tile_size if cpc else 128
        return window_ring_blocks(self.window, chunk, tc.pa_block_size)

    def init_slot_state(self, num_slots: int):
        n_window = self.kinds.count(ATTN_WINDOW)
        if not n_window:
            return None
        tc = self.config.tpu_config
        R = self.ring_blocks()
        ring = init_window_ring(
            n_window, num_slots, R, tc.pa_block_size, self.gqa.kv_heads, self.head_dim,
            to_dtype(tc.kv_cache_dtype or tc.dtype),
        )
        return ring, window_ring_pspecs(R)
