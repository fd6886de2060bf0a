"""Sharding rules: logical axes -> mesh PartitionSpecs, plus GQA head accounting.

TPU-native replacement for the reference's parallel layers + GQA sharding
strategies (reference: modules/attention/gqa.py:54-266, nxd parallel_layers).

The reference physically pre-shards weights per rank into
``tp{rank}_sharded_checkpoint.safetensors`` and pads/replicates GQA heads in
state-dict hooks. Here weights are GLOBAL arrays annotated with
``NamedSharding``; GSPMD splits them. GQA head accounting survives as array
transforms applied once at load time:

- ``REPLICATE_TO_TP_DEGREE`` (gqa.py:54-123): when num_kv_heads < model
  parallel degree, repeat each KV head so every shard owns one.
- Q-head padding: pad num_attention_heads up to a multiple of the degree with
  zero heads; the output projection ignores the pads (zero rows).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_inference_tpu.parallel.mesh import (
    ALL_AXES,
    MODEL_AXES,
    ambient_mesh,
)

# Logical axis names used in model param spec trees. Weight tensor-parallel
# dims shard over EVERY mesh axis (dp included — attention-DP subdivides the
# TP group, so the full model group is dp*ep*cp*tp; dp has size 1 unless
# attention_dp_degree > 1).
TENSOR = ALL_AXES
EXPERT = "ep"


class GQASharding:
    """Head accounting for tensor-parallel GQA (reference gqa.py:54-123).

    Given (num_attention_heads q, num_key_value_heads kv, degree d):

    - KV heads are replicated ``r = lcm(kv, d) / kv`` times so the replicated
      count divides the degree (REPLICATE_TO_TP_DEGREE, gqa.py:54-123).
    - Q heads are padded PER REPLICATED KV HEAD: each original kv group's
      ``q/kv`` query heads are distributed over its ``r`` replicas, ``m =
      ceil((q/kv)/r)`` slots each, zero-padded at each replica's tail
      (reference interleaved Q padding, gqa.py:88-123). This keeps
      ``repeat_kv`` pairing correct: padded q slot j attends replicated kv
      head ``j // m``, which is a replica of original kv head ``j // (q/kv)``.

    When ``r`` divides ``q/kv`` the permutation is the identity and no padding
    happens (all common llama/qwen configs).
    """

    def __init__(self, num_attention_heads: int, num_key_value_heads: int, degree: int):
        q, kv, d = num_attention_heads, num_key_value_heads, degree
        if q % kv != 0:
            raise ValueError(f"num_attention_heads={q} must be a multiple of kv heads={kv}")
        self.degree = d
        self.orig_q_heads = q
        self.orig_kv_heads = kv
        self.kv_repeat = math.lcm(kv, d) // kv
        self.kv_heads = kv * self.kv_repeat
        qg = q // kv  # q heads per kv group
        r = self.kv_repeat
        self.q_per_slot = math.ceil(qg / r)  # m: q heads per replicated kv head
        self.q_heads = self.kv_heads * self.q_per_slot
        self.q_pad = self.q_heads - q
        # slot_map[j] = padded slot of original q head j
        m = self.q_per_slot
        self.slot_map = np.array(
            [(j // qg * r + (j % qg) // m) * m + (j % qg) % m for j in range(q)],
            dtype=np.int64,
        )
        self.identity = self.q_pad == 0 and (self.slot_map == np.arange(q)).all()

    @property
    def needs_transform(self) -> bool:
        return self.kv_repeat > 1 or not self.identity

    def replicate_kv(self, w, head_dim: int):
        """Repeat KV projection output columns per head (weight (..., kv*D))."""
        if self.kv_repeat == 1:
            return w
        w = np.asarray(w)
        shape = w.shape
        w = w.reshape(shape[:-1] + (self.orig_kv_heads, head_dim))
        w = np.repeat(w, self.kv_repeat, axis=-2)
        return w.reshape(shape[:-1] + (self.kv_heads * head_dim,))

    def pad_q(self, w, head_dim: int):
        """Scatter Q projection output columns (..., q*D) into padded
        interleaved slots (..., q_heads*D)."""
        if self.identity:
            return w
        w = np.asarray(w)
        shape = w.shape
        w = w.reshape(shape[:-1] + (self.orig_q_heads, head_dim))
        out = np.zeros(shape[:-1] + (self.q_heads, head_dim), w.dtype)
        out[..., self.slot_map, :] = w
        return out.reshape(shape[:-1] + (self.q_heads * head_dim,))

    def pad_o(self, w, head_dim: int):
        """Scatter O projection input rows (..., q*D, H) into padded slots."""
        if self.identity:
            return w
        w = np.asarray(w)
        shape = w.shape
        w = w.reshape(shape[:-2] + (self.orig_q_heads, head_dim, shape[-1]))
        out = np.zeros(shape[:-2] + (self.q_heads, head_dim, shape[-1]), w.dtype)
        out[..., self.slot_map, :, :] = w
        return out.reshape(shape[:-2] + (self.q_heads * head_dim, shape[-1]))


def constrain(x, spec: P):
    """with_sharding_constraint that degrades to a no-op outside a mesh
    context (single-device paths). Shared by the CP/SP and attention-DP
    constraint modules."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError):
        return x
    except RuntimeError as e:
        # jax's "no mesh at the call site" (e.g. AOT lowering a step
        # function without a mesh context) degrades to a no-op like the
        # single-device case; any OTHER RuntimeError must surface — a
        # swallowed constraint here silently drops sharding pins the
        # programs depend on (e.g. the mixed-step scan-carried hidden)
        if "requires a non-empty mesh" in str(e):
            return x
        raise


def head_shard_degree() -> int:
    """How many ways the ambient mesh (the enclosing ``jax.set_mesh`` scope)
    splits the attention head axis: the product of its :data:`TENSOR` axes,
    1 outside a mesh. The observable :func:`shard_over_heads` launches on."""
    mesh = ambient_mesh()
    if mesh is None:
        return 1
    return math.prod(dict(mesh.shape).get(a, 1) for a in TENSOR)


def shard_over_heads(fn, args, in_heads, out_heads):
    """``fn(*args)``, once per model-parallel shard of the head axis.

    A ``pallas_call`` carries no partitioning rule, and a scatter whose
    INDEXED dim is sharded may be gathered; both are per-head work with no
    cross-head term, so on a head-sharded mesh they run inside
    ``jax.shard_map`` over the :data:`TENSOR` axes: every shard sees its own
    ``H / degree`` heads and the whole of everything else, and no collective
    appears inside (GQASharding makes both head counts divide the degree).
    At degree 1 — one chip, or no mesh — this IS the plain call, so a
    one-chip program lowers to exactly what it lowers to without it.

    ``in_heads`` names, per argument, the position of its head axis (``None``
    = replicated: block tables, masks, positions, layer index); a pytree
    argument takes a pytree of positions. ``out_heads`` likewise for the
    result. An argument that IS ``None`` (no sink, no dequant factors)
    reaches ``fn`` as ``None``. Shared by the ragged, paged-flash and TKG
    attention dispatches and the per-head paged KV write."""
    if head_shard_degree() == 1:
        return fn(*args)
    mesh = ambient_mesh()
    axes = tuple(a for a in TENSOR if a in mesh.shape)
    given = [i for i, a in enumerate(args) if a is not None]

    def spec(pos):
        return P() if pos is None else P(*([None] * pos), axes)

    def specs(tree):
        return jax.tree.map(spec, tree, is_leaf=lambda x: x is None)

    def per_shard(*shards):
        full = [None] * len(args)
        for i, shard in zip(given, shards):
            full[i] = shard
        return fn(*full)

    return jax.shard_map(
        per_shard, mesh=mesh, in_specs=specs(tuple(in_heads[i] for i in given)),
        out_specs=specs(out_heads), check_vma=False,
    )(*(args[i] for i in given))


def make_sharding_fn(mesh: Mesh):
    """Return spec -> NamedSharding resolver for this mesh."""

    def to_sharding(spec: P) -> NamedSharding:
        return NamedSharding(mesh, spec)

    return to_sharding


def shard_pytree(params, spec_tree, mesh: Mesh):
    """Device-put a param pytree with its PartitionSpec tree onto the mesh."""
    def _put(x, spec):
        if spec is None:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(_put, params, spec_tree)


def pspec_tree_like(params, default=None):
    return jax.tree.map(lambda _: default if default is not None else P(), params)
