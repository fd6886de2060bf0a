"""Device mesh construction.

TPU-native replacement for the reference's process-group machinery
(reference: modules/attention/attention_process_groups.py, models/config.py:333-361).

The reference builds torch.distributed process groups per parallelism flavor
(TP, TP×CP for prefill attention, TP×DP for decode attention, moe_tp×moe_ep)
with hand-written TRN2 "8x8" physical-mesh tables. On TPU all of that collapses
into ONE ``jax.sharding.Mesh`` with named axes; GSPMD emits the ICI/DCN
collectives. Axis layout:

    (dp, ep, cp, tp)
    sizes: (attention_dp_degree, ep_degree, cp_degree,
            tp_degree / (cp_degree * attention_dp_degree))

- Weight tensor-parallel dims are sharded over ALL axes combined
  (= full tp_degree × ep_degree model group; see sharding.TENSOR).
- Context-parallel prefill shards sequence over ``cp`` while heads shard over
  ``tp`` — same devices, different view (reference attention_base.py:245-257).
- Attention-DP decode shards the BATCH over ``dp`` while heads shard over the
  remaining axes — both cp and dp subdivide the TP group, exactly like the
  reference's CP/DP process groups reorganize the TP ranks
  (attention_process_groups.py:80-163).
- Expert-parallel shards the expert dim over ``ep``.

``mesh_utils.create_device_mesh`` picks an ICI-aware device ordering — the
equivalent of the reference's hand-coded physical mesh tables
(attention_process_groups.py:14-23).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DDP = "ddp"  # whole-model data parallel (multi-slice, rides DCN)
AXIS_DP = "dp"
AXIS_EP = "ep"
AXIS_CP = "cp"
AXIS_TP = "tp"

#: Axes that together form the model-parallel group (weights sharded over all).
MODEL_AXES = (AXIS_EP, AXIS_CP, AXIS_TP)
ALL_AXES = (AXIS_DP, AXIS_EP, AXIS_CP, AXIS_TP)
FULL_AXES = (AXIS_DDP,) + ALL_AXES


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
):
    """Join the multi-host runtime (reference: the torchrun env handshake of
    scripts/nxdi_distributed_launcher.py:29-80).

    On Cloud TPU pods ``jax.distributed.initialize()`` auto-discovers the
    coordinator from the TPU metadata; elsewhere pass coordinator/worldsize
    explicitly (or set JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID). After this, ``jax.devices()`` spans every host and the
    SAME single-host model code runs SPMD over all of them — there is no
    separate multi-node code path.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def build_mesh(
    tp_degree: int = 1,
    cp_degree: int = 1,
    ep_degree: int = 1,
    dp_degree: int = 1,
    ddp_degree: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build the global device mesh.

    ``tp_degree`` is the FULL tensor-parallel degree; internally the mesh
    factors it as (dp, cp, tp//(dp*cp)) so context-parallel attention can
    address the ``cp`` sub-axis and attention-DP decode the ``dp`` sub-axis
    (reference: CP/DP groups split the TP group,
    attention_process_groups.py:80-163).

    ``ddp_degree`` adds the leading whole-model data-parallel axis: weights
    replicate over it and the batch shards over it. In a multi-host run the
    device order puts ddp OUTERMOST so its collectives ride DCN while the
    model axes stay on ICI (``mesh_utils.create_hybrid_device_mesh``).
    """
    if tp_degree % (cp_degree * dp_degree) != 0:
        raise ValueError(
            f"cp_degree*dp_degree={cp_degree * dp_degree} must divide "
            f"tp_degree={tp_degree} (both split the TP group)"
        )
    shape = (
        ddp_degree,
        dp_degree,
        ep_degree,
        cp_degree,
        tp_degree // (cp_degree * dp_degree),
    )
    n = int(np.prod(shape))
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    devices = devices[:n]
    if ddp_degree > 1 and jax.process_count() > 1:
        # a failure here is an error, not a reason to flatten: on a flat
        # mesh the ddp axis may land on ICI and the tensor-parallel
        # collectives on DCN. The outer granule is the slice where the
        # devices span several (TPU multi-slice), else the process
        # (several hosts on one slice; multi-process CPU)
        n_slices = len({getattr(d, "slice_index", 0) for d in devices})
        dev_array = mesh_utils.create_hybrid_device_mesh(
            (1,) + shape[1:], (ddp_degree, 1, 1, 1, 1), devices=devices,
            process_is_granule=n_slices == 1,
        )
    elif n == len(jax.devices()):
        # the whole platform: topology-aware order, and what it refuses
        # propagates
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        # an explicit subset (one replica's partition, tp < chip count):
        # the caller's device order IS the layout
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, FULL_AXES)


def single_device_mesh(device=None) -> Mesh:
    dev = device if device is not None else jax.devices()[0]
    return Mesh(np.asarray([dev]).reshape(1, 1, 1, 1, 1), FULL_AXES)


def mesh_from_config(tpu_config, devices=None) -> Mesh:
    return build_mesh(
        tp_degree=tpu_config.tp_degree,
        cp_degree=tpu_config.cp_degree,
        ep_degree=tpu_config.ep_degree,
        dp_degree=tpu_config.attention_dp_degree,
        ddp_degree=getattr(tpu_config, "data_parallel_degree", 1),
        devices=devices,
    )


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pspec_str(spec: Optional[P]) -> str:
    """Canonical machine-readable serialization of a PartitionSpec.

    The shard-audit census (analysis/shard_audit.py GRAPH304) pins these
    strings in ``shard_baseline.json``, so the form must be deterministic
    and insensitive to cosmetic differences: trailing ``None`` entries are
    trimmed (``P(None, 'tp')`` == ``P(None, 'tp', None)``) and multi-axis
    entries render as a ``+``-joined group (``('ep','cp','tp')`` ->
    ``(ep+cp+tp)``). ``None`` serializes as the fully replicated ``P()``."""
    entries = [] if spec is None else list(spec)
    while entries and entries[-1] is None:
        entries.pop()

    def one(e) -> str:
        if e is None:
            return "None"
        if isinstance(e, (tuple, list)):
            return "(" + "+".join(str(a) for a in e) + ")"
        return str(e)

    return "P(" + ", ".join(one(e) for e in entries) + ")"


def sharding_str(sharding) -> str:
    """``pspec_str`` of a NamedSharding (the realized-sharding side of the
    census); non-named shardings fall back to their repr."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return repr(sharding)
    return pspec_str(spec)


def ambient_mesh():
    """The (abstract) mesh of the enclosing ``jax.set_mesh`` scope, or None
    outside one. Readable at TRACE time from inside jit — how the attention
    kernels' dispatches and the paged KV write find the mesh to
    ``jax.shard_map`` over without threading it through model code
    (parallel/sharding.shard_over_heads). Axis names and sizes only: it
    carries no devices."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_sizes(mesh: Mesh) -> dict:
    """Machine-readable ``{axis: size}`` declaration of a mesh — recorded in
    the shard-audit census so a baseline diff shows WHICH axis layout the
    pinned specs were committed against."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))
