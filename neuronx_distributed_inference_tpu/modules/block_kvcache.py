"""Paged (block) KV cache + host-side block allocator.

TPU-native re-design of the reference paged KV stack
(reference: modules/kvcache/block_kv_cache_manager.py — layout
``(num_blocks+1, block_size, H/tp, d)`` with one reserved garbage block;
gather-by-block-table reads, scatter-by-slot-mapping writes; vLLM
``get_active_block_table`` in modules/kvcache/utils.py).

Layout here is HEAD-MAJOR ``(L, num_blocks+1, H_kv, block_size, d)`` — unlike
the reference's token-major blocks — so a Pallas kernel can DMA one head's
block as a ``(block_size, d)`` tile whose last-two block dims equal the array
dims (Mosaic's (8, 128) divisibility rule would reject a ``(1, d)`` slice over
a token-major ``(block_size, H_kv, d)`` block for H_kv > 1). Where ``d`` divides
the chip's 128 lanes (64: granite, Llama-3.2-1B) ``g = 128 // d`` KV heads lie
SIDE BY SIDE in one row, ``(L, num_blocks+1, H_kv / g, block_size, 128)``
(:func:`kv_streams`, the one decision): the pool is then a head_dim-128 pool
to every writer and kernel, and the chip's own layout of it is row-major.

Device side (pure functions used inside the jitted step):
- writes place token K/V through a flat ``slot_mapping`` (block *
  block_size + offset); invalid slots (< 0) are dropped, idle rows' slots
  land in the reserved garbage block 0 (reference's reserved block,
  block_kv_cache_manager.py:11-80). Four forms write the same bytes
  (:func:`write_form` decides, :func:`update_block_cache_at_layer` says
  why): IN KERNEL for a one-token decode pass that rides the paged decode
  kernel (the kernel places the token in the block it holds for the row;
  nothing is written here), WHOLE BLOCKS (window ``(H, bs, D)``) for prefill
  chunks at a head_dim on the 128 lanes, PER HEAD (window ``(D,)``) at the
  other decode and speculation widths, all three leaving the layer scan's
  cache carry in the row-major layout a Pallas operand takes so that the
  step holds no copy of the pool; a TOKEN WINDOW (``(H, D)``) off the lanes
  and for the ragged step's packed axis. On a head-sharded mesh the first
  three run per shard, each writing its own heads.
- decode reads gather blocks by the per-sequence ``block_table`` and view
  them as a contiguous (B, max_blocks*block_size) cache — logical position
  order is preserved, so the normal decode masks apply unchanged.

Host side: :class:`BlockAllocator` manages the free-block pool and builds
slot mappings / block tables (the role vLLM plays for the reference).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.modules.kvcache import (
    QuantizedKV,
    _quantized_update,
    is_kv_quant_dtype,
    layer_dequant_factors,
)
from neuronx_distributed_inference_tpu.ops.kernel_mode import TKG_MAX_Q_LEN
from neuronx_distributed_inference_tpu.parallel.mesh import (
    AXIS_DDP,
    AXIS_DP,
    MODEL_AXES,
    ambient_mesh,
)
from neuronx_distributed_inference_tpu.parallel.sharding import (
    head_shard_degree,
    shard_over_heads,
)

GARBAGE_BLOCK = 0  # block id 0 reserved for invalid-slot writes

#: fewest heads a device must hold for the paged KV write to take its TOKEN
#: WINDOW ``(H, D)``: the chip's tile is (8, 128), and under a window of
#: fewer heads than sublanes the TPU compiler re-lays the whole pool around
#: every layer's scatter (update_block_cache_at_layer). Reached on the ragged
#: step's packed axis and by a chunk pass over a pool row off the lanes: a
#: head_dim that fills no 128-lane row with whole heads (72, 80, 96), an odd
#: head count a device, a quantised pool at head_dim 64 (:func:`heads_a_row`
#: folds every other head_dim under 128 onto the lanes since PR 65)
WINDOW_MIN_HEADS = 8


def prefix_chain_keys(tokens: np.ndarray, block_size: int) -> List[bytes]:
    """Content-addressing keys for prefix caching: one running-sha1 key per
    FULL block of ``tokens`` (a block matches only when its content AND
    everything before it match). Module-level so callers that query SEVERAL
    allocators with one prompt — the router's ``cache_aware`` placement —
    hash the prompt once and reuse the key list per candidate."""
    keys: List[bytes] = []
    h = hashlib.sha1()
    for i in range(len(tokens) // block_size):
        h.update(
            np.asarray(
                tokens[i * block_size : (i + 1) * block_size], np.int32
            ).tobytes()
        )
        keys.append(h.digest())
    return keys


@jax.tree_util.register_dataclass
@dataclass
class BlockKVCache:
    """k/v: (L, num_blocks+1, H_kv, block_size, D) — head-major blocks
    (arrays, or :class:`~.kvcache.QuantizedKV` streams of the same layout).
    ``extra``: the pool streams a builder declares beyond two
    (``builder.cache_streams()``: an indexer's key beside an MLA layer's latent
    and rotary key), addressed by the same block ids; empty for every model
    of two streams, whose cache then has the leaves it always had."""

    k: jax.Array
    v: jax.Array
    extra: Tuple[jax.Array, ...] = ()

    @property
    def num_layers(self):
        return self.k.shape[0]

    @property
    def num_blocks(self):
        return self.k.shape[1] - 1

    @property
    def block_size(self):
        return self.k.shape[3]


#: what a layer keeps between steps, as a model's builder declares it
#: (``builder.cache_layers()``, one entry per layer): a paged K/V stream of
#: ``(H_kv, D)`` per token whose LIFETIME is the context (``PAGED_KV``: blocks
#: from the allocator's pool, as many as the context is long) or a window
#: (``WINDOW_KV``: a ring of blocks a slot, :class:`WindowRing`), or a
#: constant-size state per serving slot
PAGED_KV = "paged_kv"
WINDOW_KV = "window_kv"
SLOT_STATE = "slot_state"


@jax.tree_util.register_dataclass
@dataclass
class HybridBlockCache(BlockKVCache):
    """The cache of a model whose layers keep two kinds of state, as ONE
    donated pytree: ``k``/``v`` are the block pool over the layers that page
    (indexed by a layer's rank among the paging layers), ``state`` is the
    builder's per-slot state: of the layers that do not page
    (modules/ssm.RecurrentState), or kept by every layer BESIDE its K/V
    (modules/latent_attention.TokenCarry). A state gives ``num_slots``,
    ``nbytes``, ``fill_slots(slots, value)`` and ``KIND``, the family of
    serving counters that counts it. The block allocator sees the pool only:
    a slot's state is as large at token 1 as at token 10^5."""

    state: object = None

    #: the fields that are NOT streams of blocks (runtime/faults.fill_kv_rows)
    SLOT_FIELDS = ("state",)


def window_ring_blocks(window: int, q_len: int, block_size: int) -> int:
    """Blocks of the ring a slot holds in a layer that attends a ``window``:
    ``window + q_len`` tokens rounded up to blocks, plus one, ``q_len`` the
    widest pass that writes (a prefill chunk). Position ``p`` lies in ring
    block ``(p // block_size) % R``, so a pass over positions ``p .. p + q -
    1`` overwrites what lay ``R`` blocks back: the last token it loses is
    ``p + q + block_size - 2 - R * block_size`` at the latest, and the oldest
    key its first query still sees is ``p - window + 1``; ``R * block_size >=
    window + q + block_size - 2`` keeps the two apart whatever ``p`` is."""
    return -(-(window + q_len) // block_size) + 1


@jax.tree_util.register_dataclass
@dataclass
class WindowRing:
    """What the layers that attend a WINDOW keep (``WINDOW_KV``), as the
    per-slot state of a :class:`HybridBlockCache`: ``k`` / ``v`` are a block
    pool of their own, ``(L_window, num_slots * R + 1, H_kv, block_size, D)``
    head-major as the paged kernels read it, block 0 the garbage block; slot
    ``s`` owns blocks ``1 + s * R ... (s + 1) * R`` for as long as it lives
    and position ``p`` of its request lies in the ``(p // block_size) % R``-th
    of them (:func:`window_ring_blocks` says why ``R`` suffices). A slot's
    ring is as large at token 1 as at token 10^5; nothing is allocated or
    freed as a request grows, and the ring's "block table" is arithmetic on
    the slot's number, made in the graph (:meth:`block_table`)."""

    k: jax.Array
    v: jax.Array
    ring_blocks: int = field(metadata=dict(static=True), default=1)

    #: which family of the serving step's counters counts this state
    KIND = "window_ring"

    @property
    def num_slots(self) -> int:
        return (self.k.shape[1] - 1) // self.ring_blocks

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def nbytes(self) -> int:
        return int(self.k.size * self.k.dtype.itemsize + self.v.size * self.v.dtype.itemsize)

    @property
    def slot_bytes(self) -> int:
        """Bytes one slot's rings hold over every window layer."""
        return self.nbytes // self.k.shape[1] * self.ring_blocks

    def fill_slots(self, slots, value: float) -> "WindowRing":
        """Overwrite the rings of whole slots in every layer (scrub: 0.0)."""
        R = self.ring_blocks
        blocks = (1 + np.asarray(slots, np.int32)[:, None] * R + np.arange(R, dtype=np.int32)).ravel()
        return WindowRing(
            k=self.k.at[:, blocks].set(value), v=self.v.at[:, blocks].set(value), ring_blocks=R
        )

    def _first_block(self, seq_ids: jax.Array) -> jax.Array:
        return 1 + jnp.maximum(seq_ids, 0).astype(jnp.int32) * self.ring_blocks

    def block_table(self, seq_ids: jax.Array, max_blocks: int) -> jax.Array:
        """(B, max_blocks): the ring block that holds each LOGICAL block of a
        row's sequence, ``seq_ids`` (B,) the rows' slots; a row that sits the
        pass out (slot < 0) reads the garbage block. Only the entries inside
        a row's window are distinct blocks; the paged kernels read no other."""
        ring = jnp.arange(max_blocks, dtype=jnp.int32) % self.ring_blocks
        table = self._first_block(seq_ids)[:, None] + ring[None, :]
        return jnp.where((seq_ids >= 0)[:, None], table, GARBAGE_BLOCK)

    def slot_mapping(self, seq_ids: jax.Array, positions: jax.Array, valid: jax.Array) -> jax.Array:
        """(B, S) flat write slots of ``positions`` in the rows' rings;
        -1 (dropped) where ``valid`` is False or the row sits out."""
        bs = self.block_size
        block = self._first_block(seq_ids)[:, None] + (positions // bs) % self.ring_blocks
        slots = block * bs + positions % bs
        return jnp.where(valid & (seq_ids >= 0)[:, None], slots, -1).astype(jnp.int32)


def init_window_ring(
    num_layers: int, num_slots: int, ring_blocks: int, block_size: int,
    num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
) -> WindowRing:
    shape = (num_layers, num_slots * ring_blocks + 1, num_kv_heads, block_size, head_dim)
    return WindowRing(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), ring_blocks=ring_blocks)


def window_ring_pspecs(ring_blocks: int) -> WindowRing:
    from jax.sharding import PartitionSpec as P

    return WindowRing(k=P(), v=P(), ring_blocks=ring_blocks)


@dataclass(frozen=True)
class CacheStream:
    """One stream of the block pool, as a model's builder declares it
    (``builder.cache_streams()``): a token leaves ``heads x width`` numbers
    in it a layer. ``pack`` tokens of a block share one pool row, which is
    then ``pack x width`` lanes wide: a stream narrower than the chip's 128
    lanes (an MLA layer's one rotary key of 64) would otherwise be padded to
    them in device memory, and no kernel could copy its blocks by hand. Token
    ``o`` of a block lies in row ``o % (block_size // pack)`` at lanes
    ``[o // (block_size // pack) * width, ...)``: a block's first
    ``block_size // pack`` tokens fill the first lane group, row by row.
    ``pack`` > 1 is reached by the one-head rotary-key stream of the latent
    pools only (models/deepseek.py and what builds on it). A K/V stream of
    several narrow heads fills its rows the other way, HEADS side by side
    (:func:`kv_streams`: ``heads`` and ``width`` are then the pool row's,
    ``H_kv / g`` and ``g x D``, at ``pack`` 1), so that the head_dim-128
    kernels and writes apply as they are."""

    heads: int
    width: int
    pack: int = 1
    #: what the stream holds, for counters and documents ("latent": an MLA
    #: layer's compressed latent, counted by ``nxdi_latent_*``)
    name: str = "kv"

    def pool_shape(self, num_layers: int, num_blocks: int, block_size: int):
        if block_size % self.pack:
            raise ValueError(f"block_size {block_size} does not hold whole rows of {self.pack} tokens")
        return (
            num_layers, num_blocks + 1, self.heads, block_size // self.pack,
            self.width * self.pack,
        )


#: the chip's lanes: the width of a pool row the paged kernels copy by hand
LANES = 128


def heads_a_row(num_kv_heads: int, head_dim: int, *, shards: int = 1, quantised: bool = False) -> int:
    """KV heads that lie SIDE BY SIDE in one pool row (``g``), from shapes
    alone: ``128 // head_dim`` where whole heads fill the 128 lanes exactly
    (head_dim 64: two; 32: four), the heads ONE device holds (``num_kv_heads
    / shards``) are a multiple of it (no group straddles a head shard) and
    the pool is not ``quantised`` (a :class:`~.kvcache.QuantizedKV` scale is a
    (layer, head)); else 1, the pool every other shape always had (head_dim
    128: the identity; 72, 80, 96; an odd head count). THE decision of the
    fold: :func:`kv_streams` declares the pool by it and everything that
    meets a pool reads ``g`` back off the pool's row against the model's
    head_dim (:func:`pool_fold`)."""
    if quantised or head_dim >= LANES or LANES % head_dim:
        return 1
    g = LANES // head_dim
    return g if num_kv_heads % (g * max(shards, 1)) == 0 else 1


def kv_streams(
    num_kv_heads: int, head_dim: int, *, shards: Optional[int] = None, quantised: bool = False
) -> Tuple[CacheStream, CacheStream]:
    """The two streams of a layer that pages K and V at ``(H_kv, D)``. At a
    head_dim that divides the 128 lanes a stream holds ``g`` KV heads side by
    side in one row (:func:`heads_a_row`): ``H_kv / g`` "heads" of ``g x D``
    lanes, row ``t`` of group ``j`` holding ``[k_{gj}(t) | ... | k_{gj+g-1}(t)]``.
    A token's new K ``(H_kv, D)`` is, row-major, already ``(H_kv / g, g x D)``,
    so to every writer the pool IS a head_dim-128 pool of ``H_kv / g`` heads,
    and the chip's own layout of it is the row-major one the paged kernels
    ask for (at ``(.., bs, 64)`` it is not: four copies of the pool a step
    program, PERF.md PR 65). The kernels attend it with the queries laid in
    their head's lanes (:func:`fold_queries`). ``shards``: the ways the head
    axis is split (the ambient mesh's where not given)."""
    if shards is None:
        shards = head_shard_degree()
    g = heads_a_row(num_kv_heads, head_dim, shards=shards, quantised=quantised)
    return (CacheStream(num_kv_heads // g, head_dim * g),) * 2


def pool_fold(pool_width: int, head_dim: int) -> int:
    """``g`` of a pool whose rows are ``pool_width`` lanes wide under a model
    of ``head_dim``: what :func:`kv_streams` folded (1: nothing)."""
    if pool_width % head_dim:
        raise ValueError(f"a pool row of {pool_width} lanes holds no whole heads of {head_dim}")
    return pool_width // head_dim


def fold_queries(q: jax.Array, g: int, n_rep: int) -> jax.Array:
    """``q (..., Hq, D)`` -> ``(..., Hq, g x D)`` for a pool of ``g`` KV heads
    a row: the ``n_rep`` query heads of KV head ``g j + i`` keep their
    numbers in lane group ``i`` and zeros in the others, so ``q' . row`` is
    ``q . k`` of their own head exactly (the other heads' lanes multiply
    zeros) and ``p @ [v_{gj} | ...]`` holds their head's output in lane group
    ``i`` (:func:`unfold_outputs`). The kernels then see a GQA model of
    ``H_kv / g`` KV heads at ``g x D`` with ``g x n_rep`` query heads each,
    in the order they always had."""
    if g == 1:
        return q
    *lead, Hq, D = q.shape
    x = q.reshape(*lead, Hq // (g * n_rep), g, n_rep, D)
    none = [(0, 0)] * (x.ndim - 2)
    parts = [
        jnp.pad(x[..., i, :, :], none + [(i * D, (g - 1 - i) * D)]) for i in range(g)
    ]
    return jnp.stack(parts, axis=-3).reshape(*lead, Hq, g * D)


def unfold_outputs(out: jax.Array, g: int, n_rep: int) -> jax.Array:
    """``(..., Hq, g x D)`` as attended with :func:`fold_queries` ->
    ``(..., Hq, D)``: each query head's own lane group."""
    if g == 1:
        return out
    *lead, Hq, W = out.shape
    D = W // g
    x = out.reshape(*lead, Hq // (g * n_rep), g, n_rep, g, D)
    return jnp.stack([x[..., i, :, i, :] for i in range(g)], axis=-3).reshape(*lead, Hq, D)


def init_block_cache(
    num_layers: int,
    num_blocks: int,
    block_size: int,
    num_kv_heads: int = None,
    head_dim: int = None,
    dtype=jnp.bfloat16,
    streams: Optional[Tuple[CacheStream, ...]] = None,
) -> BlockKVCache:
    """The pool over ``num_layers``: K and V at ``(num_kv_heads, head_dim)``,
    or the ``streams`` a builder declares (an MLA layer: the compressed
    latent in ``k``, the rotary key, packed, in ``v``; any further stream in
    ``extra``). A quantised pool keeps a float32 scale a (layer, head) beside
    each stream's codes."""
    streams = streams or kv_streams(num_kv_heads, head_dim, quantised=is_kv_quant_dtype(dtype))

    def stream(s: CacheStream):
        data = jnp.zeros(s.pool_shape(num_layers, num_blocks, block_size), dtype)
        if is_kv_quant_dtype(dtype):
            return QuantizedKV(data=data, scale=jnp.zeros((num_layers, s.heads), jnp.float32))
        return data

    return BlockKVCache(
        k=stream(streams[0]), v=stream(streams[1]), extra=tuple(stream(s) for s in streams[2:])
    )


def kv_block_bytes(
    num_layers: int, block_size: int, num_kv_heads: int = None, head_dim: int = None,
    dtype=jnp.bfloat16, streams: Optional[Tuple[CacheStream, ...]] = None,
) -> int:
    """True per-block HBM cost of ONE block over its streams (K+V at
    ``(num_kv_heads, head_dim)``, or what a builder declares), in the CACHE
    dtype — what sizes the serving block pool (a quantized cache fits ~2x the
    blocks of bf16 in the same budget; the (L, H) scales are amortized over
    the whole pool and excluded here)."""
    streams = streams or kv_streams(num_kv_heads, head_dim)
    per_token = sum(s.heads * s.width for s in streams)
    return int(num_layers * block_size * per_token * jnp.dtype(dtype).itemsize)


def block_cache_spec(quantized: bool = False, streams=None):
    from jax.sharding import PartitionSpec as P

    if streams is not None and all(s.heads == 1 for s in streams):
        # one "head" shared by every q head (an MLA layer's latent and rotary
        # key): replicated over the model axes, as the q heads shard
        return BlockKVCache(k=P(), v=P(), extra=(P(),) * (len(streams) - 2))
    spec = P(None, None, MODEL_AXES, None, None)
    if quantized:
        stream = QuantizedKV(data=spec, scale=P(None, MODEL_AXES))
        return BlockKVCache(k=stream, v=stream)
    return BlockKVCache(k=spec, v=spec)


def batch_is_sharded() -> bool:
    """Whether the enclosing ``jax.set_mesh`` scope splits the BATCH around
    the attention (attention-DP, whole-model DP): there the block pool is
    replicated over those axes, the paged kernels are not run
    (models/base.decoder_layer) and no custom call demands a layout."""
    mesh = ambient_mesh()
    return mesh is not None and any(
        dict(mesh.shape).get(a, 1) > 1 for a in (AXIS_DDP, AXIS_DP)
    )


def takes_block_form(
    q_len: int, head_dim: int, *, packed: bool = False, batch_sharded: bool = False
) -> bool:
    """Whether :func:`update_block_cache_at_layer` writes a pass of this
    width whole blocks at a time (its docstring says why and when)."""
    return (
        q_len > TKG_MAX_Q_LEN and head_dim % 128 == 0
        and not packed and not batch_sharded
    )


#: the forms of the paged KV write (:func:`update_block_cache_at_layer`)
WRITE_FORMS = ("kernel", "blocks", "per_head", "window")


def write_form(
    q_len: int, head_dim: int, heads: int, *, quantised: bool = False, packed: bool = False,
    batch_sharded: bool = False, kernel_runs: bool = False,
) -> str:
    """Which of :data:`WRITE_FORMS` the paged KV write of a pass takes
    (:func:`update_block_cache_at_layer` says why), from what a call shows
    and nothing else: ``q_len`` positions a row, ``head_dim``, ``heads`` ONE
    device holds, a ``quantised`` pool, the ragged step's ``packed`` axis, a
    batch sharded around the attention, and whether the pass's attention is
    the paged decode kernel on a K/V pool of two equal streams
    (``kernel_runs``: models/base.decode_kernel_runs). The one decision: the
    model's call sites (models/base.paged_write_attend), the writer below
    and the serving session's counter
    (``nxdi_decode_kv_write_rows_total{form}``) read it."""
    if (
        q_len == 1 and head_dim % 128 == 0 and kernel_runs
        and not (quantised or packed or batch_sharded)
    ):
        return "kernel"
    if takes_block_form(q_len, head_dim, packed=packed, batch_sharded=batch_sharded):
        return "blocks"
    if not batch_sharded and (
        heads < WINDOW_MIN_HEADS or (q_len <= TKG_MAX_Q_LEN and not packed)
    ):
        return "per_head"
    return "window"


def chunk_write_blocks(
    rows, q_len: int, *, block_size: int, head_dim: int, batch_sharded: bool = False
) -> Tuple[int, int]:
    """(whole, merged) of a pass ``q_len`` wide over ``rows`` of ``(start,
    n)``: of the pool blocks that positions ``start .. start + n - 1`` of a
    sequence touch, those the block form stores as they are and the edge
    blocks it reads and merges; ``(0, 0)`` where the write does not take the
    block form. Host code (``ServingSession`` counts it per chunk pass)."""
    whole = merged = 0
    if not takes_block_form(q_len, head_dim, batch_sharded=batch_sharded):
        return whole, merged
    for start, n in rows:
        if n <= 0:
            continue
        end = start + n
        inside = max(0, end // block_size - -(-start // block_size))
        whole += inside
        merged += -(-end // block_size) - start // block_size - inside
    return whole, merged


def check_block_form_rows(slot_mapping: np.ndarray, block_size: int) -> None:
    """Raise ``ValueError`` unless every row of a host-built ``slot_mapping``
    keeps the block form's contract: its valid slots (>= 0) a prefix of the
    row, at consecutive positions of one sequence (consecutive offsets, one
    block between two block boundaries). For slot mappings that come from
    outside the serving path (``TpuModelForCausalLM.forward``)."""
    slots = np.asarray(slot_mapping)
    valid = slots >= 0
    prefix = valid == (np.arange(slots.shape[1])[None, :] < valid.sum(axis=1, keepdims=True))
    # the next token is the next slot of its block, or opens a block
    before, after = slots[:, :-1], slots[:, 1:]
    follows = np.where((before + 1) % block_size != 0, after == before + 1, after % block_size == 0)
    if not (prefix.all() and follows[valid[:, 1:]].all()):
        raise ValueError(
            "slot_mapping: wider than a decode step the paged KV write moves whole "
            "blocks, so a row's valid slots are a prefix of the row at consecutive "
            "positions of one sequence (block_kvcache.update_block_cache_at_layer)"
        )


def _row_segments(slot_mapping: jax.Array, bs: int, dropped: int):
    """A chunk row's write, cut at the pool's block boundaries. The row's
    valid slots (>= 0) are a prefix of ``n`` tokens at consecutive positions
    of one sequence, so token ``t`` sits ``(first_off + t) % bs`` into its
    block and the row touches at most ``ceil(S / bs) + 1`` blocks. Returns
    ``blocks (B, nseg)``: the pool block of each segment (``slot // bs`` at
    the segment's first token), ``dropped`` for a segment with no valid
    token; ``first_off (B,)``: the first token's offset in its block;
    ``covered (B, nseg, bs)``: the offsets the row's tokens fill."""
    B, S = slot_mapping.shape
    nseg = -(-S // bs) + 1
    n = jnp.sum(slot_mapping >= 0, axis=1)
    first_off = jnp.where(n > 0, slot_mapping[:, 0] % bs, 0)
    first = jnp.maximum(jnp.arange(nseg)[None, :] * bs - first_off[:, None], 0)
    slot_at = jnp.take_along_axis(slot_mapping, jnp.minimum(first, S - 1), axis=1)
    blocks = jnp.where(first < n[:, None], slot_at // bs, dropped)
    grid = jnp.arange(nseg * bs)[None, :]
    covered = (grid >= first_off[:, None]) & (grid < (first_off + n)[:, None])
    return blocks, first_off, covered.reshape(B, nseg, bs)


def _lay_on_blocks(new, first_off, nseg: int, bs: int):
    """``new (B, S, h, D)`` on the rows' block grid ``(B, nseg, bs, h, D)``:
    a row's tokens start ``first_off`` into its first segment."""
    B, S, h, D = new.shape
    padded = jnp.pad(new, ((0, 0), (bs, nseg * bs - S), (0, 0), (0, 0)))
    laid = jax.vmap(
        lambda row, off: jax.lax.dynamic_slice_in_dim(row, bs - off, nseg * bs)
    )(padded, first_off)
    return laid.reshape(B, nseg, bs, h, D)


def pool_rows(new, data):
    """A pass's ``new (..., h, D)`` as the pool ``data (L, NB+1, h / g, bs,
    g x D)`` holds a token: the same numbers row-major (:func:`kv_streams`),
    ``g`` heads a row; the identity at ``g`` = 1."""
    return new.reshape(*new.shape[:-2], data.shape[2], data.shape[4])


def _write_blocks(data, new, layer_idx, blocks, first_off, covered):
    """The block form: ``new (B, S, h, D)`` laid on the rows' block grid
    ``(B, nseg, h, bs, D)`` (a row's tokens start ``first_off`` into its first
    segment), merged with what the pool holds at the offsets a row does not
    cover, and scattered with the whole block ``(h, bs, D)`` in the window:
    the pool's minor-most dims, so the carry stays row-major. Under a
    head-sharded mesh ``data`` and ``new`` are one shard's heads."""
    laid = _lay_on_blocks(pool_rows(new, data), first_off, *covered.shape[1:])
    laid = laid.transpose(0, 1, 3, 2, 4)
    held = data[layer_idx, blocks]
    merged = jnp.where(covered[:, :, None, :, None], laid, held)
    return data.at[layer_idx, blocks].set(merged, mode="drop")


def _scatter_per_head(data, rows, layer_idx, blocks, offs):
    """The per-head form: head indexed, window ``(D,)``. Under a head-sharded
    mesh ``data`` and ``rows (B * S, h, D)`` are one shard's heads."""
    heads = jnp.arange(data.shape[2])[None, :]
    return data.at[layer_idx, blocks[:, None], heads, offs[:, None]].set(
        pool_rows(rows, data), mode="drop"
    )


def update_block_cache_at_layer(
    k_cache: jax.Array,  # (L, NB+1, H, bs, D)
    v_cache: jax.Array,
    k_new: jax.Array,  # (B, S, H, D)
    v_new: jax.Array,
    layer_idx: jax.Array,
    slot_mapping: jax.Array,  # (B, S) global slots; < 0 -> garbage block
    packed: bool = False,  # the rows are the mixed step's ONE packed token axis
) -> Tuple[jax.Array, jax.Array]:
    """Write token K/V into the paged cache at one layer (reference
    scatter-by-slot, block_kv_cache_manager.py). The full stacked cache is
    carried through the layer scan and updated in place (see
    kvcache.update_cache_at_layer for why). Negative slots are DROPPED by
    mapping them PAST the last block (scatter mode="drop" discards
    out-of-range indices; -1 would WRAP to the last real block and corrupt
    it) — same net effect as the reference's garbage-block writes.

    Four forms write the same bytes; three are written here, and the first
    by the pass's attention kernel (:func:`write_form` is the one decision,
    asked with the POOL ROW's width ``D`` and head count ``H``: a pool of
    ``g`` heads a row takes ``k_new (B, S, H_kv, D_model)`` as the ``(H, D)``
    rows it is, row-major;
    models/base.paged_write_attend asks it and calls this function for
    every form but ``kernel``). What decides between them: the TPU
    compiler lays a scatter's operand out with the update WINDOW's dims
    minor-most, the layer scan's cache carry takes that layout, and the
    paged kernels (Pallas custom calls) read the stacked pool row-major; a
    window that is not the pool's own minor-most dims has the WHOLE pool
    relaid once a layer and twice more at the program's entry and exit
    (328 of a 375 ms decode dispatch: PERF.md PR 24). And on a v5e an index
    row of a scatter costs ~70 ns whatever its width. The form is chosen
    in :func:`write_form` and nowhere else, on what the call shows: the
    static ``S`` of ``slot_mapping``, ``D``, ``packed``, the heads ONE device
    holds, the pool's dtype, whether the pass's attention is the kernel.

    * IN KERNEL at ``S == 1`` and ``D`` on the 128 lanes, an unquantised
      K/V pool, no ``packed`` axis, the batch not sharded, and an attention
      that IS the paged decode kernel (models/base.decode_kernel_runs):
      every decode program of the split serving step on the chip. The kernel
      (ops/decode_attention._paged_group_kernel) already runs one grid step
      a row and holds that row's LAST live block in VMEM for all of a
      device's heads at once, and a decode row's token belongs in exactly
      that block: it lays the token's K and V rows over the block's 16-row
      tile with a select, attends as write-then-attend does, and sends the
      tile back with ONE copy a stream under the next rows' arithmetic, the
      pools aliased in and out of the custom call. No launch, no scatter and
      no gather of its own, where the per-head scatter's ``B x H`` index rows
      a stream a layer were the second largest thing on the chip (2.2 of
      11.9 ms a dispatch on the 1.7B, 4.9 of 48.5 on a looped stack of 192
      layer passes: PERF.md PR 52). This function is not called.
    * WHOLE BLOCKS (window ``(H, bs, D)``, the pool's minor-most dims) at
      ``S > TKG_MAX_Q_LEN`` and ``D`` on the 128 lanes: prefill chunks and the
      whole-prompt paged prefill. **The rows' contract**: a row's valid slots
      (>= 0) are a PREFIX of the row, at consecutive positions of one
      sequence. Every caller that reaches this width without ``packed``
      passes such rows (``ServingSession._prefill_chunks`` and
      ``._full_prefill``, held by tests/test_block_kv.py; the benchmark's
      probe); speculation widths, token trees among them, stay at or under
      ``TKG_MAX_Q_LEN`` and the ragged mixed step says ``packed``. A row then
      touches ``S / bs + 1`` blocks at most: :func:`_write_blocks` gathers
      them, merges a row's first and last block with what the pool holds at
      the offsets the row does not cover, and scatters 8 x 5 index rows a
      stream a layer where a (token, head) row a write has 8 x 128 x 8. On
      the 1.7B's pool a 28-layer scan of K and V reads 1.65 ms against 31.8
      (v5e, PERF.md PR 43), at 1 live row of 8 as at 8.
    * PER HEAD (window ``(D,)``, the head an indexed dim: minor-most
      already, H times the index rows) at ``S <= TKG_MAX_Q_LEN`` where the
      kernel form does not apply (a block step and every speculation width,
      ``S`` of 2-16; a pool row off the lanes; a quantised pool, whose scale
      update is fused into this write; a run with the kernels off; 48 rows
      x 1: 2.3 ms a dispatch on the 1.7B, where the block form moves 64 KB
      to place 2 KB and reads 2.9), and wherever a device holds fewer than
      ``WINDOW_MIN_HEADS`` heads.
    * TOKEN WINDOW (window ``(H, D)``, one index row a token, the carry
      token-major) at a pool row off the lanes (a head_dim that fills no
      128-lane row with whole heads, an odd head count, a quantised pool at
      head_dim 64: the chip's own layout of that pool is not row-major in
      any form, and the chunk's kernel takes a layer's slice; granite and
      Llama-3.2-1B left it in PR 65, their pool two heads a row), for the ragged mixed step's
      ``packed`` token axis (its kernel takes a layer's slice too; one form
      at every packed width keeps its bucket programs one structure:
      analysis/graph_audit GRAPH205), and wherever the BATCH is sharded
      around the attention (attention-DP: no kernel runs there). Under a
      window of fewer heads than the tile has sublanes the compiler re-lays
      the whole pool FOUR times around every layer's scatter (PERF.md
      PR 33), hence ``WINDOW_MIN_HEADS``.

    On a head-sharded mesh (``block_cache_spec`` over tp/ep/cp > 1) the
    kernel form and the next two run once per head shard (``parallel/sharding.shard_over_heads``,
    as the paged kernels do): each shard writes its own ``H / degree`` heads,
    the indices are replicated, and no collective can appear (an INDEXED
    sharded dim leaves GSPMD free to gather operand and updates). The token
    window has the head in the window and partitions as it stands.

    Quantized caches quantize fused into this write with the running
    per-(layer, head) absmax (see kvcache.update_cache_at_layer); invalid
    (garbage) slots are excluded from the scale update. The code streams are
    written in the same form; the scales never pass through it."""
    write = _stream_writer(k_cache.shape, slot_mapping, layer_idx, packed)
    if isinstance(k_cache, QuantizedKV):
        # scale-update mask: negative (dropped) slots AND garbage-block
        # writes are excluded — idle serving rows carry all-zero block
        # tables whose slots map INTO block 0 with slot >= 0, and the
        # monotone pool-wide scale could never un-learn their junk
        bs = k_cache.shape[3]
        valid = (slot_mapping >= 0) & (slot_mapping // bs != GARBAGE_BLOCK)
        k_codes, k_scale = _quantized_update(k_cache, k_new, layer_idx, valid)
        v_codes, v_scale = _quantized_update(v_cache, v_new, layer_idx, valid)
        return (
            QuantizedKV(write(k_cache.data, k_codes), k_scale),
            QuantizedKV(write(v_cache.data, v_codes), v_scale),
        )
    return write(k_cache, k_new), write(v_cache, v_new)


def _stream_writer(pool_shape, slot_mapping: jax.Array, layer_idx, packed: bool = False):
    """``write(data, new (B, S, H_kv, D_model))`` of one pass into a pool
    stream of ``pool_shape (L, NB+1, H, bs, D)``, in the form
    :func:`update_block_cache_at_layer` says the call takes: asked with the
    POOL ROW's width and head count (``H_kv / g`` heads of ``g x D_model``
    where :func:`kv_streams` folded ``g`` heads a row)."""
    L, NB1, H, bs, D = pool_shape
    B, S = slot_mapping.shape
    # asked to write, so the pass's attention kernel does not (kernel_runs False)
    form = write_form(
        S, D, H // head_shard_degree(), packed=packed, batch_sharded=batch_is_sharded()
    )
    if form == "blocks":
        segments = _row_segments(slot_mapping, bs, NB1)

        def write(data, new):
            return shard_over_heads(
                _write_blocks, (data, new.astype(data.dtype), layer_idx, *segments),
                in_heads=(2, 2, None, None, None, None), out_heads=2,
            )
    else:
        slots = slot_mapping.reshape(B * S)
        blocks = jnp.where(slots >= 0, slots // bs, NB1)
        offs = jnp.where(slots >= 0, slots % bs, 0)

        def write(data, new):
            rows = new.reshape(B * S, *new.shape[2:]).astype(data.dtype)
            if form == "per_head":
                return shard_over_heads(
                    _scatter_per_head, (data, rows, layer_idx, blocks, offs),
                    in_heads=(2, 1, None, None, None), out_heads=2,
                )
            # window (H, D): one index row per token, the carry token-major
            return data.at[layer_idx, blocks, :, offs].set(pool_rows(rows, data), mode="drop")

    return write


def _write_packed(data, new, layer_idx, slot_mapping, width: int):
    """The write of a PACKED stream (:class:`CacheStream`, ``pack`` > 1):
    ``data (L, NB+1, 1, rows, pack * width)``, ``new (B, S, width)``. Wider
    than a decode step the block form, under the rows' contract of
    :func:`update_block_cache_at_layer`: a row's blocks are gathered, its
    tokens laid in their rows and lane groups, merged and scattered whole.
    At decode widths a token's pool row is read, its lane group replaced and
    the row stored, one token of a row after another (two tokens of one pass
    may share a pool row). The window is the pool's minor-most dim in both."""
    L, NB1, _, rows, lanes = data.shape
    pack = lanes // width
    bs = rows * pack
    B, S = slot_mapping.shape
    new = new.astype(data.dtype)

    def in_rows(x):  # (..., bs, w) token order -> (..., rows, pack * w) as the pool holds it
        lead, w = x.shape[:-2], x.shape[-1]
        x = x.reshape(*lead, pack, rows, w)
        return jnp.swapaxes(x, -3, -2).reshape(*lead, rows, pack * w)

    if S > TKG_MAX_Q_LEN and not batch_is_sharded():
        blocks, first_off, covered = _row_segments(slot_mapping, bs, NB1)
        laid = _lay_on_blocks(new[:, :, None, :], first_off, *covered.shape[1:])[:, :, :, 0]
        laid = in_rows(laid)[:, :, None]  # (B, nseg, 1, rows, lanes)
        cover = in_rows(jnp.broadcast_to(covered[..., None], covered.shape + (width,)))
        merged = jnp.where(cover[:, :, None], laid, data[layer_idx, blocks])
        return data.at[layer_idx, blocks].set(merged, mode="drop")
    group = jnp.arange(lanes, dtype=jnp.int32) // width
    for s in range(S):
        slots = slot_mapping[:, s]
        blocks = jnp.where(slots >= 0, slots // bs, NB1)
        offs = jnp.where(slots >= 0, slots % bs, 0)
        held = data[layer_idx, jnp.minimum(blocks, NB1 - 1), 0, offs % rows]  # (B, lanes)
        mine = group[None, :] == (offs // rows)[:, None]
        row = jnp.where(mine, jnp.tile(new[:, s], (1, pack)), held)
        data = data.at[layer_idx, blocks, 0, offs % rows].set(row, mode="drop")
    return data


def update_latent_cache_at_layer(
    c_cache: jax.Array,  # (L, NB+1, 1, bs, r): the compressed latents
    kr_cache: jax.Array,  # (L, NB+1, 1, bs // pack, pack * d_rope): the rotary keys, packed
    c_new: jax.Array,  # (B, S, r)
    kr_new: jax.Array,  # (B, S, d_rope)
    layer_idx: jax.Array,
    slot_mapping: jax.Array,  # (B, S)
) -> Tuple[jax.Array, jax.Array]:
    """What an MLA layer leaves behind, written once: the latent through
    :func:`update_block_cache_at_layer`'s forms (whole blocks in the chunk
    program, a row at decode widths), the rotary key into its packed stream
    (:func:`_write_packed`)."""
    write = _stream_writer(c_cache.shape, slot_mapping, layer_idx)
    c_cache = write(c_cache, c_new[:, :, None, :])
    if kr_cache.shape[3] == c_cache.shape[3]:  # pack 1: a stream like any other
        return c_cache, _stream_writer(kr_cache.shape, slot_mapping, layer_idx)(
            kr_cache, kr_new[:, :, None, :]
        )
    return c_cache, _write_packed(kr_cache, kr_new, layer_idx, slot_mapping, kr_new.shape[-1])


def update_stream_at_layer(
    data: jax.Array,  # (L, NB+1, 1, bs, w): a one-"head" stream on whole lanes
    new: jax.Array,  # (B, S, w)
    layer_idx: jax.Array,
    slot_mapping: jax.Array,  # (B, S)
) -> jax.Array:
    """One more unpacked stream of a token (``BlockKVCache.extra``: an
    indexer's key), written in the form the latent beside it takes."""
    return _stream_writer(data.shape, slot_mapping, layer_idx)(data, new[:, :, None, :])


def read_stream_at_layer(data: jax.Array, layer_idx: jax.Array, block_table: jax.Array) -> jax.Array:
    """One layer of an unpacked one-"head" stream, gathered by the table
    into per-row views in token order ``(B, MB * bs, w)``; garbage-block
    reads are zeroed, as :func:`read_latent_cache_at_layer`'s."""
    B, MB = block_table.shape
    rows = jax.lax.dynamic_index_in_dim(data, layer_idx, axis=0, keepdims=False)[block_table]
    valid = (block_table != GARBAGE_BLOCK)[:, :, None, None]
    rows = jnp.where(valid, rows[:, :, 0], jnp.zeros((), rows.dtype))
    return rows.reshape(B, MB * data.shape[3], data.shape[4])


def read_latent_cache_at_layer(
    c_cache: jax.Array, kr_cache: jax.Array, layer_idx: jax.Array, block_table: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Gather one layer's blocks by the table into per-row views in token
    order: latents ``(B, MB * bs, r)`` and rotary keys ``(B, MB * bs,
    d_rope)`` (the native path; the kernels copy blocks as they lie).
    Garbage-block reads are zeroed, as :func:`read_block_cache_at_layer`'s."""
    B, MB = block_table.shape
    bs = c_cache.shape[3]
    rows, lanes = kr_cache.shape[3:]
    pack = bs // rows
    valid = (block_table != GARBAGE_BLOCK)[:, :, None, None]
    c = jax.lax.dynamic_index_in_dim(c_cache, layer_idx, axis=0, keepdims=False)[block_table]
    kr = jax.lax.dynamic_index_in_dim(kr_cache, layer_idx, axis=0, keepdims=False)[block_table]
    c = jnp.where(valid, c[:, :, 0], jnp.zeros((), c.dtype))
    kr = jnp.where(valid, kr[:, :, 0], jnp.zeros((), kr.dtype))
    kr = kr.reshape(B, MB, rows, pack, lanes // pack).swapaxes(2, 3)
    return c.reshape(B, MB * bs, -1), kr.reshape(B, MB * bs, lanes // pack)


def slot_mapping_from_block_table(
    block_table: jax.Array,  # (B, MB)
    positions: jax.Array,  # (B, S) logical positions
    block_size: int,
    valid: jax.Array = None,  # (B, S) bool; False -> garbage slot
) -> jax.Array:
    """IN-GRAPH slot-mapping generation for token-gen steps (reference
    block_kv_cache_manager.generate_tokengen_slot_mapping): the host sends
    only the block table; the write slot for position p is
    ``block_table[p // bs] * bs + p % bs``. Invalid rows map to -1 (garbage)."""
    idx = positions // block_size  # (B, S) block index per token
    block_ids = jnp.take_along_axis(block_table, idx, axis=1)  # (B, S)
    slots = block_ids * block_size + positions % block_size
    if valid is not None:
        slots = jnp.where(valid, slots, -1)
    return slots.astype(jnp.int32)


def read_block_cache_at_layer(
    k_cache: jax.Array,  # (L, NB+1, H, bs, D)
    v_cache: jax.Array,
    layer_idx: jax.Array,
    block_table: jax.Array,  # (B, MB) block ids; 0 for unused tail entries
    head_dim: Optional[int] = None,  # the MODEL's; None: the pool row's own
) -> Tuple[jax.Array, jax.Array]:
    """Gather one layer's active blocks into a contiguous per-sequence view
    ``(B, MB * bs, H_kv, head_dim)`` (reference gather-by-active-block-table
    reads). A pool that holds ``g`` heads a row (:func:`kv_streams`) is
    unfolded here: row-major, a token's ``(H_kv / g, g x D)`` IS its
    ``(H_kv, D)``. Quantized caches dequantize AFTER the gather to fp32 — the
    native fallback path only; the paged kernels DMA the codes straight from
    the cache instead."""
    if isinstance(k_cache, QuantizedKV):
        k_s = layer_dequant_factors(k_cache, layer_idx)
        v_s = layer_dequant_factors(v_cache, layer_idx)
        k_r, v_r = read_block_cache_at_layer(
            k_cache.data, v_cache.data, layer_idx, block_table, head_dim
        )
        return (
            k_r.astype(jnp.float32) * k_s[:, None],
            v_r.astype(jnp.float32) * v_s[:, None],
        )
    B, MB = block_table.shape
    _, _, H, bs, D = k_cache.shape
    g = pool_fold(D, head_dim or D)
    H, D = H * g, D // g
    k_l = jax.lax.dynamic_index_in_dim(k_cache, layer_idx, axis=0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(v_cache, layer_idx, axis=0, keepdims=False)
    k = k_l[block_table]  # (B, MB, H, bs, D)
    v = v_l[block_table]
    # NaN-scrub garbage reads: table-zero entries (unused tails, and the
    # surplus positions of finished drain rows) all point at reserved block
    # 0, whose contents are whatever invalid-slot writes last dumped there —
    # including NaN from a poisoned co-batched row's lockstep surplus steps.
    # Masked attention cannot filter that (the masked probability is exactly
    # 0 but 0*NaN = NaN in the P·V product), so corruption would leak across
    # rows through the shared block. Zeroing the gathered garbage blocks
    # restores "masked contribution == exactly 0" for finite AND non-finite
    # junk; healthy outputs are byte-identical (those positions were already
    # exact zeros after the mask).
    valid = (block_table != GARBAGE_BLOCK)[:, :, None, None, None]
    k = jnp.where(valid, k, jnp.zeros((), k.dtype))
    v = jnp.where(valid, v, jnp.zeros((), v.dtype))
    k = k.transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, H, D)
    v = v.transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, H, D)
    return k, v


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------


@dataclass
class BlockAllocator:
    """Free-block pool + per-sequence block lists (the vLLM role for the
    reference; here in-framework so serving works standalone)."""

    num_blocks: int
    block_size: int
    free: List[int] = field(default_factory=list)
    seq_blocks: Dict[int, List[int]] = field(default_factory=dict)

    def __post_init__(self):
        # block 0 reserved as garbage
        self.free = list(range(1, self.num_blocks + 1))

    def alloc_seq(self, seq_id: int, num_tokens: int) -> List[int]:
        """Ensure seq has blocks covering num_tokens positions."""
        blocks = self.seq_blocks.setdefault(seq_id, [])
        needed = -(-num_tokens // self.block_size) - len(blocks)
        if needed > len(self.free):
            raise RuntimeError(
                f"out of KV blocks: need {needed}, free {len(self.free)}"
            )
        for _ in range(max(0, needed)):
            blocks.append(self.free.pop(0))
        return blocks

    def free_seq(self, seq_id: int):
        self.free.extend(self.seq_blocks.pop(seq_id, []))

    def quarantine_seq(self, seq_id: int) -> List[int]:
        """Poisoned release: free this sequence's blocks and return the ids
        the caller must zero-scrub before reuse. Plain-allocator blocks are
        exclusively owned, so every block is scrubbable."""
        blocks = self.seq_blocks.pop(seq_id, [])
        self.free.extend(blocks)
        return blocks

    def slot_mapping(self, seq_id: int, positions: np.ndarray) -> np.ndarray:
        """Logical positions -> global flat slots for this sequence."""
        blocks = self.seq_blocks[seq_id]
        block_ids = np.asarray([blocks[p // self.block_size] for p in positions])
        return block_ids * self.block_size + (np.asarray(positions) % self.block_size)

    def block_table(self, seq_id: int, max_blocks: int) -> np.ndarray:
        blocks = self.seq_blocks.get(seq_id, [])
        table = np.zeros(max_blocks, np.int32)
        n = min(len(blocks), max_blocks)
        table[:n] = blocks[:n]
        return table


class NoPoolAllocator:
    """The allocator of a model NONE of whose layers pages (every entry of
    ``builder.cache_layers()`` is ``SLOT_STATE``: models/brumby.py): there is
    no pool, a request of any length holds no block, and nothing is ever
    exhausted, so the session admits by free slots alone and never preempts
    for blocks. It answers what the serving step asks of an allocator with
    nothing: an empty block list, a zero block table, and a slot mapping
    whose only content is WHICH positions are real (0 where one is; the
    chunk program reads ``slot_mapping >= 0`` as a pass's real positions and
    writes nothing anywhere)."""

    num_blocks = 0
    free = ()

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.seq_blocks: Dict[int, List[int]] = {}

    def alloc_seq(self, seq_id: int, num_tokens: int) -> List[int]:
        return []

    def free_seq(self, seq_id: int):
        pass

    def quarantine_seq(self, seq_id: int) -> List[int]:
        return []

    def slot_mapping(self, seq_id: int, positions: np.ndarray) -> np.ndarray:
        return np.zeros(len(positions), np.int32)

    def block_table(self, seq_id: int, max_blocks: int) -> np.ndarray:
        return np.zeros(max_blocks, np.int32)


@dataclass
class PrefixCachingAllocator(BlockAllocator):
    """Content-addressed block reuse (prefix caching).

    Reference: is_prefix_caching serving on the block KV cache — prior KV for
    a shared prompt prefix is reused instead of recomputed
    (attention_base.py:893 perform_prefix_prefill consumes it). Here the
    framework owns the content addressing (the reference delegates it to
    vLLM): FULL blocks are keyed by a running sha1 over the token prefix, so
    a block matches only when its content AND everything before it match.

    Lifecycle: live blocks carry a refcount (one per attached sequence);
    freeing a sequence moves refcount-0 registered blocks to an LRU evictable
    pool — still matchable — and unregistered (partial-tail) blocks back to
    the free list. Allocation evicts LRU blocks when the free list runs dry.
    """

    hash_of_block: Dict[int, bytes] = field(default_factory=dict)
    block_by_hash: Dict[bytes, int] = field(default_factory=dict)
    refcount: Dict[int, int] = field(default_factory=dict)
    evictable: "OrderedDict[int, None]" = field(default_factory=OrderedDict)

    # --- hashing ---------------------------------------------------------

    def _chain_keys(self, tokens: np.ndarray) -> List[bytes]:
        """One running-hash key per FULL block of ``tokens``."""
        return prefix_chain_keys(tokens, self.block_size)

    # --- allocation with eviction ---------------------------------------

    def alloc_seq(self, seq_id: int, num_tokens: int) -> List[int]:
        blocks = self.seq_blocks.setdefault(seq_id, [])
        needed = -(-num_tokens // self.block_size) - len(blocks)
        while needed > len(self.free) and self.evictable:
            victim, _ = self.evictable.popitem(last=False)  # LRU
            key = self.hash_of_block.pop(victim, None)
            if key is not None:
                self.block_by_hash.pop(key, None)
            self.refcount.pop(victim, None)
            self.free.append(victim)
        if needed > len(self.free):
            raise RuntimeError(
                f"out of KV blocks: need {needed}, free {len(self.free)}"
            )
        for _ in range(max(0, needed)):
            blocks.append(self.free.pop(0))
        return blocks

    # --- prefix caching API ----------------------------------------------

    def match_prefix(self, seq_id: int, tokens: np.ndarray) -> int:
        """Attach the longest cached block-chain prefix of ``tokens`` to
        ``seq_id``. Returns the number of cached TOKENS (multiple of
        block_size, capped at len(tokens)-1 so at least one token is left to
        produce next-token logits)."""
        assert seq_id not in self.seq_blocks or not self.seq_blocks[seq_id]
        matched: List[int] = []
        for key in self._chain_keys(tokens):
            b = self.block_by_hash.get(key)
            if b is None:
                break
            matched.append(b)
        # keep >= 1 token uncached (its forward produces the next token)
        while matched and len(matched) * self.block_size >= len(tokens):
            matched.pop()
        for b in matched:
            self.refcount[b] = self.refcount.get(b, 0) + 1
            self.evictable.pop(b, None)
        self.seq_blocks[seq_id] = list(matched)
        return len(matched) * self.block_size

    def match_index_blocks(self, tokens: np.ndarray) -> int:
        """READ-ONLY match-index query: how many leading FULL blocks of
        ``tokens`` this pool already holds (live or evictable — both are
        attachable without recompute). No refcounts move, no sequence
        attaches; this is the affinity score the router's ``cache_aware``
        placement ranks replicas by (runtime/router.py), not an
        allocation."""
        return self.match_keys(self._chain_keys(tokens))

    def match_keys(self, keys: List[bytes]) -> int:
        """Longest-matching-prefix count over PRECOMPUTED chain keys
        (:func:`prefix_chain_keys`) — the router computes one key list per
        request and queries every candidate replica's index with it, so
        the sha1 work is paid once, not once per replica."""
        matched = 0
        for key in keys:
            if key not in self.block_by_hash:
                break
            matched += 1
        return matched

    def commit_seq(self, seq_id: int, tokens: np.ndarray):
        """Register this sequence's full prompt blocks for future matching
        (idempotent; call once the prompt KV is fully written)."""
        blocks = self.seq_blocks.get(seq_id, [])
        for i, key in enumerate(self._chain_keys(tokens)):
            if i >= len(blocks):
                break
            b = blocks[i]
            if self.hash_of_block.get(b) == key:
                continue  # already registered (e.g. matched prefix)
            if key in self.block_by_hash:
                continue  # identical content already cached under another block
            if b in self.hash_of_block:
                continue  # block already carries different content (shouldn't)
            self.hash_of_block[b] = key
            self.block_by_hash[key] = b
            self.refcount[b] = self.refcount.get(b, 0) + 1

    def free_seq(self, seq_id: int):
        for b in self.seq_blocks.pop(seq_id, []):
            if b in self.hash_of_block:
                self.refcount[b] -= 1
                if self.refcount[b] <= 0:
                    self.evictable[b] = None  # matchable until evicted
            else:
                self.free.append(b)

    def quarantine_seq(self, seq_id: int) -> List[int]:
        """Poisoned release: this sequence's KV must never be read again.
        Blocks another live sequence still references are left registered
        and UNTOUCHED — their content is a healthy prefill's writes (a
        prompt whose final logits went non-finite is quarantined BEFORE
        commit_seq registers it) and zeroing them would corrupt the
        sharers' attention. Every other block is deregistered from the
        prefix index (its content must not be matchable again), freed, and
        returned for the caller to zero-scrub."""
        scrub: List[int] = []
        for b in self.seq_blocks.pop(seq_id, []):
            if b in self.hash_of_block:
                self.refcount[b] -= 1
                if self.refcount[b] > 0:
                    continue  # a live sharer still attends this block
                key = self.hash_of_block.pop(b)
                self.block_by_hash.pop(key, None)
                self.refcount.pop(b, None)
                self.evictable.pop(b, None)
            self.free.append(b)
            scrub.append(b)
        return scrub
