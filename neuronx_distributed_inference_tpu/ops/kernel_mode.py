"""Pallas kernel execution-mode switch + the consolidated dispatch gates.

Off-TPU hosts run every Pallas kernel in interpret mode (pure-Python
emulation) so the CPU test mesh exercises kernel numerics. That also means no
CPU test can ever hit a **Mosaic lowering** error — the class of bug that
breaks only on hardware (r1 ``_pick_chunk``; r3 the flash ``key_valid``
BlockSpec). :func:`force_compiled_kernels` flips the wrappers to emit real
Mosaic kernels regardless of host backend, so the suite can AOT-lower every
kernel (and whole model programs) for the TPU target from a CPU host via
``jax.export(..., platforms=["tpu"])`` — see tests/test_tpu_lowering.py —
and COMPILE them for a described chip (tests/test_chip_compile.py).

Whether a kernel is compiled or interpreted hangs on ``jax.default_backend()``
alone, and every auto gate below falls to the native path in silence when it
is not "tpu". Nothing on the chip path may lean on that unverified:
``chip_smoke.py`` phase 3 reads the kernels out of the compiled executables.

Dispatch gates
--------------
Every kernel/native auto-gate lives HERE, one tested predicate per kernel
(tests/test_kernel_mode.py), instead of being scattered across the kernel
modules: the gates share the same tri-state convention (config None = auto,
True = force with shape guards + a warning on fallback, False = off) and a
change to one kernel's auto condition must not silently flip another's.
The kernel modules re-export their historical names (``_use_flash``,
``use_tkg_kernel``, ...) as aliases of these predicates.

A ``pallas_call`` has no partitioning rule, so a kernel meets a model-parallel
mesh in one of two ways. The attention kernels that read a cache (paged flash,
TKG contiguous + paged, ragged) are per-head work: their dispatches launch
them once per head shard (``parallel/sharding.shard_over_heads``, the plain
call at degree 1), so their gates rest on what they can observe of the call —
shape, ``head_dim``, kv width, backend — and on both head counts dividing the
degree (:func:`heads_divide`; ``GQASharding`` guarantees it). The kernels with
no such dispatch (contiguous flash prefill, whose q may be sequence-sharded
under context parallelism; the grouped expert matmul; the int4 matmul) keep
:func:`single_shard` in their auto condition.

Gate summary (auto path):

============================  ==============================================
kernel                        auto condition beyond the shape guards
============================  ==============================================
flash / packed prefill        single model-parallel shard, TPU backend
paged flash prefill           TPU, q_len >= 64, heads divide the degree
TKG decode (contig + paged)   TPU, kv_width >= 512, heads divide the degree
grouped expert matmul         TPU, plain experts in the rows' dtype on the
                              lanes, ep = 1, single shard (see
                              :func:`use_grouped_matmul`)
ragged mixed-step             TPU backend, heads divide the degree
int4 quant matmul             TPU backend + single shard (see
                              :func:`use_quant_matmul`)
KDA chunk scan                TPU backend (or interpreted off it), one head
                              shard (see :func:`use_kda_chunk_scan`)
============================  ==============================================
"""

from __future__ import annotations

import logging
from contextlib import contextmanager

import jax

_FORCE_COMPILED = False

log = logging.getLogger(__name__)


@contextmanager
def force_compiled_kernels():
    """Within this context, kernel wrappers emit real Mosaic kernels (no
    interpret fallback) even on non-TPU hosts. Only useful together with AOT
    lowering for a TPU target — actually EXECUTING the result on CPU fails."""
    global _FORCE_COMPILED
    prev = _FORCE_COMPILED
    _FORCE_COMPILED = True
    try:
        yield
    finally:
        _FORCE_COMPILED = prev


def kernel_interpret() -> bool:
    """Interpret-mode decision for every Pallas wrapper call site."""
    if _FORCE_COMPILED:
        return False
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# dispatch gates — one predicate per kernel
# ---------------------------------------------------------------------------


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def single_shard(spec) -> bool:
    """One model-parallel shard: the auto condition for kernels whose
    pallas_call meets a sharded operand bare — it would be all-gathered per
    launch. The cache-reading attention kernels are not among them: their
    dispatches launch per head shard (:func:`heads_divide`)."""
    return spec.model_parallel == 1


def heads_divide(spec) -> bool:
    """Both head counts divide the model-parallel degree: what a per-shard
    launch over the head axis needs (parallel/sharding.shard_over_heads).
    ``GQASharding``'s kv replication guarantees it for a built model; a
    hand-built spec that breaks it degrades to the native path, forced or
    not, instead of a ``shard_map`` error."""
    mp = spec.model_parallel
    return spec.num_heads % mp == 0 and spec.num_kv_heads % mp == 0


def flash_shape_ok(spec, seq_len: int) -> bool:
    # q/k tiles are (128, D): seq must tile evenly; D must be a lane-aligned
    # multiple of 64. D=64 models (Llama-3.2-1B class) normally ride the
    # head-pair PACKED kernel (two heads fill the 128 lanes, use_packed);
    # with packing off they fall back to half-lane tiles — slight waste,
    # but still kernel-eligible.
    return seq_len >= 128 and seq_len % 128 == 0 and spec.head_dim % 64 == 0


def use_flash(spec, seq_len: int) -> bool:
    """Prefill flash attention (modules/attention.attention_prefill)."""
    if spec.use_flash_kernel is False:
        return False
    ok = flash_shape_ok(spec, seq_len)
    if spec.use_flash_kernel:  # force-enabled still honors shape guards
        if not ok:
            log.warning(
                "attn_kernel_enabled=True but shape (seq=%d, head_dim=%d) is "
                "unsupported by the flash kernel; falling back to native path",
                seq_len,
                spec.head_dim,
            )
        return ok
    return ok and single_shard(spec) and on_tpu()


def use_packed(spec) -> bool:
    """Head-pair packing decision, taken AFTER :func:`use_flash` says yes
    (seq-length eligibility is already settled there).

    Auto-on for head_dim <= 64 (the packing exists exactly because D=64
    half-fills the 128-wide MXU contraction; D=128 tiles are already full).
    Needs >= 2 heads to pair (H odd pads inside the kernel wrapper, H=1
    would only add waste). Tri-state ``use_packed_heads`` overrides like the
    other kernel switches — force-enable still honors the shape guards."""
    if spec.use_packed_heads is False:
        return False
    ok = spec.head_dim <= 64 and spec.num_heads >= 2
    if spec.use_packed_heads and not ok:
        log.warning(
            "attn_packed_kernel_enabled=True but shape (heads=%d, "
            "head_dim=%d) is unsupported by the packed kernel; using the "
            "unpacked flash path",
            spec.num_heads,
            spec.head_dim,
        )
    return ok


#: widest query the TKG decode kernels serve (decode and speculation widths).
#: Up to this width the paged KV write goes a (token, head) row at a time,
#: above it, on the 128 lanes, a whole pool block at a time
#: (modules/block_kvcache.update_block_cache_at_layer selects on it).
TKG_MAX_Q_LEN = 16

#: rows of the paged prefill-chunk program (the multi-token token-generation
#: pass that is handed BOTH a slot mapping and a block table). Its rows are
#: addressed by slot (``seq_ids``, block table, slot mapping), so the program
#: is as wide as the rows that prefill at once and not as the slot count; a
#: pass over more rows is several dispatches of the one program
#: (runtime/model_runner.SubModelRunner.chunk_rows). 8 rows x 128 positions is
#: 4x past the v5e's ridge (197 TFLOP/s / 819 GB/s = 240 FLOP per weight
#: byte), it is the sublane tile, and it is the reference's default
#: ``max_num_seqs``. A constant of the program: ONE program per (q bucket,
#: kv bucket), which a warm-up that knows only those two finds.
CHUNK_ROWS = 8


def use_tkg(spec, q_len: int, kv_width: int) -> bool:
    """Gate for the decode kernels (contiguous + paged TKG).
    ``spec.use_tkg_kernel`` (config attn_block_tkg_kernel_enabled): None =
    auto on TPU, True = force (still honoring shape guards), False = native
    path."""
    enabled = spec.use_tkg_kernel
    if enabled is False:
        return False
    ok = (
        q_len <= TKG_MAX_Q_LEN
        and spec.head_dim % 64 == 0
        and kv_width >= 128
        and kv_width % min(512, kv_width) == 0
        and heads_divide(spec)
    )
    if enabled:
        return ok
    return ok and kv_width >= 512 and on_tpu()


def use_paged_flash(spec, q_len: int) -> bool:
    """Gate for the paged prefill kernel: multi-token block attention only
    (decode q_len==1 rides the TKG kernel), lane-aligned head_dim; auto-on
    for TPU at kernel-worthy chunk sizes, force-on/off via
    attn_kernel_enabled."""
    if (
        spec.use_flash_kernel is False
        or q_len < 8
        or spec.head_dim % 64 != 0
        or not heads_divide(spec)
    ):
        return False
    if spec.use_flash_kernel:
        return True
    return q_len >= 64 and on_tpu()


#: operations a weight byte at which a v5e's arithmetic catches its weight
#: stream (197 TFLOP/s / 819 GB/s): a product of more rows than this an
#: expert is bound by arithmetic, one of fewer by the stream
OPS_PER_WEIGHT_BYTE = 240

#: rows a visit of the grouped-matmul kernel multiplies by one expert's
#: weights (ops/grouped_matmul.py: the MXU's 128 rows)
GROUPED_ROW_TILE = 128


def use_grouped_matmul(spec, experts: dict, dtype) -> bool:
    """Gate for the grouped-matmul kernel (ops/grouped_matmul.py; ``spec``
    is a MoESpec, ``experts`` one layer's expert entries or the stack of
    every layer's, ``dtype`` the activations'). It serves plain experts: a
    ``weight`` in the rows' dtype with both widths on the 128 lanes and at
    most a ``bias``, which stays outside the product (no ``scale``, no
    blockwise scales, no packed codes), on the chip, where the experts are
    not divided over a mesh axis (``ep_degree`` 1, one model-parallel shard:
    a ``pallas_call`` has no partitioning rule). No option forces it: what
    it cannot serve keeps ``jax.lax.ragged_dot``."""

    def plain(entry):
        if not isinstance(entry, dict) or not set(entry) <= {"weight", "bias"}:
            return False
        w = entry.get("weight")
        w = getattr(w, "stack", w)  # modules/moe.LayerOfStack
        return (
            w is not None
            and w.dtype == dtype
            and w.shape[-1] % 128 == 0
            # a two-matrix expert (modules/moe.two_matrix) holds its width, any
            # multiple of a packed sublane tile, on the second-minor axis
            and w.shape[-2] % (128 if gated else 16) == 0
        )

    gated = "gate_proj" in experts
    names = ("gate_proj", "up_proj", "down_proj") if gated else ("up_proj", "down_proj")
    return (
        all(plain(experts.get(k)) for k in names)
        and spec.ep_degree == 1
        and single_shard(spec)
        and on_tpu()
    )


def grouped_beats_dense(num_experts: int, top_k: int, rows: int, share: float = 1.0) -> bool:
    """Where the grouped-matmul kernel serves: grouped or dense, from the
    shapes, both reckoned in passes over the layer's expert weights. The
    dense form multiplies all ``rows`` tokens by every expert, ``rows``
    operations a weight byte: one pass while the stream bounds it, ``rows /
    OPS_PER_WEIGHT_BYTE`` once arithmetic does. The grouped form multiplies
    ``rows * top_k / num_experts`` rows an expert, under that line at every
    shape that reaches here, and costs a pass of an expert's weights a VISIT
    (a row tile of GROUPED_ROW_TILE x one expert whose rows it holds: at most
    ``rows * top_k / tile + num_experts - 1`` of them). Grouped is taken where
    it makes fewer passes: where dense is bound by arithmetic and the routing
    leaves the grouped form well under it.

    ==========================  =====  ======  =======  ========
    experts / top_k             rows   dense   grouped  taken
    ==========================  =====  ======  =======  ========
    16 / 1 (zaya1-8b)           1024   4.3     1.4      grouped
    16 / 1                      512    2.1     1.2      grouped
    128 / 8 (sdar-30b-a3b)      1024   4.3     1.5      grouped
    64 / 6 (kimi-vl-a3b)        1024   4.3     1.7      grouped
    64 / 6                      512    2.1     1.4      grouped
    8 / 2 (Mixtral)             1024   4.3     2.9      grouped
    8 / 2                       512    2.1     1.9      grouped
    any                         <=240  1       >= 1     dense
    ==========================  =====  ======  =======  ========

    Read on a v5e the products alone take 1.52, 1.55 and 1.66 x their stream
    at the first, third and fourth row (PERF.md section 6, PR 45): the
    passes this reckons, each at about what its fetch costs.

    ``share``: under a held share of the experts (modules/moe.MoESpec
    ``held_experts``) ``num_experts`` is the count held and ``share`` =
    held / published of the ``rows * top_k`` routed rows is expected here
    (64 of 128, top-6, 1024 rows: 24 + 63 visits = 1.4 passes against 4.3).
    """
    dense = max(1.0, rows / OPS_PER_WEIGHT_BYTE)
    visits = -(-int(rows * top_k * share) // GROUPED_ROW_TILE) + num_experts - 1
    return max(1.0, visits / num_experts) < dense


def use_ragged(spec, total_q: int, ragged_q_tile: int = 16) -> bool:
    """Kernel/native gate for the ragged mixed-step attention: lane-aligned
    head_dim and tile-aligned packing; tri-state force via
    ``use_flash_kernel`` like the other attention kernels.

    Like the other cache-reading kernels there is NO single-shard
    condition: the mixed step launches the kernel per head shard (q heads
    and paged KV blocks are head-sharded, descriptors are replicated host
    metadata), so tp>1 meshes run it with no collectives inside (ISSUE 17);
    the head counts must divide the degree (:func:`heads_divide`)."""
    if (
        spec.use_flash_kernel is False
        or spec.head_dim % 64 != 0
        or total_q % ragged_q_tile != 0
        or not heads_divide(spec)
    ):
        return False
    if spec.use_flash_kernel:
        return True
    return on_tpu()


def use_kda_chunk_scan(head_dim: int, q_len: int, chunk_size: int, shards: int) -> bool:
    """Gate for the chunked delta rule's sub-chunk recurrence as a kernel on
    the stacked state (ops/kda_chunk_scan.py; ``modules/kda.kda_mixer`` asks
    with its spec's ``head_dim`` and ``chunk_size``, the chunk's positions a
    row and the ambient mesh's head shards): on the chip, or off it where
    kernels run interpreted; one shard (a ``pallas_call`` has no partitioning
    rule); a head's state on whole lane rows; the chunk made of whole
    sub-chunks ``min(chunk_size, q_len)`` of whole sublane tiles (8 / 16 / 32
    / 64 / 128 positions under a sub-chunk of 16: the 8 as one sub-chunk).
    No option forces it: what it cannot serve keeps the scan of
    ``modules/kda.kda_chunk``."""
    sub = min(int(chunk_size), q_len)
    return (
        (on_tpu() or kernel_interpret())
        and shards == 1
        and head_dim % 128 == 0
        and sub % 8 == 0
        and q_len % sub == 0
    )


# --- int4 quant matmul (ops/quant_matmul.py) -------------------------------
#
# The decode linears reach the kernel through ops/quant.linear(), which sees
# only the packed entry and the activations — no AttnSpec/config. The mode
# is therefore process-level module state, set once by the application at
# load time ("auto" unless tp>1 forces it off) and overridable in tests via
# the quant_matmul_mode context.

_QMM_MODE: list = ["auto"]  # stack: [base, *context overrides]

#: default scale-group size along the input axis (two nibble planes of
#: 2*QMM_GROUP codes per packed byte row — see ops/quant_matmul.py)
QMM_GROUP = 128


def set_quant_matmul_mode(mode) -> None:
    """Set the process-level base mode: "auto" | True | False. The
    application calls this at load for weight_dtype="int4" (False on tp>1
    meshes: pallas_call has no GSPMD rule, so sharded packed weights would
    be all-gathered per launch — the native int4 path is GSPMD-shardable
    and serves those meshes instead)."""
    if mode not in ("auto", True, False):
        raise ValueError(f"quant matmul mode must be 'auto'/True/False, got {mode!r}")
    _QMM_MODE[0] = mode


@contextmanager
def quant_matmul_mode(mode):
    """Temporarily override the quant-matmul dispatch mode (tests force the
    kernel on CPU hosts with ``quant_matmul_mode(True)`` — it then runs in
    interpret mode via :func:`kernel_interpret`)."""
    if mode not in ("auto", True, False):
        raise ValueError(f"quant matmul mode must be 'auto'/True/False, got {mode!r}")
    _QMM_MODE.append(mode)
    try:
        yield
    finally:
        _QMM_MODE.pop()


def use_quant_matmul(rows: int, k: int, n: int, group: int = QMM_GROUP) -> bool:
    """Gate for the int4 fused-dequant matmul kernel: decode-sized row
    counts (the kernel keeps the full row block resident), lane-aligned
    output width, at least one full double-group along the input axis.
    Force-enable (mode True) still honors the shape guards but warns on
    fallback, the convention every other gate follows."""
    mode = _QMM_MODE[-1]
    if mode is False:
        return False
    from neuronx_distributed_inference_tpu.parallel.sharding import (
        head_shard_degree,
    )

    sharded = head_shard_degree() > 1  # the weights' TENSOR axes
    ok = rows <= 64 and n % 128 == 0 and k >= 2 * group and not sharded
    if mode is True:
        if not ok:
            log.warning(
                "quant matmul forced on but the call (rows=%d, k=%d, n=%d, "
                "group=%d, model-sharded mesh=%s) is unsupported by the "
                "kernel; using the native int4 dequant path",
                rows,
                k,
                n,
                group,
                sharded,
            )
        return ok
    return ok and on_tpu()
