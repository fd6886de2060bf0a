"""Registry of every Pallas kernel in ``ops/`` for the kernel audit (KERN70x).

One :class:`KernelSpec` per ``pl.pallas_call`` site. Instead of hand-mirroring
each kernel's grid/BlockSpec/scratch layout (which would drift the moment a
kernel changes), the registry TRACES the real entry point with
``jax.make_jaxpr`` at the committed bench shapes and reads the truth off the
``pallas_call`` equation's ``grid_mapping``:

- ``grid_mapping.grid`` — the launch grid;
- ``grid_mapping.block_mappings`` — one per tensor operand/output (scalar-
  prefetch operands ride SMEM and are excluded), each carrying
  ``block_shape`` and ``array_shape_dtype``;
- the kernel jaxpr's trailing invars — the ``pltpu.VMEM`` scratch avals.

Tracing is abstract (ShapeDtypeStruct args, no compile, no devices), so the
whole census runs on a CPU-only host in seconds. Tile candidates are
injected through :func:`ops.tile_defaults.tile_overrides` — the same lookup
path the kernels use for their committed defaults — so a candidate exercises
exactly the code a user would hit by editing ``tuning_table.json``.

Each spec also names the kernel's NATIVE FALLBACK and the tests that must
reference it (KERN703): a new kernel cannot ship unregistered (the audit
AST-scans ``ops/`` for unclaimed ``pallas_call`` sites) or unreferenced
(fallback must import, parity/lowering test files must mention the entry).
"""

from __future__ import annotations

import ast
import functools
import pathlib
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

OPS_DIR = pathlib.Path(__file__).resolve().parent.parent / "ops"
REPO_ROOT = OPS_DIR.parent.parent

#: committed 1B/8B attention shapes (device_model.LLAMA_1B / LLAMA_8B) —
#: literal here so a registry import cannot recurse into the traced-suite
#: modules
_1B = dict(H=2048, I=8192, Hq=32, Hkv=8, D=64, L=16)
_8B = dict(H=4096, I=14336, Hq=32, Hkv=8, D=128, L=32)
#: the attention of the benchmark's served decode programs at head_dim 128
_QWEN3_1P7B = dict(Hq=16, Hkv=8, D=128, L=28)
_KV2 = dict(Hq=8, Hkv=2, D=128, L=20)
#: a block step: 4 query positions a row over 4 KV heads (sdar-30b-a3b)
_BLOCK4 = dict(Hq=32, Hkv=4, D=128, L=6)
#: 16 KV heads a chip, one query head a KV head, 4 x 48 streams (ouro-2.6b)
_KV16 = dict(Hq=16, Hkv=16, D=128, L=192)


@dataclass(frozen=True)
class KernelCase:
    """One committed (shape-class, dtype) instantiation of a kernel."""

    shape_class: str
    dtype: str  # census label AND the tuning-table dtype key
    build: Callable[[], Tuple[Callable, tuple]]  # -> (fn, abstract args)


@dataclass(frozen=True)
class KernelSpec:
    name: str
    site: Tuple[str, str]  # (ops file, enclosing function of the pallas_call)
    entry: str  # public entry point name (test files must mention it)
    fallback: str  # "dotted.module:attr" native path
    parity_test: str  # repo-relative test file exercising kernel vs fallback
    cases: Tuple[KernelCase, ...]
    lowering_test: str = "tests/test_tpu_lowering.py"
    tile_params: Tuple[str, ...] = ()  # free tile params read from the table
    sweep: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()  # param -> candidates
    table_kernel: Optional[str] = None  # tuning-table key (defaults to name)
    # bytes a step copies by hand (``make_async_copy``) out of operands that
    # stay in HBM and so have no block window, from the traced instance
    step_copy_bytes: Optional[Callable[["KernelInstance"], int]] = None

    @property
    def table_key(self) -> str:
        return self.table_kernel or self.name


@dataclass
class BlockInfo:
    role: str  # "in" | "out"
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    itemsize: int


@dataclass
class KernelInstance:
    kernel: str
    shape_class: str
    dtype: str
    tiles: Dict[str, int]  # the resolved tile params (empty if none)
    grid: Tuple[int, ...]
    blocks: List[BlockInfo]
    scratch: List[Tuple[Tuple[int, ...], str, int]]  # (shape, dtype, bytes)
    flops_per_step: int
    dot_stats: List[Tuple[int, int, int]]  # (flops, contract_depth, out_lanes)
    copy_bytes: int = 0  # hand copies a step (KernelSpec.step_copy_bytes)
    # the scoped-VMEM limit the call asks the compiler for (``vmem_limit_bytes``);
    # None: the compiler's default, which is the device model's budget
    vmem_limit: Optional[int] = None

    @property
    def key(self) -> str:
        return f"{self.kernel}/{self.shape_class}/{self.dtype}"

    @property
    def scratch_bytes(self) -> int:
        return sum(b for _, _, b in self.scratch)

    @property
    def block_bytes_single(self) -> int:
        """One copy of every operand/output window (the per-step DMA set)."""
        out = 0
        for b in self.blocks:
            n = 1
            for d in b.block_shape:
                n *= d
            out += n * b.itemsize
        return out

    @property
    def vmem_bytes(self) -> int:
        """Static VMEM model (KERN701): every blocked operand/output window
        is double-buffered by the Pallas pipeline; scratch is single."""
        return 2 * self.block_bytes_single + self.scratch_bytes


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _unjit(fn):
    """The unjitted callable behind a ``jax.jit`` wrapper — tracing through
    the wrapper would let jit's trace cache return a stale jaxpr when only a
    tile override (invisible to the cache key) changed."""
    return getattr(fn, "__wrapped__", fn)


# ---------------------------------------------------------------------------
# case builders (committed bench shapes)
# ---------------------------------------------------------------------------


def _flash_case(S, dtype, *, window=None, packed=False):
    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import flash_attention as fa

        dt = jnp.dtype(dtype)
        m = _1B
        q = _sds((1, m["Hq"], S, m["D"]), dt)
        valid = _sds((1, S), jnp.int32)
        fn = functools.partial(
            _unjit(fa.flash_attention_bhsd),
            scale=m["D"] ** -0.5, causal=True, window=window, packed=packed,
        )
        return fn, (q, q, q, valid)

    return build


def _tkg_case(B, bucket, model, cache_dtype):
    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import decode_attention as da

        m = model
        q = _sds((B, 1, m["Hq"], m["D"]), jnp.bfloat16)
        cache = _sds((m["L"], B, bucket, m["Hkv"], m["D"]), jnp.dtype(cache_dtype))
        li = _sds((), jnp.int32)
        mask = _sds((B, 1, 1, bucket), jnp.bool_)
        fn = functools.partial(
            _unjit(da.tkg_decode_attention), scale=m["D"] ** -0.5, n_kv=m["Hkv"]
        )
        return fn, (q, cache, cache, li, mask)

    return build


def _paged_tkg_case(B, MB, bs, cache_dtype, m=_1B, K=1):
    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import decode_attention as da

        q = _sds((B, K, m["Hq"], m["D"]), jnp.bfloat16)
        cache = _sds((m["L"], 65, m["Hkv"], bs, m["D"]), jnp.dtype(cache_dtype))
        li = _sds((), jnp.int32)
        bt = _sds((B, MB), jnp.int32)
        mask = _sds((B, 1, K, MB * bs), jnp.bool_)
        fn = functools.partial(
            _unjit(da.paged_tkg_decode_attention),
            scale=m["D"] ** -0.5, n_kv=m["Hkv"],
        )
        return fn, (q, cache, cache, li, bt, mask)

    return build


def _ssm_update_case(rows, groups, layers):
    """The two served Mamba-2 geometries (64 heads of 64, state 128) at the
    benchmark's decode cells: Granite-4.0-H-micro (48 slots, one group of B/C,
    36 layers) and ``nemotron_h`` (64 slots, eight groups, 6 layers held)."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import ssm_state_update as su

        H, P, N = 64, 64, 128
        state = _sds((layers, rows, H, P, N), jnp.float32)
        x = _sds((rows, H, P), jnp.bfloat16)
        bc = _sds((rows, groups, N), jnp.bfloat16)
        args = (state, _sds((), jnp.int32), x, bc, bc, _sds((rows, H), jnp.float32),
                _sds((H,), jnp.float32), _sds((rows,), jnp.bool_), _sds((rows,), jnp.bool_))
        return _unjit(su.ssm_state_update), args

    return build


def _kda_update_case(rows):
    """Kimi-Linear's KDA geometry (12 of its layers, 32 heads of 128 x 128) at
    ``rows`` serving slots: the benchmark's long-generation cell."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import kda_state_update as ku

        L, H, D = 12, 32, 128
        vec = _sds((rows, H, D), jnp.float32)
        args = (_sds((L, rows, H, D, D), jnp.float32), _sds((), jnp.int32), vec, vec, vec, vec,
                _sds((rows, H), jnp.float32), _sds((rows,), jnp.bool_), _sds((rows,), jnp.bool_))
        return _unjit(ku.kda_state_update), args

    return build


def _kda_chunk_case(q_len, rows=8, slots=128):
    """Kimi-Linear's KDA geometry (above) in the chunk program: ``rows`` rows
    of ``q_len`` positions over the stacked state of ``slots`` serving slots."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import kda_chunk_scan as kc

        L, H, D = 12, 32, 128
        x = _sds((rows, q_len, H, D), jnp.float32)
        args = (_sds((L, slots, H, D, D), jnp.float32), _sds((), jnp.int32), x, x, x, x,
                _sds((rows, q_len, H), jnp.float32), _sds((rows, q_len), jnp.bool_),
                _sds((rows,), jnp.bool_), _sds((rows,), jnp.int32))
        return _unjit(kc.kda_chunk_scan), args

    return build


def _power_update_case(rows):
    """Brumby-14B's power-retention geometry (8 of its layers, 8 KV heads of
    8704 x 128 read by 5 query heads each) at ``rows`` serving slots: the
    benchmark's generation cell."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import power_state_update as pu

        L, H, G, D, d = 8, 40, 8, 8704, 128
        f32 = jnp.float32
        args = (_sds((L, rows, G, D, d), f32), _sds((L, rows, G, D), f32), _sds((), jnp.int32),
                _sds((rows, H, d), f32), _sds((rows, G, d), f32), _sds((rows, G, d), f32),
                _sds((rows, G), f32), _sds((rows,), jnp.bool_), _sds((rows,), jnp.bool_))
        return _unjit(pu.power_state_update), args

    return build


def _paged_flash_case(B, Sq, MB, bs, cache_dtype, m=_1B):
    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import paged_flash_attention as pf

        quant = jnp.dtype(cache_dtype) == jnp.int8
        q = _sds((B, Sq, m["Hq"], m["D"]), jnp.bfloat16)
        cache = _sds((m["L"], 65, m["Hkv"], bs, m["D"]), jnp.dtype(cache_dtype))
        li = _sds((), jnp.int32)
        bt = _sds((B, MB), jnp.int32)
        pos = _sds((B, Sq), jnp.int32)
        lim = _sds((B,), jnp.int32)
        raw = _unjit(pf.paged_flash_attention)
        kw = dict(scale=m["D"] ** -0.5, n_rep=m["Hq"] // m["Hkv"])
        if quant:
            scale = _sds((m["Hkv"],), jnp.float32)

            def fn(q, k, v, li, bt, pos, lim, ks, vs):
                return raw(q, k, v, bt, pos, lim, layer_idx=li, k_scale=ks, v_scale=vs, **kw)

            return fn, (q, cache, cache, li, bt, pos, lim, scale, scale)

        def fn(q, k, v, li, bt, pos, lim):
            return raw(q, k, v, bt, pos, lim, layer_idx=li, **kw)

        return fn, (q, cache, cache, li, bt, pos, lim)

    return build


#: Kimi-VL-A3B's language model (benchmark/configs/kimi-vl-a3b.json): 16 q
#: heads over ONE latent of 512 and one rotary key of 64 a token, 7 layers
_MLA = dict(Hq=16, r=512, rope=64, L=7)


def _latent_case(B, Sq, MB, bs, m=_MLA):
    """A latent-attention kernel of ops/latent_attention.py over the packed
    pool: the decode kernel at ``Sq`` 1, the chunk kernel above decode widths."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import latent_attention as la

        pack = 128 // m["rope"]
        q_c = _sds((B, Sq, m["Hq"], m["r"]), jnp.bfloat16)
        q_pe = _sds((B, Sq, m["Hq"], m["rope"]), jnp.bfloat16)
        c = _sds((m["L"], 65, 1, bs, m["r"]), jnp.bfloat16)
        kr = _sds((m["L"], 65, 1, bs // pack, 128), jnp.bfloat16)
        li, bt = _sds((), jnp.int32), _sds((B, MB), jnp.int32)
        scale = (128 + m["rope"]) ** -0.5
        if Sq == 1:
            raw = _unjit(la.paged_latent_decode_attention)
            mask = _sds((B, 1, 1, MB * bs), jnp.bool_)
            return functools.partial(raw, scale=scale), (q_c, q_pe, c, kr, li, bt, mask)
        raw = _unjit(la.paged_latent_flash_attention)
        pos, lim = _sds((B, Sq), jnp.int32), _sds((B,), jnp.int32)
        return functools.partial(raw, scale=scale), (q_c, q_pe, c, kr, li, bt, pos, lim)

    return build


def _index_case(B, Sq, MB, bs, heads=32, dim=128, layers=5):
    """The paged index-score kernel of ops/index_scores.py over the pool's
    index-key stream at glm-5's widths (benchmark/configs/glm-5.json: 32
    index heads of 128, 5 layers): the decode program's 32 rows of one query,
    a chunk pass's 8 rows of ``Sq``."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import index_scores as ix

        q = _sds((B, Sq, heads, dim), jnp.bfloat16)
        w = _sds((B, Sq, heads), jnp.float32)
        keys = _sds((layers, 65, 1, bs, dim), jnp.bfloat16)
        li, bt, frontier = _sds((), jnp.int32), _sds((B, MB), jnp.int32), _sds((B,), jnp.int32)
        return _unjit(ix.paged_index_scores), (q, w, keys, li, bt, frontier)

    return build


def _ragged_case(T, R, MB, bs, cache_dtype):
    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import ragged_paged_attention as rp

        m = _1B
        quant = jnp.dtype(cache_dtype) == jnp.int8
        q = _sds((T, m["Hq"], m["D"]), jnp.bfloat16)
        cache = _sds((65, m["Hkv"], bs, m["D"]), jnp.dtype(cache_dtype))
        bt = _sds((R, MB), jnp.int32)
        row = _sds((R,), jnp.int32)
        raw = _unjit(rp.ragged_paged_attention)
        kw = dict(scale=m["D"] ** -0.5, n_rep=m["Hq"] // m["Hkv"])
        if quant:
            scale = _sds((m["Hkv"],), jnp.float32)

            def fn(q, k, v, bt, rs, rl, cl, ks, vs):
                return raw(q, k, v, bt, rs, rl, cl, k_scale=ks, v_scale=vs, **kw)

            return fn, (q, cache, cache, bt, row, row, row, scale, scale)
        return functools.partial(raw, **kw), (q, cache, cache, bt, row, row, row)

    return build


def _qmm_case(B, model):
    """Decode-shaped int4 fused-dequant matmul at the model's widest linear
    (the H -> I up/gate projection — the weight-read roofline term)."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import quant_matmul as qm

        m = model
        K, N = m["H"], m["I"]
        span = 2 * qm.INT4_GROUP
        Kp = -(-K // span) * span
        x = _sds((B, K), jnp.bfloat16)
        w = _sds((Kp // 2, N), jnp.uint8)
        s = _sds((Kp // qm.INT4_GROUP, N), jnp.float32)
        return _unjit(qm.quant_matmul), (x, w, s)

    return build


def _grouped_mm_case(T, k, E, K, N, L):
    """One product of a chunk program's expert layer: ``T * k`` sorted rows
    over the ``(L, E, K, N)`` stack the layer scan holds."""

    def build():
        import jax.numpy as jnp

        from neuronx_distributed_inference_tpu.ops import grouped_matmul as gm

        x = _sds((T * k, K), jnp.bfloat16)
        w = _sds((L, E, K, N), jnp.bfloat16)
        sizes = _sds((E,), jnp.int32)
        layer = _sds((), jnp.int32)
        return _unjit(gm.grouped_matmul), (x, w, sizes, layer)

    return build


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_ATTN = "neuronx_distributed_inference_tpu.modules.attention"

REGISTRY: Tuple[KernelSpec, ...] = (
    KernelSpec(
        name="flash_attention",
        site=("flash_attention.py", "flash_attention_bhsd"),
        entry="flash_attention_bhsd",
        fallback=f"{_ATTN}:_masked_softmax_attention",
        parity_test="tests/test_flash_attention.py",
        tile_params=("bq", "bkv"),
        sweep=(("bq", (128, 256, 512)), ("bkv", (128, 256, 512))),
        cases=(
            KernelCase("plain", "bfloat16", _flash_case(8192, "bfloat16")),
            KernelCase("plain", "float32", _flash_case(512, "float32")),
            KernelCase(
                "masked", "bfloat16", _flash_case(8192, "bfloat16", window=128)
            ),
        ),
    ),
    KernelSpec(
        name="flash_attention_packed",
        site=("flash_attention.py", "_packed_flash_call"),
        entry="flash_attention_bhsd",
        fallback=f"{_ATTN}:_masked_softmax_attention",
        parity_test="tests/test_flash_attention.py",
        tile_params=("bq", "bkv"),
        table_kernel="flash_attention",  # shares the unpacked tile rule
        cases=(
            KernelCase(
                "plain", "bfloat16", _flash_case(8192, "bfloat16", packed=True)
            ),
        ),
    ),
    KernelSpec(
        name="tkg_decode_attention",
        site=("decode_attention.py", "_common_call"),
        entry="tkg_decode_attention",
        fallback=f"{_ATTN}:attention_decode",
        parity_test="tests/test_decode_attention.py",
        tile_params=("bs",),
        sweep=(("bs", (128, 256, 512, 1024)),),
        cases=(
            KernelCase("kv512", "bfloat16", _tkg_case(1, 512, _1B, "bfloat16")),
            KernelCase("kv512", "int8", _tkg_case(1, 512, _1B, "int8")),
            KernelCase("kv1024", "bfloat16", _tkg_case(8, 1024, _1B, "bfloat16")),
            KernelCase(
                "kv16896", "bfloat16", _tkg_case(1, 16896, _1B, "bfloat16")
            ),
            KernelCase("kv512", "int8_8b", _tkg_case(1, 512, _8B, "int8")),
        ),
    ),
    KernelSpec(
        name="paged_tkg_decode_attention",
        site=("decode_attention.py", "_common_call"),
        entry="paged_tkg_decode_attention",
        fallback=f"{_ATTN}:attention_decode",
        parity_test="tests/test_decode_attention.py",
        # the kv tile is ``pages`` pool blocks (ops/decode_attention.py::
        # pages_per_step): the block size is a cache-layout decision of the
        # serving config, how many blocks a step takes follows the block's
        # SHAPE, which is the shape class here ("blk<Hkv>x<bs>x<D>")
        tile_params=("pages",),
        sweep=(("pages", (2, 4, 8, 16, 32)),),
        # at head_dim 128 K and V stay in HBM: a step fills ONE of the two
        # slots of each stream's VMEM scratch (the other is being attended);
        # at head_dim 64 the blocks are windows and the scratch is statistics
        step_copy_bytes=lambda inst: inst.scratch_bytes // 2 if len(inst.grid) == 1 else 0,
        cases=(
            # a pool row of 64 lanes: one block a grid step (a quantised pool
            # at head_dim 64, an odd head count; a bfloat16 pool of an even
            # head count holds two heads a row and is a blk<H/2>x<bs>x128)
            KernelCase(
                "blk8x128x64", "bfloat16", _paged_tkg_case(8, 8, 128, "bfloat16")
            ),
            KernelCase("blk8x128x64", "int8", _paged_tkg_case(8, 8, 128, "int8")),
            # the benchmark's decode programs: Qwen3-1.7B (48 slots, kv 1024)
            # and 2 KV heads a chip (Qwen3-14B at tp = 4, ZAYA1-8B)
            KernelCase(
                "blk8x32x128", "bfloat16",
                _paged_tkg_case(48, 32, 32, "bfloat16", _QWEN3_1P7B),
            ),
            KernelCase(
                "blk8x32x128", "int8", _paged_tkg_case(48, 32, 32, "int8", _QWEN3_1P7B)
            ),
            KernelCase(
                "blk2x32x128", "bfloat16", _paged_tkg_case(48, 32, 32, "bfloat16", _KV2)
            ),
            # a block step: K = 4 query positions a row, 32 query rows a KV head
            KernelCase(
                "blk4x32x128", "bfloat16",
                _paged_tkg_case(48, 32, 32, "bfloat16", _BLOCK4, K=4),
            ),
            # a looped stack's decode program: 8 slots, a block of 128 KiB a
            # stream, so a group is 8 blocks
            KernelCase(
                "blk16x32x128", "bfloat16", _paged_tkg_case(8, 32, 32, "bfloat16", _KV16)
            ),
        ),
    ),
    KernelSpec(
        name="paged_flash_attention",
        site=("decode_attention.py", "_common_call"),
        entry="paged_flash_attention",
        fallback=f"{_ATTN}:attention_decode",
        parity_test="tests/test_chunked_prefill.py",
        # as the paged decode kernel: the shape class is the pool block's
        # shape a chip ("blk<Hkv>x<bs>x<D>"); ``pages`` is the group of
        # blocks a pass of the kernel's loop copies and attends
        # (ops/paged_flash_attention.py::blocks_per_group), ``tq`` the q tile
        tile_params=("tq", "pages"),
        sweep=(("tq", (64, 128, 256, 512)), ("pages", (8, 16, 32))),
        # at head_dim 128 a pass fills ONE of the two slots of K's and V's
        # VMEM scratch (its first two entries; the rest is the statistics of
        # every (KV head, part)) by hand; at head_dim 64 the blocks are windows
        step_copy_bytes=lambda inst: (
            sum(b for _, _, b in inst.scratch[:2]) // 2 if len(inst.grid) == 1 else 0
        ),
        cases=(
            # a pool row of 64 lanes: one block a grid step (a quantised pool
            # at head_dim 64, an odd head count; a bfloat16 pool of an even
            # head count holds two heads a row and is a blk<H/2>x<bs>x128)
            KernelCase(
                "blk8x128x64", "bfloat16", _paged_flash_case(1, 512, 16, 128, "bfloat16")
            ),
            KernelCase("blk8x128x64", "int8", _paged_flash_case(1, 512, 16, 128, "int8")),
            # the benchmark's chunk programs (8 rows of 128, blocks of 32):
            # Qwen3-1.7B at kv 8192, 2 KV heads a chip (ZAYA1-8B; Qwen3-14B
            # at tp = 4 alike) and SDAR's 8 q heads a KV head, taken in parts
            KernelCase(
                "blk8x32x128", "bfloat16",
                _paged_flash_case(8, 128, 256, 32, "bfloat16", _QWEN3_1P7B),
            ),
            KernelCase(
                "blk8x32x128", "int8", _paged_flash_case(8, 128, 256, 32, "int8", _QWEN3_1P7B)
            ),
            KernelCase(
                "blk2x32x128", "bfloat16", _paged_flash_case(8, 128, 64, 32, "bfloat16", _KV2)
            ),
            KernelCase(
                "blk4x32x128", "bfloat16", _paged_flash_case(8, 128, 64, 32, "bfloat16", _BLOCK4)
            ),
            KernelCase(
                "blk16x32x128", "bfloat16", _paged_flash_case(8, 128, 64, 32, "bfloat16", _KV16)
            ),
        ),
    ),
    # the latent pool's two kernels (ops/latent_attention.py): the shape
    # class is the latent block a chip, as the paged kernels'. The decode
    # kernel's ``pages`` follows pages_per_step's rule under the paged decode
    # kernel's name; the chunk kernel's tiles are its OWN, under its own name
    # (latent_attention.blocks_per_group and Q_ROWS: every head shares the one
    # latent, so the row cap sets the part, which ``n_rep`` does for the GQA
    # kernel), and the session counts a latent pool's walked blocks by them
    KernelSpec(
        name="paged_latent_decode_attention",
        site=("decode_attention.py", "_common_call"),
        entry="paged_latent_decode_attention",
        fallback="neuronx_distributed_inference_tpu.ops.latent_attention:native_latent_attention",
        parity_test="tests/test_deepseek_reference.py",
        lowering_test="tests/test_chip_compile.py",
        # a step fills ONE of the two slots of both streams' VMEM scratch by hand
        step_copy_bytes=lambda inst: sum(b for _, _, b in inst.scratch[:2]) // 2,
        cases=(KernelCase("blk1x32x512", "bfloat16", _latent_case(64, 1, 256, 32)),),
    ),
    KernelSpec(
        name="paged_latent_flash_attention",
        site=("decode_attention.py", "_common_call"),
        entry="paged_latent_flash_attention",
        fallback="neuronx_distributed_inference_tpu.ops.latent_attention:native_latent_attention",
        parity_test="tests/test_deepseek_reference.py",
        lowering_test="tests/test_chip_compile.py",
        # ``rows``: the most query rows of a part (heads x one q tile);
        # ``pages``: the blocks of a group
        tile_params=("rows", "pages"),
        sweep=(("rows", (256, 512, 1024)), ("pages", (16, 32, 64))),
        step_copy_bytes=lambda inst: sum(b for _, _, b in inst.scratch[:2]) // 2,
        cases=(KernelCase("blk1x32x512", "bfloat16", _latent_case(8, 128, 256, 32)),),
    ),
    # the indexer's scores off the pool's third stream (ops/index_scores.py):
    # one kernel for both step programs, so the decode shape is a second case
    # of the one block shape, told apart by its label. ``rows``: the most
    # score rows of a product (index heads of a tile x the pass's positions);
    # ``pages``: the blocks of a group
    KernelSpec(
        name="paged_index_scores",
        site=("decode_attention.py", "_common_call"),
        entry="paged_index_scores",
        fallback="neuronx_distributed_inference_tpu.modules.sparse_index:index_scores",
        parity_test="tests/test_index_scores.py",
        lowering_test="tests/test_chip_compile.py",
        tile_params=("rows", "pages"),
        sweep=(("rows", (256, 512, 1024, 2048)), ("pages", (16, 32, 64))),
        # a step fills ONE of the two slots of the keys' VMEM scratch by hand
        step_copy_bytes=lambda inst: inst.scratch[0][2] // 2,
        cases=(
            KernelCase("blk1x32x128", "bfloat16", _index_case(8, 128, 528, 32)),
            KernelCase("blk1x32x128", "bfloat16_decode", _index_case(32, 1, 528, 32)),
        ),
    ),
    KernelSpec(
        name="ragged_paged_attention",
        site=("ragged_paged_attention.py", "ragged_paged_attention"),
        entry="ragged_paged_attention",
        fallback=(
            "neuronx_distributed_inference_tpu.ops.ragged_paged_attention"
            ":ragged_attention_native"
        ),
        parity_test="tests/test_ragged_attention.py",
        tile_params=("tq",),
        sweep=(("tq", (8, 16, 32)),),
        cases=(
            KernelCase(
                "mixed", "bfloat16", _ragged_case(512, 8, 16, 128, "bfloat16")
            ),
            KernelCase("mixed", "int8", _ragged_case(512, 8, 16, 128, "int8")),
        ),
    ),
    KernelSpec(
        name="grouped_matmul",
        site=("grouped_matmul.py", "grouped_matmul"),
        entry="grouped_matmul",
        fallback="neuronx_distributed_inference_tpu.modules.moe:expert_mlps_dense",
        parity_test="tests/test_moe_dispatch.py",
        lowering_test="tests/test_chip_compile.py",
        # the row tile; the output tile follows from the shapes (_tiles)
        tile_params=("tm",),
        sweep=(("tm", (64, 128, 256)),),
        cases=(
            # the benchmark's chunk programs at 8 rows x 128: zaya1-8b's
            # products (16 experts, top-1), sdar-30b-a3b's gate and down
            # (128, top-8), kimi-vl-a3b's gate (64, top-6: 11 lane groups)
            KernelCase("k2048_n2048", "bfloat16", _grouped_mm_case(1024, 1, 16, 2048, 2048, 20)),
            KernelCase("k2048_n768", "bfloat16", _grouped_mm_case(1024, 8, 128, 2048, 768, 6)),
            KernelCase("k768_n2048", "bfloat16", _grouped_mm_case(1024, 8, 128, 768, 2048, 6)),
            KernelCase("k2048_n1408", "bfloat16", _grouped_mm_case(1024, 6, 64, 2048, 1408, 6)),
        ),
    ),
    KernelSpec(
        name="ssm_state_update",
        site=("ssm_state_update.py", "ssm_state_update"),
        entry="ssm_state_update",
        fallback="neuronx_distributed_inference_tpu.modules.ssm:mamba2_step",
        parity_test="tests/test_ssm.py",
        lowering_test="tests/test_chip_compile.py",
        # ``heads``: the heads a tile (the entry's ``heads_per_block`` keyword
        # overrides it). Swept on the chip at both shapes (PERF.md, PR 63):
        # 16 / 32 / 64 heads lie within 1% of each other with the read-out on
        # the matrix unit; 32 (a 1 MiB tile) is the table's at both
        tile_params=("heads",),
        sweep=(("heads", (16, 32, 64)),),
        cases=(
            KernelCase("h64g1x64x128", "float32", _ssm_update_case(48, 1, 36)),
            KernelCase("h64g8x64x128", "float32", _ssm_update_case(64, 8, 6)),
        ),
    ),
    KernelSpec(
        name="kda_state_update",
        site=("kda_state_update.py", "kda_state_update"),
        entry="kda_state_update",
        fallback="neuronx_distributed_inference_tpu.modules.kda:kda_step",
        parity_test="tests/test_kimi_linear_reference.py",
        lowering_test="tests/test_chip_compile.py",
        # heads_per_block is a keyword of the entry (16: a 1 MiB tile), not a
        # tuning-table entry: nothing was swept on the chip yet
        cases=(KernelCase("rows128", "float32", _kda_update_case(128)),),
    ),
    # the chunk program's recurrence between sub-chunks on the stacked state
    # (ops/kda_chunk_scan.py). ``heads``: the heads a tile (the entry's
    # ``heads_per_block`` keyword overrides it), swept on the chip at the
    # widest chunk (PERF.md, PR 68); the narrowest width, one sub-chunk of 8,
    # is a case of its own
    KernelSpec(
        name="kda_chunk_scan",
        site=("kda_chunk_scan.py", "scan_on_stack"),
        entry="kda_chunk_scan",
        fallback="neuronx_distributed_inference_tpu.modules.kda:kda_chunk",
        parity_test="tests/test_kda_chunk_scan.py",
        lowering_test="tests/test_chip_compile.py",
        tile_params=("heads",),
        sweep=(("heads", (2, 4, 8)),),
        cases=(
            KernelCase("q128c16x128", "float32", _kda_chunk_case(128)),
            KernelCase("q8c8x128", "float32", _kda_chunk_case(8)),
        ),
    ),
    KernelSpec(
        name="power_state_update",
        site=("power_state_update.py", "power_state_update"),
        entry="power_state_update",
        fallback="neuronx_distributed_inference_tpu.modules.power_retention:power_step",
        parity_test="tests/test_brumby_reference.py",
        lowering_test="tests/test_chip_compile.py",
        # the tile (34 block pairs = 2176 rows) is the kernel's constant
        # (PAIRS_PER_TILE: 17 / 34 / 68 read on the chip), not a tuning-table entry
        cases=(KernelCase("rows16", "float32", _power_update_case(16)),),
    ),
    KernelSpec(
        name="quant_matmul",
        site=("quant_matmul.py", "quant_matmul"),
        entry="quant_matmul",
        fallback=(
            "neuronx_distributed_inference_tpu.ops.quant_matmul"
            ":int4_matmul_native"
        ),
        parity_test="tests/test_quant_matmul.py",
        tile_params=("bn",),
        sweep=(("bn", (128, 256, 512)),),
        cases=(
            KernelCase("k2048_n8192", "bfloat16", _qmm_case(8, _1B)),
            KernelCase("k4096_n14336", "bfloat16", _qmm_case(8, _8B)),
        ),
    ),
)


#: in-code fallback tile constants per (table_kernel, param) — the values
#: the kernels pass as ``tile_default(..., fallback=...)``. KERN704 pins
#: hand_picked table entries to these, so the table and the code cannot
#: silently disagree about today's defaults.
HAND_PICKED: Dict[str, Dict[str, Dict[str, int]]] = {
    "flash_attention": {
        "plain": {"bq": 512, "bkv": 512},
        "masked": {"bq": 128, "bkv": 128},
    },
    "tkg_decode_attention": {"*": {"bs": 512}},
    # the q tile, and pages_per_step's rule under this kernel's name
    "paged_flash_attention": {
        "blk8x128x64": {"tq": 128, "pages": 1},
        "blk16x32x128": {"tq": 128, "pages": 8},
        "*": {"tq": 128, "pages": 16},
    },
    # what pages_per_step's rule gives at each registered block shape (at
    # most 1 MiB a stream and 512 tokens a group; 1 off the 128 lanes)
    "paged_tkg_decode_attention": {
        "blk8x128x64": {"pages": 1},
        "blk8x32x128": {"pages": 16},
        "blk2x32x128": {"pages": 16},
        "blk4x32x128": {"pages": 16},
        "blk16x32x128": {"pages": 8},
    },
    # latent_attention.Q_ROWS, and GROUP_TOKENS (1024) over the block's 32 tokens
    "paged_latent_flash_attention": {"blk1x32x512": {"rows": 512, "pages": 32}},
    # index_scores.Q_ROWS, and GROUP_TOKENS (1024) over the block's 32 tokens
    "paged_index_scores": {"blk1x32x128": {"rows": 1024, "pages": 32}},
    "ragged_paged_attention": {"*": {"tq": 16}},
    # ssm_state_update.DEFAULT_HEADS_PER_BLOCK
    "ssm_state_update": {"*": {"heads": 16}},
    # kda_chunk_scan.DEFAULT_HEADS_PER_BLOCK
    "kda_chunk_scan": {"*": {"heads": 8}},
    "grouped_matmul": {"*": {"tm": 128}},
    "quant_matmul": {"*": {"bn": 256}},
}


def hand_picked_tiles(table_kernel: str, shape_class: str) -> Optional[Dict[str, int]]:
    per = HAND_PICKED.get(table_kernel)
    if per is None:
        return None
    return per.get(shape_class, per.get("*"))


# ---------------------------------------------------------------------------
# trace-based extraction
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    """Every sub-jaxpr an equation carries — including tuple-valued params
    (``cond``'s ``branches``)."""
    from jax.extend import core as jcore

    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


def _block_dim(d) -> int:
    """Size of one BlockSpec dimension as the traced grid mapping carries
    it: ``pl.Blocked(n)`` / ``pl.Element(n)`` wrap the size, a squeezed
    (``None``) dimension holds one element."""
    from jax.experimental import pallas as pl

    if isinstance(d, pl.Squeezed):
        return 1
    return int(d.block_size)


def find_pallas_eqns(jaxpr):
    hits = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            hits.append(eqn)
        for sub in _sub_jaxprs(eqn):
            hits.extend(find_pallas_eqns(sub))
    return hits


def _dot_stats(jaxpr, out):
    """(flops, contraction_depth, out_lane_width) per dot_general, cond
    branches included (KERN705 MXU-occupancy input)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            k = 1
            for ax in lc:
                k *= lhs.shape[ax]
            oshape = eqn.outvars[0].aval.shape
            n = 1
            for d in oshape:
                n *= d
            lanes = oshape[-1] if oshape else 1
            out.append((2 * n * k, k, lanes))
        for sub in _sub_jaxprs(eqn):
            _dot_stats(sub, out)
    return out


#: vector-unit arithmetic counted for a kernel that has NO matrix product
#: (kda_state_update: multiply-adds over a float32 tile and two sums)
_VECTOR_OPS = frozenset({"mul", "add", "sub", "reduce_sum"})


def _vector_flops(jaxpr) -> int:
    """Elementwise multiplies, adds and sum-reductions of a kernel jaxpr, one
    operation per element (``reduce_sum``: per element read)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _VECTOR_OPS:
            aval = (eqn.invars if eqn.primitive.name == "reduce_sum" else eqn.outvars)[0].aval
            n = 1
            for d in getattr(aval, "shape", ()):
                n *= d
            total += n
        for sub in _sub_jaxprs(eqn):
            total += _vector_flops(sub)
    return total


def instantiate(
    spec: KernelSpec, case: KernelCase, tiles: Optional[Dict[str, int]] = None
) -> KernelInstance:
    """Trace one committed case (optionally under tile overrides) and read
    the kernel's launch truth off the traced ``pallas_call`` equation."""
    import jax
    import numpy as np

    from neuronx_distributed_inference_tpu.analysis.cost_audit import jaxpr_flops
    from neuronx_distributed_inference_tpu.ops.tile_defaults import (
        table_entry,
        tile_overrides,
    )

    fn, args = case.build()
    if tiles:
        ctx = tile_overrides(spec.table_key, tiles)
    else:
        import contextlib

        ctx = contextlib.nullcontext()
    with ctx:
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    eqns = find_pallas_eqns(jaxpr.jaxpr)
    if not eqns:
        raise RuntimeError(f"{spec.name}/{case.shape_class}: no pallas_call traced")
    eqn = eqns[0]
    gm = eqn.params["grid_mapping"]
    blocks: List[BlockInfo] = []
    for i, bm in enumerate(gm.block_mappings):
        if str(getattr(bm.block_aval, "memory_space", None)) in ("any", "hbm"):
            continue  # stays in HBM, copied by hand into scratch: no window
        sd = bm.array_aval
        blocks.append(
            BlockInfo(
                role="in" if i < gm.num_inputs else "out",
                block_shape=tuple(_block_dim(d) for d in bm.block_shape),
                array_shape=tuple(int(d) for d in sd.shape),
                dtype=str(sd.dtype),
                itemsize=int(np.dtype(sd.dtype).itemsize),
            )
        )
    kj = eqn.params["jaxpr"]
    scratch = []
    if gm.num_scratch_operands:
        for v in kj.invars[-gm.num_scratch_operands:]:
            if str(getattr(v.aval, "memory_space", None)) in ("semaphore_mem", "smem"):
                continue  # not vector memory
            shape = tuple(int(d) for d in v.aval.shape)
            n = 1
            for d in shape:
                n *= d
            scratch.append(
                (shape, str(v.aval.dtype), n * int(np.dtype(v.aval.dtype).itemsize))
            )
    resolved: Dict[str, int] = {}
    if tiles:
        resolved = dict(tiles)
    elif spec.tile_params:
        entry = table_entry(spec.table_key, case.shape_class, case.dtype) or {}
        hand = hand_picked_tiles(spec.table_key, case.shape_class) or {}
        for p in spec.tile_params:
            v = (entry.get("tiles") or {}).get(p, hand.get(p))
            if v is not None:
                resolved[p] = int(v)
    inst = KernelInstance(
        kernel=spec.name,
        shape_class=case.shape_class,
        dtype=case.dtype,
        tiles=resolved,
        grid=tuple(int(g) for g in gm.grid),
        blocks=blocks,
        scratch=scratch,
        # matrix-unit FLOPs; a kernel with no product at all is counted by
        # its vector-unit arithmetic instead (it has work, just no MXU work)
        flops_per_step=int(jaxpr_flops(kj) or _vector_flops(kj)),
        dot_stats=_dot_stats(kj, []),
    )
    if spec.step_copy_bytes is not None:
        inst.copy_bytes = int(spec.step_copy_bytes(inst))
    mosaic = dict(eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    inst.vmem_limit = getattr(mosaic, "vmem_limit_bytes", None)
    return inst


@functools.lru_cache(maxsize=1)
def collect_instances() -> Tuple[KernelInstance, ...]:
    """Every registered kernel traced at its committed cases with the
    (table-routed) default tiles. Memoized: the suite, ``legal_tiles`` and
    the tests all share one trace pass."""
    out = []
    for spec in REGISTRY:
        for case in spec.cases:
            out.append(instantiate(spec, case))
    return tuple(out)


def reset_cache() -> None:
    collect_instances.cache_clear()


# ---------------------------------------------------------------------------
# AST census of pallas_call sites (KERN703's "no unregistered kernel")
# ---------------------------------------------------------------------------


def pallas_sites() -> List[Tuple[str, str, int]]:
    """Every ``pl.pallas_call`` call expression under ``ops/`` as
    (file, enclosing function, line)."""
    sites = []
    for path in sorted(OPS_DIR.glob("*.py")):
        tree = ast.parse(path.read_text())

        def walk(node, fn_name):
            for child in ast.iter_child_nodes(node):
                name = fn_name
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    name = child.name
                if isinstance(child, ast.Call):
                    f = child.func
                    callee = f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")
                    if callee == "pallas_call":
                        sites.append((path.name, fn_name or "<module>", child.lineno))
                walk(child, name)

        walk(tree, None)
    return sites
