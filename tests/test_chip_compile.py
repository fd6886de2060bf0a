"""Ask the chip's compiler, without the chip.

``tests/test_tpu_lowering.py`` stops at Mosaic MLIR (``jax.export``): it
cannot see the fast-memory limit, a mis-tiled slice, or a kernel that cannot
be partitioned. Here the main-path kernels at Llama-3.2-1B and 3.1-8B widths,
one whole 1B decode step, the tp=4 ragged mixed step and the four-chip
cell's two paged step programs (the per-shard dispatch) are COMPILED for a
described ``v5e:2x2`` chip — the TPU compiler is installed on the CPU harness
and raises what the chip's would. Nothing runs, so this says nothing about
results or times (chip_smoke.py does).

The topology, and everything built from it, lives in module-scoped fixtures:
only one process may load the TPU library, so it must not be touched while
any module is imported. The persistent compile cache is off around the
module — a TPU executable written from here cannot be read back without a
chip, and the next run would warn on every entry.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr
from neuronx_distributed_inference_tpu.analysis.device_model import LLAMA_1B, LLAMA_8B
from neuronx_distributed_inference_tpu.ops.kernel_mode import force_compiled_kernels
from neuronx_distributed_inference_tpu.parallel.mesh import FULL_AXES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    """n -> a (1,1,1,1,n) model mesh over the first n described chips."""

    def make(n):
        return Mesh(np.array(topo.devices[:n]).reshape(1, 1, 1, 1, n), FULL_AXES)

    return make


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# (kernel, shape class, dtype label) of analysis/kernel_registry.REGISTRY —
# the committed bench shapes of every attention kernel the 1B/8B generate and
# serving paths dispatch, plus the 8B int4 matmul
MAIN_PATH_KERNELS = [
    ("flash_attention_packed", "plain", "bfloat16"),  # 1B CTE (D=64 head pairs)
    ("flash_attention", "masked", "bfloat16"),
    ("tkg_decode_attention", "kv512", "bfloat16"),  # 1B decode
    ("tkg_decode_attention", "kv512", "int8_8b"),  # 8B widths, int8 cache
    ("paged_tkg_decode_attention", "blk8x128x64", "bfloat16"),  # serving decode, 1B: a block a step
    ("paged_tkg_decode_attention", "blk8x32x128", "bfloat16"),  # Qwen3-1.7B: 16 blocks a step, 48 slots
    ("paged_tkg_decode_attention", "blk8x32x128", "int8"),
    ("paged_tkg_decode_attention", "blk2x32x128", "bfloat16"),  # 2 kv heads a chip (14B at tp=4, ZAYA1)
    ("paged_flash_attention", "blk8x128x64", "bfloat16"),  # chunked prefill, 1B: a block a step
    ("paged_flash_attention", "blk8x32x128", "bfloat16"),  # Qwen3-1.7B: 8 rows of 128, groups of 16 blocks
    ("paged_flash_attention", "blk8x32x128", "int8"),
    ("paged_flash_attention", "blk2x32x128", "bfloat16"),  # 2 kv heads a chip, 4 q heads each
    ("paged_flash_attention", "blk4x32x128", "bfloat16"),  # SDAR: 8 q heads a kv head, in parts
    ("paged_tkg_decode_attention", "blk16x32x128", "bfloat16"),  # Ouro: 16 kv heads, 1 q head each, 8 blocks a step
    ("paged_flash_attention", "blk16x32x128", "bfloat16"),
    ("paged_latent_decode_attention", "blk1x32x512", "bfloat16"),  # Kimi-VL: 64 rows, kv 8192, 512 + 64 lanes
    ("paged_latent_flash_attention", "blk1x32x512", "bfloat16"),  # its chunk: 8 rows of 128, 16 heads a latent
    ("paged_index_scores", "blk1x32x128", "bfloat16"),  # glm-5's indexer, a chunk pass: 8 rows of 128, the 16896 bucket
    ("paged_index_scores", "blk1x32x128", "bfloat16_decode"),  # its decode program: 32 rows of one query
    ("ragged_paged_attention", "mixed", "bfloat16"),  # ragged mixed step
    ("ragged_paged_attention", "mixed", "int8"),
    ("quant_matmul", "k4096_n14336", "bfloat16"),  # 8B int4 weights
    ("ssm_state_update", "h64g1x64x128", "float32"),  # granite-4.0-h-micro decode: 48 slots, one group, 32 heads a tile
    ("ssm_state_update", "h64g8x64x128", "float32"),  # nemotron-3-nano-30b-a3b decode: 64 slots, 8 groups of B/C
    ("kda_state_update", "rows128", "float32"),  # kimi-linear-48b-a3b decode, 128 slots
    ("kda_chunk_scan", "q128c16x128", "float32"),  # its chunk program: 8 rows of 128 = 8 sub-chunks of 16 on the stacked state
    ("kda_chunk_scan", "q8c8x128", "float32"),  # the narrowest chunk width: one sub-chunk of 8
    ("power_state_update", "rows16", "float32"),  # brumby-14b-base decode, 16 slots: tiles of 1088 x 128 of a KV head's 8704
    ("grouped_matmul", "k2048_n2048", "bfloat16"),  # zaya1-8b's chunk program: 1024 rows, 16 experts of a 20-layer stack
    ("grouped_matmul", "k2048_n768", "bfloat16"),  # sdar-30b-a3b's gate and up: 8192 rows, 128 experts
    ("grouped_matmul", "k768_n2048", "bfloat16"),  # its down
    ("grouped_matmul", "k2048_n1408", "bfloat16"),  # kimi-vl-a3b's gate: 6144 rows, 64 experts, 11 lane groups
]


@pytest.mark.parametrize(
    "kernel,shape_class,dtype", MAIN_PATH_KERNELS,
    ids=["/".join(k) for k in MAIN_PATH_KERNELS],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, shape_class, dtype):
    (case,) = [
        c
        for spec in kr.REGISTRY if spec.name == kernel
        for c in spec.cases if (c.shape_class, c.dtype) == (shape_class, dtype)
    ]
    fn, args = case.build()
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args
    )
    with force_compiled_kernels():
        compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) >= 1


@pytest.mark.parametrize("q_len", [128, 32, 8])
def test_latent_chunk_kernel_takes_a_selection_at_glm5s_widths(one_chip, q_len):
    """``paged_latent_flash_attention`` under a selection's predicate at
    glm-5's widths (8 rows, 64 heads over a latent of 512 + 64, the 16896
    bucket) at the kernel's own tiles (16 parts of 512 score rows, 17 groups
    of 32 blocks; a row's predicate slab 2.1 MB of int8 at 128 queries, int32
    at a q tile under 32 rows) compiles for a v5e with what it asks of VMEM
    (``latent_attend`` gives it every chunk width under a selection), and the
    decode kernel is not asked for 8 positions x 64 heads (its mask slab
    would be 34 MB a row)."""
    from neuronx_distributed_inference_tpu.ops import latent_attention as la

    B, H, r, rope, bs, W = 8, 64, 512, 64, 32, 16896
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (
        sds((B, q_len, H, r), jnp.bfloat16), sds((B, q_len, H, rope), jnp.bfloat16),
        sds((5, 4225, 1, bs, r), jnp.bfloat16), sds((5, 4225, 1, bs // 2, 128), jnp.bfloat16),
        sds((), jnp.int32), sds((B, 1, q_len, W), jnp.bool_), sds((B, W // bs), jnp.int32),
        sds((B,), jnp.int32), sds((B, q_len), jnp.int32), sds((B, q_len, W), jnp.bool_),
    )
    with force_compiled_kernels(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(la, "on_tpu", lambda: True)  # the gate asks jax.default_backend()
        compiled = jax.jit(
            functools.partial(la.latent_attend, scale=256 ** -0.5, interpret=False)
        ).lower(*args).compile()
    text = compiled.as_text()
    assert la.CHUNK_KERNEL in text and la.DECODE_KERNEL not in text
    # the predicate in the kernel's column order: 17 groups x 2 lane groups x (32 blocks x 16 rows)
    assert ("s8[8,17,2,1,128,512]" in text) == (q_len == 128)


@pytest.mark.parametrize("rows,q_len,kv", [
    (32, 1, 8192), (32, 1, 12288), (32, 1, 16896),
    (8, 8, 16896), (8, 16, 12288), (8, 32, 8192), (8, 64, 16896), (8, 128, 12288),
])
def test_index_score_kernel_compiles_at_every_rung_glm5_warms(one_chip, rows, q_len, kv):
    """``paged_index_scores`` at the shapes glm-5.sparsectx's warm-up compiles
    it for: the decode program's 32 rows of one query and a chunk pass's 8
    rows at the q ladder's five widths, over the three kv buckets past
    ``index_topk`` (16896 is 16.5 groups of 1024: the last group is drawn
    back), 32 index heads of 128 over blocks of 32. The gate admits each on
    the chip, the scores come out at the bucket's width in float32, and at
    128 queries the heads are taken eight a product (1024 score rows)."""
    from neuronx_distributed_inference_tpu.ops import index_scores as ix

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    keys = sds((5, 16769, 1, 32, 128), jnp.bfloat16)
    args = (
        sds((rows, q_len, 32, 128), jnp.bfloat16), sds((rows, q_len, 32), jnp.float32), keys,
        sds((), jnp.int32), sds((rows, kv // 32), jnp.int32), sds((rows,), jnp.int32),
    )
    with force_compiled_kernels(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(ix, "on_tpu", lambda: True)  # the gate asks jax.default_backend()
        assert ix.use_index_kernel(keys, kv)
        compiled = jax.jit(ix.paged_index_scores.__wrapped__).lower(*args).compile()
    text = compiled.as_text()
    assert ix.KERNEL in text and _custom_calls(compiled) == 1
    assert f"f32[{rows},{q_len},{kv}]" in text
    if q_len == 128:
        assert "bf16[8,4,1024,128]" in text  # 4 tiles of 8 heads x 128 positions


@pytest.mark.parametrize("q_len", [8, 16, 32, 64, 128])
def test_kda_chunk_scan_compiles_at_every_chunk_width_kimi_linear_warms(one_chip, q_len, monkeypatch):
    """``kda_chunk_scan`` at the widths kimi-linear-48b-a3b.longgen's warm-up
    compiles its chunk program for (modules/autobucketing.
    generate_chunk_q_buckets: 8 / 16 / 32 / 64 / 128 positions a row, 8 rows),
    on the stacked state of 12 KDA layers x 128 slots x 32 heads x 128 x 128
    float32: the gate admits each on the chip (the 8 as one sub-chunk of 8),
    ONE kernel, the state aliased in and out (no copy of it, whole, a layer's
    or the rows'), no loop of the scan, ``o`` leaves as (rows, q, heads, 128)."""
    from neuronx_distributed_inference_tpu.ops import kda_chunk_scan as kc, kernel_mode

    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)  # the gate asks jax.default_backend()
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state, x = sds((12, 128, 32, 128, 128)), sds((8, q_len, 32, 128))
    args = (state, sds((), jnp.int32), x, x, x, x, sds((8, q_len, 32)), sds((8, q_len), jnp.bool_),
            sds((8,), jnp.bool_), sds((8,), jnp.int32))
    with force_compiled_kernels():
        assert kernel_mode.use_kda_chunk_scan(128, q_len, 16, 1)
        compiled = jax.jit(kc.kda_chunk_scan.__wrapped__, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert kc.KERNEL in text and _custom_calls(compiled) == 1
    assert " while(" not in text
    for shape in (state.shape, state.shape[1:], (8,) + state.shape[2:]):
        assert not _copies_of(compiled, "f32", shape)
    assert f"f32[8,{q_len},32,128]" in text


def test_flash_compiles_at_8b_head_dim(one_chip):
    """Llama-3.1-8B prefill: D=128 heads fill the lanes unpacked."""
    from neuronx_distributed_inference_tpu.ops.flash_attention import (
        flash_attention_bhsd,
    )

    q = jax.ShapeDtypeStruct(
        (1, LLAMA_8B["num_attention_heads"], 1024, LLAMA_8B["head_dim"]),
        jnp.bfloat16, sharding=one_chip,
    )
    valid = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    with force_compiled_kernels():
        compiled = jax.jit(
            lambda q, k, v, m: flash_attention_bhsd(
                q, k, v, m, scale=LLAMA_8B["head_dim"] ** -0.5, causal=True
            )
        ).lower(q, q, q, valid).compile()
    assert _custom_calls(compiled) >= 1


def _abstract_app(attrs, mesh, **tpu):
    """(app, params, cache): a 1B-class app over a DESCRIBED mesh with its
    params and KV cache as ShapeDtypeStructs carrying the declared shardings
    — no array is ever placed (there is no device to hold one)."""
    from neuronx_distributed_inference_tpu.config import TpuConfig, to_dtype
    from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    tc = TpuConfig(dtype="bfloat16", enable_bucketing=True, fused_qkv=True, **tpu)
    cfg = LlamaInferenceConfig(
        tc, load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()]
    )
    app = TpuModelForCausalLM(None, cfg, mesh=mesh)
    b = app.builder
    dt = to_dtype(tc.kv_cache_dtype or tc.dtype)
    if tc.is_block_kv_layout:
        from neuronx_distributed_inference_tpu.modules.block_kvcache import (
            block_cache_spec,
            init_block_cache,
        )

        cache = jax.eval_shape(
            lambda: init_block_cache(
                cfg.num_hidden_layers, tc.pa_num_blocks, tc.pa_block_size,
                b.gqa.kv_heads, b.head_dim, dtype=dt,
            )
        )
        cache_specs = block_cache_spec(quantized=tc.kv_quantized)
    else:
        from neuronx_distributed_inference_tpu.modules.kvcache import init_cache

        cache = jax.eval_shape(
            lambda: init_cache(
                cfg.num_hidden_layers, tc.kv_cache_batch_size or tc.max_batch_size,
                tc.seq_len, b.gqa.kv_heads, b.head_dim, dtype=dt, dp=1,
            )
        )
        cache_specs = b.cache_pspecs()

    def place(x, spec):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec or P())
        )

    params = jax.tree.map(place, jax.eval_shape(b.random_params), b.param_pspecs())
    return app, params, jax.tree.map(place, cache, cache_specs)


def _compile_step(app, runner, inputs, params, cache):
    rep = NamedSharding(app.mesh, P())
    inputs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), inputs
    )
    with force_compiled_kernels():
        return runner.trace_program(params, cache, inputs, None)[2]


def test_whole_1b_decode_step_compiles_for_v5e(chip_mesh):
    """embed -> 16-layer scan with the TKG decode kernel -> lm head, at the
    full published width, with the donated cache."""
    app, params, cache = _abstract_app(
        LLAMA_1B, chip_mesh(1), batch_size=1, seq_len=512,
        context_encoding_buckets=[128], token_generation_buckets=[512],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    tkg = app.token_generation_model
    compiled = _compile_step(app, tkg, tkg.example_inputs(512), params, cache)
    assert _custom_calls(compiled) >= 1
    # ~3 GB of bf16 weights (materialised lm_head included) on the one chip
    assert 2.5e9 < compiled.memory_analysis().argument_size_in_bytes < 4e9


def test_tp4_ragged_mixed_step_keeps_its_kernel(chip_mesh):
    """The serving mixed step on a 2x2 mesh: the ragged kernel rides
    ``jax.shard_map`` over the model axes, so the PARTITIONED executable must
    still hold the custom call (a sharded operand handed to a bare
    pallas_call is refused: 'Mosaic kernels cannot be automatically
    partitioned') and each chip holds a quarter of the weights."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    app, params, cache = _abstract_app(
        LLAMA_1B, chip_mesh(4), batch_size=8, seq_len=1024, tp_degree=4,
        context_encoding_buckets=[1024], token_generation_buckets=[1024],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_num_blocks=64, pa_block_size=32, is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=8, kernel_q_tile_size=128
        ),
        serving_ragged=True, attn_kernel_enabled=True,
    )
    mixed = app.mixed_step_model
    compiled = _compile_step(
        app, mixed, mixed.example_inputs(mixed.buckets[0]), params, cache
    )
    assert _custom_calls(compiled) >= 1
    assert "all-reduce" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < 1.2e9


# Qwen3-1.7B's head and layer geometry (16/8 heads of 128, 28 layers, hidden
# 2048, vocab 151936) on the llama graph: the paged serving step of the
# benchmark's one-chip configuration, whose KV pool is 2 x 1.94 GB
QWEN3_1P7B_GEOMETRY = dict(
    LLAMA_1B, intermediate_size=6144, num_attention_heads=16,
    num_key_value_heads=8, num_hidden_layers=28, vocab_size=151936,
    head_dim=128, max_position_embeddings=40960,
)


def _pool_copies(compiled, pool_shape):
    """(in a called computation, in the entry computation): the ``copy`` ops
    of the optimized HLO whose result has as many elements as the block
    pool, whatever shape it was bitcast to on the way (the relayout around a
    window-form scatter copies ``bf16[L*(NB+1)*bs, H, D]``). The layer scan's
    body is a called computation and is printed before ``ENTRY``; a copy at
    the program's entry or exit is in the entry computation."""
    size = int(np.prod(pool_shape))
    called, _, entry = compiled.as_text().partition("\nENTRY ")

    def count(text):
        shapes = re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text)
        return sum(np.prod([int(d) for d in sh.split(",")]) == size for sh in shapes)
    return count(called), count(entry)


def _stack_shaped(compiled, stack_shapes):
    """Instructions of the optimized HLO whose result has the shape of one
    layer's expert stack ``(E, in, out)``: what a scan that sliced the
    ``(L, E, in, out)`` weights for a custom call would hold, a copy a
    product a layer."""
    want = {"[" + ",".join(str(d) for d in sh) + "]" for sh in stack_shapes}
    return [
        line.strip()[:160] for line in compiled.as_text().splitlines()
        if (m := re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = \w+(\[[\d,]+\])", line)) and m.group(1) in want
    ]


def _assert_expert_products(compiled, program, stack_shapes):
    """The expert layer of a step program compiled with the grouped-matmul
    gate on (ops/kernel_mode.use_grouped_matmul). chunk: its three products
    are the kernel, reading the stacked weights in place: no ``ragged-dot``,
    NO instruction of the shape of a layer's expert stack, and the kernel's
    instructions (the compiler names a Pallas call by the kernel's ``name``)
    stand under ``layer.moe.experts`` in the scope table. decode (and a block
    step): the batched dense products over every expert, no grouped kernel."""
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    text = compiled.as_text()
    assert "ragged-dot" not in text
    table = device_scopes.scope_table(text)["ops"]
    calls = [name for name in table if name.startswith("grouped_matmul")]
    if program == "chunk":
        assert not _stack_shaped(compiled, stack_shapes), _stack_shaped(compiled, stack_shapes)[:3]
        assert len(calls) == 3 and {table[c] for c in calls} == {"layer.moe.experts"}
    else:
        assert not calls
        E = stack_shapes[0][0]
        assert re.search(r"= \w+\[" + str(E) + r",\d+,\d+\]\S* (?:fusion|convolution|dot)\(", text)


def _assert_experts_sort_real_positions(tkg, program):
    """Of a runner whose step program was just traced: the 8 x 128 chunk
    program's expert layers sorted the pass's real positions alone (a padded
    position is routed to no expert: models/base.expert_positions), a decode
    program's (and a block step's) were handed no mask."""
    assert tkg.masked_sort_shapes == ({(8, 128)} if program == "chunk" else set())


def _planned_bytes(compiled) -> int:
    """What the executable plans on the device: arguments, outputs that are
    not aliased to one, temporaries."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def _scatter_index_rows(compiled):
    """Index rows of every ``scatter`` in the optimized HLO: the elements of
    its indices operand over the length of an index vector. The per-head
    paged KV write has ``B * S * H`` of them, the block form a row's few
    blocks."""
    text = compiled.as_text()
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    rows = []
    for indices, vector_dim in re.findall(
        r" scatter\(%[\w.\-]+, %([\w.\-]+), .*?index_vector_dim=(\d+)", text
    ):
        dims = [int(d) for d in shapes[indices].split(",") if d]
        vector = dims[int(vector_dim)] if int(vector_dim) < len(dims) else 1
        rows.append(int(np.prod(dims)) // vector)
    return rows


def _assert_chunk_write_moves_blocks(compiled, rows, q, heads, pool_shape):
    """The chunk program's paged KV write in its block form
    (modules/block_kvcache._write_blocks): no scatter walks an index row a
    (token, head) — each of the two has ``rows x (q / bs + 1)`` — and the
    instructions that hold the write's scatters and its gathers of pool
    blocks are under ``layer.kv_write`` in the program's scope table
    (telemetry/device_scopes), which is what ``chunk.kv_write_dev_ms`` sums."""
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    from tests.conftest import paged_write_instructions

    bs = pool_shape[3]
    index_rows = _scatter_index_rows(compiled)
    assert rows * q * heads not in index_rows
    assert index_rows.count(rows * (q // bs + 1)) == 2  # K and V
    text = compiled.as_text()
    table = device_scopes.scope_table(text)["ops"]
    segments = q // bs + 1
    writes = [
        n for n in paged_write_instructions(text, pool_shape, rows, segments) if n in table
    ]
    assert len(writes) >= 4  # K and V: a gather of the held blocks, a scatter
    assert {table[name] for name in writes} == {"layer.kv_write"}


def _assert_decode_write_is_the_kernels(compiled):
    """A one-token decode program whose paged KV write is the decode kernel's
    (block_kvcache.write_form ``kernel``): no scatter in the program, nothing
    under ``layer.kv_write``, and the kernel, by the name the rooflines match,
    under ``layer.attn``."""
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    assert _scatter_index_rows(compiled) == []
    table = device_scopes.scope_table(compiled.as_text())["ops"]
    assert "layer.kv_write" not in set(table.values())
    kernels = [name for name in table if name.startswith("paged_tkg_decode_attention")]
    assert kernels and {table[name] for name in kernels} == {"layer.attn"}


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_serving_step_does_not_relay_the_block_pool(chip_mesh, program, monkeypatch):
    """The paged KV write leaves the layer scan's cache carry in the layout
    the kernel reads (modules/block_kvcache.update_block_cache_at_layer), at
    the benchmark's widths: 48 slots, 1056 blocks x 32 tokens, 28 layers.

    Both programs: NO pool-shaped copy anywhere. decode (48 x 1): the paged
    decode kernel places the row's token itself, the pools aliased in and out
    of the custom call (block_kvcache.write_form ``kernel``): NO scatter, no
    op under ``layer.kv_write``, the kernel under ``layer.attn``, and the plan
    what the per-head scatter (window ``(D,)``) planned, 7.40 GiB; with the
    head in a scatter's window it held 6 copies (two per layer in the scan,
    two at entry, two at exit) and 3.89 GB of temporaries. chunk (CHUNK_ROWS x 128 = 8 x 128
    whatever the slot count, its rows addressed by slot) writes whole blocks
    (window ``(H, bs, D)``, the pool's minor-most dims: 40 index rows a
    stream a layer where the per-head form had 8192); with the token window
    ``(H, D)`` it held the entry/exit pair for K and for V and a layer's
    slice relaid for the kernel in every layer. chunk plans 7.69 GiB where
    it planned 9.33 with the window scatter (and 11.17 at 48 rows): a row's
    gathered blocks are 2.5 MB and two VMEM slots of a group of blocks cost
    the device's memory nothing."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.ops import kernel_mode

    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)  # as on the chip
    app, params, cache = _abstract_app(
        QWEN3_1P7B_GEOMETRY, chip_mesh(1), batch_size=48, seq_len=8192,
        context_encoding_buckets=[8192], token_generation_buckets=[1024, 8192],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_num_blocks=1056, pa_block_size=32, is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=48),
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(8192, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((48, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    # a model without an expert layer holds what it held before the layer scan
    # learnt to keep expert stacks out of its operands (PR 45): the one
    # attention kernel and every layer's weights as the scan's slices
    assert _custom_calls(compiled) == 1 and "grouped_matmul" not in compiled.as_text()
    in_scan, outside = _pool_copies(compiled, cache.k.shape)
    assert in_scan == 0
    if program == "decode":
        assert outside == 0
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
        assert _planned_bytes(compiled) < 7.41 * 2**30
        _assert_decode_write_is_the_kernels(compiled)
    else:
        # the paged flash kernel copies its blocks by hand out of the
        # STACKED pool (ops/paged_flash_attention.py): no layer's slice is
        # cut out of the carry to feed it, in any layout
        assert "paged_flash_attention" in compiled.as_text()
        assert _pool_copies(compiled, cache.k.shape[1:]) == (0, 0)
        assert outside == 0
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
        assert _planned_bytes(compiled) < 7.9 * 2**30
        _assert_chunk_write_moves_blocks(compiled, 8, 128, 8, cache.k.shape)


# Qwen3-14B's geometry (40/8 heads of 128, 40 layers, hidden 5120, vocab
# 151936) on the llama graph, as benchmark/configs/qwen3-14b-tp4.json serves
# it on four chips: 64 slots, 1024 blocks x 32 tokens, kv 1024/2048/4096
QWEN3_14B_GEOMETRY = dict(
    LLAMA_1B, hidden_size=5120, intermediate_size=17408, num_attention_heads=40,
    num_key_value_heads=8, num_hidden_layers=40, vocab_size=151936,
    head_dim=128, max_position_embeddings=40960,
)


def _cache_gathers(compiled, pool_shape):
    """The all-gathers of the optimized HLO whose result is the block pool
    or one layer's slice of it, at any head count: what a paged kernel or a
    per-head scatter handed a head-sharded pool BARE costs."""
    _, nb1, _, bs, d = pool_shape
    want = re.compile(rf"= \w+\[(\d+,)?{nb1},\d+,{bs},{d}\]\S* all-gather(-start)?\(")
    return [line for line in compiled.as_text().splitlines() if want.search(line)]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_tp4_paged_serving_step_runs_its_kernels_per_shard(chip_mesh, program):
    """The four-chip cell's two step programs (qwen3-14b-tp4.chat) on a 2x2
    mesh: the paged kernels and the paged KV write run once per head shard
    (parallel/sharding.shard_over_heads), so the PARTITIONED executable holds
    the custom call, each chip's 2 of the 8 kv heads stay where they are (no
    all-gather of anything pool-shaped), and the layer scan's cache carry is
    in the kernel's layout.

    Both programs: NO copy of a chip's pool slice anywhere, in any shape,
    and next to no temporaries. decode (64 x 1, the write in each shard's
    kernel, a chip's 2 heads of the pools aliased through it): native
    attention over 64 rows x the kv bucket planned 12.13 GiB a chip (PERF.md,
    PR 26), this plans under 8.5; with the write in its token-window form
    (what a sharded head axis took before) the scan's body held two such
    copies per layer. chunk (8 x 128): each shard writes its 2 heads of a
    row's blocks whole (window ``(2, bs, D)``); under a token window of 2
    heads, fewer than the tile's 8 sublanes (block_kvcache.WINDOW_MIN_HEADS),
    the scan's body re-laid the pool FOUR times per layer as
    ``bf16[L*(NB+1)*bs, 2, 128]`` (~360 ms a dispatch on the chip, PERF.md
    PR 33), which a search for the pool's own shape does not see."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig

    app, params, cache = _abstract_app(
        QWEN3_14B_GEOMETRY, chip_mesh(4), batch_size=64, seq_len=4096, tp_degree=4,
        context_encoding_buckets=[4096], token_generation_buckets=[1024, 2048, 4096],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_num_blocks=1024, pa_block_size=32, is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=64),
        # the auto gates ask jax.default_backend(), which is the CPU here
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(4096, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((64, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    kernel = "paged_tkg_decode_attention" if program == "decode" else "paged_flash_attention"
    assert _custom_calls(compiled) >= 1 and kernel in compiled.as_text()
    assert not _cache_gathers(compiled, cache.k.shape)
    L, nb1, heads, bs, d = cache.k.shape
    assert _pool_copies(compiled, (L, nb1, heads // 4, bs, d)) == (0, 0)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    assert _planned_bytes(compiled) < 8.5 * 2**30
    if program == "chunk":
        _assert_chunk_write_moves_blocks(compiled, 8, 128, heads // 4, (L, nb1, heads // 4, bs, d))
    else:
        _assert_decode_write_is_the_kernels(compiled)


def test_tp4_contiguous_decode_step_runs_its_kernel_per_shard(chip_mesh):
    """``generate()``'s decode step (contiguous cache) at tp = 4: the TKG
    kernel shares the paged one's gate, so it shares the per-shard launch."""
    app, params, cache = _abstract_app(
        LLAMA_1B, chip_mesh(4), batch_size=4, seq_len=512, tp_degree=4,
        context_encoding_buckets=[128], token_generation_buckets=[512],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    tkg = app.token_generation_model
    compiled = _compile_step(app, tkg, tkg.example_inputs(512), params, cache)
    assert _custom_calls(compiled) >= 1 and "tkg_decode_attention" in compiled.as_text()
    assert "all-reduce" in compiled.as_text()


# ---------------------------------------------------------------------------
# granite-4.0-h-micro: state-space layers beside paged attention
# ---------------------------------------------------------------------------


def _abstract_hybrid_app(mesh, config="granite-4.0-h-micro"):
    """A benchmark configuration whose layers keep per-slot state
    (benchmark/configs/<config>.json: the published widths, its slots and
    pool) over a described chip, with params and the hybrid cache as
    ShapeDtypeStructs."""
    import json
    import os

    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig, TpuConfig
    from neuronx_distributed_inference_tpu.models import get_model_builder
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        HybridBlockCache,
        init_block_cache,
    )
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        file = json.load(f)
    meta = {"name", "source", "deployment", "assumed", "reduced", "tpu_config",
            "chunked_prefill", "why", "rehearsal", "notes", "memory", "reference",
            "weights", "probe_tpu_config"}
    attrs = {k: v for k, v in file.items() if k not in meta}
    tc = TpuConfig(
        **file["tpu_config"],
        chunked_prefill_config=ChunkedPrefillConfig(**file["chunked_prefill"]),
        # the auto gates ask jax.default_backend(), which is the CPU here
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    cls = get_model_builder(attrs["model_type"]).config_cls
    cfg = cls(tc, load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])
    app = TpuModelForCausalLM(None, cfg, mesh=mesh)
    b = app.builder

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, P()))

    def cache():
        pool = init_block_cache(app.paged_layers, tc.pa_num_blocks, tc.pa_block_size,
                                b.gqa.kv_heads, b.head_dim, dtype=jnp.bfloat16)
        return HybridBlockCache(k=pool.k, v=pool.v, state=b.init_slot_state(tc.batch_size)[0])

    params = jax.tree.map(place, jax.eval_shape(b.random_params))
    return app, params, jax.tree.map(place, jax.eval_shape(cache))


def _copies_of(compiled, dtype, shape):
    want = dtype + "[" + ",".join(str(d) for d in shape) + "]"
    return [
        line for line in compiled.as_text().splitlines()
        if re.search(r"= " + re.escape(want) + r"\{[^}]*\} copy\(", line)
    ]


def _loops_under(text, scope):
    """The ``while`` ops of a compiled program traced under the named scope
    (a ``lax.scan`` inside a layer; the stack's own loop over layers carries
    no layer scope)."""
    return [line for line in text.splitlines() if " while(" in line and scope + "/" in line]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_hybrid_serving_step_holds_no_copy_of_the_recurrent_state(chip_mesh, program):
    """granite-4.0-h-micro at the benchmark's widths (48 slots, 36 x 96 MiB of
    float32 state, a pool of 2048 blocks over the 4 attention layers), both
    step programs compiled for a described v5e.

    The pool holds its 8 KV heads of 64 two a 128-lane row, ``(4, 2049, 4, 32,
    128)`` (``block_kvcache.kv_streams``): the chip's own layout of it is the
    row-major one the paged kernels ask for, and NEITHER program holds a copy
    of the pool's size (four a program at ``(.., 8, 32, 64)``, 0.83 s of a 6 s
    slice: PERF.md PR 65), in any shape it may have been bitcast to.

    decode: ``ssm_state_update`` (state aliased in and out, one layer's tiles
    visited, 32 heads a tile as the tuning table gives this shape: ``y`` leaves
    as 2 lane-dense rows of 32 x 64 a slot) is in the executable, there is NO
    copy of the state's shape, the paged KV write is the decode kernel's own
    (no scatter in the program, nothing under ``layer.kv_write``), and the
    temporaries are 0.063 GB where the four copies of the 0.27 GB pool made
    them 1.1: under 0.1 GB, where one copy of the state is 3.6 GB. chunk (8
    rows, their state gathered and written back by ``seq_ids`` a layer): no
    copy of the state either — not of the whole, not of one layer's (48, 64,
    64, 128) slice — its KV write moves whole blocks, and the whole program
    (arguments + temporaries) plans 10.31 GiB where it planned 11.59 with the
    copies (the issue's bound for 48 slots was 14.75)."""
    app, params, cache = _abstract_hybrid_app(chip_mesh(1))
    assert cache.k.shape == (4, 2049, 4, 32, 128) and cache.state.ssm.shape[:2] == (36, 48)
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(1024, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((48, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    text = compiled.as_text()
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape)
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape[1:])
    assert _pool_copies(compiled, cache.k.shape) == (0, 0)
    mem = compiled.memory_analysis()
    if program == "decode":
        assert "ssm_state_update" in text and "paged_tkg_decode_attention" in text
        assert "f32[48,2,1,2048]" in text  # the kernel's y at the tile taken
        _assert_decode_write_is_the_kernels(compiled)
        assert mem.temp_size_in_bytes < 0.1e9
    else:
        assert "paged_flash_attention" in text and "ssm_state_update" not in text
        _assert_chunk_write_moves_blocks(compiled, 8, 128, 4, cache.k.shape)
        assert _planned_bytes(compiled) < 10.6 * 2**30


# ---------------------------------------------------------------------------
# zaya1-8b: a one-token carry per slot beside paged KV, top-1 experts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_zaya_serving_step_runs_the_paged_kernels_and_fits_the_chip(chip_mesh, program, monkeypatch):
    """zaya1-8b at the benchmark's widths (benchmark/configs/zaya1-8b.json: 16
    experts, the whole vocabulary, 20 layers, 48 slots, 2048 blocks), both
    step programs compiled for a described v5e: the pool spans all 20 layers
    at (2, 128) a token beside a (20, 48, 2688) carry; the decode program
    holds ``paged_tkg_decode_attention`` and the 8-row chunk program
    ``paged_flash_attention`` (two KV heads a device; its KV write moves
    whole blocks, the decode program's is the decode kernel's own: no copy of
    the pool in the layer scan), and each plans under 14.75 GiB of the chip's 15.75. The
    chunk program's expert products are ``grouped_matmul`` on the scanned
    stacks in place (no ``ragged-dot``, nothing of the shape of a layer's 16
    x 2048 x 2048 stack); the decode program keeps its batched products."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode

    # the gate asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_hybrid_app(chip_mesh(1), "zaya1-8b")
    assert cache.k.shape == (20, 2049, 2, 32, 128) and cache.state.last.shape == (20, 48, 2688)
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(2048, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((48, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    kernel = "paged_tkg_decode_attention" if program == "decode" else "paged_flash_attention"
    assert kernel in text and _custom_calls(compiled) >= 1
    assert _pool_copies(compiled, cache.k.shape)[0] == 0
    if program == "chunk":
        _assert_chunk_write_moves_blocks(compiled, 8, 128, 2, cache.k.shape)
    else:
        _assert_decode_write_is_the_kernels(compiled)
    _assert_expert_products(compiled, program, [(16, 2048, 2048)])
    mem = compiled.memory_analysis()
    print(f"\nzaya1-8b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# sdar-30b-a3b: a decode step that fills a block of 4 positions, top-8 of 128 experts
# ---------------------------------------------------------------------------


def _abstract_paged_app(mesh, config):
    """A benchmark configuration with a plain paged cache
    (benchmark/configs/<config>.json: the published widths, its slots and
    pool) over a described chip, params and pool as ShapeDtypeStructs."""
    import json
    import os

    from benchmark.harness import system
    from neuronx_distributed_inference_tpu.modules.block_kvcache import init_block_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        file = json.load(f)
    # the auto gates ask jax.default_backend(), which is the CPU here
    file["tpu_config"].update(attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)
    app = system.build_app(file, mesh.devices.ravel().tolist(), 0)
    tc, b = app.config.tpu_config, app.builder

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, P()))

    def cache():
        return init_block_cache(app.paged_layers, tc.pa_num_blocks, tc.pa_block_size,
                                dtype=jnp.bfloat16, streams=b.cache_streams())

    params = jax.tree.map(place, jax.eval_shape(b.random_params))
    return app, params, jax.tree.map(place, jax.eval_shape(cache))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_sdar_block_step_runs_the_paged_kernels_and_fits_the_chip(chip_mesh, program, monkeypatch):
    """sdar-30b-a3b at the benchmark's widths (benchmark/configs/sdar-30b-a3b.json:
    128 experts, the whole vocabulary, 6 of 48 layers, 48 slots, 2048 blocks),
    both step programs compiled for a described v5e at kv bucket 2048: the
    block step is (48, 4) and holds ``paged_tkg_decode_attention`` (K = 4: 32
    query rows a KV head), the 8-row chunk program ``paged_flash_attention``
    (under the block frontier; its KV write moves whole blocks); neither
    copies the pool, and each plans under 14.75 GiB of the chip's 15.75. The
    chunk program's expert products are ``grouped_matmul`` on the scanned
    stacks in place (8192 sorted rows over 128 experts); the block step (S =
    4) keeps its batched products over every expert."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode

    # the gate asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_paged_app(chip_mesh(1), "sdar-30b-a3b")
    assert cache.k.shape == (6, 2049, 4, 32, 128) and app.spec.block_step.block_length == 4
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(2048, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((48, 4) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    kernel = "paged_tkg_decode_attention" if program == "decode" else "paged_flash_attention"
    assert kernel in text and _custom_calls(compiled) >= 1
    assert _pool_copies(compiled, cache.k.shape)[0] == 0
    if program == "chunk":
        _assert_chunk_write_moves_blocks(compiled, 8, 128, 4, cache.k.shape)
    else:  # a block step (S = 4) keeps the per-head scatter: 48 x 4 x 4 index rows a stream
        assert _scatter_index_rows(compiled) == [768, 768]
    _assert_expert_products(compiled, program, [(128, 2048, 768), (128, 768, 2048)])
    mem = compiled.memory_analysis()
    print(f"\nsdar-30b-a3b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# kimi-vl-a3b: a pool of compressed latents, a dense layer before top-6 of 64 experts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_kimi_serving_step_runs_the_latent_kernels_and_fits_the_chip(chip_mesh, program, monkeypatch):
    """kimi-vl-a3b at the benchmark's widths (benchmark/configs/kimi-vl-a3b.json:
    64 experts, the whole vocabulary, 1 dense + 6 expert layers, 64 slots,
    12288 blocks), both step programs compiled for a described v5e at kv
    bucket 8192: the pool is a latent stream of 512 and a rotary-key stream
    packed two tokens a 128-lane row, 1152 B a token a layer and no lane of
    padding; the decode program (64 x 1) holds ``paged_latent_decode_attention``
    and the 8-row chunk program ``paged_latent_flash_attention``, one call a
    layer group; neither copies the pool, and each plans under 14.75 GiB of
    the chip's 15.75. The chunk program's six expert layers take the grouped
    strategy (64 / 6: dense before PR 45), their products ``grouped_matmul``
    on the expert group's stacks in place, indexed from the group's first
    layer; the decode program keeps its batched products."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode, latent_attention

    # the gates ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_paged_app(chip_mesh(1), "kimi-vl-a3b")
    assert cache.k.shape == (7, 12289, 1, 32, 512) and cache.v.shape == (7, 12289, 1, 16, 128)
    assert (cache.k.size + cache.v.size) * 2 == 7 * 12289 * 32 * 1152
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(8192, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((64, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    kernel = "paged_latent_decode_attention" if program == "decode" else "paged_latent_flash_attention"
    # the dense group's scan and the expert group's, whose chunk program adds the three products
    assert kernel in text and _custom_calls(compiled) == (5 if program == "chunk" else 2)
    _assert_expert_products(compiled, program, [(64, 2048, 1408), (64, 1408, 2048)])
    assert _pool_copies(compiled, cache.k.shape)[0] == 0 and _pool_copies(compiled, cache.v.shape)[0] == 0
    mem = compiled.memory_analysis()
    print(f"\nkimi-vl-a3b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# ---------------------------------------------------------------------------
# nemotron-3-nano-30b-a3b: single-part blocks of three kinds, 64 of 128 experts held
# ---------------------------------------------------------------------------


def _work_under_no_scope(compiled):
    """Kernels and matrix products of a layer loop's body that stand under no
    scope of the vocabulary (``layer.other`` is the loops' own bookkeeping:
    tuple elements, index arithmetic, the rows' state gathered and put back)."""
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    table = device_scopes.scope_table(compiled.as_text())["ops"]
    work = ("convolution", "dot", "grouped_matmul", "ssm_state_update", "kda_state_update",
            "power_state_update", "paged_", "reduce")
    return sorted(name for name, scope in table.items()
                  if scope == device_scopes.LAYER_OTHER and name.startswith(work))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_nemotron_serving_step_runs_its_kernels_in_place_and_fits_the_chip(
        chip_mesh, program, monkeypatch):
    """nemotron-3-nano-30b-a3b at the benchmark's widths (benchmark/configs/
    nemotron-3-nano-30b-a3b.json: MEMEM*EMEMEM*, 64 of 128 experts held, the
    whole vocabulary, 64 slots, 12288 blocks over the 2 attention blocks),
    both step programs compiled for a described v5e at kv bucket 8192.

    decode (64 x 1): ``ssm_state_update`` with 8 groups of B/C under
    ``layer.ssm`` (state aliased in and out: no copy of the state's shape,
    whole or one block's; 32 heads a tile, four whole groups, as the tuning
    table gives this shape) and ``paged_tkg_decode_attention``; the experts are
    the batched products over the 64 held. chunk (8 x 128):
    ``paged_flash_attention`` and the TWO grouped products of a two-matrix
    expert as ``grouped_matmul`` on the stacks in place under
    ``layer.moe.experts``; no ``ragged-dot``, nothing of the shape of a block's
    (64, 1856, 2688) stack. Seven block bodies for thirteen blocks
    (granite_hybrid.layer_plan). Each plans under 14.75 GiB."""
    from neuronx_distributed_inference_tpu.models.granite_hybrid import layer_plan
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    # the gate asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_hybrid_app(chip_mesh(1), "nemotron-3-nano-30b-a3b")
    assert cache.k.shape == (2, 12289, 2, 32, 128)
    assert cache.state.ssm.shape == (6, 64, 64, 64, 128) and cache.state.conv.shape == (6, 3, 64, 6144)
    experts = params["layers"]["moe"]["mlp"]["experts"]
    assert experts["up_proj"]["weight"].shape == experts["down_proj"]["weight"].shape == (5, 64, 1856, 2688)
    assert params["layers"]["moe"]["mlp"]["router"]["weight"].shape == (5, 2688, 128)
    assert sum(len(runs) for _, runs, _ in layer_plan(app.builder.layer_types)) == 7
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(8192, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((64, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    table = device_scopes.scope_table(text)["ops"]
    assert "ragged-dot" not in text
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape)
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape[1:])
    assert _pool_copies(compiled, cache.k.shape)[0] == 0
    gmm = [name for name in table if name.startswith("grouped_matmul")]
    ssm = [name for name in table if name.startswith("ssm_state_update")]
    if program == "decode":
        assert "paged_tkg_decode_attention" in text and not gmm
        # one kernel call a state-space body of the plan: (ME) x 2, M *, (EM) x 3
        assert len(ssm) == 3 and {table[c] for c in ssm} == {"layer.ssm"}
        assert "f32[64,2,1,2048]" in text  # the kernel's y at the tile taken
    else:
        assert "paged_flash_attention" in text and not ssm
        assert not _stack_shaped(compiled, [(64, 1856, 2688)]), _stack_shaped(compiled, [(64, 1856, 2688)])[:3]
        # two products a routed-expert body: (ME) x 2, (EM) x 3
        assert len(gmm) == 4 and {table[c] for c in gmm} == {"layer.moe.experts"}
    assert not _work_under_no_scope(compiled), _work_under_no_scope(compiled)[:5]
    # decode 9.513 GiB (arguments 9.484: weights 7.97 + state 0.76 + pool 0.75), chunk 9.777
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (0.1 if program == "decode" else 0.4) * 2**30
    print(f"\nnemotron-3-nano-30b-a3b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# ---------------------------------------------------------------------------
# ouro-2.6b: 48 layers run 4 times over one set of weights, a pool of 192 streams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_ouro_serving_step_loops_one_layer_body_over_the_pool_in_place(chip_mesh, program, monkeypatch):
    """ouro-2.6b at the benchmark's widths (benchmark/configs/ouro-2.6b.json:
    nothing cut, 8 slots, 192 blocks), both step programs compiled for a
    described v5e at kv bucket 2048. The pool spans 4 x 48 = 192 streams of 16
    KV heads (48 MiB a block of 32 tokens, 9.05 GiB in all) and rides the
    carry of BOTH scans, also through the decode kernel that now writes it
    (aliased in and out at cache index ``t * L + l``): no copy of the pool's
    shape, in a loop body or at the entry. One compiled layer body (the loop is a scan, not an unrolling): the
    paged kernel is in the program ONCE: ``paged_tkg_decode_attention`` at 16
    KV heads and one query head a KV head in the decode program (8 x 1),
    ``paged_flash_attention`` in the chunk program (8 x 128). The norms the
    loop adds stand under ``layer.post_norm`` and ``loop.norm``. Each plans
    under 14.75 GiB of the chip's 15.75 (208 blocks would plan 14.77)."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    # the gate asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_paged_app(chip_mesh(1), "ouro-2.6b")
    assert app.spec.loop_steps == 4 and app.paged_layers == 192
    assert cache.k.shape == cache.v.shape == (192, 193, 16, 32, 128)
    assert params["layers"]["mlp"]["gate_proj"]["weight"].shape == (48, 2048, 5632)  # ONE set of weights
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(2048, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((8, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    text = compiled.as_text()
    kernel = "paged_tkg_decode_attention" if program == "decode" else "paged_flash_attention"
    assert kernel in text and _custom_calls(compiled) == 1
    assert _pool_copies(compiled, cache.k.shape) == (0, 0)
    if program == "chunk":
        _assert_chunk_write_moves_blocks(compiled, 8, 128, 16, cache.k.shape)
    scopes = set(device_scopes.scope_table(text)["ops"].values())
    assert {"layer.post_norm", "loop.norm", "layer.attn", "layer.mlp"} <= scopes
    if program == "chunk":
        assert "layer.kv_write" in scopes
    else:
        _assert_decode_write_is_the_kernels(compiled)
    assert not _work_under_no_scope(compiled), _work_under_no_scope(compiled)[:5]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1 * 2**30
    print(f"\nouro-2.6b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# ---------------------------------------------------------------------------
# glm-5: a pool of three streams a token, an indexer's top-2048 before latent attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_glm5_serving_step_selects_then_attends_and_fits_the_chip(chip_mesh, program, monkeypatch):
    """glm-5 at the benchmark's widths (benchmark/configs/glm-5.json: 64
    heads, 32 index heads, 16 of 256 experts held, an eighth of the
    vocabulary, 1 dense + 4 expert layers, 32 slots, 16768 blocks), both step
    programs compiled for a described v5e at the widest kv bucket, 16896:
    the pool is three streams (latent 512, rotary key packed two tokens a
    128-lane row, indexer key 128: 1408 B a token a layer, no lane of
    padding); past ``index_topk`` neither program attends every live token:
    each runs its latent kernel under the selection's predicate, under
    ``layer.attn`` (the decode program the decode kernel, the chunk program
    the chunk kernel over a row's live block groups: no row's bucket of
    latents is gathered, ``(8, 16896, 512)``, and its temporaries stand under
    the 0.978 GiB the dense walk planned, the kernel's VMEM request under
    the chip's), the three scopes of the mechanism hold work, no program
    copies a stream of the pool, and each plans under 14.75 GiB of the
    chip's 15.75. The indexer in front of it reads its keys where they lie:
    ``paged_index_scores`` under ``layer.indexer`` in both programs, and no
    gathered copy of the bucket's index keys, ``bf16[B, 16896, 128]``. At a
    kv bucket of ``index_topk`` the same programs hold the dense latent
    kernels and no indexer score."""
    from neuronx_distributed_inference_tpu.ops import index_scores, kernel_mode, latent_attention
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    # the gates ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(index_scores, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_paged_app(chip_mesh(1), "glm-5")
    assert cache.k.shape == (5, 16769, 1, 32, 512) and cache.v.shape == (5, 16769, 1, 16, 128)
    assert [x.shape for x in cache.extra] == [(5, 16769, 1, 32, 128)]
    assert (cache.k.size + cache.v.size + cache.extra[0].size) * 2 == 5 * 16769 * 32 * 1408
    tkg = app.token_generation_model
    q = 128 if program == "chunk" else None
    inputs = tkg.example_inputs(16896, q_len=q)
    assert inputs.input_ids.shape == ((32, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    kernel = "paged_latent_decode_attention" if program == "decode" else "paged_latent_flash_attention"
    table = device_scopes.scope_table(text)["ops"]
    assert {scope for name, scope in table.items() if name.startswith(kernel)} == {"layer.attn"}
    assert {"layer.indexer", "layer.select", "layer.attn", "layer.kv_write"} <= set(table.values())
    scorer = index_scores.KERNEL
    assert {scope for name, scope in table.items() if name.startswith(scorer)} == {"layer.indexer"}
    assert not re.search(r"bf16\[(8|32),16896,128\]", text)  # the index keys stay in the pool
    for stream in (cache.k, cache.v, cache.extra[0]):
        assert _pool_copies(compiled, stream.shape)[0] == 0
    mem = compiled.memory_analysis()
    if program == "chunk":
        # the selection rides the kernel: no gathered bucket of latents, no
        # float32 scores of a row's bucket, less planned than the dense walk
        assert not re.search(r"bf16\[8,16896,512\]|f32\[64,128,16896\]", text)
        assert mem.temp_size_in_bytes < 0.978 * 2**30
        asked = [int(x) for x in re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"([0-9]+)"', text)]
        assert asked and max(asked) <= 100 * 2**20  # of the chip's 128 MiB of VMEM
    print(f"\nglm-5 {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30
    # at index_topk the selection is everything: dense latent attention, as it was
    dense = _compile_step(app, tkg, tkg.example_inputs(2048, q_len=q), params, cache).as_text()
    dense_ops = device_scopes.scope_table(dense)["ops"]
    assert kernel in dense and not any(name.startswith(scorer) for name in dense_ops)
    assert "layer.select" not in set(dense_ops.values())


# ---------------------------------------------------------------------------
# mellum2-12b-a2.5b: window and full attention mixed, a paged cache of two lifetimes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_mellum_serving_step_walks_each_kinds_keys_and_fits_the_chip(chip_mesh, program, monkeypatch):
    """mellum2-12b-a2.5b at the benchmark's widths (benchmark/configs/
    mellum2-12b-a2.5b.json: 64 experts top-8, the whole vocabulary, 8 of 28
    layers = two periods [window, window, window, full], 48 slots), both step
    programs compiled for a described v5e at the widest kv bucket, 16384: the
    cache has two lifetimes (a pool of 24576 blocks over the 2 full layers, a
    ring of 37 blocks a slot over the 6 window layers); each RUN of like
    layers ([W, W, W], [F], [W, W, W], [F]) is one scan over its own stacked
    weights whose body runs the paged kernel of the program under its kind's
    scope inside ``layer.attn`` (the decode kernel places the token
    too: no scatter, nothing under ``layer.kv_write``; the chunk kernel under
    a window's lower frontier: no mask of the bucket's width is built);
    neither copies a pool, and each plans under 14.75 GiB of the chip's
    15.75."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    # the gate asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_hybrid_app(chip_mesh(1), "mellum2-12b-a2.5b")
    assert cache.k.shape == (2, 24577, 4, 32, 128)
    assert cache.state.k.shape == cache.state.v.shape == (6, 48 * 37 + 1, 4, 32, 128)
    assert cache.state.ring_blocks == 37 and app.builder.cache_layers().count("window_kv") == 6
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(16384, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((48, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    kernel = "paged_tkg_decode_attention" if program == "decode" else "paged_flash_attention"
    table = device_scopes.scope_table(text)
    calls = [name for name in table["ops"] if name.startswith(kernel)]
    # one body a run of like layers: four calls of the kernel, each named by its kind
    assert len(calls) == 4 and {table["ops"][c] for c in calls} == {"layer.attn"}
    assert sorted(table["kinds"][c] for c in calls) == ["layer.attn.full"] * 2 + ["layer.attn.window"] * 2
    assert set(table["kinds"].values()) == {"layer.attn.window", "layer.attn.full"}
    assert all(table["ops"][name] == "layer.attn" for name in table["kinds"])
    for pool in (cache.k, cache.state.k):
        assert _pool_copies(compiled, pool.shape)[0] == 0
    if program == "decode":
        _assert_decode_write_is_the_kernels(compiled)
    else:
        # the window rides the kernel as a frontier: no (rows, queries, bucket) mask, no
        # gathered bucket of keys
        assert not re.search(r"\[8,(1,)?128,16384\]|\[8,16384,4,128\]", text)
        assert "layer.kv_write" in set(table["ops"].values())
    stacks = [(64, 2304, 896), (64, 896, 2304)]
    if program == "decode":
        _assert_expert_products(compiled, program, stacks)
    else:
        # the grouped-matmul kernel on a run's stacked weights in place, three products a
        # body, no instruction of the shape of a layer's expert stack
        grouped = [name for name in table["ops"] if name.startswith("grouped_matmul")]
        assert len(grouped) == 12 and {table["ops"][c] for c in grouped} == {"layer.moe.experts"}
        assert not _stack_shaped(compiled, stacks) and "ragged-dot" not in text
    if program == "chunk":
        # the narrow chunk passes: XLA's own expert products (16 and 32 positions a row)
        # copy no stack of expert weights, and 16 positions x 8 q heads a KV head over the
        # widest bucket fit the decode kernel's VMEM (it asks for its 18 MiB mask slab)
        for q in (32, 16):
            narrow = _compile_step(app, tkg, tkg.example_inputs(16384, q_len=q), params, cache)
            assert narrow.memory_analysis().temp_size_in_bytes < 2.5 * 2**30, q
            assert _planned_bytes(narrow) < 14.75 * 2**30
    mem = compiled.memory_analysis()
    print(f"\nmellum2-12b-a2.5b {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# ---------------------------------------------------------------------------
# kimi-linear-48b-a3b: a delta-rule matrix state a head beside a latent pool
# ---------------------------------------------------------------------------


def _abstract_kimi_linear_app(mesh):
    """benchmark/configs/kimi-linear-48b-a3b.json over a described chip: params,
    the latent pool and the per-slot delta-rule state as ShapeDtypeStructs."""
    import json
    import os

    from benchmark.harness import system
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        HybridBlockCache,
        init_block_cache,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "kimi-linear-48b-a3b.json")) as f:
        file = json.load(f)
    # the auto gates ask jax.default_backend(), which is the CPU here
    file["tpu_config"].update(attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)
    app = system.build_app(file, mesh.devices.ravel().tolist(), 0)
    tc, b = app.config.tpu_config, app.builder

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, P()))

    def cache():
        pool = init_block_cache(app.paged_layers, tc.pa_num_blocks, tc.pa_block_size,
                                dtype=jnp.bfloat16, streams=b.cache_streams())
        return HybridBlockCache(k=pool.k, v=pool.v, state=b.init_slot_state(tc.batch_size)[0])

    params = jax.tree.map(place, jax.eval_shape(b.random_params))
    return app, params, jax.tree.map(place, jax.eval_shape(cache))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_kimi_linear_serving_step_updates_the_state_in_place_and_fits_the_chip(
        chip_mesh, program, monkeypatch):
    """kimi-linear-48b-a3b at the benchmark's widths (benchmark/configs/
    kimi-linear-48b-a3b.json: 16 of 27 layers = 12 KDA + 4 MLA, one dense MLP
    and 15 expert layers of 16 held experts of 256, an eighth of the
    vocabulary, 128 slots, 20480 blocks over the 4 MLA layers), both step
    programs compiled for a described v5e at kv bucket 8192.

    The cache is a LATENT pool (512 + 64 numbers a token a layer, the key
    packed two tokens a lane row) beside a per-slot state of (12, 128, 32,
    128, 128) float32 = 3 GiB. decode (128 x 1): ``kda_state_update`` under
    ``layer.kda`` (the state aliased in and out: no copy of the state's
    shape, whole or one layer's) and ``paged_latent_decode_attention``; the
    experts are the batched products over the 16 held. chunk (8 x 128):
    ``paged_latent_flash_attention``, the chunked delta rule's recurrence
    between sub-chunks as ``kda_chunk_scan`` under ``layer.kda`` on the stacked
    state in place (no ``kda_state_update``, no loop of the scan, no rows'
    state gathered) and the three grouped products of an expert layer as
    ``grouped_matmul`` on the stacks in place. Neither copies the
    pool; each plans under 14.75 GiB."""
    from neuronx_distributed_inference_tpu.models.granite_hybrid import layer_plan
    from neuronx_distributed_inference_tpu.ops import kda_chunk_scan, kernel_mode, latent_attention
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    # the gates ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_kimi_linear_app(chip_mesh(1))
    assert cache.k.shape == (4, 20481, 1, 32, 512) and cache.v.shape == (4, 20481, 1, 16, 128)
    assert cache.state.ssm.shape == (12, 128, 32, 128, 128) and cache.state.conv.shape == (12, 3, 128, 12288)
    experts = params["layers"]["moe"]["mlp"]["experts"]
    assert experts["gate_proj"]["weight"].shape == (15, 16, 2304, 1024)
    assert params["layers"]["moe"]["mlp"]["router"]["weight"].shape == (15, 2304, 256)
    assert params["lm_head"]["weight"].shape == (2304, 20480)
    bodies = sum(len(runs) for _, runs, _ in layer_plan(app.builder.layer_types))
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(8192, q_len=128 if program == "chunk" else None)
    assert inputs.input_ids.shape == ((128, 1) if program == "decode" else (8, 128))
    compiled = _compile_step(app, tkg, inputs, params, cache)
    _assert_experts_sort_real_positions(tkg, program)
    text = compiled.as_text()
    table = device_scopes.scope_table(text)["ops"]
    assert "ragged-dot" not in text
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape)
    assert not _copies_of(compiled, "f32", cache.state.ssm.shape[1:])
    assert _pool_copies(compiled, cache.k.shape)[0] == 0 and _pool_copies(compiled, cache.v.shape)[0] == 0
    gmm = [name for name in table if name.startswith("grouped_matmul")]
    kda = [name for name in table if name.startswith("kda_state_update")]
    scan = [name for name in table if name.startswith(kda_chunk_scan.KERNEL)]
    stacks = [(16, 2304, 1024), (16, 1024, 2304)]
    if program == "decode":
        assert "paged_latent_decode_attention" in text and not gmm
        assert kda and {table[c] for c in kda} == {"layer.kda"} and not scan
        # 128 rows over 16 held experts: the batched form reads the stacks as stored (the
        # unbatched one re-laid both whole stacks, 2 x 1.05 GiB, on every step: modules/moe.py)
        assert not re.search(r"= \w+\[15,16,\d+,\d+\]\S* copy\(", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
    else:
        assert "paged_latent_flash_attention" in text and not kda
        # the recurrence between sub-chunks: the kernel on the stacked state, not the scan
        # over the 8 rows' gathered state (no loop under the layer's scope)
        assert scan and {table[c] for c in scan} == {"layer.kda"}
        assert not _loops_under(text, "layer.kda")
        assert not _stack_shaped(compiled, stacks), _stack_shaped(compiled, stacks)[:3]
        assert gmm and len(gmm) % 3 == 0 and {table[c] for c in gmm} == {"layer.moe.experts"}
    assert not _work_under_no_scope(compiled), _work_under_no_scope(compiled)[:5]
    if program == "chunk":
        # the narrowest chunk pass: 32 heads x 8 positions are 256 score rows of the latent
        # DECODE kernel, whose mask slab over the 8192 bucket asks VMEM for what it holds
        # (refused by 1.1 MiB under the compiler's own limit), and 64 rows of XLA's own
        # expert products copy no stack of expert weights
        narrow = _compile_step(app, tkg, tkg.example_inputs(8192, q_len=8), params, cache)
        assert "paged_latent_decode_attention" in narrow.as_text()
        # and the kernel at one sub-chunk of 8; at this width nothing else has the shape of the
        # 8 rows' state, so: no rows' state gathered, scanned over or selected at all
        assert kda_chunk_scan.KERNEL in narrow.as_text() and not _loops_under(narrow.as_text(), "layer.kda")
        assert "f32[8,32,128,128]" not in narrow.as_text()
        assert not re.search(r"= \w+\[15,16,\d+,\d+\]\S* copy\(", narrow.as_text())
        assert _planned_bytes(narrow) < 14.75 * 2**30
    mem = compiled.memory_analysis()
    print(f"\nkimi-linear-48b-a3b {program}: {bodies} block bodies, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30


# ---------------------------------------------------------------------------
# brumby-14b-base: a power-retention state a KV head in every layer, no pool
# ---------------------------------------------------------------------------


def _abstract_brumby_app(mesh):
    """benchmark/configs/brumby-14b-base.json over a described chip: params, the
    pool of ZERO layers and the per-slot power-retention state as
    ShapeDtypeStructs."""
    import json
    import os

    from benchmark.harness import system
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        HybridBlockCache,
        init_block_cache,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "brumby-14b-base.json")) as f:
        file = json.load(f)
    app = system.build_app(file, mesh.devices.ravel().tolist(), 0)
    tc, b = app.config.tpu_config, app.builder

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, P()))

    def cache():
        pool = init_block_cache(app.paged_layers, 0, tc.pa_block_size,
                                dtype=jnp.bfloat16, streams=b.cache_streams())
        return HybridBlockCache(k=pool.k, v=pool.v, state=b.init_slot_state(tc.batch_size)[0])

    params = jax.tree.map(place, jax.eval_shape(b.random_params))
    return app, params, jax.tree.map(place, jax.eval_shape(cache))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_brumby_serving_step_has_no_pool_and_updates_the_state_in_place(
        chip_mesh, program, monkeypatch):
    """brumby-14b-base at the benchmark's widths (benchmark/configs/
    brumby-14b-base.json: 8 of 40 layers, 40 query heads over 8 KV heads of
    128, the whole vocabulary, 16 slots), both step programs compiled for a
    described v5e at the one kv bucket.

    The cache is a pool of ZERO layers (no byte) beside a per-slot state of
    (8, 16, 8, 8704, 128) float32 = 4.25 GiB and its normaliser. Neither
    program takes the block table (an argument of the traced function that
    nothing reads: jit leaves it out of the executable, whose entry has no
    int32 parameter of the table's shape), neither writes K/V (nothing under
    ``layer.kv_write`` or ``layer.attn``, no scatter), and neither holds a copy
    of the state: not of the stack, not of a layer's slice, not of a slot's
    (8, 8704, 128). decode (16 x 1): ``power_state_update`` under
    ``layer.power``, the state aliased in and out. chunk (8 x 128): the chunked
    form as XLA's own products under ``layer.power`` (no state kernel), the
    live rows' tiles taken from and put back into the stack in place. Each
    plans under 14.75 GiB."""
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.telemetry import device_scopes

    monkeypatch.setattr(kernel_mode, "on_tpu", lambda: True)
    app, params, cache = _abstract_brumby_app(chip_mesh(1))
    assert app.paged_layers == 0 and cache.k.shape[0] == 0 and cache.k.size == 0
    assert cache.state.ssm.shape == (8, 16, 8, 8704, 128) and cache.state.conv.shape == (8, 16, 8, 8704)
    assert params["lm_head"]["weight"].shape == (5120, 151936)
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(8192, q_len=128 if program == "chunk" else None)
    rows = 16 if program == "decode" else 8
    assert inputs.input_ids.shape == ((16, 1) if program == "decode" else (8, 128))
    assert inputs.block_table.shape == (rows, 256)
    compiled = _compile_step(app, tkg, inputs, params, cache)
    text = compiled.as_text()
    entry = text.partition("\nENTRY ")[2].split("\n", 1)[0]
    assert f"s32[{rows},256]" not in entry, "the block table is a parameter of the executable"
    assert f"s32[{rows},8192]" not in entry, "the kv mask is a parameter of the executable"
    table = device_scopes.scope_table(text)["ops"]
    assert not {"layer.kv_write", "layer.attn"} & set(table.values())
    assert {"layer.qkv", "layer.power", "layer.o_proj", "layer.mlp"} <= set(table.values())
    assert " scatter(" not in text
    state = cache.state.ssm.shape
    for shape in (state, state[1:], state[2:]):
        assert not _copies_of(compiled, "f32", shape), _copies_of(compiled, "f32", shape)[:3]
    kernel = [name for name in table if name.startswith("power_state_update")]
    if program == "decode":
        assert kernel and {table[c] for c in kernel} == {"layer.power"}
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
    else:
        assert not kernel and "layer.power" in set(table.values())
    assert not _work_under_no_scope(compiled), _work_under_no_scope(compiled)[:5]
    mem = compiled.memory_analysis()
    print(f"\nbrumby-14b-base {program}: arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"planned {_planned_bytes(compiled) / 2**30:.3f} GiB")
    assert _planned_bytes(compiled) < 14.75 * 2**30
