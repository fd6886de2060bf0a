"""Ask the chip's compiler, without the chip.

``tests/test_tpu_lowering.py`` stops at Mosaic MLIR (``jax.export``): it
cannot see the fast-memory limit, a mis-tiled slice, or a kernel that cannot
be partitioned. Here the main-path kernels at Llama-3.2-1B and 3.1-8B widths,
one whole 1B decode step and the tp=4 ragged mixed step (the ``shard_map``
dispatch) are COMPILED for a described ``v5e:2x2`` chip — the TPU compiler is
installed on the CPU harness and raises what the chip's would. Nothing runs,
so this says nothing about results or times (chip_smoke.py does).

The topology, and everything built from it, lives in module-scoped fixtures:
only one process may load the TPU library, so it must not be touched while
any module is imported. The persistent compile cache is off around the
module — a TPU executable written from here cannot be read back without a
chip, and the next run would warn on every entry.
"""

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
    ("paged_tkg_decode_attention", "kv1024", "bfloat16"),  # serving decode
    ("paged_flash_attention", "sq512", "bfloat16"),  # chunked prefill
    ("ragged_paged_attention", "mixed", "bfloat16"),  # ragged mixed step
    ("ragged_paged_attention", "mixed", "int8"),
    ("quant_matmul", "k4096_n14336", "bfloat16"),  # 8B int4 weights
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
