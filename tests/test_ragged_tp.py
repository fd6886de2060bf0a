"""Sharded ragged mixed-step dispatch (ISSUE 17 tentpole a): tp>1 runs the
Pallas kernel per-shard via shard_map instead of the native gather fallback.

The acceptance pins:
- on a model_parallel=2 virtual mesh the mixed step DISPATCHES the kernel
  (the native fallback never fires) through the shard_map dispatch, with
  the head-parallel operands sharded and descriptors replicated;
- the tp=2 kernel stream is byte-identical to the tp=2 native fallback AND
  to the tp=1 stream for plain and int8-KV configs;
- zero steady-state recompiles at tp=2 with the mixed runner sealed;
- the WHOLE sharded mixed program AOT-lowers for the TPU target from this
  CPU host (shard_map + forced Mosaic kernels + fused quantized scatters).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import make_tiny_config, make_random_hf_state_dict

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

PROMPTS = {
    "r1": [5, 17, 92, 41],
    "r2": list(range(30, 52)),  # 22 tokens: chunks across several steps
    "r3": [7, 7, 7],
}


def _cfg(tp=1, **extra):
    tpu = dict(
        is_continuous_batching=True, batch_size=4, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=24,
        is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(
            max_num_seqs=2, kernel_q_tile_size=16
        ),
        serving_ragged=True, seq_len=64,
    )
    tpu.update(extra)
    # head_dim must be lane-aligned (64) for the ragged gate: 256 over
    # 4 q heads / 2 kv heads — both divide tp=2
    cfg = make_tiny_config(hidden_size=256, intermediate_size=512, tpu=tpu)
    cfg.tpu_config.tp_degree = tp
    return cfg


@pytest.fixture(scope="module")
def state_dict():
    return make_random_hf_state_dict(_cfg())


def _load(cfg, sd):
    return TpuModelForCausalLM(None, cfg).load(state_dict=sd)


def _standard_mix(app):
    app.init_kv_cache()
    sess = ServingSession(app)
    assert sess.add_request("r1", PROMPTS["r1"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r2", PROMPTS["r2"], max_new_tokens=6)
    sess.step()
    assert sess.add_request("r3", PROMPTS["r3"], max_new_tokens=5)
    return sess.run_to_completion()


# ---------------------------------------------------------------------------
# byte-identical streams: tp=2 kernel == tp=2 native == tp=1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("extra", [{}, {"kv_cache_dtype": "int8"}],
                         ids=["plain", "kv_int8"])
def test_tp2_kernel_matches_native_and_tp1(state_dict, extra):
    """attn_kernel_enabled=True forces the ragged kernel (interpret mode on
    CPU — the identical per-shard math hardware compiles); the default auto
    gate takes the native gather on this host. All three greedy streams
    must be byte-identical."""
    out_tp1 = _standard_mix(_load(_cfg(1, **extra), state_dict))
    out_tp2_native = _standard_mix(_load(_cfg(2, **extra), state_dict))
    out_tp2_kernel = _standard_mix(
        _load(_cfg(2, attn_kernel_enabled=True, **extra), state_dict)
    )
    assert all(len(v) > 0 for v in out_tp1.values())
    assert out_tp2_native == out_tp1
    assert out_tp2_kernel == out_tp1


# ---------------------------------------------------------------------------
# the tp=2 mixed step actually dispatches the kernel (no native fallback)
# ---------------------------------------------------------------------------


def test_tp2_dispatches_kernel_over_sharded_mesh(state_dict):
    from neuronx_distributed_inference_tpu.ops import ragged_paged_attention as rpa
    from neuronx_distributed_inference_tpu.parallel.mesh import (
        ALL_AXES,
        ambient_mesh,
    )

    calls = {"dispatch": 0, "native": 0, "degrees": set()}
    orig_dispatch = rpa._dispatch_ragged_kernel
    orig_native = rpa.ragged_attention_native

    def counting_dispatch(*a, **kw):
        calls["dispatch"] += 1
        mesh = ambient_mesh()
        deg = 1
        for ax in ALL_AXES:
            deg *= dict(mesh.shape).get(ax, 1) if mesh is not None else 1
        calls["degrees"].add(deg)
        return orig_dispatch(*a, **kw)

    def counting_native(*a, **kw):
        calls["native"] += 1
        return orig_native(*a, **kw)

    rpa._dispatch_ragged_kernel = counting_dispatch
    rpa.ragged_attention_native = counting_native
    try:
        # the jit cache is process-global and earlier tests compiled this
        # exact program: drop it so the mixed step TRACES inside the patch
        jax.clear_caches()
        out = _standard_mix(
            _load(_cfg(2, attn_kernel_enabled=True), state_dict)
        )
    finally:
        rpa._dispatch_ragged_kernel = orig_dispatch
        rpa.ragged_attention_native = orig_native
    assert all(len(v) > 0 for v in out.values())
    assert calls["dispatch"] > 0  # the kernel dispatch fired
    assert calls["native"] == 0  # the fallback never did
    assert calls["degrees"] == {2}  # over the model-parallel mesh


# ---------------------------------------------------------------------------
# zero steady-state recompiles, sealed, tp=2
# ---------------------------------------------------------------------------


def test_tp2_zero_steady_state_recompiles_sealed(state_dict):
    from neuronx_distributed_inference_tpu.analysis import RetraceGuard

    app = _load(_cfg(2, attn_kernel_enabled=True), state_dict)
    golden = _standard_mix(app)  # warm the mix
    app.mixed_step_model.seal()
    try:
        with RetraceGuard() as guard:
            out = _standard_mix(app)
    finally:
        app.mixed_step_model._sealed = False
    assert out == golden
    assert guard.traces == []  # zero steady-state recompiles at tp=2


# ---------------------------------------------------------------------------
# TPU-target AOT lowering of the WHOLE sharded mixed program
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_lower_sharded_mixed_step_program_tp2():
    """The whole mixed_step program at model_parallel=2 — embed -> layer
    scan with the shard_map'd ragged kernel (forced Mosaic) + fused int8
    scatters -> gather -> lm head — AOT-lowers for the TPU target. This is
    the sharded twin of test_ragged_attention's whole-program export: it
    catches shard_map/Mosaic interactions the per-kernel lowering cannot."""
    from jax import export

    from neuronx_distributed_inference_tpu.models.base import (
        MixedStepInputs,
        mixed_forward,
    )
    from neuronx_distributed_inference_tpu.models.llama import LlamaModelBuilder
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        init_block_cache,
    )
    from neuronx_distributed_inference_tpu.ops.kernel_mode import (
        force_compiled_kernels,
    )
    from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    cfg = make_tiny_config(
        hidden_size=256,
        intermediate_size=512,
        tpu=dict(
            batch_size=4, seq_len=256, dtype="bfloat16",
            is_continuous_batching=True,
            is_block_kv_layout=True, pa_block_size=32, pa_num_blocks=32,
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=2, kernel_q_tile_size=32
            ),
            serving_ragged=True, kv_cache_dtype="int8",
            attn_kernel_enabled=True,
        ),
    )
    cfg.tpu_config.tp_degree = 2
    mesh = mesh_from_config(cfg.tpu_config)
    builder = LlamaModelBuilder(cfg)
    spec = builder.model_spec()
    assert spec.attn.model_parallel == 2
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), builder.random_params()
    )
    cache = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        init_block_cache(
            spec.num_layers, 32, 32, spec.attn.num_kv_heads,
            spec.attn.head_dim, dtype=jnp.int8,
        ),
    )
    R, T, mb = 4, 128, 256 // 32
    inputs = MixedStepInputs(
        input_ids=sds((1, T), jnp.int32),
        position_ids=sds((1, T), jnp.int32),
        slot_mapping=sds((1, T), jnp.int32),
        block_table=sds((R, mb), jnp.int32),
        row_start=sds((R,), jnp.int32),
        row_len=sds((R,), jnp.int32),
        ctx_len=sds((R,), jnp.int32),
        sampling_params=sds((R, 3), jnp.float32),
        chain_src=sds((1, T), jnp.int32),
        chain_tokens=sds((R, 1), jnp.int32),
    )
    fn = functools.partial(mixed_forward, spec=spec)
    with jax.set_mesh(mesh), force_compiled_kernels():
        exp = export.export(jax.jit(fn), platforms=["tpu"])(
            params, cache, inputs, None
        )
    assert exp.platforms == ("tpu",)
