"""Config system tests (reference: test/unit/models/test_config.py)."""

import pytest

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig,
    MoETpuConfig,
    OnDeviceSamplingConfig,
    TpuConfig,
)


def test_defaults_derive():
    tc = TpuConfig(batch_size=4, seq_len=256)
    assert tc.max_batch_size == 4
    assert tc.ctx_batch_size == 4
    assert tc.max_context_length == 256
    assert tc.world_size == 1


def test_world_size():
    tc = TpuConfig(tp_degree=8, ep_degree=2)
    assert tc.world_size == 16


def test_validation_dp_requires_continuous_batching():
    with pytest.raises(ValueError):
        TpuConfig(tp_degree=8, attention_dp_degree=2, is_continuous_batching=False)


def test_validation_cp_divides_tp():
    with pytest.raises(ValueError):
        TpuConfig(tp_degree=8, cp_degree=3)


def test_chunked_prefill_requires_block_kv():
    with pytest.raises(ValueError):
        TpuConfig(is_chunked_prefill=True, is_block_kv_layout=False)


def test_fault_containment_knob_defaults():
    """ISSUE 7: the containment knobs exist, default sane (validation on,
    bounded retries, watchdog armed, no deadline), and round-trip to_dict."""
    tc = TpuConfig()
    assert tc.admission_validation is True
    assert tc.request_deadline_s is None
    assert tc.dispatch_max_retries == 2
    assert tc.watchdog_no_progress_steps == 256
    d = tc.to_dict()
    tc2 = TpuConfig.from_dict(d)
    assert tc2.admission_validation is True
    assert tc2.watchdog_no_progress_steps == 256


#: options that went: the two expert strategies no measurement chose (PR 50),
#: the six reference-only names that were fields only so that setting them
#: raised, the two hardware knobs that did nothing, and the ragged step's two
#: (PR 64: speculation inside it, and its form, which follows async_mode)
GONE_OPTIONS = {
    "capacity_factor": 1.5,
    "moe_fused_kernel_enabled": True,
    "is_eagle_target": True,
    "is_eagle_draft": True,
    "k_cache_transposed": True,
    "rpl_reduce_dtype": "float32",
    "kv_cache_padding_size": 2,
    "weights_to_skip_layout_optimization": ["lm_head"],
    "logical_nc_config": 2,
    "scratchpad_page_size": 1024,
    "serving_spec_ragged": True,
    "serving_ragged_async": False,
}


@pytest.mark.parametrize("how", ["constructor", "from_dict"])
@pytest.mark.parametrize("name", list(GONE_OPTIONS))
def test_an_option_that_went_is_refused_by_name(name, how):
    """A name that is no field is refused BY NAME, live and from a file:
    never accepted and ignored."""
    kwargs = {name: GONE_OPTIONS[name]}
    if how == "constructor":
        with pytest.raises(TypeError, match=name):
            MoETpuConfig(**kwargs)
    else:
        with pytest.raises(ValueError, match=name):
            MoETpuConfig.from_dict(dict(MoETpuConfig().to_dict(), **kwargs))


@pytest.mark.parametrize(
    "kwargs, match",
    [
        (dict(request_deadline_s=0.0), "request_deadline_s"),
        (dict(request_deadline_s=-1.5), "request_deadline_s"),
        (dict(dispatch_max_retries=-1), "dispatch_max_retries"),
        (dict(watchdog_no_progress_steps=-5), "watchdog_no_progress_steps"),
    ],
)
def test_fault_containment_knob_validation(kwargs, match):
    """Rejected-by-validation containment configs fail loudly at
    construction, never mid-serving."""
    with pytest.raises(ValueError, match=match):
        TpuConfig(**kwargs)


def test_router_knob_defaults_and_roundtrip():
    """ISSUE 10: the multi-replica router knobs exist, default to a single
    session with telemetry-driven placement, and round-trip to_dict."""
    tc = TpuConfig()
    assert tc.serving_replicas == 1
    assert tc.router_policy == "least_loaded"
    tc2 = TpuConfig.from_dict(tc.to_dict())
    assert tc2.serving_replicas == 1
    assert tc2.router_policy == "least_loaded"
    ok = TpuConfig(is_continuous_batching=True, serving_replicas=2,
                   router_policy="round_robin")
    assert ok.serving_replicas == 2


@pytest.mark.parametrize(
    "kwargs, match",
    [
        (dict(serving_replicas=0), "serving_replicas"),
        (dict(serving_replicas=-2), "serving_replicas"),
        (dict(router_policy="fastest"), "router_policy"),
        (dict(serving_replicas=2), "is_continuous_batching"),
    ],
)
def test_router_knob_validation(kwargs, match):
    with pytest.raises(ValueError, match=match):
        TpuConfig(**kwargs)


def test_json_round_trip(tmp_path, tiny_config):
    tiny_config.tpu_config.on_device_sampling_config = OnDeviceSamplingConfig(
        do_sample=True, top_k=5
    )
    tiny_config.save(str(tmp_path))
    loaded = InferenceConfig.load(str(tmp_path))
    assert type(loaded).__name__ == "LlamaInferenceConfig"
    assert loaded.hidden_size == tiny_config.hidden_size
    assert loaded.tpu_config.on_device_sampling_config.top_k == 5
    assert loaded.tpu_config.batch_size == tiny_config.tpu_config.batch_size


def test_attribute_map():
    tc = TpuConfig()
    cfg = InferenceConfig(tc, n_positions=42)
    cfg.attribute_map = {"max_len_alias": "n_positions"}
    assert cfg.max_len_alias == 42
    cfg.max_len_alias = 99
    assert cfg.n_positions == 99


def _presharded_roundtrip(tmp_path, **tpu_kwargs):
    """Shared harness: build + load + compile(path) an app, then restore a
    FRESH app from the artifact (model_path=None: a restore failure would
    fall back to random weights and break the token comparison). Returns
    (restored_app, reference_sequences, restored_sequences)."""
    import numpy as np

    from tests.conftest import make_tiny_config, make_random_hf_state_dict
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
        load_model,
    )

    cfg = make_tiny_config(tpu=dict(save_sharded_checkpoint=True, **tpu_kwargs))
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=sd)
    path = str(tmp_path / "artifact")
    app.compile(path)
    ids = np.array([[1, 2, 3, 4]])
    ref = app.generate(ids, np.ones_like(ids), max_new_tokens=6).sequences

    import os

    assert os.path.exists(os.path.join(path, "presharded", "manifest.pkl"))
    app2 = load_model(path)
    out = app2.generate(ids, np.ones_like(ids), max_new_tokens=6).sequences
    return app2, ref, out


@pytest.mark.slow
def test_presharded_save_load_roundtrip(tmp_path):
    """save_sharded_checkpoint: compile() writes a presharded weight artifact
    and a fresh app restores it WITHOUT re-running checkpoint conversion
    (reference application_base.py:240-265)."""
    import numpy as np

    _, ref, out = _presharded_roundtrip(tmp_path, tp_degree=2)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.slow
def test_presharded_quantized_roundtrip(tmp_path):
    """Quantized params (int8 weights + scale leaves) round-trip through the
    presharded artifact — restore must skip BOTH conversion and
    re-quantization."""
    import jax.numpy as jnp
    import numpy as np

    # tp_degree=2: also exercises the sharded quantized-SCALE restore path
    app2, ref, out = _presharded_roundtrip(tmp_path, quantized=True, tp_degree=2)
    # int8 weights + scales restored (not re-derived)
    w = app2.params["layers"]["self_attn"]["q_proj"]
    assert w["weight"].dtype == jnp.int8 and "scale" in w
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# a model whose layers keep a constant-size per-slot state (state-space
# layers: models/granite_hybrid.py) refuses what cannot serve that state
# ---------------------------------------------------------------------------

_HYBRID_ATTRS = dict(
    model_type="granitemoehybrid", hidden_size=64, shared_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    layer_types=["mamba", "attention"], vocab_size=512, attention_multiplier=0.125,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    position_embedding_type="nope", num_local_experts=0, tie_word_embeddings=True,
)


def _hybrid_config(**tpu):
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.models import get_model_builder

    opts = dict(
        batch_size=2, seq_len=128, is_continuous_batching=True, ctx_batch_size=1,
        is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=16, is_chunked_prefill=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=16),
    )
    opts.update(tpu)
    cls = get_model_builder("granitemoehybrid").config_cls
    return cls(TpuConfig(**opts),
               load_config=lambda c: [setattr(c, k, v) for k, v in _HYBRID_ATTRS.items()])


def test_slot_state_model_accepts_the_paged_chunked_path():
    cfg = _hybrid_config()
    assert cfg.layer_types == ["mamba", "attention"]


@pytest.mark.parametrize("tpu,match", [
    (dict(is_prefix_caching=True), "is_prefix_caching"),
    (dict(serving_ragged=True), "serving_ragged"),
    (dict(speculation_length=4), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(tp_degree=2), "degree > 1"),
], ids=["prefix_caching", "ragged", "speculation", "kv_quant", "tp2"])
def test_slot_state_model_refuses_what_cannot_serve_its_state(tpu, match):
    from neuronx_distributed_inference_tpu.config import SlotStateServingError

    with pytest.raises(SlotStateServingError, match=match):
        _hybrid_config(**tpu)


def test_slot_state_model_refuses_the_unpaged_paths():
    from neuronx_distributed_inference_tpu.models import get_model_builder

    cfg = _hybrid_config(is_block_kv_layout=False, is_chunked_prefill=False,
                         chunked_prefill_config=None)
    with pytest.raises(NotImplementedError, match="paged, chunked path"):
        get_model_builder("granitemoehybrid")(cfg)


# ---------------------------------------------------------------------------
# a model whose attention caches a compressed latent (MLA: models/deepseek.py)
# ---------------------------------------------------------------------------

_MLA_ATTRS = dict(
    model_type="deepseek_v3", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1, vocab_size=512,
)


def _mla_config(paged=True, **tpu):
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    from neuronx_distributed_inference_tpu.models import get_model_builder

    opts = dict(batch_size=2, seq_len=128)
    if paged:
        opts.update(
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=16, pa_num_blocks=16, is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=2, kernel_q_tile_size=16),
        )
    opts.update(tpu)
    cls = get_model_builder("deepseek_v3").config_cls
    return cls(TpuConfig(**opts),
               load_config=lambda c: [setattr(c, k, v) for k, v in _MLA_ATTRS.items()])


def test_latent_attention_accepts_the_paged_chunked_path_and_declares_its_streams():
    from neuronx_distributed_inference_tpu.models import get_model_builder

    streams = get_model_builder("deepseek_v3")(_mla_config()).cache_streams()
    # one latent and one rotary key a token, the key packed 8 tokens a 128-lane row
    assert [(s.heads, s.width, s.pack, s.name) for s in streams] == [
        (1, 32, 1, "latent"), (1, 16, 8, "rope_key")]
    assert _mla_config(paged=False, tp_degree=2).tpu_config.tp_degree == 2  # contiguous: tp stays


@pytest.mark.parametrize("paged,tpu,match", [
    (False, dict(fused_qkv=True), "fused_qkv"),
    (False, dict(cp_degree=2, tp_degree=2), "cp_degree"),
    (False, dict(attention_dp_degree=2, tp_degree=2, is_continuous_batching=True),
     "attention_dp_degree"),
    (True, dict(is_prefix_caching=True), "is_prefix_caching"),
    (True, dict(serving_ragged=True), "serving_ragged"),
    (True, dict(speculation_length=4), "speculation"),
    (True, dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (True, dict(tp_degree=2), "degree > 1"),
], ids=["fused_qkv", "cp", "attention_dp", "prefix_caching", "ragged", "speculation", "kv_quant",
        "paged_tp2"])
def test_latent_attention_refuses_by_type_what_cannot_run_it(paged, tpu, match):
    """What ``DeepseekV3ModelBuilder.__init__`` raised as NotImplementedError
    is a typed refusal at config time, one line an option."""
    from neuronx_distributed_inference_tpu.config import LatentAttentionError

    with pytest.raises(LatentAttentionError, match=match):
        _mla_config(paged=paged, **tpu)


def test_latent_attention_refuses_lora_and_whole_model_dp():
    from neuronx_distributed_inference_tpu.config import LatentAttentionError, validate_latent_attention

    class Options:  # the fields validate_latent_attention reads, one set that TpuConfig itself refuses earlier
        cp_degree = attention_dp_degree = tp_degree = ep_degree = 1
        data_parallel_degree = 1
        fused_qkv = is_block_kv_layout = is_prefix_caching = serving_ragged = kv_quantized = False
        speculation_length = medusa_speculation_length = 0
        enable_fused_speculation = enable_eagle_speculation = False
        lora_config = None

    validate_latent_attention(Options())
    for field, value, match in (("lora_config", object(), "lora_config"),
                                ("data_parallel_degree", 2, "data_parallel_degree")):
        opts = Options()
        setattr(opts, field, value)
        with pytest.raises(LatentAttentionError, match=match):
            validate_latent_attention(opts)
