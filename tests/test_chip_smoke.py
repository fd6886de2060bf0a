"""chip_smoke.py on the CPU harness: every phase function passes at a tiny
shape with the kernels FORCED on (so they run interpreted — the same
dispatch, census and comparison code the chip run takes), the script itself
refuses to pass off the chip, and the pieces that keep a run from missing
the chip in silence (the compile-cache helper) hold; ``build`` ends with the
``TpuConfig`` each of its four uses asks for.
"""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

# head_dim 64 is the narrowest the kernels take; everything else is tiny
ATTRS = dict(
    model_type="llama", hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=2, vocab_size=128, rms_norm_eps=1e-5,
    rope_theta=10000.0, max_position_embeddings=256, hidden_act="silu",
    tie_word_embeddings=False, head_dim=64,
)
FORCE = dict(attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True)
GEN = dict(batch=2, prompt_lens=(128, 97), new_tokens=8, seq_len=256,
           ce_buckets=(128,), tkg_buckets=(256,))
SERVE = dict(prompt_lens=(16, 9, 40, 12), budgets=(6, 5, 6, 4), seq_len=128,
             blocks=24, block_size=16, max_seqs=4, q_tile=16)


@pytest.fixture(scope="module")
def compile_log():
    return cs.CompileLog()


@pytest.fixture(scope="module")
def generated(compile_log):
    """Phase 2 once; its warmed app feeds phases 3-5 like in the real run."""
    return cs.phase_generate(ATTRS, GEN, 0, compile_log, extra=FORCE)


def test_phase_generate_tiny(generated):
    _, facts = generated
    assert facts["compiles_after_warmup"] == 0
    assert facts["warmup_compiles"] > 0
    assert (facts["batch"], facts["new_tokens"], facts["vocab"]) == (2, 8, 128)


def test_phase_kernels_tiny_interpreted(generated):
    app, _ = generated
    facts = cs.phase_kernels(app)
    assert facts["kernel_interpret"] is True
    rows = facts["programs"]
    assert set(rows) == {"cte[128]", "tkg[256]", "tkg_decode[8x,256]"}
    # forced on: the kernel is in every traced program, interpreted (so no
    # custom call on this backend) — and the chip run's assertion refuses that
    assert all(r["gate"] and r["pallas_calls"] == 1 for r in rows.values())
    with pytest.raises(cs.SmokeError, match="no tpu_custom_call"):
        cs.check_census(rows, interpret=False)
    with pytest.raises(cs.SmokeError, match="kernel_interpret"):
        cs.check_census(rows, interpret=True, require=("cte", "tkg"))


def test_census_refuses_gate_program_disagreement():
    rows = {"cte[128]": {"gate": False, "pallas_calls": 1, "tpu_custom_calls": 1}}
    with pytest.raises(cs.SmokeError, match="gate=False"):
        cs.check_census(rows, interpret=False)
    # what the chip run looks like when all is well, and when a gate has
    # flipped off in silence
    on = {"mixed[16]": {"gate": True, "pallas_calls": 1, "tpu_custom_calls": 1}}
    cs.check_census(on, interpret=False, require=("mixed",))
    off = {"mixed[16]": {"gate": False, "pallas_calls": 0, "tpu_custom_calls": 0}}
    cs.check_census(off, interpret=False)
    with pytest.raises(cs.SmokeError, match="no compiled kernel in the mixed"):
        cs.check_census(off, interpret=False, require=("mixed",))


def test_phase_kernel_vs_native_tiny(generated):
    app, _ = generated
    facts = cs.phase_kernel_vs_native(ATTRS, GEN, 0, app)
    for part in ("prefill", "first_decode"):
        assert facts[part]["max_abs_err"] <= cs.LOGIT_TOL * facts[part]["logit_scale"]
    # the comparison has teeth: logits off by their own scale are refused
    with pytest.raises(cs.SmokeError, match="max logit error"):
        cs.check_logits_close("x", [[1.0, -1.0]], [[-1.0, 1.0]])


def test_phase_serving_tiny_split_and_ragged(generated, compile_log):
    app, _ = generated
    facts = cs.phase_serving(ATTRS, SERVE, 0, compile_log, app, extra=FORCE)
    for path in ("split", "ragged"):
        assert facts[path]["finished"] == facts[path]["requests"] == 4
        assert facts[path]["tokens"] == sum(SERVE["budgets"])
        assert facts[path]["compiles_after_warmup"] == 0
    assert any(n.startswith("mixed[") for n in facts["ragged"]["programs"])
    assert "tkg_chunk_prefill[q16,128]" in facts["split"]["programs"]


def test_phase_device_refuses_the_cpu():
    with pytest.raises(cs.SmokeError, match="not a TPU"):
        cs.phase_device()


def _run(cmd, cwd, **env):
    return subprocess.run(
        cmd, cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_script_fails_off_the_chip_and_prints_no_result():
    proc = _run([sys.executable, "chip_smoke.py"], ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_script_alone_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run([sys.executable, "chip_smoke.py"], tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ---- the compile-cache helper ---------------------------------------------


@pytest.fixture
def cache_calls(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    calls = []
    monkeypatch.setattr(compilation_cache, "set_cache_dir", calls.append)
    return calls


def test_cache_helper_env_var_wins_and_sets_nothing(monkeypatch, cache_calls):
    from neuronx_distributed_inference_tpu.utils import compile_cache as cc

    monkeypatch.setenv(cc.ENV_VAR, "/somewhere/outside")
    assert cc.configure_compile_cache() == "/somewhere/outside"
    assert cc.configure_compile_cache("/from/tpu_config") == "/somewhere/outside"
    assert cache_calls == []


def test_cache_helper_unset_uses_the_fixed_checkout_dir(monkeypatch, cache_calls):
    from neuronx_distributed_inference_tpu.utils import compile_cache as cc

    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    fixed = os.path.join(ROOT, ".bench_cache", "xla")
    assert cc.DEFAULT_CACHE_DIR == fixed
    assert cc.configure_compile_cache() == fixed
    assert cc.configure_compile_cache("/from/tpu_config") == "/from/tpu_config"
    assert cache_calls == [fixed, "/from/tpu_config"]


# ---- build: the one construction the phases share --------------------------


@pytest.mark.parametrize("case", ["unpaged", "paged", "weights_from", "unloaded"])
def test_build_ends_with_the_config_it_was_asked_for(case, generated):
    base, _ = generated
    if case == "unpaged":
        app = cs.build(ATTRS, GEN, 3, extra=dict(attn_kernel_enabled=False), load=False)
    elif case == "paged":
        app = cs.build(ATTRS, SERVE, 3, paged=True, load=False)
    elif case == "weights_from":
        app = cs.build(ATTRS, SERVE, 3, paged=True, weights_from=base,
                       extra=dict(serving_ragged=True))
    else:
        app = cs.build(ATTRS, GEN, 3, load=False)
    tc = app.config.tpu_config
    # every application: bf16, the fused QKV layout, logits out, the guard on
    assert (tc.dtype, tc.fused_qkv, tc.output_logits, tc.retrace_guard, tc.seed) == (
        "bfloat16", True, True, True, 3)
    assert tc.quantized is False and tc.tp_degree == 1
    if case in ("paged", "weights_from"):
        assert (tc.batch_size, tc.seq_len) == (SERVE["max_seqs"], SERVE["seq_len"])
        assert tc.context_encoding_buckets == tc.token_generation_buckets == [SERVE["seq_len"]]
        assert tc.is_block_kv_layout and tc.is_chunked_prefill and tc.is_continuous_batching
        assert (tc.pa_num_blocks, tc.pa_block_size) == (SERVE["blocks"], SERVE["block_size"])
        cp = tc.chunked_prefill_config
        assert (cp.max_num_seqs, cp.kernel_q_tile_size) == (SERVE["max_seqs"], SERVE["q_tile"])
    else:
        assert (tc.batch_size, tc.seq_len) == (GEN["batch"], GEN["seq_len"])
        assert tc.context_encoding_buckets == list(GEN["ce_buckets"])
        assert tc.token_generation_buckets == list(GEN["tkg_buckets"])
        assert not tc.is_block_kv_layout and not tc.is_chunked_prefill
    if case == "unpaged":
        assert tc.attn_kernel_enabled is False  # ``extra`` reaches the config
    if case == "weights_from":
        assert tc.serving_ragged is True
        assert app.params is base.params and app._pspecs is base._pspecs
        # a cache of its own, in the paged layout: the pool and its garbage block
        assert app.kv_cache is not base.kv_cache
        assert app.kv_cache.k.shape[1] == SERVE["blocks"] + 1
        assert app.kv_cache.k.shape != base.kv_cache.k.shape
    else:
        assert app.params is None and app.kv_cache is None
