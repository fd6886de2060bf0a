"""chip_smoke.py on the CPU harness: every phase function passes at a tiny
shape with the kernels FORCED on (so they run interpreted — the same
dispatch, census and comparison code the chip run takes), the script itself
refuses to pass off the chip, and the pieces that keep a run from missing
the chip in silence (the compile-cache helper, bench's device check and exit
code, a bench parent that stays off the backend) hold.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402
import chip_smoke as cs  # noqa: E402

# head_dim 64 is the narrowest the kernels take; everything else stays TINY
ATTRS = dict(bench.TINY, head_dim=64)
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


# ---- bench: no way to miss the chip ---------------------------------------


def test_bench_measuring_path_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="measures on a TPU"):
        bench._require_chip()
    with pytest.raises(RuntimeError, match="measures on a TPU"):
        bench._device_spec(tiny=False)
    assert bench._device_spec(tiny=True) is None  # tests' path: counts


@pytest.mark.parametrize(
    "points,failed",
    [
        ({"a": {"decode_tok_s": 1.0}}, False),
        ({"a": {"decode_tok_s": 1.0}, "b": {"error": "boom"}}, True),
        ({"a": {"decode_tok_s": 1.0}, "b": {"skipped_budget": True}}, True),
    ],
)
def test_bench_suite_exit_code_follows_errors(points, failed):
    assert bench.suite_failed(points) is failed


def test_bench_parent_never_initialises_a_backend(tmp_path):
    """Suite mode starts one child per point and a chip belongs to one
    process: on every route the parent takes besides run_suite itself
    (summary line, --metrics-out, --ops-port) no backend may come up."""
    out = tmp_path / "metrics.json"
    code = (
        "import sys, bench\n"
        f"sys.argv = ['bench.py', '--metrics-out', {str(out)!r}, '--ops-port', '0']\n"
        "with bench._ops_server() as ops:\n"
        "    assert ops is not None\n"
        "    bench._emit({})\n"
        "    bench._dump_metrics(bench._metrics_out_path())\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'backend initialised'\n"
    )
    proc = _run([sys.executable, "-c", code], ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["value"] is None
    assert out.exists()
