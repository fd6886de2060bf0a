"""The rule that decides ``correct`` (``harness/correct.py``: err <= K x the
bf16 twin's error), at a small size on the CPU: a 4-layer Qwen3-shaped model
through the probe's ``ServingSession`` (chunked prefill, then decode through
the paged cache) against ``references/dense.py``.

- a sound program passes at tp = 1 and, on four virtual devices, at tp = 4;
- four faults fail it: a norm weight not applied, a KV head dropped, the
  mask off by one tile, and the CONTROL — the reference itself computed in
  fp8-e4m3, the nearest precision below bf16, put in the program's place;
- the twin's partial sums change it only when ``degree`` > 1, and a twin
  told to round nowhere is the float32 pass;
- a whole run (``run.main`` in rehearsal, which skips the look for a chip)
  with the timed path broken underneath — a token altered where the session
  hands it back — ends ``correct: false``.

The four-device case runs this file as a script in a process of its own
(``XLA_FLAGS`` must be set before JAX starts)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import dense

SEED = 4000000321
MAX_PROMPT = 300  # three chunks of 128, the last partial
FAULTS = ("norm_weight_not_applied", "kv_head_dropped", "mask_off_by_one_tile")


def tiny_config(degree: int) -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "configs", "qwen3-1p7b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=8, num_key_value_heads=4,
               head_dim=32, num_hidden_layers=4, max_window_layers=4, vocab_size=1024)
    cfg["tpu_config"]["tp_degree"] = degree
    return cfg


def break_weights(params, fault: str, attrs: dict):
    """The parameter tree the PROGRAM is given; the reference keeps the true one."""
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(lambda x: x, params)
    layers = dict(params["layers"])
    if fault == "norm_weight_not_applied":
        ln = layers["input_layernorm"]["weight"]
        layers["input_layernorm"] = {"weight": jnp.ones_like(ln)}
    elif fault == "kv_head_dropped":  # degree 1: the fused matrix is [q | k | v]
        sa = dict(layers["self_attn"])
        w = sa["qkv_proj"]["weight"]
        d = attrs["head_dim"]
        nq, nkv = attrs["num_attention_heads"] * d, attrs["num_key_value_heads"] * d
        sa["qkv_proj"] = {"weight": w.at[:, :, nq + nkv : nq + nkv + d].set(0)}  # v of KV head 0
        layers["self_attn"] = sa
    params["layers"] = layers
    return params


def facts_of(degree: int = 1, fault: str = None) -> dict:
    """check_model's facts for the tiny model (``error`` among them when the
    rule fails), with ``fault`` put under the probe application."""
    import jax

    cfg = tiny_config(degree)
    attrs = system.model_attrs(cfg)
    devices = jax.devices()[:degree]
    app = system.build_app(cfg, devices, SEED)
    params, pspecs = system.make_weights(app, SEED)
    give, build = system.give_weights, system.build_app

    def give_broken(probe, p, specs):
        give(probe, break_weights(p, fault, attrs) if fault in FAULTS[:2] else p, specs)

    def build_masked(*a, **kw):
        probe = build(*a, **kw)
        forward = probe.forward
        tile = cfg["tpu_config"]["pa_block_size"]

        def off_by_one_tile(*args, attention_mask, **rest):
            mask = np.array(attention_mask)
            mask[:, :tile] = 0  # the first tile of every row's context is not attended
            return forward(*args, attention_mask=mask, **rest)

        probe.forward = off_by_one_tile
        return probe

    system.give_weights = give_broken
    if fault == "mask_off_by_one_tile":
        system.build_app = build_masked
    try:
        return correct.check_model(cfg, devices, SEED, params, pspecs, degree, MAX_PROMPT)
    except correct.CorrectnessError as e:
        return {"error": str(e), **e.facts}
    finally:
        system.give_weights, system.build_app = give, build


def ratios(facts: dict):
    return [row["ratio"] for row in facts["rows"]]


def test_a_sound_program_passes_on_one_device():
    facts = facts_of(1)
    assert "error" not in facts, facts
    assert all(0.3 < r <= correct.K for r in ratios(facts)), facts
    short = facts["rows"][1]
    assert short["session_token_regret"] <= short["limit"]
    assert {"err", "floor", "scale", "ratio"} <= set(short)


def test_a_sound_program_passes_at_tp4_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-m", "benchmark.selftest.test_correct", "4"], env=env, cwd=catalog.REPO_DIR,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    facts = json.loads(p.stdout.strip().splitlines()[-1])
    assert "error" not in facts, facts
    assert all(0.3 < r <= correct.K for r in ratios(facts)), facts


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_program_fails_the_rule(fault):
    facts = facts_of(1, fault)
    assert "error" in facts, facts
    assert max(ratios(facts)) > 2 * correct.K, facts


@pytest.fixture(scope="module")
def tiny():
    """(cfg, params, geometry at degree 1, tokens, positions) of the tiny model."""
    import jax

    cfg = tiny_config(1)
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    params, _ = system.make_weights(app, SEED)
    tokens = np.random.default_rng(5).integers(0, cfg["vocab_size"], size=96)
    return cfg, params, dense.geometry(system.model_attrs(cfg), 1), tokens, [60, 95]


def test_the_control_fp8_in_place_of_bf16_fails_the_rule(tiny):
    """The reference, computed in the nearest precision below the one the
    configuration states, in the program's place."""
    import jax.numpy as jnp

    cfg, params, geo, tokens, positions = tiny
    prompt, chosen = tokens[:91], [int(t) for t in tokens[91:]] + [0]
    steps = [90 + k for k in range(correct.PROBE_DECODE_STEPS + 1)]
    fp8 = dense.reference_logits(params, geo, tokens, steps, rounding=jnp.float8_e4m3fn)
    with pytest.raises(correct.CorrectnessError) as e:
        correct.judge(cfg, params, 1, [prompt], [chosen], [fp8])
    assert e.value.facts["rows"][0]["ratio"] > 3 * correct.K, e.value.facts
    # and the twin itself, in the program's place, is at the floor by construction
    twin = dense.twin_logits(params, geo, tokens, steps)
    assert correct.judge(cfg, params, 1, [prompt], [chosen], [twin])["rows"][0]["ratio"] == 1.0


def test_partial_sums_change_the_twin_only_when_degree_exceeds_one(tiny, monkeypatch):
    import dataclasses

    cfg, params, geo, tokens, positions = tiny
    whole = dense.twin_logits(params, geo, tokens, positions)
    calls = []
    monkeypatch.setattr(dense, "_row_parallel",
                        lambda a, w, rounding, partials: calls.append(partials) or dense._mm(a, w, rounding))
    assert np.array_equal(dense.twin_logits(params, geo, tokens, positions), whole)  # degree 1: one product
    assert set(calls) == {1}
    monkeypatch.undo()
    # degree 4 (the weights of degree 1 are laid out alike but for the fused QKV: un-fuse by the same rule)
    four = dataclasses.replace(geo, degree=4)
    monkeypatch.setattr(dense, "layer_weights", lambda p, i, g, lw=dense.layer_weights: lw(p, i, geo))
    split = dense.twin_logits(params, four, tokens, positions)
    assert not np.array_equal(split, whole)
    ref = dense.reference_logits(params, geo, tokens, positions)
    assert np.array_equal(dense.reference_logits(params, four, tokens, positions), ref)  # float32 takes none
    assert 0.5 < np.abs(split - ref).max() / np.abs(whole - ref).max() < 2.0


def test_a_twin_that_rounds_nowhere_is_the_float32_pass(tiny):
    import jax.numpy as jnp

    cfg, params, geo, tokens, positions = tiny
    ref = dense.reference_logits(params, geo, tokens, positions)
    assert np.array_equal(dense.reference_logits(params, geo, tokens, positions, rounding=None), ref)
    # float32 "rounding" is the identity on float32 values: the same roundings, none of them moves a bit
    same = dense.reference_logits(params, geo, tokens, positions, rounding=jnp.float32)
    assert np.abs(same - ref).max() <= 1e-5 * np.abs(ref).max()
    assert np.abs(dense.twin_logits(params, geo, tokens, positions) - ref).max() > 1e-4 * np.abs(ref).max()


def test_a_run_whose_timed_path_alters_a_token_is_not_correct(monkeypatch, capsys):
    """``run.main`` from the weights on, on the CPU's tiny preset; underneath,
    ``ServingSession.step`` hands one request a token outside the vocabulary."""
    from benchmark import run
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    argv = ["--workload", "qwen3-1p7b.decode", "--seed", str(SEED), "--seconds", "2",
            "--rehearsal", "1", "--trace", "0"]
    assert run.main(argv) == 0
    sound = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sound["correct"] is True and sound["failed"] == 0

    step = ServingSession.step

    def altered(self):
        out = step(self)
        for rid in out:
            if not rid.startswith(("probe", "warm")) and self.requests[rid].generated:
                self.requests[rid].generated[-1] = 10**6
                break
        return out

    monkeypatch.setattr(ServingSession, "step", altered)
    assert run.main(argv) == 0
    broken = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert broken["correct"] is False


if __name__ == "__main__":  # the four-device case: python -m benchmark.selftest.test_correct <degree>
    print(json.dumps(facts_of(int(sys.argv[1]))))
