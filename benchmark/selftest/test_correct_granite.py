"""The rule that decides ``correct`` (``harness/correct.py``, unchanged: err
<= K x the bf16 twin's error) on the state-space configuration, at a small
size on the CPU: a 4-layer Granite-4.0-H-shaped model (three Mamba-2 layers
and one attention layer) through the probe's ``ServingSession`` and the
teacher-forced chunks, against ``references/granite_hybrid.py``.

- a sound program passes;
- four faults fail it: the gate ``silu(z)`` dropped, ``dt_bias`` not applied,
  the carry between two prefill chunks zeroed, and the CONTROL — the
  reference itself in fp8-e4m3, the nearest precision below bf16, in the
  program's place.

The weights are ``system.make_weights``'s with the recurrence's published
initialisation laid over them (``read_slow_decay.published_init``:
slow-decay heads, conv taps of ``nn.Conv1d``'s own size), under which what
the state holds is most of a state-space layer's output; under N(0, 0.02)
everywhere a head forgets in two tokens and a lost carry moves nothing."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import granite_hybrid as ref
from benchmark.selftest.read_slow_decay import _prefill, _rest, published_init

SEED = 4000000421
PROMPT = 264  # two chunks of 128 and a last one of 8: probed 7-11 tokens after a boundary


def tiny_config() -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "configs", "granite-4.0-h-micro.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, shared_intermediate_size=512, intermediate_size=512,
               num_attention_heads=8, num_key_value_heads=4, num_hidden_layers=4,
               layer_types=["mamba", "mamba", "attention", "mamba"], vocab_size=1024,
               mamba_n_heads=16, mamba_d_head=32, mamba_d_state=32)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    """(cfg, devices, params, pspecs, geometry, prompt, forced tokens, kv width)."""
    import jax

    cfg = tiny_config()
    devices = jax.devices()[:1]
    app = system.build_app(cfg, devices, SEED)
    params, pspecs = system.make_weights(app, SEED)
    params = published_init(params, SEED)
    # x, B and C as large as they are at the published width (in_proj's output
    # grows with sqrt(hidden)): what the state holds goes with their cube, the
    # D x skip with x, and at hidden 256 the skip would hide the state
    mamba = dict(params["layers"]["mamba"])
    mixer = dict(mamba["mixer"])
    mixer["in_proj"] = {"weight": mixer["in_proj"]["weight"] * (2048 / cfg["hidden_size"]) ** 0.5}
    params = dict(params, layers=dict(params["layers"], mamba=dict(mamba, mixer=mixer)))
    rng = np.random.default_rng([SEED, 7])
    prompt = rng.integers(0, cfg["vocab_size"], size=PROMPT).astype(np.int32)
    chosen = [int(t) for t in rng.integers(0, cfg["vocab_size"], size=correct.PROBE_DECODE_STEPS + 1)]
    geo = ref.geometry(system.model_attrs(cfg), 1)
    return cfg, devices, params, pspecs, geo, prompt, chosen, correct.probe_width(cfg, PROMPT)


def make_probe(cfg, devices, params, pspecs):
    over = correct.probe_overrides(cfg, PROMPT)
    probe = system.build_app(cfg, devices, SEED, tpu_overrides=over["tpu"],
                             chunked_overrides=over["chunked"])
    system.give_weights(probe, params, pspecs)
    return probe


def judged(tiny, served):
    cfg, _, params, _, _, prompt, chosen, _ = tiny
    try:
        return correct.judge(cfg, params, 1, [prompt], [chosen], [served])["rows"][0]["ratio"], True
    except correct.CorrectnessError as e:
        return e.facts["rows"][0]["ratio"], False


def test_a_sound_program_passes(tiny):
    cfg, devices, params, pspecs, *_ = tiny
    facts = correct.check_model(cfg, devices, SEED, params, pspecs, 1, PROMPT)
    assert facts["reference"] == "granite_hybrid"
    assert all(0.3 < row["ratio"] <= correct.K for row in facts["rows"]), facts
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]


def test_the_carry_between_two_chunks_zeroed_fails_the_rule(tiny):
    from neuronx_distributed_inference_tpu.runtime.faults import fill_slot_state

    cfg, devices, params, pspecs, geo, prompt, chosen, width = tiny
    probe = make_probe(cfg, devices, params, pspecs)
    sound = correct._forced_logits(probe, [prompt], [chosen], width)[0]
    assert judged(tiny, sound)[1]
    head = (PROMPT - 1) // 128 * 128
    probe.init_kv_cache()
    _prefill(probe, prompt[:head], 0, width)
    kept = _rest(probe, prompt, head, chosen, width)  # the same calls, the state carried
    assert np.array_equal(kept, sound)
    probe.init_kv_cache()
    _prefill(probe, prompt[:head], 0, width)
    probe.kv_cache = fill_slot_state(probe.kv_cache, [0], 0.0)
    ratio, ok = judged(tiny, _rest(probe, prompt, head, chosen, width))
    assert not ok and ratio > 2 * correct.K, ratio


def test_dt_bias_not_applied_fails_the_rule(tiny):
    import jax.numpy as jnp

    cfg, devices, params, pspecs, geo, prompt, chosen, width = tiny
    broken = dict(params, layers=dict(params["layers"]))
    mamba = dict(broken["layers"]["mamba"])
    mamba["mixer"] = dict(mamba["mixer"], dt_bias=jnp.zeros_like(mamba["mixer"]["dt_bias"]))
    broken["layers"]["mamba"] = mamba
    probe = make_probe(cfg, devices, broken, pspecs)  # the program's tree; the reference keeps the true one
    ratio, ok = judged(tiny, correct._forced_logits(probe, [prompt], [chosen], width)[0])
    assert not ok and ratio > 2 * correct.K, ratio


def _mixer_without_gate(x, w, geo, rounding=None):
    """``references/granite_hybrid._mamba_mixer`` with ``y * silu(z)`` left
    as ``y``. No setting of the weights drops the gate, so the fault is put
    into the equations: the mixer applies silu twice, to the conv output and
    then to z, and the second call gives 1."""
    import jax

    silu = jax.nn.silu
    calls = {"n": 0}

    def silu_but_not_the_gate(a):
        calls["n"] += 1
        return silu(a) if calls["n"] == 1 else a * 0.0 + 1.0

    jax.nn.silu = silu_but_not_the_gate
    try:
        return ref._mamba_mixer(x, w, geo, rounding)
    finally:
        jax.nn.silu = silu


@pytest.mark.parametrize("fault", ["gate_dropped", "fp8_in_place_of_bf16"])
def test_a_fault_in_the_equations_fails_the_rule(tiny, fault):
    """The reference with the fault, rounded as the twin is (fp8: rounded to
    the nearest precision below), in the program's place."""
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen, _ = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    if fault == "gate_dropped":
        served = ref.reference_logits(params, geo, tokens, positions, rounding=jnp.bfloat16,
                                      mixer=_mixer_without_gate)
    else:
        served = ref.reference_logits(params, geo, tokens, positions, rounding=jnp.float8_e4m3fn)
    ratio, ok = judged(tiny, served)
    assert not ok and ratio > 2 * correct.K, ratio
    twin = ref.twin_logits(params, geo, tokens, positions)
    assert judged(tiny, twin) == (1.0, True)  # the twin itself is at the floor by construction
