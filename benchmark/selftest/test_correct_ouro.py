"""The rule that decides ``correct`` (``harness/correct.py``, unchanged: err
<= K x the bf16 twin's error) on the looped configuration, at a small size on
the CPU: an Ouro-shaped model (hidden 256, 3 layers run 4 times over one set of
weights, 12 K/V streams a token) through the probe's ``ServingSession`` and the
teacher-forced chunks, against ``references/ouro.py``.

- a sound program passes;
- three faults of the LOOP fail it: three loops of four, the last loop's K/V
  stream written and read by every loop (one loop's K/V standing in for all
  four), the norm between two loops left out;
- and the CONTROL fails it: the reference itself in fp8-e4m3, the nearest
  precision below bf16, in the program's place.

A shared stream shows from a row's SECOND chunk pass on (inside one pass a
loop attends what it has just written), so the probed prompt is two chunks and
a bit."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import ouro as ref

SEED = 5100000043
PROMPT = 264  # two chunks of 128 and a last one of 8
LAYERS, LOOPS = 3, 4


def tiny_config() -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "configs", "ouro-2.6b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, intermediate_size=704, num_attention_heads=8, num_key_value_heads=8,
               head_dim=32, num_hidden_layers=LAYERS, layer_types=["full_attention"] * LAYERS,
               max_window_layers=LAYERS, vocab_size=1024, total_ut_steps=LOOPS)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    """(cfg, devices, params, pspecs, geometry, prompt, forced tokens, kv width)."""
    import jax

    cfg = tiny_config()
    devices = jax.devices()[:1]
    app = system.build_app(cfg, devices, SEED)
    assert app.spec.loop_steps == LOOPS and app.paged_layers == LOOPS * LAYERS
    params, pspecs = system.make_weights(app, SEED)
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
    assert facts["reference"] == "ouro"
    assert all(0.3 < row["ratio"] <= correct.K for row in facts["rows"]), facts
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]


def test_the_last_loops_stream_for_every_loop_fails_the_rule(tiny, monkeypatch):
    """The PROGRAM with the fault: every loop writes and attends stream
    ``(T - 1) * L + l``, so a later pass reads, at loops 0..T-2, the K/V the
    last loop left for the earlier positions."""
    from neuronx_distributed_inference_tpu.models import base

    cfg, devices, params, pspecs, geo, prompt, chosen, width = tiny
    sound_layer = base.decoder_layer
    probe = make_probe(cfg, devices, params, pspecs)
    sound = correct._forced_logits(probe, [prompt], [chosen], width)[0]
    assert judged(tiny, sound)[1]

    def faulty(layer_params, hidden, cos, sin, k_cache, v_cache, layer_idx, *args, **kw):
        return sound_layer(layer_params, hidden, cos, sin, k_cache, v_cache,
                           (LOOPS - 1) * LAYERS + layer_idx % LAYERS, *args, **kw)

    monkeypatch.setattr(base, "decoder_layer", faulty)
    probe = make_probe(cfg, devices, params, pspecs)  # traced anew, with the fault
    ratio, ok = judged(tiny, correct._forced_logits(probe, [prompt], [chosen], width)[0])
    assert not ok and ratio > 2 * correct.K, ratio


@pytest.mark.parametrize("fault", ["three_loops_of_four", "no_norm_between_loops",
                                   "fp8_in_place_of_bf16"])
def test_a_fault_in_the_equations_fails_the_rule(tiny, fault):
    """The reference with the fault, rounded as the twin is (fp8: rounded to
    the nearest precision below), in the program's place."""
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen, _ = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    kw = {"three_loops_of_four": dict(rounding=jnp.bfloat16, loops=LOOPS - 1),
          "no_norm_between_loops": dict(rounding=jnp.bfloat16, between_loop_norm=False),
          "fp8_in_place_of_bf16": dict(rounding=jnp.float8_e4m3fn)}[fault]
    ratio, ok = judged(tiny, ref.reference_logits(params, geo, tokens, positions, **kw))
    assert not ok and ratio > 2 * correct.K, ratio
    twin = ref.twin_logits(params, geo, tokens, positions)
    assert judged(tiny, twin) == (1.0, True)  # the twin itself is at the floor by construction


def test_the_readers_count_a_looped_stacks_streams():
    """``readers/loop_roofline.py``'s counts at the published sizes, and what
    it gives where there is nothing to read."""
    from benchmark.harness.readers import loop_roofline as lr

    with open(os.path.join(catalog.BENCH_DIR, "configs", "ouro-2.6b.json")) as f:
        attrs = system.model_attrs(json.load(f))
    assert lr.layer_passes(attrs) == 192
    assert lr.kv_bytes_per_token(attrs) == 1572864  # 1.5 MiB
    assert round(lr.layer_weight_bytes(attrs) / 1e6, 1) == 102.8  # 51.4 M parameters
    assert round(lr.dispatch_weight_bytes(attrs) / 1e9, 2) == 19.93
    ctx = {"attrs": attrs, "trace": None, "counters": None, "peaks": None}
    for kind in ("paged_attn_roofline", "stream_roofline", "pool_used_share"):
        assert lr.read({"kind": kind, "pattern": "^x", "decode_module": "^y", "pool": "p", "free": "f"}, ctx) is None
    assert lr.read({"kind": "stream_roofline"}, {"attrs": {"num_hidden_layers": 28}}) is None  # no loop
    # 10 decode dispatches of 50 ms, 8 rows x 440 live tokens each: (19.93 + 5.54) GB at 819 GB/s a dispatch
    tr = {"chips": 1, "module_sums": {"jit_token_generation_model_decode(1)": (10, 0.5)},
          "op_sums": {"paged_tkg_decode_attention.3": (1920, 0.1)}, "span_counts": {"step": 10}}
    ctx = {"attrs": attrs, "trace": tr, "peaks": {"hbm_bytes_per_s": 819e9}, "slice": (0.0, 1.0),
           "samples": {"live_kv_tokens": [(0.05 * i, 3520.0) for i in range(10)]},
           "counters": {"after": {"nxdi_kv_pool_bytes": {"samples": [{"labels": {}, "value": 100.0}]},
                                  "nxdi_kv_free_bytes": {"samples": [{"labels": {}, "value": 40.0}]}}}}
    stream = lr.read({"kind": "stream_roofline", "decode_module": "^jit_token_generation_model_decode\\("}, ctx)
    attn = lr.read({"kind": "paged_attn_roofline", "pattern": "^paged_tkg_decode_attention"}, ctx)
    assert round(stream, 1) == round(100 * (19.93e9 + 3520 * 1572864) / 819e9 / 0.05, 1) == 62.2
    assert round(attn, 1) == round(100 * 10 * 3520 * 1572864 / 819e9 / 0.1, 1) == 67.6
    assert lr.read({"kind": "pool_used_share", "pool": "nxdi_kv_pool_bytes", "free": "nxdi_kv_free_bytes"}, ctx) == 60.0
