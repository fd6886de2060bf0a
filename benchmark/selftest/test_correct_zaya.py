"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the expert configuration ``zaya1-8b``, at a small
size on the CPU: a 4-layer ZAYA1-shaped model (latent attention with conv
mixing, 8 top-1 experts behind the carried MLP router) through the probe's
``ServingSession`` and the teacher-forced chunks, against
``references/zaya.py``, with the weights the configuration's own ``weights``
rules give.

- a sound program passes, logits and margins, and its routing spreads;
- a fault of each new part fails it by at least 3 x its limit: the conv
  carry zeroed at a chunk boundary, the value shift dropped, ``p_e`` not
  applied, the router's carry dropped, rotary on all of a head's dimensions
  (the reference's equations with the fault, rounded as the twin is, in the
  program's place, reporting the choices it made), and the CONTROL, the
  reference itself in fp8-e4m3."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import zaya as ref

SEED = 4000000535
PROMPT = 256  # two whole chunks of 128: the first decode step takes its carry from the prefill


def tiny_config() -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "configs", "zaya1-8b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               num_hidden_layers=4, layer_types=["hybrid"] * 4, vocab_size=1024,
               num_experts=8, moe_intermediate_size=256, router_hidden_size=64)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    """(cfg, devices, params, pspecs, geometry, prompt, forced tokens)."""
    import jax

    cfg = tiny_config()
    devices = jax.devices()[:1]
    app = system.build_app(cfg, devices, SEED)
    params, pspecs = system.make_weights(app, SEED, cfg["weights"])
    rng = np.random.default_rng([SEED, 7])
    prompt = rng.integers(0, cfg["vocab_size"], size=PROMPT).astype(np.int32)
    chosen = [int(t) for t in rng.integers(0, cfg["vocab_size"], size=correct.PROBE_DECODE_STEPS + 1)]
    return cfg, devices, params, pspecs, ref.geometry(system.model_attrs(cfg), 1), prompt, chosen


def judged(tiny, served, choices):
    """(err / floor, the worst layer's regret / score_floor, passed)."""
    cfg, _, params, _, _, prompt, chosen = tiny
    try:
        facts, ok = correct.judge(cfg, params, 1, [prompt], [chosen], [served], [choices]), True
    except correct.CorrectnessError as e:
        facts, ok = e.facts, False
    row = facts["rows"][0]
    margin = max(r / f for r, f in zip(row["choice_regret"], row["choice_score_floor"]))
    return row["ratio"], margin, ok


def test_a_sound_program_passes_and_its_routing_spreads(tiny, capsys):
    cfg, devices, params, pspecs, geo, *_ = tiny
    assert cfg["probe_tpu_config"] == {"output_choices": True} and ref.CHOICES
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "zaya"
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    # the weights rules: no expert of a layer takes most of the probe's tokens
    taken = choices[0][ref.NAME][:, :, 0]  # (tokens, layers)
    share = [np.bincount(taken[:, l], minlength=geo.experts).max() / len(taken) * geo.experts
             for l in range(geo.layers)]
    with capsys.disabled():
        print("\nbusiest expert's share of a layer's tokens, x uniform:", np.round(share, 2).tolist())
    assert max(share) < 3.0 and all(len(set(taken[:, l])) >= geo.experts - 1 for l in range(geo.layers))


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8_in_place_of_bf16",))
def test_a_fault_of_each_new_part_fails_the_rule_by_three_times_its_limit(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    if fault.startswith("fp8"):
        kw = dict(rounding=jnp.float8_e4m3fn)
    else:
        kw = dict(rounding=jnp.bfloat16, fault=fault)
    served, _, own = ref.forward(params, geo, tokens, positions, **kw)
    ratio, margin, ok = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K}), "
              f"regret / score_floor {margin:.3g} (limit {2 * correct.K})")
    assert not ok
    assert ratio > 3 * correct.K or margin > 3 * 2 * correct.K


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served, _, own = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)
    ratio, margin, ok = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()["zaya1-8b.decode"]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("zaya1-8b", "decode", 1)
    assert cell.config["reference"] == "zaya" and cell.config["num_hidden_layers"] == 20
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(cell.spec["reports"]) - {"out_tok_s", "setup_s"}
    assert {"moe.rows_per_expert", "moe.expert_stream_roofline", "kernel.paged_attn_roofline",
            "step.chunk_dev_ms.tok_s"} <= reported and "kernel.ssm_update_roofline" not in reported


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_expert_readers_read_the_programs_counters_and_nothing_where_there_are_none():
    from benchmark.harness.readers import moe_roofline

    cell = catalog.load_cell("zaya1-8b.decode")
    readers = {m["name"]: m["reader"] for m in cell.per_layer if m["name"].startswith("moe.")}
    dec, chunk = {"program": "decode"}, {"program": "chunk"}
    before = _snapshot(nxdi_moe_rows_routed_total=[(dec, 960.0)], nxdi_moe_experts_hit_total=[(dec, 320.0)],
                       nxdi_steps_total=[({"kind": "decode"}, 1.0)])
    # 10 decode dispatches of 47 live rows over 20 layers of 16 experts; chunk passes are not read
    after = _snapshot(
        nxdi_moe_rows_routed_total=[(dec, 960.0 + 10 * 47 * 20), (chunk, 5000.0)],
        nxdi_moe_experts_hit_total=[(dec, 320.0 + 10 * 20 * 16), (chunk, 640.0)],
        nxdi_steps_total=[({"kind": "decode"}, 11.0), ({"kind": "prefill"}, 3.0)])
    attrs = system.model_attrs(cell.config)
    ctx = {"counters": {"before": before, "after": after}, "attrs": attrs,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"chips": 1, "module_sums": {"jit_token_generation_model_decode(1)": (4, 0.1),
                                                 "jit_token_generation_model_chunk(2)": (2, 0.2)}}}
    assert moe_roofline.read(readers["moe.rows_per_expert"], ctx) == pytest.approx(47 / 16)
    # 4 traced dispatches x 20 x 16 experts x 3 x 2048 x 2048 x 2 B at 819 GB/s, over 0.1 s
    want = 100 * (4 * 320 * 3 * 2048 * 2048 * 2 / 819e9) / 0.1
    assert moe_roofline.read(readers["moe.expert_stream_roofline"], ctx) == pytest.approx(want)
    assert 0 < want < 100
    # a program without the counters (the parent commit), or a run without a trace: None, no raise
    old = {**ctx, "counters": {"before": _snapshot(), "after": _snapshot()}}
    for reader in readers.values():
        assert moe_roofline.read(reader, old) is None
        assert moe_roofline.read(reader, {}) is None
    assert moe_roofline.read(readers["moe.expert_stream_roofline"], {**ctx, "trace": None}) is None
