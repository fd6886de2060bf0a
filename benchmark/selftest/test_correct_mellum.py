"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the configuration ``mellum2-12b-a2.5b``, at its
``rehearsal`` preset widened to hidden 256 on the CPU: a ``mellum`` stack of
two periods [window, window, window, full] (window 16, 8 experts top-2)
through the probe's ``ServingSession`` and the teacher-forced chunks -- the
ring of blocks a slot, the two rotary tables, the window's mask in both step
programs -- against ``references/mellum.py``, with the weights the
configuration's own ``weights`` rule gives.

- a sound program passes, logits and the experts' margins;
- the faults ``correct`` must fail for this configuration (the reference's
  equations with the fault, rounded as the twin is, in the program's place):
  the two CONTROLS the issue names, the window ignored in the window layers
  and the default rotary table in the full layers; a window one key too
  wide; the renormalisation left out; and the reference itself in fp8-e4m3;
- the new readers count their own work and read nothing where there is none.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import mellum as ref

SEED = 5800000535
PROMPT = 256  # two whole chunks of 128, four times the preset's window
CELL = "mellum2-12b-a2.5b.mixedlen"


def tiny_config() -> dict:
    """The rehearsal preset at hidden 256: how sharply a query picks its keys
    goes with the width (the configuration's ``why.weights``)."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "mellum2-12b-a2.5b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, head_dim=32, sliding_window=16)
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
    """(err / floor, the worst layer's regret / score_floor, passed, the message)."""
    cfg, _, params, _, _, prompt, chosen = tiny
    try:
        facts, ok, said = correct.judge(cfg, params, 1, [prompt], [chosen], [served], [choices]), True, ""
    except correct.CorrectnessError as e:
        facts, ok, said = e.facts, False, str(e)
    row = facts["rows"][0]
    margin = max(r / f for r, f in zip(row["choice_regret"], row["choice_score_floor"]))
    return row["ratio"], margin, ok, said


def own_choices(params, geo, tokens, rounding, fault=None) -> dict:
    """What a program that runs the reference's equations (with ``fault``) reports."""
    _, _, experts = ref.forward(params, geo, tokens, [0], None, rounding, fault)
    return {ref.NAME: np.transpose(experts, (1, 0, 2))}


def test_a_sound_program_passes_logits_and_margins(tiny, capsys):
    cfg, devices, params, pspecs, geo, *_ = tiny
    assert cfg["probe_tpu_config"] == {"output_choices": True} and ref.CHOICES
    assert geo.kinds == (ref.WINDOW,) * 3 + (ref.FULL,) + (ref.WINDOW,) * 3 + (ref.FULL,)
    assert (geo.window, geo.experts, geo.top_k) == (16, 8, 2)
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "mellum"
    assert choices[0][ref.NAME].shape == (PROMPT + correct.PROBE_DECODE_STEPS, 8, 2)
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert len(row["choice_regret"]) == 8
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    with capsys.disabled():
        print("\nmellum rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]],
              "regret / score_floor", [np.round(np.divide(r["choice_regret"], r["choice_score_floor"]), 2).tolist()
                                       for r in facts["rows"]])


@pytest.mark.parametrize("fault", ["window_ignored", "default_rope_in_full", "window_off_by_one",
                                   "not_renormalised", "fp8_in_place_of_bf16"])
def test_a_fault_of_the_mechanism_fails_by_the_logits(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    kw = dict(rounding=jnp.float8_e4m3fn) if fault.startswith("fp8") else dict(rounding=jnp.bfloat16, fault=fault)
    served = ref.forward(params, geo, tokens, positions, **kw)[0]
    ratio, margin, ok, said = judged(tiny, served, own_choices(params, geo, tokens, kw["rounding"], kw.get("fault")))
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K}), "
              f"regret / score_floor {margin:.3g} (limit {2 * correct.K})")
    assert not ok and "max logit error" in said and ratio > 1.2 * correct.K
    if fault in ("window_ignored", "default_rope_in_full", "fp8_in_place_of_bf16"):
        assert ratio > 1.6 * correct.K  # the issue's controls, here as on the chip


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)[0]
    ratio, margin, ok, _ = judged(tiny, served, own_choices(params, geo, tokens, jnp.bfloat16))
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()[CELL]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("mellum2-12b-a2.5b", "mixedlen", 1)
    cfg = cell.config
    assert cfg["reference"] == "mellum" and cfg["num_hidden_layers"] == 8
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    # the catalog row's widths, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2304, 32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]) == (64, 8, 896)
    assert (cfg["sliding_window"], cfg["vocab_size"], cfg["intermediate_size"]) == (1024, 98304, 7168)
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} <= reported
    assert {"decode.window_attn_dev_ms.tok_s", "decode.full_attn_dev_ms.tok_s", "chunk.window_attn_dev_ms.tok_s",
            "chunk.full_attn_dev_ms.tok_s", "kernel.window_paged_attn_roofline",
            "kernel.window_prefill_attn_roofline", "step.window_stream_roofline", "attn.window_attended_share",
            "kv.window_pool_used_share", "moe.expert_stream_roofline", "moe.rows_per_expert",
            "kv.preemptions", "decode.attn_dev_ms.tok_s", "chunk.attn_dev_ms.tok_s"} <= reported
    assert not {"kernel.paged_attn_roofline", "kv.latent_pool_used_share", "attn.attended_share"} & reported
    # the mix: two tenants in one queue
    tenants = {t["name"]: t for t in cell.traffic["tenants"]}
    assert (tenants["editor"]["prompt"]["min"], tenants["editor"]["prompt"]["max"]) == (256, 2048)
    assert (tenants["repo"]["prompt"]["min"], tenants["repo"]["prompt"]["max"]) == (8192, 15360)
    assert all(t["weight"] == 0.5 and (t["output"]["min"], t["output"]["max"]) == (256, 768) for t in tenants.values())
    assert cell.spec["clients"] == cell.spec["prestart"] == cfg["tpu_config"]["batch_size"] == 48
    # every TpuConfig option the file names as left at its default exists today
    import dataclasses

    from neuronx_distributed_inference_tpu.config import TpuConfig

    fields = {f.name for f in dataclasses.fields(TpuConfig)}
    named = [w.strip(" ,()") for w in cfg["why"]["left_at_default"].split(":")[0].split(",")]
    assert named and all(n in fields or n.startswith("every ") for n in named), named


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_window_reader_counts_its_own_work_and_reads_nothing_where_there_is_none():
    from benchmark.harness.readers import window_roofline as reader

    cell = catalog.load_cell(CELL)
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer if m["reader"]["reader"] == "window_roofline"}
    assert set(readers) == {
        "decode.window_attn_dev_ms.tok_s", "decode.full_attn_dev_ms.tok_s", "chunk.window_attn_dev_ms.tok_s",
        "chunk.full_attn_dev_ms.tok_s", "kernel.window_paged_attn_roofline",
        "kernel.window_prefill_attn_roofline", "step.window_stream_roofline", "kv.window_pool_used_share"}
    assert reader.key_bytes(attrs) == 2048 and reader.pair_flops(attrs) == 4 * 32 * 128
    # this issue's arithmetic: 417.75 M a layer, embedding aside 226.5 M of head: 7.14 GB a dispatch
    assert reader.weight_bytes(attrs) / 2 == pytest.approx(8 * 417.75e6 + 226.5e6, rel=2e-3)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "module_sums": {"jit_token_generation_model_decode(1)": (10, 0.16)}}
    fam = lambda dec, chunk: [({"program": "decode", "layer_kind": "window"}, dec * 0.25),
                              ({"program": "decode", "layer_kind": "full"}, dec * 0.75),
                              ({"program": "chunk", "layer_kind": "window"}, chunk * 0.5),
                              ({"program": "chunk", "layer_kind": "full"}, chunk * 0.5)]
    before = _snapshot(nxdi_attn_keys_attended_total=fam(0.0, 0.0), nxdi_attn_keys_live_total=fam(0.0, 0.0),
                       nxdi_steps_total=[({"kind": "decode"}, 0.0)],
                       nxdi_prefill_chunk_dispatches_total=[({}, 0.0)])
    after = _snapshot(nxdi_attn_keys_attended_total=fam(20 * 9e5, 40 * 3e6),
                      nxdi_attn_keys_live_total=fam(20 * 24e5, 40 * 9e6),
                      nxdi_steps_total=[({"kind": "decode"}, 20.0)],
                      nxdi_prefill_chunk_dispatches_total=[({}, 40.0)],
                      nxdi_kv_window_blocks_held=[({}, 44 * 37 * 6.0)],
                      nxdi_kv_window_blocks_total=[({}, 48 * 37 * 6.0)])
    table = {"decode": {"dispatches": 10, "tabled": 10, "op_s": 0.15,
                        "by_scope": {"layer.attn": 0.03, "layer.mlp": 0.1}},
             "chunk": {"dispatches": 20, "tabled": 20, "op_s": 0.5, "by_scope": {"layer.attn": 0.1}}}
    kinds = {"decode": {"dispatches": 10, "tabled": 10, "op_s": 0.15,
                        "by_scope": {"layer.attn.window": 0.012, "layer.attn.full": 0.018, "layer.mlp": 0.1}},
             "chunk": {"dispatches": 20, "tabled": 20, "op_s": 0.5,
                       "by_scope": {"layer.attn.window": 0.04, "layer.attn.full": 0.06}}}
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "counters": {"before": before, "after": after},
           "device_scope_table": table, "window_kind_table": kinds}
    assert reader.read(readers["kernel.window_paged_attn_roofline"], ctx) == pytest.approx(
        100 * (9e5 * 2048 / 819e9) / 0.003)
    assert reader.read(readers["kernel.window_prefill_attn_roofline"], ctx) == pytest.approx(
        100 * (3e6 * 16384 / 197e12) / 0.005)
    assert reader.read(readers["step.window_stream_roofline"], ctx) == pytest.approx(
        100 * ((reader.weight_bytes(attrs) + 9e5 * 2048) / 819e9) / 0.016)
    assert reader.read(readers["decode.window_attn_dev_ms.tok_s"], ctx) == pytest.approx(1.2)
    assert reader.read(readers["decode.full_attn_dev_ms.tok_s"], ctx) == pytest.approx(1.8)
    assert reader.read(readers["chunk.window_attn_dev_ms.tok_s"], ctx) == pytest.approx(2.0)
    assert reader.read(readers["chunk.full_attn_dev_ms.tok_s"], ctx) == pytest.approx(3.0)
    assert reader.read(readers["kv.window_pool_used_share"], ctx) == pytest.approx(100 * 44 / 48)
    from benchmark.harness.readers import counter_ratio

    share = next(m["reader"] for m in cell.per_layer if m["name"] == "attn.window_attended_share")
    assert counter_ratio.read(share, ctx) == pytest.approx(100 * (20 * 9e5 + 40 * 3e6) / (20 * 24e5 + 40 * 9e6))
    # a program without the counters, the gauges or the kinds (the parent commit), another model: nothing
    bare = dict(ctx, counters={"before": {}, "after": {}})
    assert all(reader.read(r, bare) is None for n, r in readers.items() if "attn_dev_ms" not in n)
    assert counter_ratio.read(share, bare) is None
    unkinded = dict(ctx, window_kind_table=None)
    assert reader.read(readers["decode.window_attn_dev_ms.tok_s"], unkinded) is None
    other = dict(ctx, attrs={"num_hidden_layers": 28, "hidden_size": 2048})
    assert all(reader.read(r, other) is None for r in readers.values())
    assert all(reader.read(r, dict(ctx, trace=None, counters=None)) is None for r in readers.values())
