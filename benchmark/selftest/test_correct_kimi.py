"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the configuration ``kimi-vl-a3b``, at its
``rehearsal`` preset widened to hidden 1024 on the CPU: a ``deepseek_v3``
stack of one dense and three expert layers (latent attention; 8 experts,
top-2, one shared) through the probe's ``ServingSession`` and the teacher-forced chunks —
the latent pool, the absorbed attention of both step programs — against
``references/deepseek_mla.py`` (the expanded form), with the weights the
configuration's own ``weights`` rules give.

- a sound program passes, logits and margins, and its routing spreads;
- a fault of each new part fails it by a named rule: rotary on the nope
  dimensions, the latent's norm dropped, ``b`` added to the weights and not
  only to the choice, the scaling factor dropped, the shared MLP dropped, the
  value read from the joined key's last lanes (the reference's equations with
  the fault, rounded as the twin is, in the program's place, reporting the
  choices it made), and the CONTROL, the reference itself in fp8-e4m3."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import deepseek_mla as ref

SEED = 4400000535
PROMPT = 256  # two whole chunks of 128 over the latent pool


def tiny_config() -> dict:
    """The rehearsal preset at hidden 1024: the projections' scale, and with
    it how sharply a query picks its keys, goes with the hidden width (the
    configuration's ``why.weights``); at 256 attention is near uniform and an
    attention fault reads 2-4 x the floor where it reads 8-13 here."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "kimi-vl-a3b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=1024)
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


def test_a_sound_program_passes_and_its_routing_spreads(tiny, capsys):
    cfg, devices, params, pspecs, geo, *_ = tiny
    assert cfg["probe_tpu_config"] == {"output_choices": True} and ref.CHOICES
    assert (geo.first_dense, geo.layers, geo.experts, geo.top_k, geo.shared) == (1, 4, 8, 2, 1)
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "deepseek_mla"
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    # the weights rules: every expert of a layer is taken (top-2 of 8: uniform is a quarter of the
    # tokens; a selection bias of 0.1 beside scores that spread by ~0.2 puts the busiest expert
    # well over uniform: the configuration's `why.weights` says what that costs)
    taken = choices[0][ref.NAME]  # (tokens, expert layers, 2)
    assert taken.shape == (PROMPT + correct.PROBE_DECODE_STEPS, 3, 2)
    share = [np.bincount(taken[:, l].ravel(), minlength=geo.experts).max() / len(taken) / 2 * geo.experts
             for l in range(3)]
    with capsys.disabled():
        print("\nkimi-vl-a3b rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]],
              "busiest expert's share of a layer's routed rows, x uniform:", np.round(share, 2).tolist())
    assert max(share) < 3.0 and all(len(set(taken[:, l].ravel())) == geo.experts for l in range(3))


#: the rule each fault must fail by: a fault of the forward pass moves the
#: logits (``err <= K x floor``); none of these is a wrong CHOICE among
#: right scores, so none is left to the margin alone
RULE = "max logit error"


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8_in_place_of_bf16",))
def test_a_fault_of_each_new_part_fails_by_a_named_rule(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    if fault.startswith("fp8"):
        kw = dict(rounding=jnp.float8_e4m3fn)
    else:
        kw = dict(rounding=jnp.bfloat16, fault=fault)
    served, _, own = ref.forward(params, geo, tokens, positions, **kw)
    ratio, margin, ok, said = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K}), "
              f"regret / score_floor {margin:.3g} (limit {2 * correct.K})")
    assert not ok and RULE in said
    assert ratio > 1.8 * correct.K


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served, _, own = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)
    ratio, margin, ok, _ = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()["kimi-vl-a3b.longctx"]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("kimi-vl-a3b", "longctx", 1)
    assert cell.config["reference"] == "deepseek_mla" and cell.config["num_hidden_layers"] == 7
    assert cell.config["first_k_dense_replace"] == 1 and cell.config["reduced"] == ["num_hidden_layers"]
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    # the cell file lists what the cell reported when it was written; a later PR appends to BENCHMARK.json
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} <= reported
    assert {"kernel.latent_attn_roofline", "kernel.latent_prefill_roofline", "decode.attn_dev_ms.tok_s",
            "chunk.attn_dev_ms.tok_s", "decode.absorb_dev_ms.tok_s", "kv.latent_pool_used_share",
            "moe.rows_per_expert", "moe.expert_stream_roofline", "kv.preemptions"} <= reported
    assert "kernel.paged_attn_roofline" not in reported


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_latent_reader_counts_latents_and_reads_nothing_where_there_are_none():
    from benchmark.harness.readers import latent_roofline

    cell = catalog.load_cell("kimi-vl-a3b.longctx")
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer if m["reader"]["reader"] == "latent_roofline"}
    assert latent_roofline.latent_bytes_per_token(attrs) == 7 * 576 * 2 == 8064
    assert latent_roofline.expanded_pair_flops(attrs) == 2 * (192 + 128) * 16 * 7
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "span_counts": {"step": 2},
             "op_sums": {"paged_latent_decode_attention.1": (14, 0.010),
                         "paged_latent_flash_attention.3": (7, 0.020), "fusion.9": (5, 1.0)}}
    samples = {"live_kv_tokens": [(0.5, 1.0), (1.0, 280000.0), (2.0, 281000.0), (3.0, 9.0)],
               "prefill_qk_pairs": [(0.5, 1.0), (1.0, 2.0e6), (2.0, 0.0), (3.0, 9.0)]}
    gauges = _snapshot(nxdi_kv_pool_bytes=[({}, 4.0e9)], nxdi_kv_free_bytes=[({}, 1.0e9)])
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "slice": (1.0, 3.0), "samples": samples,
           "counters": {"before": gauges, "after": gauges}}
    dec = latent_roofline.read(readers["kernel.latent_attn_roofline"], ctx)
    assert dec == pytest.approx(100 * (561000 * 8064 / 819e9) / 0.010)
    pre = latent_roofline.read(readers["kernel.latent_prefill_roofline"], ctx)
    assert pre == pytest.approx(100 * (2.0e6 * 71680 / 197e12) / 0.020)
    assert latent_roofline.read(readers["kv.latent_pool_used_share"], ctx) == pytest.approx(75.0)
    # a program without the kernels, the gauges, or the model's keys: nothing, and no error
    bare = dict(ctx, trace=dict(trace, op_sums={"fusion.9": (5, 1.0)}), counters={"before": {}, "after": {}})
    assert all(latent_roofline.read(r, bare) is None for r in readers.values())
    other = dict(ctx, attrs={"num_hidden_layers": 28, "hidden_size": 2048})
    assert all(latent_roofline.read(r, other) is None for r in readers.values())
    assert all(latent_roofline.read(r, dict(ctx, trace=None, counters=None)) is None for r in readers.values())
