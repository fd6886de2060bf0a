"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the configuration ``kimi-linear-48b-a3b``, at its
``rehearsal`` preset widened on the CPU: a ``kimi_linear`` stack of four
layers (KDA, KDA, KDA, MLA; a dense MLP then top-2 of 8 experts of which 4
are held, a shared expert) through the probe's ``ServingSession`` and the
teacher-forced chunks — ``HybridBlockCache`` with a latent pool beside the
delta-rule state, the chunked form with its carry, the state kernel, MLA
without rotation, the held share in both expert strategies — against
``references/kimi_linear.py``, with the weights the configuration's own
``weights`` rules give.

- a sound program passes, logits and margins, and its routing spreads;
- a fault in each part fails it by a named rule: the decay one number a head,
  the state not read before its write, the state not carried from one chunk
  to the next, q and k not normalised, the MLA layer rotated, the output gate
  dropped, the shared expert dropped (the reference's equations with the
  fault, rounded as the twin is, in the program's place, reporting the
  choices it made); so does a WRONG SHARE (experts 4-7 computed where the
  reference holds 0-3), and the CONTROL, the reference itself in fp8-e4m3."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import kimi_linear as ref

SEED = 6100000535
PROMPT = 256  # two whole chunks of 128 with a carry between them
CELL = "kimi-linear-48b-a3b.longgen"


def tiny_config() -> dict:
    """The rehearsal preset at hidden 1024 with KDA heads of 64: a
    projection's scale goes with the hidden width (the configuration's
    ``why.weights``)."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "kimi-linear-48b-a3b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=1024, intermediate_size=512, moe_intermediate_size=96)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], num_heads=8, head_dim=64)
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
    assert (geo.mixers, geo.first_dense, geo.experts, geo.held, geo.first, geo.top_k) == (
        ("kda", "kda", "kda", "mla"), 1, 8, 4, 0, 2)
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "kimi_linear"
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    taken = choices[0][ref.NAME]  # (tokens, expert layers, 2) over the PUBLISHED width
    assert taken.shape == (PROMPT + correct.PROBE_DECODE_STEPS, 3, 2)
    with capsys.disabled():
        print("\nkimi-linear rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]])
    assert all(len(set(taken[:, l].ravel())) >= geo.experts - 1 for l in range(3))
    assert all((taken[:, l] >= geo.held).any() for l in range(3))


#: a fault of the forward pass moves the logits (``err <= K x floor``)
RULE = "max logit error"


@pytest.mark.parametrize("fault", ref.FAULTS + ("wrong_share", "fp8_in_place_of_bf16"))
def test_a_fault_in_each_part_and_a_wrong_share_fail_by_a_named_rule(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    if fault.startswith("fp8"):
        kw = dict(rounding=jnp.float8_e4m3fn)
    elif fault == "wrong_share":  # the program computing experts 4-7 with the weights of 0-3's place
        kw = dict(rounding=jnp.bfloat16, first=geo.held)
    else:
        kw = dict(rounding=jnp.bfloat16, fault=fault)
    served, _, own = ref.forward(params, geo, tokens, positions, **kw)
    ratio, margin, ok, said = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K}), "
              f"regret / score_floor {margin:.3g} (limit {2 * correct.K})")
    assert not ok and RULE in said
    # the rotation of ONE latent layer over 256 positions, the last of four layers, reads 2.15 here
    assert ratio > (1.3 if fault == "mla_rotated" else 1.8) * correct.K


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served, _, own = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)
    ratio, margin, ok, _ = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_weights_rules_do_what_their_why_says(tiny):
    """Slow decay (softplus(dt_bias) ~ 0.007 a channel: a state that remembers
    more than a chunk) that differs by channel and by head, conv taps of unit
    output, a step size spread over (0, 1), sharp latent attention, a
    selection bias that moves choices, an embedding that stays in the state."""
    cfg, _, params, *_ = tiny
    m = params["layers"]["kda"]["mixer"]
    f = lambda a: np.asarray(a, np.float32)
    dt = np.log1p(np.exp(f(m["dt_bias"])))
    assert 0.002 < np.median(dt) < 0.02 and dt.max() / dt.min() > 5
    assert 0.3 < f(m["A_log"]).std() < 0.7
    assert 0.4 < f(m["conv1d"]["weight"]).std() < 0.6
    assert 0.03 < f(m["b_proj"]["weight"]).std() < 0.05
    sa = params["layers"]["mla"]["self_attn"]
    assert 0.08 < f(sa["q_proj"]["weight"]).std() < 0.12 and abs(f(sa["kv_a_layernorm"]["weight"]).mean() - 2) < 0.1
    assert 0.07 < f(params["layers"]["moe"]["mlp"]["router"]["e_score_correction_bias"]).std() < 0.13
    assert 0.45 < f(params["embed_tokens"]["weight"]).std() < 0.55
    assert abs(f(params["layers"]["moe"]["mlp"]["experts"]["up_proj"]["weight"]).std() - 0.02) < 0.002


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()[CELL]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("kimi-linear-48b-a3b", "longgen", 1)
    cfg = cell.config
    assert cfg["reference"] == "kimi_linear" and cfg["num_hidden_layers"] == 16
    assert cfg["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16]
    assert len(cfg["linear_attn_config"]["kda_layers"]) == 12 and cfg["linear_attn_config"]["head_dim"] == 128
    assert cfg["num_experts"] == 16 and cfg["num_experts_published"] == 256
    assert cfg["expert_share"] == {"first": 0, "of": 16} and cfg["vocab_size"] == 20480
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} <= reported
    assert {"kernel.kda_update_roofline", "decode.kda_dev_ms.tok_s", "chunk.kda_dev_ms.tok_s",
            "kv.latent_pool_used_share", "moe.expert_stream_roofline", "kv.preemptions"} <= reported
    assert not {"moe.held_expert_stream_roofline", "moe.rows_per_expert", "kernel.latent_attn_roofline",
                "kernel.latent_prefill_roofline", "kernel.ssm_group_update_roofline"} & reported
    mix = cell.traffic
    assert mix["first_round"] == "mid_decode" and mix["arrivals"] == {"kind": "constant"}
    tenant, = mix["tenants"]
    assert (tenant["prompt"]["min"], tenant["prompt"]["max"]) == (512, 2048)
    assert (tenant["output"]["min"], tenant["output"]["max"]) == (2048, 6144) and tenant["shared_prefix_len"] == 0
    assert (cell.spec["loop"], cell.spec["clients"], cell.spec["prestart"]) == ("closed", 128, 128)
    assert cell.config["tpu_config"]["batch_size"] == 128


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_new_reader_counts_needed_bytes_and_reads_nothing_where_there_is_nothing():
    from benchmark.harness.readers import kda_roofline

    cell = catalog.load_cell(CELL)
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer}
    # 12 layers x (32 x 128 x 128 x 4 B + 3 x 12288 x 2 B)
    assert kda_roofline.state_bytes_per_row(attrs) == 12 * (2097152 + 73728)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "span_counts": {"step": 2},
             "module_sums": {"jit_token_generation_model_decode(123)": (10, 0.300)},
             "op_sums": {"kda_state_update.3": (120, 0.160), "fusion.9": (5, 1.0)}}
    before = _snapshot(nxdi_kda_rows_advanced_total=[({"program": "decode"}, 100.0)],
                       nxdi_steps_total=[({"kind": "decode"}, 10.0)])
    after = _snapshot(nxdi_kda_rows_advanced_total=[({"program": "decode"}, 100.0 + 20 * 126)],
                      nxdi_steps_total=[({"kind": "decode"}, 30.0)])
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "counters": {"before": before, "after": after}}
    share = kda_roofline.read(readers["kernel.kda_update_roofline"], ctx)
    assert share == pytest.approx(100 * (126 * 10 * 2 * 12 * 2170880 / 819e9) / 0.160) and share < 100
    # a program without the kernel or the counter, another family's keys, no trace: nothing, no error
    bare = dict(ctx, trace=dict(trace, op_sums={"fusion.9": (5, 1.0)}))
    reader = readers["kernel.kda_update_roofline"]
    assert kda_roofline.read(reader, bare) is None
    assert kda_roofline.read(reader, dict(ctx, counters={"before": {}, "after": {}})) is None
    assert kda_roofline.read(reader, dict(ctx, attrs={"num_hidden_layers": 28, "hidden_size": 2048})) is None
    assert kda_roofline.read(reader, dict(ctx, trace=None, counters=None)) is None
