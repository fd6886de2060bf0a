"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the configuration ``nemotron-3-nano-30b-a3b``, at its
``rehearsal`` preset widened on the CPU: a ``nemotron_h`` stack ``MEM*EME``
(Mamba-2 with 4 groups of B/C, top-2 of 8 two-matrix relu^2 experts of which 4
are held, a shared expert, NoPE GQA) through the probe's ``ServingSession`` and
the teacher-forced chunks — ``HybridBlockCache``, the chunk scan with its
carry, the state kernel, the held share in both expert strategies — against
``references/nemotron_h.py``, with the weights the configuration's own
``weights`` rules give.

- a sound program passes, logits and margins, and its routing spreads;
- a fault in each of the four parts fails it by a named rule: every head of a
  state-space block reading group 0's B and C, the gated norm over one group,
  the state not carried from one chunk to the next, the routed experts' relu
  not squared, the shared expert dropped, q and k of
  an attention block rotated (the reference's equations with the fault,
  rounded as the twin is, in the program's place, reporting the choices it
  made); so does a WRONG SHARE (experts 4-7 computed where the reference holds
  0-3), and the CONTROL, the reference itself in fp8-e4m3."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import nemotron_h as ref

SEED = 4700000535
PROMPT = 256  # two whole chunks of 128 with a carry between them
CELL = "nemotron-3-nano-30b-a3b.longctx"


def tiny_config() -> dict:
    """The rehearsal preset at hidden 1024 and seven blocks: a projection's
    scale goes with the hidden width (the configuration's ``why.weights``),
    and every kind of block stands at least once before an expert block."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=1024, num_hidden_layers=7, hybrid_override_pattern="MEM*EME",
               moe_intermediate_size=96, moe_shared_expert_intermediate_size=192)
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
    """(err / floor, the worst block's regret / score_floor, passed, the message)."""
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
    assert (geo.pattern, geo.experts, geo.held, geo.first, geo.top_k, geo.m_groups) == ("MEM*EME", 8, 4, 0, 2, 4)
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "nemotron_h"
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    taken = choices[0][ref.NAME]  # (tokens, expert blocks, 2) over the PUBLISHED width
    assert taken.shape == (PROMPT + correct.PROBE_DECODE_STEPS, 3, 2)
    share = [np.bincount(taken[:, l].ravel(), minlength=geo.experts).max() / len(taken) / 2 * geo.experts
             for l in range(3)]
    with capsys.disabled():
        print("\nnemotron rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]],
              "busiest expert's share of a block's routed rows, x uniform:", np.round(share, 2).tolist())
    # the experts of the published width are taken, those held elsewhere too, but at most one a
    # block whose selection bias lies two deviations down; at this width the bias (0.1) is nearly
    # as large as the scores' spread (router logits of deviation 0.64), so a block's favourite is in
    # most tokens' two (4.0 = every token); at the published width the busiest takes 2.6-4.0 x
    # uniform of a possible 5.3 (the configuration's why.weights)
    assert max(share) < 4.0 and all(len(set(taken[:, l].ravel())) >= geo.experts - 1 for l in range(3))
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
    assert ratio > 1.8 * correct.K


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served, _, own = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)
    ratio, margin, ok, _ = judged(tiny, served, {ref.NAME: np.transpose(own, (1, 0, 2))})
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_weights_rules_do_what_their_why_says(tiny):
    """Slow decay (dt ~ 0.007: a state that remembers more than a chunk), A
    spread over heads, D about 1, conv taps of unit size, sharp attention, a
    selection bias that moves choices, an embedding that stays in the state."""
    import jax

    cfg, _, params, *_ = tiny
    m = params["layers"]["mamba"]["mixer"]
    f = lambda a: np.asarray(a, np.float32)
    dt = np.log1p(np.exp(f(m["dt_bias"])))
    assert 0.002 < np.median(dt) < 0.02 and f(m["dt_proj"]["weight"]).std() < 0.004
    assert 0.3 < f(m["A_log"]).std() < 0.7 and abs(f(m["D"]).mean() - 1) < 0.1
    assert 0.4 < f(m["conv1d"]["weight"]).std() < 0.6
    assert 0.08 < f(params["layers"]["attention"]["self_attn"]["q_proj"]["weight"]).std() < 0.12
    assert 0.07 < f(params["layers"]["moe"]["mlp"]["router"]["e_score_correction_bias"]).std() < 0.13
    assert 0.45 < f(params["embed_tokens"]["weight"]).std() < 0.55
    assert abs(f(params["layers"]["moe"]["mlp"]["experts"]["up_proj"]["weight"]).std() - 0.02) < 0.002


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()[CELL]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("nemotron-3-nano-30b-a3b", "longctx", 1)
    cfg = cell.config
    assert cfg["reference"] == "nemotron_h" and cfg["num_hidden_layers"] == 13
    assert cfg["hybrid_override_pattern"] == "MEMEM*EMEMEM*" and cfg["n_routed_experts"] == 64
    assert cfg["n_routed_experts_published"] == 128 and cfg["expert_share"] == {"first": 0, "of": 2}
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"]
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} <= reported
    assert {"kernel.ssm_group_update_roofline", "moe.held_expert_stream_roofline", "chunk.ssm_dev_ms.tok_s",
            "decode.ssm_dev_ms.tok_s", "chunk.moe_experts_dev_ms.tok_s", "kv.preemptions"} <= reported
    assert not {"moe.expert_stream_roofline", "moe.rows_per_expert", "kernel.ssm_update_roofline",
                "kernel.paged_attn_roofline"} & reported
    # the same mix, request for request, as kimi-vl-a3b.longctx
    kimi = catalog.load_cell("kimi-vl-a3b.longctx")
    assert cell.traffic == kimi.traffic
    assert {k: cell.spec[k] for k in ("loop", "clients", "prestart")} == \
        {k: kimi.spec[k] for k in ("loop", "clients", "prestart")}


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_new_readers_count_needed_bytes_and_read_nothing_where_there_is_nothing():
    from benchmark.harness.readers import held_moe_roofline, ssm_group_roofline

    cell = catalog.load_cell(CELL)
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer}
    # 6 blocks x (64 x 64 x 128 x 4 B + 3 x 6144 x 2 B)
    assert ssm_group_roofline.state_bytes_per_row(attrs) == 6 * (2097152 + 36864)
    assert held_moe_roofline.expert_bytes(attrs) == 2 * 2688 * 1856 * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "span_counts": {"step": 2},
             "module_sums": {"jit_token_generation_model_decode(123)": (10, 0.150)},
             "op_sums": {"ssm_state_update.3": (60, 0.020), "fusion.9": (5, 1.0)}}
    before = _snapshot(nxdi_ssm_rows_advanced_total=[({"program": "decode"}, 100.0)],
                       nxdi_moe_experts_hit_total=[({"program": "decode"}, 3200.0)],
                       nxdi_steps_total=[({"kind": "decode"}, 10.0)])
    after = _snapshot(nxdi_ssm_rows_advanced_total=[({"program": "decode"}, 100.0 + 20 * 62)],
                      nxdi_moe_experts_hit_total=[({"program": "decode"}, 3200.0 + 20 * 320)],
                      nxdi_steps_total=[({"kind": "decode"}, 30.0)])
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "counters": {"before": before, "after": after}}
    ssm = ssm_group_roofline.read(readers["kernel.ssm_group_update_roofline"], ctx)
    assert ssm == pytest.approx(100 * (62 * 10 * 2 * 6 * 2134016 / 819e9) / 0.020)
    held = held_moe_roofline.read(readers["moe.held_expert_stream_roofline"], ctx)
    assert held == pytest.approx(100 * (320 * 10 * 19955712 / 819e9) / 0.150) and held < 100
    # a program without the kernel or the counters, another family's keys, no trace: nothing, no error
    bare = dict(ctx, trace=dict(trace, op_sums={"fusion.9": (5, 1.0)}))
    assert ssm_group_roofline.read(readers["kernel.ssm_group_update_roofline"], bare) is None
    for r, mod in (("kernel.ssm_group_update_roofline", ssm_group_roofline),
                   ("moe.held_expert_stream_roofline", held_moe_roofline)):
        assert mod.read(readers[r], dict(ctx, counters={"before": {}, "after": {}})) is None
        assert mod.read(readers[r], dict(ctx, attrs={"num_hidden_layers": 28, "hidden_size": 2048,
                                                     "moe_intermediate_size": 768})) is None
        assert mod.read(readers[r], dict(ctx, trace=None, counters=None)) is None
