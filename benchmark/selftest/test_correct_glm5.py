"""The rule that decides ``correct`` (``harness/correct.py``, unchanged:
replay, then margin) on the configuration ``glm-5``, at its ``rehearsal``
preset widened to hidden 512 on the CPU: a ``glm_moe_dsa`` stack of one dense
and two expert layers (latent attention behind an indexer's top-64; 4 of 8
experts held, top-2, one shared) through the probe's ``ServingSession`` and
the teacher-forced chunks -- the three-stream pool, the selection and the
attention over the chosen keys in both step programs -- against
``references/glm_dsa.py``, with the weights the configuration's own
``weights`` rules give. TWO kinds of choice are replayed: the experts' and
the selection of keys.

- a sound program passes, logits and both kinds of margin;
- the faults ``correct`` must fail for this configuration, each by a named
  rule (the reference's equations with the fault, rounded as the twin is, in
  the program's place, reporting the choices it made): a dense attention
  (every live token attended: the logits move, and the keys it can report
  are no top-k); a halved top-k (replayed, the logits agree, and the best 32
  of 64 leave no better key out: the margin fails it by the SIZE of the set);
  the ReLU, the indexer's rotation or the q latent's norm left out, and a
  selection that is not the indexer's (the LOWEST scores taken): replayed,
  the logits agree and the MARGIN fails; and the CONTROL, the reference
  itself in fp8-e4m3 (the logits)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import glm_dsa as ref

SEED = 5400000535
PROMPT = 256  # two whole chunks of 128, four times the preset's index_topk


def tiny_config() -> dict:
    """The rehearsal preset at hidden 512: how sharply a query picks its
    keys goes with the hidden width (the configuration's ``why.weights``)."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "glm-5.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=512)
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
    """What a program that runs the reference's equations (with ``fault``)
    reports: the experts it took and the keys it attended."""
    sets = []
    _, _, experts = ref.forward(params, geo, tokens, [0], None, rounding, fault,
                                per_layer=lambda l, h, w, sel: sets.append(np.asarray(sel)))
    S, k = len(tokens), geo.index_topk
    keys = np.full((S, geo.layers, k), -1, np.int32)
    for l, sel in enumerate(sets):
        for t in range(S):
            at = np.flatnonzero(sel[t])[:k]
            keys[t, l, : len(at)] = at
    return {ref.EXPERTS: np.transpose(experts, (1, 0, 2)), ref.SELECTION: keys}


def test_a_sound_program_passes_both_kinds_of_margin(tiny, capsys):
    cfg, devices, params, pspecs, geo, *_ = tiny
    assert cfg["probe_tpu_config"] == {"output_choices": True} and ref.CHOICES
    assert (geo.first_dense, geo.layers, geo.experts, geo.held, geo.top_k) == (1, 3, 8, 4, 2)
    assert (geo.index_heads, geo.index_dim, geo.index_topk) == (2, 16, 64)
    prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    facts = correct.judge(cfg, params, 1, prompts, chosen, served, choices)
    assert facts["reference"] == "glm_dsa"
    assert set(choices[0]) == {ref.EXPERTS, ref.SELECTION}
    assert choices[0][ref.SELECTION].shape == (PROMPT + correct.PROBE_DECODE_STEPS, 3, 64)
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
        assert len(row["choice_regret"]) == 2 + 3  # the expert layers, then every layer's keys
        assert all(r <= lim for r, lim in zip(row["choice_regret"], row["choice_limit"]))
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    with capsys.disabled():
        print("\nglm-5 rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]],
              "regret / score_floor", [np.round(np.divide(r["choice_regret"], r["choice_score_floor"]), 2).tolist()
                                       for r in facts["rows"]])


#: the rule each fault of the forward pass must fail by: it moves the logits
RULE = "max logit error"
FORWARD_FAULTS = ("attend_all", "topk_halved", "relu_dropped", "index_rotary_dropped",
                  "index_q_unnormed", "fp8_in_place_of_bf16")


@pytest.mark.parametrize("fault", FORWARD_FAULTS)
def test_a_fault_of_the_mechanism_fails_by_the_logits(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    if fault.startswith("fp8"):
        kw = dict(rounding=jnp.float8_e4m3fn)
    else:
        kw = dict(rounding=jnp.bfloat16, fault=fault)
    served = ref.forward(params, geo, tokens, positions, **kw)[0]
    # a dense attention reports the keys it attended: all of them, which no top-k holds; the
    # harness sees the first index_topk (what the program's output has room for)
    choices = own_choices(params, geo, tokens, kw["rounding"], kw.get("fault"))
    ratio, margin, ok, said = judged(tiny, served, choices)
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K}), "
              f"regret / score_floor {margin:.3g} (limit {2 * correct.K})")
    assert not ok and (RULE in said or "margin" in said)
    if fault in ("attend_all", "fp8_in_place_of_bf16"):
        assert RULE in said and ratio > 1.8 * correct.K
    else:  # the reference follows the keys the fault reports, so the logits agree: the margin holds it
        assert "margin" in said and margin > 2 * 2 * correct.K


def test_a_selection_that_is_not_the_indexers_fails_by_the_margin(tiny, capsys):
    """Every other line sound and the keys replayed, so the logits agree
    with the reference that follows them: only the margin can see it."""
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16,
                         fault="lowest_selected")[0]
    choices = own_choices(params, geo, tokens, jnp.bfloat16, "lowest_selected")
    ratio, margin, ok, said = judged(tiny, served, choices)
    with capsys.disabled():
        print(f"\nlowest_selected: err / floor {ratio:.3g}, regret / score_floor {margin:.3g}")
    assert not ok and "margin" in said and margin > 2 * correct.K
    assert ratio <= correct.K  # replayed: the logits are the twin's own


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    served = ref.forward(params, geo, tokens, positions, rounding=jnp.bfloat16)[0]
    ratio, margin, ok, _ = judged(tiny, served, own_choices(params, geo, tokens, jnp.bfloat16))
    assert ok and ratio == 1.0 and margin <= 2 * correct.K


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()["glm-5.sparsectx"]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("glm-5", "sparsectx", 1)
    cfg = cell.config
    assert cfg["reference"] == "glm_dsa" and cfg["num_hidden_layers"] == 5
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert cfg["n_routed_experts_published"] == 256 and cfg["expert_share"] == {"first": 0, "of": 16}
    assert (cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]) == (32, 128, 2048)
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} <= reported
    assert {"chunk.indexer_dev_ms.tok_s", "decode.indexer_dev_ms.tok_s", "chunk.select_dev_ms.tok_s",
            "decode.select_dev_ms.tok_s", "attn.attended_share", "kernel.sparse_latent_attn_roofline",
            "kernel.sparse_latent_prefill_roofline", "step.sparse_stream_roofline",
            "moe.expert_stream_roofline", "kv.latent_pool_used_share"} <= reported
    assert not {"kernel.latent_attn_roofline", "kernel.latent_prefill_roofline"} & reported
    # every TpuConfig option the file names as left at its default exists today
    from neuronx_distributed_inference_tpu.config import TpuConfig
    import dataclasses

    fields = {f.name for f in dataclasses.fields(TpuConfig)}
    named = [w.strip(" ,()") for w in cfg["why"]["left_at_default"].split(":")[0].split(",")]
    assert named and all(n in fields or n.startswith("every ") for n in named), named


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_sparse_reader_counts_its_own_work_and_reads_nothing_where_there_is_none():
    from benchmark.harness.readers import sparse_latent_roofline as reader

    cell = catalog.load_cell("glm-5.sparsectx")
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer
               if m["reader"]["reader"] == "sparse_latent_roofline"}
    assert set(readers) == {"kernel.sparse_latent_attn_roofline", "kernel.sparse_latent_prefill_roofline",
                            "step.sparse_stream_roofline"}
    assert reader.latent_bytes(attrs) == 1152 and reader.index_key_bytes(attrs) == 256
    assert reader.pair_flops(attrs) == (2 * 32 * 128, 2 * 64 * (256 + 256))
    # this issue's arithmetic: 174.4 M of attention a layer, a dense MLP of 226.5 M, router and
    # shared expert 39.3 M an expert layer, a head slice of 118.9 M
    fixed = reader.fixed_weight_bytes(attrs) / 2
    assert fixed == pytest.approx(5 * 174.4e6 + 226.5e6 + 4 * 39.3e6 + 118.9e6, rel=2e-3)
    assert reader.expert_bytes(attrs) == 3 * 6144 * 2048 * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "module_sums": {"jit_token_generation_model_decode(1)": (10, 0.25)}}
    before = _snapshot(
        nxdi_sparse_keys_scored_total=[({"program": "decode"}, 0.0), ({"program": "chunk"}, 0.0)],
        nxdi_sparse_keys_attended_total=[({"program": "decode"}, 0.0), ({"program": "chunk"}, 0.0)],
        nxdi_moe_experts_hit_total=[({"program": "decode"}, 0.0)],
        nxdi_steps_total=[({"kind": "decode"}, 0.0)], nxdi_prefill_chunk_dispatches_total=[({}, 0.0)])
    after = _snapshot(
        nxdi_sparse_keys_scored_total=[({"program": "decode"}, 20 * 32 * 12000 * 5.0),
                                       ({"program": "chunk"}, 40 * 5e6 * 5.0)],
        nxdi_sparse_keys_attended_total=[({"program": "decode"}, 20 * 32 * 2048 * 5.0),
                                         ({"program": "chunk"}, 40 * 1.5e6 * 5.0)],
        nxdi_moe_experts_hit_total=[({"program": "decode"}, 20 * 64.0)],
        nxdi_steps_total=[({"kind": "decode"}, 20.0)], nxdi_prefill_chunk_dispatches_total=[({}, 40.0)])
    table = {"decode": {"dispatches": 10, "tabled": 10, "op_s": 0.2,
                        "by_scope": {"layer.indexer": 0.05, "layer.select": 0.01, "layer.attn": 0.04,
                                     "layer.mlp": 0.1}},
             "chunk": {"dispatches": 20, "tabled": 20, "op_s": 2.0,
                       "by_scope": {"layer.indexer": 0.1, "layer.select": 0.04, "layer.attn": 1.5}}}
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "counters": {"before": before, "after": after},
           "device_scope_table": table}
    dec_bytes = 5 * 32 * (12000 * 256 + 2048 * 1152)
    assert reader.read(readers["kernel.sparse_latent_attn_roofline"], ctx) == pytest.approx(
        100 * (dec_bytes / 819e9) / 0.010)
    ops = 5 * (5e6 * 8192 + 1.5e6 * 65536)
    assert reader.read(readers["kernel.sparse_latent_prefill_roofline"], ctx) == pytest.approx(
        100 * (ops / 197e12) / (1.64 / 20))
    stream = reader.fixed_weight_bytes(attrs) + 64 * reader.expert_bytes(attrs) + dec_bytes
    assert reader.read(readers["step.sparse_stream_roofline"], ctx) == pytest.approx(
        100 * (stream / 819e9) / 0.025)
    # a program without the counters or the scopes (the parent commit), another model: nothing
    bare = dict(ctx, counters={"before": {}, "after": {}})
    assert all(reader.read(r, bare) is None for r in readers.values())
    unscoped = dict(ctx, device_scope_table=None)
    assert reader.read(readers["kernel.sparse_latent_attn_roofline"], unscoped) is None
    other = dict(ctx, attrs={"num_hidden_layers": 28, "hidden_size": 2048})
    assert all(reader.read(r, other) is None for r in readers.values())
    assert all(reader.read(r, dict(ctx, trace=None, counters=None)) is None for r in readers.values())
