"""The rule that decides ``correct`` (``harness/correct.py``, unchanged: the
probe drives passes the reference plans, a token is judged where it was
predicted, choices are replayed and every choosing layer, the reveal among
them, is held to its margin) on the block-step configuration
``sdar-30b-a3b``, at a small size on the CPU: a 4-layer SDAR-shaped model
(block 4, 4 denoise steps, top-4 of 16 experts) served by the REAL program
— the probe's ``ServingSession`` generating block by block, the 8-row chunk
program under the block-causal mask, the planned passes through
``app.forward`` — against ``references/sdar_moe.py``, with the weights the
configuration's own ``weights`` rules give.

- a sound program passes on 12 seeds, logits, the session's tokens where
  they were predicted, every expert layer's margin and the reveal's;
- a fault of each new part fails a rule, its ratio printed: in-block
  attention causal, the commit pass's K and V not kept, logits read one
  position early, top-k not renormalised (``max logit error``); the reveal
  taking the LEAST confident (``margin``, the reveal's layer alone); the
  CONTROL, the reference itself in fp8-e4m3 (``max logit error``): the
  reference's equations with the fault, rounded as the twin
  is, in the program's place, generating and reporting the choices it made;
- a block committed with a mask token in it is an error of the program, not
  a token; a mask id among generated tokens is a fault of the window;
- the catalog takes the new files and the two counter ratios read the
  program's counters, and nothing where there are none."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import sdar_moe as ref
from benchmark.harness.traffic import draw_ids

SEEDS = [4000000700 + 2 * k for k in range(12)]
MAX_PROMPT = 302  # two whole chunks of 128 and a partial one; 75 blocks of 4 and 2 tokens over
MASK = 1023


def tiny_config() -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "configs", "sdar-30b-a3b.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               num_hidden_layers=4, max_window_layers=4, vocab_size=1024, num_experts=16,
               num_experts_per_tok=4, moe_intermediate_size=128, mask_token_id=MASK,
               reserved_token_ids=[MASK])
    return cfg


@pytest.fixture(scope="module")
def tiny():
    """(cfg, devices, geometry, seed -> (params, pspecs))."""
    import jax

    cfg = tiny_config()
    devices = jax.devices()[:1]
    app = system.build_app(cfg, devices, SEEDS[0])
    made = {}

    def weights(seed):
        if seed not in made:
            made[seed] = system.make_weights(app, seed, cfg["weights"])
        return made[seed]

    return cfg, devices, ref.geometry(system.model_attrs(cfg), 1), weights


def use(facts):
    """Per row, regret / score_floor of every choosing layer (limit 2 K); the last is the reveal's."""
    return [[r / f if f else 0.0 for r, f in zip(row["choice_regret"], row["choice_score_floor"])]
            for row in facts["rows"]]


def test_a_sound_program_passes_on_twelve_seeds(tiny, capsys):
    cfg, devices, geo, weights = tiny
    assert cfg["probe_tpu_config"] == {"output_choices": True} and ref.PASSES and ref.CHOICES
    ratios, margins, reveal, regrets = [], [], [], []
    for seed in SEEDS:
        params, pspecs = weights(seed)
        facts = correct.check_model(cfg, devices, seed, params, pspecs, 1, MAX_PROMPT)
        assert facts["reference"] == "sdar_moe" and facts["prompts"] == [MAX_PROMPT, 100]
        long, short = facts["rows"]
        assert (long["prefill_len"], short["prefill_len"]) == (300, 100)
        # the long prompt's partial block takes 2 denoise passes and a commit, then a whole block;
        # the short prompt ends at a block's edge: two whole blocks of 4 denoise passes and a commit
        assert (long["passes"], short["passes"]) == (3 + 5, 10)
        assert all(len(u) == geo.dense.layers + 1 for u in use(facts))
        ratios += [row["ratio"] for row in facts["rows"]]
        margins += [max(u[:-1]) for u in use(facts)]
        reveal.append(use(facts)[1][-1])
        regrets.append(short["session_token_regret"] / short["floor"])
    with capsys.disabled():
        spread = lambda v: {"min": min(v), "median": float(np.median(v)), "max": max(v)}
        print(f"\nsound program, {len(SEEDS)} seeds: err / floor (limit {correct.K})", json.dumps(spread(ratios)),
              f"\nworst expert layer's regret / score_floor (limit {2 * correct.K})", json.dumps(spread(margins)),
              "\nthe reveal's regret / score_floor", json.dumps(spread(reveal)),
              "\nthe session's regret where a token was predicted, / floor", json.dumps(spread(regrets)))
    assert all(0.1 < r <= correct.K for r in ratios), sorted(ratios)


def in_the_programs_place(tiny, seed, rounding, fault=None):
    """``judge``'s arguments with the reference itself (``rounding``,
    ``fault``) where the program stands: it generates the short prompt's
    tokens and ``revealed_at``, runs the planned passes and reports its own
    choices."""
    cfg, _, geo, weights = tiny
    params, _ = weights(seed)
    rng = np.random.default_rng([int(seed), 7])
    prompts = [draw_ids(rng, cfg["vocab_size"], n, [MASK]).astype(np.int32)
               for n in (MAX_PROMPT, correct.PROBE_SHORT_PROMPT)]
    budget = ref.probe_budget(geo)
    seeded = [int(t) for t in draw_ids(rng, cfg["vocab_size"], budget, [MASK])]
    session, when, _ = ref.generate(params, geo, prompts[1], budget, rounding, fault)
    chosen = [seeded, session]
    plans = [ref.probe_passes(geo, prompts[0], seeded), ref.probe_passes(geo, prompts[1], session, when)]
    served, choices = [], []
    for p, (_, passes) in zip(prompts, plans):
        out = ref.replay(params, geo, p, passes, None, rounding, None if fault == "least_confident" else fault)
        served.append(out["logits"])
        choices.append({ref.NAME: np.transpose(out["chosen"], (1, 0, 2))})
    return cfg, params, 1, prompts, chosen, served, choices, plans


def judged(args) -> dict:
    try:
        return correct.judge(*args)
    except correct.CorrectnessError as e:
        return {"error": str(e), **e.facts}


def test_the_twin_itself_is_at_the_floor(tiny):
    import jax.numpy as jnp

    facts = judged(in_the_programs_place(tiny, SEEDS[0], jnp.bfloat16))
    assert "error" not in facts and [row["ratio"] for row in facts["rows"]] == [1.0, 1.0]
    assert max(max(u) for u in use(facts)) <= 2 * correct.K


#: read at this size (PR 39, CPU; the worse row's err / floor, limit K = 1.5): causal_in_block 4.17,
#: commit_skipped 2.57 (the last denoise pass differs from the commit in ONE position's token),
#: read_early 6.84, not_renormalised 2.39, the fp8 control 3.92. The configuration's weights rule
#: (q/k norm weights of 2.5: peaked attention) raises the bf16 twin's own floor at a model this
#: small, so the ratios are lower than the dense selftest's; the chip's readings are in PERF.md.
LOGIT_FAULTS = ("causal_in_block", "commit_skipped", "read_early", "not_renormalised", "fp8_in_place_of_bf16")


@pytest.mark.parametrize("fault", LOGIT_FAULTS)
def test_a_fault_of_each_new_part_fails_the_logit_rule(tiny, fault, capsys):
    import jax.numpy as jnp

    kw = dict(rounding=jnp.float8_e4m3fn) if fault.startswith("fp8") else dict(rounding=jnp.bfloat16, fault=fault)
    facts = judged(in_the_programs_place(tiny, SEEDS[0], **kw))
    ratios = [row["ratio"] for row in facts["rows"]]
    with capsys.disabled():
        print(f"\n{fault}: err / floor {json.dumps(ratios)} (limit {correct.K})")
    assert "max logit error" in facts.get("error", ""), facts
    assert max(ratios) > correct.K


def test_the_reveal_taking_the_least_confident_fails_its_margin_and_nothing_else(tiny, capsys):
    import jax.numpy as jnp

    cfg, _, geo, _ = tiny
    facts = judged(in_the_programs_place(tiny, SEEDS[0], jnp.bfloat16, "least_confident"))
    long, short = use(facts)
    with capsys.disabled():
        print(f"\nthe reveal takes the least confident: the reveal's regret / score_floor {short[-1]} "
              f"(limit {2 * correct.K}); err / floor {[row['ratio'] for row in facts['rows']]}")
    assert "margin" in facts.get("error", "") and "max logit error" not in facts["error"], facts
    assert f"choosing layer {geo.dense.layers}" in facts["error"]  # the layer appended for the reveal
    assert short[-1] > 2 * correct.K and max(short[:-1]) <= 2 * correct.K  # 4.16 read (PR 39)
    assert long[-1] == 0.0  # the long prompt's order is the seed's: it reveals nothing by confidence


def test_a_block_committed_with_a_mask_is_the_programs_error_and_a_mask_id_a_window_fault(tiny, monkeypatch):
    import types

    from neuronx_distributed_inference_tpu.runtime import block_step

    cfg, devices, geo, weights = tiny
    params, pspecs = weights(SEEDS[0])
    over = correct.probe_overrides(cfg, MAX_PROMPT)
    probe = system.build_app(cfg, devices, SEEDS[0], tpu_overrides=over["tpu"], chunked_overrides=over["chunked"])
    system.give_weights(probe, params, pspecs)
    keep = block_step.BlockRows.consume
    monkeypatch.setattr(block_step.BlockRows, "consume", lambda self, req, block, k, ids: keep(
        self, req, block, k, np.where(np.arange(len(ids)) == 0, MASK, ids)))
    with pytest.raises(RuntimeError, match="committed with a mask token in it"):
        correct._session_tokens(probe, [np.arange(100, dtype=np.int32)], ref.probe_budget(geo))
    rec = types.SimpleNamespace(req_id="t-1", failed=None, finished=True, budget=2)
    session = types.SimpleNamespace(requests={"t-1": types.SimpleNamespace(generated=[5, MASK])})
    assert correct.check_window([rec], session, cfg["vocab_size"], cfg["reserved_token_ids"]) == [
        f"t-1: reserved token {MASK} among its generated tokens"]


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()["sdar-30b-a3b.decode"]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("sdar-30b-a3b", "decode", 1)
    cfg = cell.config
    assert cfg["reference"] == "sdar_moe" and cfg["num_hidden_layers"] == 6 and cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["block_length"], cfg["denoise_steps"], cfg["mask_token_id"]) == (4, 4, 151669)
    assert cfg["reserved_token_ids"] == [cfg["mask_token_id"]]
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(cell.spec["reports"]) - {"out_tok_s", "setup_s"}
    assert {"block.passes_per_block", "block.positions_per_token", "moe.rows_per_expert",
            "moe.expert_stream_roofline", "kernel.paged_attn_roofline", "step.decode_dev_ms.tok_s"} <= reported
    assert "sched.tpot_p95_ms" not in reported  # tokens arrive four at a time


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_block_readers_read_the_programs_counters_and_nothing_where_there_are_none():
    from benchmark.harness.readers import counter_ratio

    cell = catalog.load_cell("sdar-30b-a3b.decode")
    readers = {m["name"]: m["reader"] for m in cell.per_layer if m["name"].startswith("block.")}
    before = _snapshot(nxdi_block_row_passes_total=[({"kind": "denoise"}, 40.0), ({"kind": "commit"}, 10.0)],
                       nxdi_block_positions_total=[({}, 200.0)], nxdi_block_blocks_committed_total=[({}, 10.0)],
                       nxdi_block_tokens_committed_total=[({}, 40.0)])
    # 100 dispatches of 48 rows: 960 blocks of 4 masks and 12 a prompt opened with 2 known tokens
    denoise, commit = 960 * 4 + 12 * 2, 972
    after = _snapshot(
        nxdi_block_row_passes_total=[({"kind": "denoise"}, 40.0 + denoise), ({"kind": "commit"}, 10.0 + commit)],
        nxdi_block_positions_total=[({}, 200.0 + 4 * (denoise + commit))],
        nxdi_block_blocks_committed_total=[({}, 10.0 + commit)],
        nxdi_block_tokens_committed_total=[({}, 40.0 + 960 * 4 + 12 * 2)])
    ctx = {"counters": {"before": before, "after": after}}
    assert counter_ratio.read(readers["block.passes_per_block"], ctx) == pytest.approx((denoise + commit) / commit)
    assert counter_ratio.read(readers["block.positions_per_token"], ctx) == pytest.approx(
        4 * (denoise + commit) / (960 * 4 + 24))
    old = {"counters": {"before": _snapshot(), "after": _snapshot()}}  # the parent commit has no such counter
    for reader in readers.values():
        assert counter_ratio.read(reader, old) is None and counter_ratio.read(reader, {}) is None
