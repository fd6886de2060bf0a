"""``correct`` on a model whose step fills a block of positions
(``harness/correct.py``, "A model whose step is a block": the probe drives
passes the reference plans, a token is judged where it was predicted, the
reveal is held as a choice), at a small size on the CPU, against the fixture
``block_reference.py`` (injected by monkeypatching ``correct.load_reference``).

The program has no such model yet, so the served side is a STAND-IN: the
fixture's own equations in bf16, roundings placed as another sound
implementation might, behind an object that answers ``forward`` as the probe
application does, on a cache of K and V that lives from pass to pass (the
reference keeps none: it runs every pass as one full forward), and a session
of its own that generates block by block and records ``revealed_at``.

1. A sound stand-in passes ``check_model`` whole over 20 seeds, ``err /
   floor`` inside the band ``test_correct.py`` holds the dense model to; at
   block length 8 both probe prompts end in a partial block (302 = 37 x 8 +
   6, 100 = 12 x 8 + 4), at block length 4 the short one ends on a boundary.
2. Faults that must fail, each by the rule named beside it: in-block
   attention causal where it is both ways, a denoise pass's K and V left in
   place of the commit pass's, logits read one position early (``max logit
   error``); the fp8-e4m3 control (``max logit error``, over 10 x the
   floor); with a top-2-of-8 expert MLP replayed pass by pass (``CHOICES``
   too) the reveal taking the LEAST confident position (``margin``, and it
   alone); a ``PASSES`` reference whose session returned no ``revealed_at``,
   and a ``CHOICES`` reference with no third value.
3. A reference that plans no passes is called exactly as before; the forced
   pass drives planned passes of unequal rows as the docstring says; the plan
   of the fixture is a function of the tokens.
4. Ids a configuration reserves: a generated one is a window fault, and a
   whole run (``run.main`` in rehearsal, which skips the look for a chip) whose
   ``step()`` commits one ends ``correct: false``; a block-step configuration
   added to a copy of the catalog as files and entries alone runs end to end.
"""

import json
import types

import numpy as np
import pytest

from benchmark.harness import correct, system
from benchmark.selftest import block_reference as fixture
from benchmark.selftest.test_correct import tiny_config
from benchmark.selftest.test_correct_choices import spread

SEEDS = [4000000900 + 2 * k for k in range(20)]
MAX_PROMPT = 302  # two whole chunks of 128 and a partial one; 37 blocks of 8 and 6 tokens over
BAND = (0.3, correct.K)
MASK = 1023  # the last id of the tiny vocabulary
#: under the default N(0, 0.02) the attention of a model this small is flat and its output a tenth
#: of the residual: every masked position of a block then predicts the SAME token with the same
#: confidence to four digits (2.737e-3 .. 2.741e-3 read, the bf16 twin's confidence 6e-6 off), and
#: the reveal has nothing to decide. With these rules the masked positions of a pass differ by
#: 10-20% in confidence and predict different tokens.
RULES = [{"match": "self_attn/qkv_proj", "std": 0.1}, {"match": "self_attn/o_proj", "std": 0.06}]


def block_config(block: int = 8, experts: int = 0) -> dict:
    cfg = tiny_config(1)
    cfg.update(block_length=block, denoise_steps=4, mask_token_id=MASK, reserved_token_ids=[MASK],
               weights=RULES)
    if experts:
        cfg.update(model_type="mixtral", num_local_experts=experts, num_experts_per_tok=2,
                   norm_topk_prob=True, probe_tpu_config={})
    return cfg


@pytest.fixture
def reference(monkeypatch):
    monkeypatch.setattr(correct, "load_reference", lambda cfg: fixture)
    monkeypatch.setattr(fixture, "CHOICES", False)
    return fixture


@pytest.fixture(scope="module")
def weights():
    """(seed, experts) -> the parameter tree ``system.make_weights`` gives the program."""
    import jax

    apps = {}

    def make(seed: int, experts: int = 0):
        cfg = block_config(experts=experts)
        if experts not in apps:
            apps[experts] = system.build_app(cfg, jax.devices()[:1], seed)
        return system.make_weights(apps[experts], seed, cfg.get("weights"))

    return make


class StandIn:
    """A served block model: ``forward`` as the probe application answers it,
    by ``fixture.run_rows`` on a per-row cache of K and V that every pass
    writes before it attends; ``session_tokens`` generates block by block."""

    def __init__(self, cfg, rounding, placement="served", fault=None, report=True, record=True):
        tc = cfg["tpu_config"]
        self.config = types.SimpleNamespace(tpu_config=types.SimpleNamespace(
            pa_block_size=tc["pa_block_size"],
            chunked_prefill_config=types.SimpleNamespace(kernel_q_tile_size=128)))
        self.geo = fixture.geometry(system.model_attrs(cfg), 1)
        self.width = correct.probe_width(cfg, MAX_PROMPT)
        self.kw = dict(rounding=rounding, placement=placement,
                       fault=fault if fault == "causal_in_block" else None)
        self.fault, self.report, self.record = fault, report, record
        self.params = self.kv_cache = None
        self.caches = {}

    def init_kv_cache(self):
        self.caches = {}

    def run(self, row, ids, positions):
        """(logits (n, V), selection (n, L, k) or None) of one pass of one row."""
        ids = [int(t) for t in ids]
        cache = self.caches.get(row) or fixture.empty_cache(self.geo, self.width)
        # a commit pass: no mask among its ids, over positions a denoise pass has written
        commit = self.geo.mask_id not in ids and len(ids) == self.geo.block
        write = not (self.fault == "stale_kv" and commit)
        logits, self.caches[row], _, chosen = fixture.run_rows(
            self.params, self.geo, ids, positions, max(positions), cache, range(len(ids)),
            write=write, **self.kw)
        if self.fault == "read_early":  # position i answers with what position i - 1 predicts
            logits = np.concatenate([logits[:1], logits[:-1]])
        return logits, None if chosen is None else np.transpose(np.asarray(chosen), (1, 0, 2))

    def forward(self, ids, pos, rows, *, attention_mask, slot_mapping, block_table, phase):
        B, S = ids.shape
        g = self.geo
        logits = np.zeros((B, S, g.dense.vocab), np.float32)
        chose = np.zeros((B, S, g.dense.layers, max(getattr(g.base, "top_k", 1), 1)), np.int32)
        for r in range(B):
            if rows[r] < 0:
                continue
            n = int((slot_mapping[r] >= 0).sum())
            assert int(attention_mask[r].sum()) - 1 == int(pos[r, :n].max())
            logits[r, :n], sel = self.run(int(rows[r]), ids[r, :n], [int(q) for q in pos[r, :n]])
            if sel is not None:
                chose[r, :n] = sel
        out = (logits.argmax(-1), logits)
        return out + ({fixture.NAME: chose},) if self.report and g.experts else out

    def session_tokens(self, prompts, budget):
        """(generated, revealed_at) per prompt: greedy generation block by
        block on a cache of its own, ``per_pass`` positions revealed a pass
        by confidence (the LEAST confident under ``fault="least_confident"``)."""
        g, B = self.geo, self.geo.block
        outs, whens = [], []
        for i, prompt in enumerate(prompts):
            row = ("session", i)
            prefill_len = len(prompt) // B * B
            for start in range(0, prefill_len, 128):
                stop = min(start + 128, prefill_len)
                self.run(row, prompt[start:stop], list(range(start, stop)))
            left = [int(t) for t in prompt[prefill_len:]]
            gen, when, start = [], [], prefill_len
            while len(gen) < budget:
                ids = left + [g.mask_id] * (B - len(left))
                positions, at = list(range(start, start + B)), {}
                masked, k = list(range(len(left), B)), 0
                sign = 1 if self.fault == "least_confident" else -1
                while masked:
                    logits, _ = self.run(row, ids, positions)
                    conf = fixture.confidence(logits)
                    for j in sorted(masked, key=lambda j: sign * conf[j])[: g.per_pass]:
                        ids[j], at[j] = int(logits[j].argmax()), k
                        masked.remove(j)
                    k += 1
                self.run(row, ids, positions)  # the commit pass
                gen += ids[len(left):]
                when += [at[j] for j in range(len(left), B)]
                left, start = [], start + B
            outs.append(gen[:budget])
            whens.append(when[:budget] if self.record else None)
            del self.caches[row]
        return outs, whens


def check_with(monkeypatch, cfg, seed, params, pspecs, stand_in) -> dict:
    """``correct.check_model`` whole, with ``stand_in`` where it builds the probe."""
    import jax

    monkeypatch.setattr(system, "build_app", lambda *a, **kw: stand_in)
    monkeypatch.setattr(correct, "_session_tokens",
                        lambda probe, prompts, budget: probe.session_tokens(prompts, budget))
    try:
        return correct.check_model(cfg, jax.devices()[:1], seed, params, pspecs, 1, MAX_PROMPT)
    except correct.CorrectnessError as e:
        return {"error": str(e), **e.facts}


def margin_use(facts):
    """Per row, regret / score_floor of every choosing layer (limit 2 K); the last is the reveal's."""
    return [[r / f for r, f in zip(row["choice_regret"], row["choice_score_floor"])]
            for row in facts["rows"]]


@pytest.mark.parametrize("block", [8, 4])
def test_a_sound_block_model_passes_inside_the_dense_band(block, reference, weights, monkeypatch, capsys):
    import jax.numpy as jnp

    cfg = block_config(block)
    ratios, regrets = [], []
    for seed in SEEDS if block == 8 else SEEDS[:3]:
        params, pspecs = weights(seed)
        facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16))
        assert "error" not in facts, facts
        assert facts["prompts"] == [MAX_PROMPT, correct.PROBE_SHORT_PROMPT]
        long, short = facts["rows"]
        assert long["prefill_len"] == MAX_PROMPT // block * block and short["prefill_len"] == 100 // block * block
        # two blocks a row where the prompt ends on a boundary, else the partial one and one more;
        # a block of b masks is ceil(b / per_pass) denoise passes and a commit
        assert min(long["passes"], short["passes"]) >= 4 and min(long["reads"], short["reads"]) >= block
        assert long["session_token_regret"] is None and short["session_token_regret"] <= short["limit"]
        ratios += [row["ratio"] for row in facts["rows"]]
        regrets.append(short["session_token_regret"] / short["floor"])
    with capsys.disabled():
        print(f"\nblock {block}: err / floor, sound stand-in, {len(ratios)} rows:",
              json.dumps({**spread(ratios), "band": BAND}),
              "\nthe session's regret where a token was predicted, / floor (limit K):",
              json.dumps(spread(regrets)))
    assert all(BAND[0] < r <= BAND[1] for r in ratios), sorted(ratios)


FAULTS = {
    # name: (stand-in's arguments, what must be in the error, the least err / floor)
    "in_block_attention_causal": (dict(fault="causal_in_block"), "max logit error", 3 * correct.K),
    "denoise_kv_left_for_the_commits": (dict(fault="stale_kv"), "max logit error", 3 * correct.K),
    "logits_read_one_position_early": (dict(fault="read_early"), "max logit error", 3 * correct.K),
    "control_fp8_in_place_of_bf16": (dict(placement="reference"), "max logit error", 10.0),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_rule(fault, reference, weights, monkeypatch, capsys):
    import jax.numpy as jnp

    kw, names, least = FAULTS[fault]
    cfg, seed = block_config(), SEEDS[0]
    params, pspecs = weights(seed)
    rounding = jnp.float8_e4m3fn if fault.startswith("control") else jnp.bfloat16
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, rounding, **kw))
    assert "error" in facts and names in facts["error"], facts
    ratios = [row["ratio"] for row in facts["rows"]]
    with capsys.disabled():
        print(f"\n{fault}: err / floor {json.dumps(ratios)}")
    assert max(ratios) > least


def test_with_experts_replayed_pass_by_pass_and_the_reveal_held_by_its_margin(
        reference, weights, monkeypatch, capsys):
    import jax.numpy as jnp

    monkeypatch.setattr(fixture, "CHOICES", True)
    cfg = block_config(experts=8)
    layers = cfg["num_hidden_layers"]
    for seed in SEEDS[:3]:
        params, pspecs = weights(seed, 8)
        facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16))
        assert "error" not in facts, facts
        use = margin_use(facts)
        assert all(len(u) == layers + 1 for u in use)  # the expert layers, then the reveal
        assert all(BAND[0] < row["ratio"] <= BAND[1] for row in facts["rows"])
        with capsys.disabled():
            print(f"\nexperts, seed {seed}: err / floor {[row['ratio'] for row in facts['rows']]}, "
                  f"regret / score_floor (limit {2 * correct.K}; last: the reveal) {json.dumps(use)}")
    # the reveal taking the LEAST confident position: every logit follows the plan faithfully
    facts = check_with(monkeypatch, cfg, seed, params, pspecs,
                       StandIn(cfg, jnp.bfloat16, fault="least_confident"))
    assert "error" in facts and "margin" in facts["error"] and "max logit error" not in facts["error"], facts
    long, short = margin_use(facts)
    with capsys.disabled():
        print(f"\nthe reveal takes the least confident: the reveal's regret / score_floor {short[-1]}")
    assert max(row["ratio"] for row in facts["rows"]) <= correct.K
    assert short[-1] > 3 * 2 * correct.K and max(short[:-1]) <= 2 * correct.K
    assert long[-1] == 0.0  # the long prompt's order is the reference's own, and it reveals nothing by confidence


def test_choices_set_and_no_third_value_is_an_error(reference, weights, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(fixture, "CHOICES", True)
    cfg, seed = block_config(experts=8), SEEDS[0]
    params, pspecs = weights(seed, 8)
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16, report=False))
    assert "returned none" in facts["error"] and facts["rows"] == []


def test_a_session_that_records_no_revealed_at_is_an_error(reference, weights, monkeypatch):
    import jax.numpy as jnp

    cfg, seed = block_config(), SEEDS[0]
    params, pspecs = weights(seed)
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16, record=False))
    assert "carries no revealed_at" in facts["error"] and "rows" not in facts


def test_the_plan_is_the_references_and_a_function_of_the_tokens(reference):
    geo = fixture.geometry(system.model_attrs(block_config()), 1)
    prompt, following = list(range(100, 122)), [7, 3, 9, 1, 5, 8, 2, 6, 4, 0, 11, 10, 13, 12, 15, 14]
    assert fixture.probe_budget(geo) == len(following)
    prefill_len, passes = fixture.probe_passes(geo, prompt, following)
    assert prefill_len == 16 and [p["kind"] for p in passes] == ["denoise"] * 1 + ["commit"] + ["denoise"] * 4 + ["commit"]
    first, commit = passes[0], passes[1]
    assert first["ids"] == prompt[16:] + [MASK, MASK] and first["positions"] == list(range(16, 24))
    assert first["read"] == [6, 7] and first["chosen"] == [7, 3]  # both masks go in one pass of two
    assert commit["ids"] == prompt[16:] + [7, 3] and commit["read"] == [7] and commit["chosen"] == [-1]
    second = passes[2]  # the block 9 1 5 8 2 6 4 0, by token id two a pass: 0 and 1 first
    assert second["ids"] == [MASK] * 8 and second["read"] == list(range(8))
    assert second["chosen"] == [-1, 1, -1, -1, -1, -1, -1, 0]
    assert passes[5]["read"] == [0, 3] and passes[5]["chosen"] == [9, 8]
    # the session's record in place of the seed's order; the last 6 tokens fill no whole block
    at = [0, 0] + [3, 2, 1, 0, 0, 1, 2, 3] + [0] * 6
    _, passes = fixture.probe_passes(geo, prompt, following, at)
    assert passes[2]["chosen"] == [-1, -1, -1, 8, 2, -1, -1, -1] and len(passes) == 7


def test_a_reference_that_plans_no_passes_is_called_as_before(monkeypatch):
    """``dense``, ``granite_hybrid`` and ``zaya`` set no ``PASSES``: the
    budget is five tokens, no plan is made, and the forced pass feeds one
    token a row a step."""
    cfg = tiny_config(1)
    assert correct.probe_budget(cfg) == correct.PROBE_DECODE_STEPS + 1
    assert correct.probe_width(cfg, 300) == 512
    calls = []

    class Probe:
        config = types.SimpleNamespace(tpu_config=types.SimpleNamespace(
            pa_block_size=32, chunked_prefill_config=types.SimpleNamespace(kernel_q_tile_size=128)))

        def forward(self, ids, pos, rows, **kw):
            calls.append((ids.shape, sorted(kw)))
            return ids, np.zeros(ids.shape + (16,), np.float32)

    prompts = [np.arange(150), np.arange(100)]
    served, choices = correct._forced_pass(Probe(), prompts, [[1] * 5, [2] * 5], 256)
    assert choices is None and [s.shape for s in served] == [(5, 16)] * 2
    chunk_keys = ["attention_mask", "block_table", "phase", "slot_mapping"]
    assert calls == [((2, 128), chunk_keys)] * 2 + [((2, 1), ["attention_mask", "block_table", "phase"])] * 4
    with pytest.raises(correct.CorrectnessError, match="and it alone"):
        correct.judge(cfg, None, 1, prompts, [[1] * 5, [2] * 5], served, None, plans=[(96, [])] * 2)


def test_the_forced_pass_drives_the_planned_passes_through_forward():
    """Rows of unequal plans: the shorter sits out (seq id -1, no slot), a
    pass's mask ends at its last position, its slots are its positions'."""
    calls = []

    class Probe:
        config = types.SimpleNamespace(tpu_config=types.SimpleNamespace(
            pa_block_size=32, chunked_prefill_config=types.SimpleNamespace(kernel_q_tile_size=128)))

        def forward(self, ids, pos, rows, *, attention_mask, slot_mapping, block_table, phase):
            calls.append(dict(ids=ids.copy(), pos=pos.copy(), rows=rows.copy(), mask=attention_mask.sum(1),
                              sm=slot_mapping.copy()))
            logits = np.zeros(ids.shape + (4,), np.float32)
            logits[..., 0] = pos  # a logit that names the position it was read at
            return ids, logits, {"experts": pos[..., None]}

    block = lambda start, read: {"ids": [9] * 4, "positions": list(range(start, start + 4)),
                                 "read": read, "chosen": [-1] * len(read)}
    plans = [(8, [block(8, [0, 3]), block(8, [3]), block(12, [])]), (4, [block(4, [1])])]
    prompts = [np.arange(10), np.arange(6)]
    served, choices = correct._forced_pass(Probe(), prompts, None, 64, plans)
    assert [c["ids"].shape for c in calls] == [(2, 128), (2, 4), (2, 4), (2, 4)]
    assert list(calls[0]["mask"]) == [8, 4]  # the chunk passes carry prompt[:prefill_len]
    assert [list(c["rows"]) for c in calls[1:]] == [[0, 1], [0, -1], [0, -1]]
    assert list(calls[1]["mask"]) == [12, 8] and list(calls[3]["mask"]) == [16, 0]
    assert list(calls[1]["sm"][0]) == [32 + 8, 32 + 9, 32 + 10, 32 + 11]  # row 0 owns block 1, row 1 blocks 3, 4
    assert list(calls[1]["sm"][1]) == [3 * 32 + 4, 3 * 32 + 5, 3 * 32 + 6, 3 * 32 + 7]
    assert (calls[2]["sm"][1] == -1).all()
    assert [list(s[:, 0]) for s in served] == [[7, 8, 11, 11], [3, 5]]  # prefill_len - 1, then every read
    assert [c["experts"][:, 0].tolist() for c in choices] == [list(range(8)) + [8, 9, 10, 11] * 2 + [12, 13, 14, 15],
                                                              list(range(4)) + [4, 5, 6, 7]]


def test_a_reserved_id_among_the_generated_tokens_is_a_window_fault():
    rec = types.SimpleNamespace(req_id="t-000001", failed=None, finished=True, budget=3)
    session = types.SimpleNamespace(requests={"t-000001": types.SimpleNamespace(generated=[4, MASK, 9])})
    faults = correct.check_window([rec], session, 1024, [MASK])
    assert faults == [f"t-000001: reserved token {MASK} among its generated tokens"]
    assert correct.check_window([rec], session, 1024) == []  # a configuration that reserves none
    assert correct.compared({}, faults, 0, 3, 3)["window_faults"] == [1, 0]


def test_a_run_whose_timed_path_commits_a_reserved_id_is_not_correct(tmp_path, monkeypatch, capsys):
    """``run.main`` from the weights on, on the CPU's tiny preset, of a
    configuration that reserves an id (added to a copy of the catalog as a
    later PR adds one: a file and entries): no prompt of the run holds the id;
    underneath, ``ServingSession.step`` commits it once, as a block model that
    commits a block with a mask still in it would."""
    from benchmark import run
    from benchmark.selftest.test_catalog import add_configuration_with_decode_cell, copy_catalog
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    root, reserved = copy_catalog(tmp_path), 5
    add_configuration_with_decode_cell(root, "tiny-reserving", reserved_token_ids=[reserved])
    argv = ["--workload", "tiny-reserving.decode", "--seed", str(SEEDS[0]), "--seconds", "2",
            "--rehearsal", "1", "--trace", "0", "--catalog-root", str(root)]
    sent, add = [], ServingSession.add_request
    monkeypatch.setattr(ServingSession, "add_request",
                        lambda self, rid, ids, **kw: sent.append(np.asarray(ids)) or add(self, rid, ids, **kw))
    assert run.main(argv) == 0
    sound = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sound["correct"] is True and sound["compared"]["window_faults"] == [0, 0]
    assert len(sent) > 10 and not any((ids == reserved).any() for ids in sent)

    step = ServingSession.step

    def committed_with_a_mask(self):
        out = step(self)
        for rid in out:
            if not rid.startswith(("probe", "warm")) and self.requests[rid].generated:
                self.requests[rid].generated[-1] = reserved
                break
        return out

    monkeypatch.setattr(ServingSession, "step", committed_with_a_mask)
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    broken, window = json.loads(out[-1]), next(json.loads(l) for l in out if '"phase": "window"' in l)
    assert broken["correct"] is False and broken["compared"]["window_faults"][0] >= 1
    assert any(f"reserved token {reserved} among its generated tokens" in f for f in window["faults"])


def test_the_next_pr_adds_a_block_step_cell_with_files_and_entries_alone(tmp_path, monkeypatch, capsys):
    """What a block-step configuration's PR does, rehearsed on a copy of the
    catalog: one configuration file (its reference named, its mask token
    reserved, the probe's option) and one cell file with their entries, and
    ``run.main`` builds it, plans by its reference, draws its traffic without
    the reserved id, warms up, serves its window and prints its line, with no
    file of ``harness/`` edited for it. The program here is the autoregressive
    one, whose session records no ``revealed_at``: that is said, and the run is
    ``correct: false`` by it and by nothing else."""
    from benchmark import run
    from benchmark.harness import catalog
    from benchmark.selftest.test_catalog import add_configuration_with_decode_cell, copy_catalog

    root = copy_catalog(tmp_path)
    add_configuration_with_decode_cell(
        root, "tiny-blocks", reference="block_reference", block_length=4, denoise_steps=4,
        mask_token_id=511, reserved_token_ids=[511], probe_tpu_config={})
    cells = catalog.check_catalog(str(root), str(root / "benchmark"))
    assert cells["tiny-blocks.decode"].config["reserved_token_ids"] == [511]
    assert "reserved_token_ids" not in system.model_attrs(cells["tiny-blocks.decode"].config)
    monkeypatch.setattr(correct, "load_reference", lambda c: fixture)
    monkeypatch.setattr(fixture, "CHOICES", False)
    assert run.main(["--workload", "tiny-blocks.decode", "--seed", str(SEEDS[0]), "--seconds", "2",
                     "--rehearsal", "1", "--trace", "0", "--catalog-root", str(root)]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    last, by_phase = lines[-1], {l.get("phase"): l for l in lines}
    assert by_phase["reference"]["ok"] is False and "carries no revealed_at" in by_phase["reference"]["error"]
    assert by_phase["window"]["compiled_in_window"] == 0 and by_phase["window"]["faults"] == []
    assert last["correct"] is False and last["failed"] == 0 and last["metrics"]["out_tokens"]["value"] > 0
    assert last["compared"]["window_faults"] == [0, 0]
