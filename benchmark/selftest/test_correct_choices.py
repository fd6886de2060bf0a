"""``correct`` on a model that makes discrete choices (``harness/correct.py``,
"A model that chooses": replay, then margin), at a small size on the CPU,
against the fixture ``expert_reference.py`` (a plain top-k softmax-router
expert decoder; injected by monkeypatching ``correct.load_reference``).

1. THE DEFECT, recorded with the real program: a 4-layer top-1 ``mixtral``
   of 8 experts (hidden 256, as ``test_correct.py::tiny_config``) through
   ``serve_probe`` and the UNREPLAYED judge over 20 seeds. Served model,
   float32 reference and bf16 twin each take their own route; ``err / floor``
   is a ratio of two maxima over a handful of flips. The test prints its
   minimum, median and maximum and the seeds over ``K``, and asserts only
   that the program ran and its logits are finite.
2. THE PROTOCOL, end to end (``check_model``: probe overrides, forced pass
   with the third value's bookkeeping, replayed reference and twin, margin):
   a stand-in served model — the fixture's own bf16 pass with its roundings
   placed differently, answering ``forward`` as the program does and
   returning its choices in the third value — passes over the same 20 seeds
   with ``err / floor`` inside the band ``test_correct.py`` reads for the
   dense model, and every layer inside its margin.
3. FAULTS THAT MUST FAIL: the wrong expert's weights used for a choice, the
   affinity not applied, 1% of the served choices replaced at random (by the
   margin rule ALONE: the logits follow the replaced choices faithfully),
   the fp8-e4m3 control, ``CHOICES`` set and no third value returned.

The program returns no choices yet (the option comes with the first expert
configuration's PR), so the replay is held here by the stand-in.
"""

import json
import types

import numpy as np
import pytest

from benchmark.harness import correct, system
from benchmark.selftest import expert_reference as fixture
from benchmark.selftest.test_correct import tiny_config

SEEDS = [4000000700 + 2 * k for k in range(20)]
MAX_PROMPT = 300  # three chunks of 128, the last partial
BAND = (0.3, correct.K)  # what test_correct.py holds the dense model's err / floor to


def expert_config(normalize: bool = True) -> dict:
    cfg = tiny_config(1)
    cfg.update(model_type="mixtral", num_local_experts=8, num_experts_per_tok=1,
               norm_topk_prob=normalize, probe_tpu_config={})
    return cfg


@pytest.fixture
def reference(monkeypatch):
    """The fixture in place of ``references/<name>.py``, not replaying."""
    monkeypatch.setattr(correct, "load_reference", lambda cfg: fixture)
    monkeypatch.setattr(fixture, "CHOICES", False)
    return fixture


@pytest.fixture(scope="module")
def weights():
    """seed, normalize -> the served parameter tree (``system.make_weights``)."""
    import jax

    apps = {}

    def make(seed: int, normalize: bool = True):
        cfg = expert_config(normalize)
        if normalize not in apps:
            apps[normalize] = system.build_app(cfg, jax.devices()[:1], seed)
        return system.make_weights(apps[normalize], seed, cfg.get("weights"))

    return make


def one_application_per_shape(monkeypatch):
    """``serve_probe`` builds a probe a call; its programs are the same for
    every seed, so over 20 seeds one application is built (and compiled)
    once and handed the seed's weights anew."""
    built, build = {}, system.build_app

    def cached(cfg, devices, seed, **over):
        key = json.dumps(over, sort_keys=True)
        if key not in built:
            built[key] = build(cfg, devices, seed, **over)
        return built[key]

    monkeypatch.setattr(system, "build_app", cached)


def spread(values):
    v = sorted(values)
    return {"min": v[0], "median": v[len(v) // 2], "max": v[-1]}


def test_the_defect_unreplayed_the_ratio_is_a_ratio_of_flips(reference, weights, monkeypatch, capsys):
    import jax

    one_application_per_shape(monkeypatch)
    cfg, devices = expert_config(), jax.devices()[:1]
    ratios, over = [], 0
    for seed in SEEDS:
        params, pspecs = weights(seed)
        prompts, chosen, served, choices, _ = correct.serve_probe(cfg, devices, seed, params, pspecs, MAX_PROMPT)
        assert choices is None  # the program returns no choices yet
        assert all(np.isfinite(s).all() and s.shape == (5, cfg["vocab_size"]) for s in served)
        try:
            facts = correct.judge(cfg, params, 1, prompts, chosen, served)
        except correct.CorrectnessError as e:
            facts = e.facts
        seed_ratios = [row["ratio"] for row in facts["rows"]]
        over += max(seed_ratios) > correct.K
        ratios += seed_ratios
    with capsys.disabled():
        print("\nunreplayed err / floor, real top-1 mixtral, 40 rows of 20 seeds:",
              json.dumps({**spread(ratios), "seeds_over_K": over, "K": correct.K}))
    assert len(ratios) == 2 * len(SEEDS) and all(np.isfinite(ratios))


class StandIn:
    """A served model that answers ``forward`` as the probe application does
    (paged-cache arguments and all), by the fixture's pass over the row's
    tokens so far: bf16 with the roundings placed as another sound
    implementation might (``placement="served"``), or with a fault planted.
    Returns its choices as the third value unless ``report`` is off."""

    def __init__(self, cfg, rounding, placement="served", fault=None, swap_share=0.0,
                 report=True, seed=0):
        tc = cfg["tpu_config"]
        self.config = types.SimpleNamespace(tpu_config=types.SimpleNamespace(
            pa_block_size=tc["pa_block_size"],
            chunked_prefill_config=types.SimpleNamespace(kernel_q_tile_size=128)))
        self.geo = fixture.geometry(system.model_attrs(cfg), 1)
        self.kw = dict(rounding=rounding, placement=placement, fault=fault)
        self.report = report
        self.params = self.kv_cache = None
        self.rows = {}
        # a router that picks at random now and then: fixed per (position, layer), so that
        # every pass over a growing row decides a token's routes alike
        rng = np.random.default_rng([seed, 11])
        shape = (tc["seq_len"], self.geo.dense.layers)
        self.swap = (rng.random(shape) < swap_share, rng.integers(0, self.geo.experts, shape))

    def init_kv_cache(self):
        self.rows = {}

    def run(self, tokens, positions):
        """(logits, selection (len(positions), L, k)) at ``positions`` of one row."""
        n = len(tokens)
        padded = list(tokens) + [0] * (-n % 64)  # few shapes; causal, so the padding is not seen
        swap = tuple(a[: len(padded)] for a in self.swap) if self.swap[0].any() else None
        logits, _, chosen = fixture.forward(self.params, self.geo, padded, positions, swap=swap, **self.kw)
        return logits, np.transpose(chosen, (1, 0, 2))[list(positions)]

    def forward(self, ids, pos, rows, *, attention_mask, block_table, phase, slot_mapping=None):
        B, S = ids.shape
        geo = self.geo
        logits = np.zeros((B, S, geo.dense.vocab), np.float32)
        chose = np.zeros((B, S, geo.dense.layers, geo.top_k), np.int32)
        for r in range(B):
            if rows[r] < 0:
                continue
            n = S if slot_mapping is None else int((slot_mapping[r] >= 0).sum())
            row = self.rows.setdefault(int(rows[r]), [])
            assert list(pos[r, :n]) == list(range(len(row), len(row) + n))
            row += [int(t) for t in ids[r, :n]]
            logits[r, :n], chose[r, :n] = self.run(row, [int(q) for q in pos[r, :n]])
        out = (logits.argmax(-1), logits)
        return out + ({fixture.NAME: chose},) if self.report else out

    def session_tokens(self, prompts, budget):
        """Greedy continuation, as the probe's ``ServingSession`` gives the program's."""
        out = []
        for p in prompts:
            row, gen = [int(t) for t in p], []
            for _ in range(budget):
                gen.append(int(self.run(row, [len(row) - 1])[0][0].argmax()))
                row.append(gen[-1])
            out.append(gen)
        return out


def check_with(monkeypatch, cfg, seed, params, pspecs, stand_in) -> dict:
    """``correct.check_model`` whole, with ``stand_in`` where it builds the probe."""
    import jax

    monkeypatch.setattr(system, "build_app", lambda *a, **kw: stand_in)
    monkeypatch.setattr(correct, "_session_tokens",
                        lambda probe, prompts, budget: (probe.session_tokens(prompts, budget), [None] * len(prompts)))
    try:
        return correct.check_model(cfg, jax.devices()[:1], seed, params, pspecs, 1, MAX_PROMPT)
    except correct.CorrectnessError as e:
        return {"error": str(e), **e.facts}


def margin_use(facts):
    """Per row, the largest regret / score_floor over its choosing layers (limit 2 K)."""
    return [max(r / f for r, f in zip(row["choice_regret"], row["choice_score_floor"]))
            for row in facts["rows"]]


def test_the_protocol_replayed_a_sound_served_model_passes_inside_the_dense_band(
        reference, weights, monkeypatch, capsys):
    import jax.numpy as jnp

    monkeypatch.setattr(fixture, "CHOICES", True)
    cfg = expert_config()
    ratios, margins, differing = [], [], []
    for seed in SEEDS:
        params, pspecs = weights(seed)
        facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16))
        assert "error" not in facts, facts
        assert facts["prompts"] == [MAX_PROMPT, correct.PROBE_SHORT_PROMPT]
        for row in facts["rows"]:
            assert len(row["choice_regret"]) == cfg["num_hidden_layers"]
            assert row["session_token_regret"] is None or row["session_token_regret"] <= row["limit"]
            differing.append(sum(row["choices_not_float32s"]))
        ratios += [row["ratio"] for row in facts["rows"]]
        margins += margin_use(facts)
    with capsys.disabled():
        print("\nreplayed err / floor, stand-in served model, 40 rows of 20 seeds:",
              json.dumps({**spread(ratios), "band": BAND}),
              "\nregret / score_floor, worst layer of a row (limit 2 K = %.1f):" % (2 * correct.K),
              json.dumps(spread(margins)),
              "\ndecisions not float32's own, of (300 + 4) x 4 and (100 + 4) x 4 a row:",
              json.dumps(spread(differing)))
    assert all(BAND[0] < r <= BAND[1] for r in ratios), sorted(ratios)
    assert all(m <= 2 * correct.K for m in margins)
    assert max(differing) > 0  # the served model did leave float32's route: the replay had work to do


FAULTS = {
    # name: (stand-in's arguments, normalised affinities, what must be in the error)
    "wrong_expert_weights": (dict(fault="wrong_expert"), True, "max logit error"),
    "affinity_not_applied": (dict(fault="no_affinity"), False, "max logit error"),
    "one_percent_random_choices": (dict(swap_share=0.01), True, "margin"),
    "control_fp8_in_place_of_bf16": (dict(placement="reference"), True, "max logit error"),
    "choices_set_and_no_third_value": (dict(report=False), True, "returned none"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_replayed_rule(fault, reference, weights, monkeypatch, capsys):
    import jax.numpy as jnp

    monkeypatch.setattr(fixture, "CHOICES", True)
    kw, normalize, names = FAULTS[fault]
    cfg, seed = expert_config(normalize), SEEDS[0]
    params, pspecs = weights(seed, normalize)
    rounding = jnp.float8_e4m3fn if fault.startswith("control") else jnp.bfloat16
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, rounding, seed=seed, **kw))
    assert "error" in facts and names in facts["error"], facts
    if fault == "choices_set_and_no_third_value":
        assert facts["rows"] == []  # refused before a pass of the reference is paid for
        return
    ratios, margins = [row["ratio"] for row in facts["rows"]], margin_use(facts)
    with capsys.disabled():
        print(f"\n{fault}: err / floor {json.dumps(ratios)}, regret / score_floor {json.dumps(margins)}")
    if names == "margin":  # by the margin rule alone: the logits follow the replaced choices
        assert "max logit error" not in facts["error"] and max(ratios) <= correct.K
        assert max(margins) > 3 * 2 * correct.K
    else:
        assert max(ratios) > 3 * correct.K


def test_a_sound_affinity_weighted_model_passes_too(reference, weights, monkeypatch):
    """The configuration the affinity fault is planted in (affinities not
    normalised to 1) passes when nothing is planted."""
    import jax.numpy as jnp

    monkeypatch.setattr(fixture, "CHOICES", True)
    cfg, seed = expert_config(normalize=False), SEEDS[0]
    params, pspecs = weights(seed, False)
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16))
    assert "error" not in facts, facts


def test_a_reference_that_does_not_replay_is_called_as_before(reference, weights, monkeypatch):
    """``dense`` and ``granite_hybrid`` set no ``CHOICES``: whatever the
    program returns, they are called with four arguments and no margin is read."""
    import jax.numpy as jnp

    calls = []
    for name in ("reference_logits", "twin_logits"):
        fn = getattr(fixture, name)
        monkeypatch.setattr(fixture, name, lambda *a, _fn=fn, **kw: calls.append((len(a), kw)) or _fn(*a, **kw))
    monkeypatch.setattr(fixture, "choice_margins", lambda *a, **kw: pytest.fail("margin read"))
    cfg, seed = expert_config(), SEEDS[0]
    params, pspecs = weights(seed)
    facts = check_with(monkeypatch, cfg, seed, params, pspecs, StandIn(cfg, jnp.bfloat16))
    assert calls == [(4, {})] * 4 and "choice_regret" not in facts["rows"][0]


def test_probe_tpu_config_is_laid_over_the_probe_application_only():
    cfg = expert_config()
    cfg["probe_tpu_config"] = {"router_dtype": "bfloat16", "batch_size": 3}
    over = correct.probe_overrides(cfg, MAX_PROMPT)["tpu"]
    assert over["router_dtype"] == "bfloat16"
    assert over["batch_size"] == correct.PROBE_SLOTS and over["output_logits"] is True  # the probe's own stand
    assert "router_dtype" not in cfg["tpu_config"] and "probe_tpu_config" not in system.model_attrs(cfg)
    del cfg["probe_tpu_config"]
    assert set(correct.probe_overrides(cfg, MAX_PROMPT)["tpu"]) == {"batch_size", "output_logits", "pa_num_blocks"}


def test_the_next_pr_adds_an_expert_cell_with_files_and_entries_alone(tmp_path, monkeypatch, capsys):
    """What the first expert configuration's PR does, rehearsed on a copy of
    the catalog: one configuration file (a tiny top-1 ``mixtral`` with
    ``weights`` rules and an empty ``probe_tpu_config``), one cell file, their
    entries, a reference that does not replay yet — and ``run.main`` builds
    it, makes its weights by the rules, checks it, warms up, serves its window
    and prints its line, with no file of ``harness/`` edited for it. The
    unreplayed verdict on it is the defect of case 1 and is not asserted."""
    from benchmark import run
    from benchmark.harness import catalog
    from benchmark.selftest.test_catalog import add_configuration_with_decode_cell, copy_catalog

    root = copy_catalog(tmp_path)
    rules = [{"match": "router/weight$", "std": 0.5}, {"match": "experts/.*_proj", "std": 0.05}]
    add_configuration_with_decode_cell(
        root, "tiny-experts", model_type="mixtral", num_local_experts=4, num_experts_per_tok=1,
        reference="expert_reference", weights=rules, probe_tpu_config={})
    cells = catalog.check_catalog(str(root), str(root / "benchmark"))
    assert cells["tiny-experts.decode"].config["weights"] == rules

    monkeypatch.setattr(correct, "load_reference", lambda c: fixture)
    monkeypatch.setattr(fixture, "CHOICES", False)
    given, make = [], system.make_weights
    monkeypatch.setattr(system, "make_weights",
                        lambda app, seed, rules=None: given.append(rules) or make(app, seed, rules))
    assert run.main(["--workload", "tiny-experts.decode", "--seed", str(SEEDS[0]), "--seconds", "2",
                     "--rehearsal", "1", "--trace", "0", "--catalog-root", str(root)]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    last, by_phase = lines[-1], {l.get("phase"): l for l in lines}
    assert given == [rules]
    assert by_phase["reference"]["reference"] == "expert_reference" and len(by_phase["reference"]["rows"]) == 2
    assert by_phase["window"]["compiled_in_window"] == 0 and by_phase["window"]["faults"] == []
    assert last["failed"] == 0 and last["attempted"] > 0 and last["metrics"]["out_tokens"]["value"] > 0
    assert isinstance(last["correct"], bool)
