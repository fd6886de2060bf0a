"""The rule that decides ``correct`` (``harness/correct.py``, unchanged) on the
configuration ``brumby-14b-base``, at its ``rehearsal`` preset widened on the
CPU: a ``brumby`` stack of two layers (10 query heads over 2 KV heads of 32:
a state of 640 x 32 a KV head) through the probe's ``ServingSession`` and the
teacher-forced chunks — ``HybridBlockCache`` with a pool of ZERO layers beside
the power-retention state, the chunked form on the live rows of the stack, the
state kernel, no block table read anywhere — against ``references/brumby.py``
(the attention form), with the weights the configuration's own ``weights``
rules give.

- a sound program passes;
- a fault in each part fails it: the gate ignored, the normaliser dropped, the
  weights of degree one, the state not carried from one chunk to the next, the
  query heads reading the wrong KV head (the reference's equations with the
  fault, rounded as the twin is, in the program's place); so does the CONTROL,
  the reference itself in fp8-e4m3. A state held in bf16 is the one fault the
  logit rule does NOT catch at this size (the test says why and what does)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.references import brumby as ref

SEED = 6600000437
PROMPT = 256  # two whole chunks of 128 with a carry between them
CELL = "brumby-14b-base.gen2k"


def tiny_config() -> dict:
    """The rehearsal preset at hidden 1024 with heads of 32: a projection's
    scale goes with the hidden width (the gate's logit is 4.5 +- 0.02
    sqrt(hidden): the configuration's ``why.weights``)."""
    with open(os.path.join(catalog.BENCH_DIR, "configs", "brumby-14b-base.json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(hidden_size=1024, intermediate_size=512, head_dim=32, power_state_dim=640)
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


def judged(tiny, served):
    """(err / floor, passed, the message)."""
    cfg, _, params, _, _, prompt, chosen = tiny
    try:
        facts, ok, said = correct.judge(cfg, params, 1, [prompt], [chosen], [served]), True, ""
    except correct.CorrectnessError as e:
        facts, ok, said = e.facts, False, str(e)
    return facts["rows"][0]["ratio"], ok, said


def test_a_sound_program_passes(tiny, capsys):
    cfg, devices, params, pspecs, geo, *_ = tiny
    assert (geo.heads, geo.kv_heads, geo.head_dim, geo.layers) == (10, 2, 32, 2)
    prompts, chosen, served, choices, plans = correct.serve_probe(cfg, devices, SEED, params, pspecs, PROMPT)
    assert choices is None and plans is None
    facts = correct.judge(cfg, params, 1, prompts, chosen, served)
    assert facts["reference"] == "brumby"
    for row in facts["rows"]:
        assert 0.3 < row["ratio"] <= correct.K, facts
    assert facts["rows"][1]["session_token_regret"] <= facts["rows"][1]["limit"]
    with capsys.disabled():
        print("\nbrumby rehearsal: err / floor", [round(r["ratio"], 3) for r in facts["rows"]])


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8_in_place_of_bf16",))
def test_a_fault_in_each_part_fails_the_logit_rule(tiny, fault, capsys):
    import jax.numpy as jnp

    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    kw = dict(rounding=jnp.float8_e4m3fn) if fault.startswith("fp8") else dict(rounding=jnp.bfloat16, fault=fault)
    served = ref.reference_logits(params, geo, tokens, positions, **kw)
    ratio, ok, said = judged(tiny, served)
    with capsys.disabled():
        print(f"\n{fault}: err / floor {ratio:.3g} (limit {correct.K})")
    if fault == "state_bf16":
        # NOT caught by the logit rule at this size (it reads 1.13 here): the error of a
        # state rounded after every token averages out over the D entries a read sums and
        # stays inside the noise the twin's bf16 activations make. What holds the state's
        # precision is tier-1's float32 tolerance (tests/test_brumby_reference.py:
        # five times under a bf16 state); PERF.md has the chip's reading at d = 128
        assert 0.5 < ratio < 4 * correct.K
        return
    assert not ok and "max logit error" in said
    assert ratio > 1.3 * correct.K


def test_the_twin_itself_is_at_the_floor(tiny):
    cfg, _, params, _, geo, prompt, chosen = tiny
    tokens, positions = correct.probe_row(prompt, chosen)
    ratio, ok, _ = judged(tiny, ref.twin_logits(params, geo, tokens, positions))
    assert ok and ratio == 1.0


def test_the_weights_rules_do_what_their_why_says(tiny):
    """A gate whose decay is 0.95 - 0.998 a token (a state that remembers more
    than a chunk), an embedding that stays in the state, defaults elsewhere."""
    cfg, _, params, *_ = tiny
    sa = params["layers"]["power"]["self_attn"]
    f = lambda a: np.asarray(a, np.float32)
    bias = f(sa["g_proj"]["bias"])
    assert abs(bias.mean() - 4.5) < 0.3 and 1 / (1 + np.exp(-bias.min())) > 0.97
    assert abs(f(sa["g_proj"]["weight"]).std() - 0.02) < 0.003
    assert 0.45 < f(params["embed_tokens"]["weight"]).std() < 0.55
    assert abs(f(sa["q_proj"]["weight"]).std() - 0.02) < 0.002
    assert abs(f(sa["q_norm"]["weight"]).mean() - 1) < 0.05


def test_the_catalog_takes_the_new_files():
    cell = catalog.check_catalog()[CELL]
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("brumby-14b-base", "gen2k", 1)
    cfg = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f if '"Brumby-14B-Base"' in line)
    assert cfg["source"] == row["source_url"] and cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        assert cfg[key] == (8 if key == "num_hidden_layers" else value), key
    assert cfg["reference"] == "brumby" and cfg["power_state_dim"] == 8704 and cfg["power_degree"] == 2
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(cell.spec["reports"]) - {"out_tok_s", "setup_s"} == reported
    assert {"kernel.power_update_roofline", "decode.power_dev_ms.tok_s", "chunk.power_dev_ms.tok_s",
            "chunk.live_row_share.tok_s", "decode.head_dev_ms.tok_s"} <= reported
    assert not [name for name in reported if name.startswith("kv.") or "attn" in name or name.startswith("moe.")]
    mix = cell.traffic
    assert mix["first_round"] == "mid_decode" and mix["arrivals"] == {"kind": "constant"}
    tenant, = mix["tenants"]
    assert (tenant["prompt"]["min"], tenant["prompt"]["max"]) == (512, 2048)
    assert (tenant["output"]["min"], tenant["output"]["max"]) == (1024, 3072) and tenant["shared_prefix_len"] == 0
    assert (cell.spec["loop"], cell.spec["clients"], cell.spec["prestart"]) == ("closed", 16, 16)
    assert cell.config["tpu_config"]["batch_size"] == 16


def _snapshot(**families):
    return {name: {"samples": [{"labels": labels, "value": v} for labels, v in samples]}
            for name, samples in families.items()}


def test_the_new_reader_counts_needed_bytes_and_reads_nothing_where_there_is_nothing():
    from benchmark.harness.readers import power_roofline

    cell = catalog.load_cell(CELL)
    attrs = system.model_attrs(cell.config)
    readers = {m["name"]: m["reader"] for m in cell.per_layer}
    # 8 layers x (8 KV heads x 8256 x 128 + 8 x 8256) x 4 B: the EXACT symmetric square
    assert power_roofline.state_bytes_per_row(attrs) == 8 * (8 * 8256 * 128 + 8 * 8256) * 4
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"chips": 1, "span_counts": {"step": 2},
             "module_sums": {"jit_token_generation_model_decode(123)": (10, 0.300)},
             "op_sums": {"power_state_update.8": (80, 0.200), "fusion.9": (5, 1.0)}}
    before = _snapshot(nxdi_power_rows_advanced_total=[({"program": "decode"}, 100.0)],
                       nxdi_steps_total=[({"kind": "decode"}, 10.0)])
    after = _snapshot(nxdi_power_rows_advanced_total=[({"program": "decode"}, 100.0 + 20 * 15)],
                      nxdi_steps_total=[({"kind": "decode"}, 30.0)])
    ctx = {"attrs": attrs, "peaks": peaks, "trace": trace, "counters": {"before": before, "after": after}}
    reader = readers["kernel.power_update_roofline"]
    share = power_roofline.read(reader, ctx)
    assert share == pytest.approx(100 * (15 * 10 * 2 * 8 * 8 * 8256 * 129 * 4 / 819e9) / 0.200) and share < 100
    # a program without the kernel or the counter (the parent), another family's keys, no trace: nothing, no error
    assert power_roofline.read(reader, dict(ctx, trace=dict(trace, op_sums={"fusion.9": (5, 1.0)}))) is None
    assert power_roofline.read(reader, dict(ctx, counters={"before": {}, "after": {}})) is None
    assert power_roofline.read(reader, dict(ctx, attrs={"model_type": "qwen3", "num_hidden_layers": 28})) is None
    assert power_roofline.read(reader, dict(ctx, trace=None, counters=None)) is None
