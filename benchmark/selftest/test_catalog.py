"""Every data file resolves, and a later PR adds a cell, a mix, a
configuration or a counter-/span-based metric with new files and entries
only — shown on a throw-away copy of the catalog in a temporary directory."""

import importlib
import json
import math
import os
import shutil

import pytest

from benchmark.harness import catalog, correct
from benchmark.harness.system import model_attrs
from benchmark.harness.traffic import Traffic

DATA_DIRS = ("configs", "traffic", "workloads", "layer_metrics")


def test_the_catalog_holds_to_its_own_rules():
    cells = catalog.check_catalog()
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert list(cells) == [w["name"] for w in bench["workloads"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in cells.values():
        for m in cell.per_layer:
            assert m["moves"] in e2e
            importlib.import_module("benchmark.harness.readers." + m["reader"]["reader"])
        # a cell file lists what the cell reported when the file was written; a
        # later PR adds metrics by entries in BENCHMARK.json, at the end of their
        # list, and may not edit the cell file: the list is then the head of it
        names = [m["name"] for m in cell.end_to_end + cell.per_layer]
        assert cell.spec["reports"] == names[: len(cell.spec["reports"])]
    # every data file is named by some entry: nothing lies about unused
    named = {os.path.basename(c["file"]) for c in bench["configs"]}
    assert set(os.listdir(os.path.join(catalog.BENCH_DIR, "configs"))) == named
    assert set(os.listdir(os.path.join(catalog.BENCH_DIR, "workloads"))) == {n + ".json" for n in cells}
    assert set(os.listdir(os.path.join(catalog.BENCH_DIR, "layer_metrics"))) == {
        m["name"] + ".json" for m in bench["per_layer"]
    }
    assert {w["traffic"] + ".json" for w in bench["workloads"]} == set(
        os.listdir(os.path.join(catalog.BENCH_DIR, "traffic"))
    )


def test_no_width_is_cut_and_assumed_keys_are_listed():
    """The contract's rules, of every configuration whatever its family:
    ``reduced`` names no width, agrees with BENCHMARK.json, and every model
    key that no catalog row backs is listed as assumed: all of them, or, where
    the file names the catalog row it was read from key for key, none."""
    from benchmark.selftest.test_contract import WIDTH

    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        entries = {os.path.basename(c["file"]): c for c in json.load(f)["configs"]}
    for name in os.listdir(os.path.join(catalog.BENCH_DIR, "configs")):
        with open(os.path.join(catalog.BENCH_DIR, "configs", name)) as f:
            cfg = json.load(f)
        assert not any(WIDTH.search(k) for k in cfg["reduced"])
        assert cfg["reduced"] == entries[name]["reduced"] and cfg["source"] == entries[name]["source"]
        assumed, note = cfg["assumed"]["keys"], cfg["assumed"].get("_note", "")
        from_catalog_row = "architectures.jsonl" in note and cfg["name"] in note
        assert sorted(assumed) == sorted(model_attrs(cfg)) or (assumed == [] and from_catalog_row)
        # the reference it names (``dense`` where it names none) is a module with the interface
        ref = correct.load_reference(cfg)
        assert all(callable(getattr(ref, f)) for f in ("geometry", "reference_logits", "twin_logits"))


def test_a_cell_file_says_what_benchmark_json_says_and_sits_where_it_says():
    """A cell's one-line ``why`` is the same in both places, and an open-loop
    cell whose file gives the arithmetic of its seat (the knee read on the
    chip, the share of it the cell sits at, the service time read there) is
    seated by it: ``rate_rps`` = share x knee rounded down to 0.1 req/s,
    ``prestart`` = rate x service time (the steady number in flight), and
    the requests due in a window are what the generator will draw."""
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    why = {w["name"]: w["why"] for w in bench["workloads"]}
    for cell in catalog.check_catalog().values():
        assert cell.spec["why"] == why[cell.name]
        knee = cell.spec.get("knee", {})
        if "share" in knee:
            assert cell.spec["rate_rps"] == math.floor(knee["share"] * knee["rps"] * 10 + 1e-9) / 10
            assert cell.spec["prestart"] == round(cell.spec["rate_rps"] * knee["service_s"])
            assert round(cell.spec["rate_rps"] * bench["run_seconds"]) == knee["due_in_window"]


def copy_catalog(tmp_path):
    root = tmp_path / "repo"
    (root / "benchmark").mkdir(parents=True)
    shutil.copy(os.path.join(catalog.REPO_DIR, "BENCHMARK.json"), root / "BENCHMARK.json")
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(catalog.BENCH_DIR, d), root / "benchmark" / d)
    return root


@pytest.fixture
def copy(tmp_path):
    return copy_catalog(tmp_path)


def write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def add_configuration_with_decode_cell(root, name: str, **keys) -> dict:
    """What a later PR does with files and entries alone, on a copy of the
    catalog: ``configs/<name>.json`` (the qwen3-1p7b file with ``keys`` laid
    over it), the cell ``<name>.decode`` (qwen3-1p7b.decode's file and
    metrics) and their entries. Returns the configuration written."""
    bdir = root / "benchmark"
    with open(bdir / "configs" / "qwen3-1p7b.json") as f:
        cfg = dict(json.load(f), name=name, **keys)
    write(bdir / "configs" / (name + ".json"), cfg)
    with open(bdir / "workloads" / "qwen3-1p7b.decode.json") as f:
        spec = dict(json.load(f), config=name)
    write(bdir / "workloads" / (name + ".decode.json"), spec)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": cfg["source"], "reduced": [],
                             "file": f"benchmark/configs/{name}.json", "why": "x"})
    bench["workloads"].append({"name": name + ".decode", "config": name, "traffic": "decode",
                               "chips": 1, "why": spec["why"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-1p7b.decode" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + [name + ".decode"]
    write(root / "BENCHMARK.json", bench)
    return cfg


def test_a_later_pr_adds_files_and_entries_only(copy, monkeypatch):
    bdir = copy / "benchmark"
    with open(copy / "BENCHMARK.json") as f:
        bench = json.load(f)
    # a new traffic mix (bursty arrivals, two tenants, a shared prefix)
    write(bdir / "traffic" / "burst.json", {
        "shape_seed": 5, "arrivals": {"kind": "onoff", "period_s": 5.0, "on_share": 0.25},
        "tenants": [
            {"name": "agent", "weight": 2.0, "shared_prefix_len": 64,
             "prompt": {"dist": "uniform", "min": 128, "max": 256},
             "output": {"dist": "zipf", "a": 2.0, "min": 8, "max": 64}},
            {"name": "chat", "weight": 1.0,
             "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 16, "max": 400},
             "output": {"dist": "fixed", "value": 32, "min": 32, "max": 32}}]})
    # a new configuration of a registered model_type (mistral through the llama graph)
    with open(bdir / "configs" / "qwen3-1p7b.json") as f:
        cfg = json.load(f)
    cfg.update(name="other-1b", model_type="mistral", source="https://example.org/other/config.json",
               hidden_size=1024, num_hidden_layers=4, reference="other_family")
    # ... whose family brings its own plain reference: a module file, found by the name the configuration gives
    from benchmark.harness import references

    (copy / "references").mkdir()
    (copy / "references" / "other_family.py").write_text(
        "def geometry(attrs, degree):\n    return (attrs['hidden_size'], degree)\n"
        "def reference_logits(params, geo, tokens, positions):\n    return 'float32'\n"
        "def twin_logits(params, geo, tokens, positions):\n    return 'twin'\n")
    monkeypatch.setattr(references, "__path__", list(references.__path__) + [str(copy / "references")])
    write(bdir / "configs" / "other-1b.json", cfg)
    bench["configs"].append({"name": "other-1b", "source": cfg["source"],
                             "file": "benchmark/configs/other-1b.json", "reduced": [], "why": "x"})
    # a fifth cell on an existing configuration, and one on the new configuration
    for cell, config in (("qwen3-1p7b.burst", "qwen3-1p7b"), ("other-1b.burst", "other-1b")):
        bench["workloads"].append({"name": cell, "config": config, "traffic": "burst",
                                   "chips": 1, "why": "x"})
        write(bdir / "workloads" / (cell + ".json"),
              {"config": config, "traffic": "burst", "chips": 1, "loop": "open", "rate_rps": 2.0})
    new_cells = ["qwen3-1p7b.burst", "other-1b.burst"]
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "tpot_p95_ms"):
            m["workloads"] = m["workloads"] + new_cells
    # a counter-based and a span-based per-layer metric: an entry and a file each
    for name, reader in (
        ("sched.decode_steps", {"reader": "counter", "counter": "nxdi_steps_total",
                                "labels": {"kind": "decode"}}),
        ("admit.call_ms", {"reader": "driver_span", "span": "admit", "stat": "mean_ms"}),
    ):
        entry = {"name": name, "unit": "count" if "counter" in reader else "ms", "better": "lower",
                 "source": "program_counter" if "counter" in reader else "host_clock",
                 "layer": "scheduler step", "moves": "tpot_p95_ms", "workloads": new_cells}
        bench["per_layer"].append(entry)
        write(bdir / "layer_metrics" / (name + ".json"),
              {**reader, **{k: entry[k] for k in ("layer", "unit", "moves", "source")}})
    write(copy / "BENCHMARK.json", bench)

    cells = catalog.check_catalog(root=str(copy))
    assert len(cells) == len(bench["workloads"])
    cell = cells["other-1b.burst"]
    assert cell.config["model_type"] == "mistral" and cell.traffic_name == "burst"
    ref = correct.load_reference(cell.config)
    assert ref.geometry(cell.config, 1) == (1024, 1) and ref.twin_logits(None, None, [], []) == "twin"
    assert "reference" not in model_attrs(cell.config)  # the program's config never sees the key
    assert correct.load_reference(cells["qwen3-1p7b.burst"].config).__name__.endswith(".dense")
    assert [m["name"] for m in cell.per_layer] == ["sched.decode_steps", "admit.call_ms"]
    # the one general generator reads the new mix; the readers read the new metrics
    t = Traffic(cell.traffic, seed=1, vocab_size=1000, loop="open", seconds=10.0,
                rate_rps=cell.spec["rate_rps"])
    assert len(t) == 20 and {t.request(i).tenant for i in range(20)} == {"agent", "chat"}
    ctx = {"spans": {"admit": {"count": 3, "total_s": 0.3, "mean_ms": 100.0}}, "summary": {},
           "samples": {}, "counters": {
               "before": {"nxdi_steps_total": {"samples": [{"labels": {"kind": "decode"}, "value": 5.0},
                                                          {"labels": {"kind": "prefill"}, "value": 9.0}]}},
               "after": {"nxdi_steps_total": {"samples": [{"labels": {"kind": "decode"}, "value": 47.0},
                                                         {"labels": {"kind": "prefill"}, "value": 11.0}]}}}}
    got = {}
    for m in cell.per_layer:
        reader = importlib.import_module("benchmark.harness.readers." + m["reader"]["reader"])
        got[m["name"]] = reader.read(m["reader"], ctx)
    assert got == {"sched.decode_steps": 42.0, "admit.call_ms": 100.0}


def test_a_configuration_may_carry_weight_rules_and_probe_options(copy):
    """``weights`` and ``probe_tpu_config`` are the benchmark's keys: a later
    PR's configuration file may carry both, the catalog loads it, and neither
    reaches the model's attributes or the served application's options."""
    import jax

    from benchmark.harness import system

    rules = [{"match": "router/weight$", "std": 0.5}, {"match": "q_norm", "mean": 1.0, "std": 0.0}]
    add_configuration_with_decode_cell(copy, "ruled-1b", weights=rules,
                                       probe_tpu_config={"output_logits": False})
    cell = catalog.check_catalog(root=str(copy))["ruled-1b.decode"]
    assert cell.config["weights"] == rules and cell.config["probe_tpu_config"] == {"output_logits": False}
    run = system.resolve_config(cell.config, rehearsal=True)
    assert not {"weights", "probe_tpu_config"} & set(model_attrs(run))
    app = system.build_app(run, jax.devices()[:1], 1)
    assert not hasattr(app.config, "weights") and not hasattr(app.config, "probe_tpu_config")
    assert app.config.tpu_config.output_logits is False  # the served application: the file's, not the probe's
    probe = correct.probe_overrides(run, 64)["tpu"]
    assert probe["output_logits"] is True and probe["batch_size"] == correct.PROBE_SLOTS


def test_a_reader_that_finds_nothing_returns_nothing():
    from benchmark.harness.readers import counter, driver_span, trace

    ctx = {"spans": {}, "summary": {}, "samples": {}, "counters": None, "trace": None}
    assert driver_span.read({"span": "step"}, ctx) is None
    assert driver_span.read({"summary": "late_p95_ms"}, ctx) is None
    assert counter.read({"counter": "nope"}, ctx) is None
    assert trace.read({"kind": "idle_share"}, ctx) is None
    tr = {"chips": 1, "busy_s": 1.0, "window_s": 4.0, "module_sums": {}, "op_sums": {},
          "collectives": {"collective_s": 0.0, "exposed_s": 0.0}}
    ctx["trace"] = tr
    assert trace.read({"kind": "idle_share"}, ctx) == 75.0
    assert trace.read({"kind": "exposed_collective_share"}, ctx) is None
    assert trace.read({"kind": "module_ms_per_dispatch", "pattern": "^jit_"}, ctx) is None


def test_a_dangling_name_is_refused(copy):
    with open(copy / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["per_layer"][0]["moves"] = "no_such_metric"
    write(copy / "BENCHMARK.json", bench)
    with pytest.raises(catalog.CatalogError):
        catalog.check_catalog(root=str(copy))
    with pytest.raises(catalog.CatalogError):
        catalog.load_cell("no.such.cell")
