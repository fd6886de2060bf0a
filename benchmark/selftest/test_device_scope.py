"""The reader of device time by the program's own names
(``readers/device_scope.py``): events with known answers, and the small
trace recorded on the chip beside this file with the table the program wrote
next to it (``data/device_scope_small.xplane.pb.gz`` and
``data/device_scope_small.device_scopes.json``: eight split serving steps of
the tiny rehearsal preset, an admission and its chunk passes among them, as
``record_serving_trace.py`` drives them). Recorded on the chip by

    python3 benchmark/selftest/test_device_scope.py <out dir>

which keeps what ``TelemetrySession.stop()`` wrote beside the trace."""

import gzip
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import catalog, trace_reduce as tr  # noqa: E402
from benchmark.harness.readers import device_scope as ds  # noqa: E402
from benchmark.harness.trace_reduce import Event, TraceEvents  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED_GZ = os.path.join(DATA, "device_scope_small.xplane.pb.gz")
RECORDED_TABLE = os.path.join(DATA, "device_scope_small.device_scopes.json")
NEW = ("chunk.kv_write_dev_ms", "chunk.kv_write_dev_ms.tok_s", "chunk.head_dev_ms",
       "chunk.head_dev_ms.tok_s", "chunk.layer_matmul_dev_ms.tok_s", "decode.layer_matmul_dev_ms",
       "decode.layer_matmul_dev_ms.tok_s", "decode.head_dev_ms", "decode.head_dev_ms.tok_s",
       "step.unscoped_share.ttft", "step.unscoped_share.tpot", "step.unscoped_share.tok_s")

TABLES = {
    "decode:q1:kv256": {"module": "jit_token_generation_model_decode",
                        "ops": {"fusion.1": "layer.attn", "fusion.2": "head", "copy.1": ""}},
    "chunk:q128:kv256": {"module": "jit_token_generation_model_chunk",
                         "ops": {"fusion.1": "layer.kv_write", "fusion.2": "layer.mlp",
                                 "fusion.3": "layer.other"}},
}


def synthetic():
    """One chip, three dispatches: decode [1,2], chunk [3,5], decode [6,7].
    ``fusion.1`` is another op in each program; the chunk program's loop
    ``while.4`` [3.1,4.1] holds two passes of ``fusion.1`` and ``fusion.2``;
    ``custom.9`` has no table entry; ``jit__where`` is no step program."""
    spans = [(0.9, "decode", "decode:q1:kv256"), (2.9, "chunk", "chunk:q128:kv256"),
             (5.9, "decode", "decode:q1:kv256")]
    ops = [
        Event("fusion.1", 1.0, 0.5), Event("fusion.2", 1.5, 0.25), Event("copy.1", 1.75, 0.25),
        Event("while.4", 3.1, 1.0), Event("fusion.1", 3.1, 0.3), Event("fusion.2", 3.4, 0.2),
        Event("fusion.1", 3.6, 0.3), Event("fusion.2", 3.9, 0.2), Event("fusion.3", 4.2, 0.1),
        Event("custom.9", 4.5, 0.5),
        Event("fusion.1", 6.0, 0.5), Event("fusion.2", 6.5, 0.5),
        Event("fusion.7", 8.0, 0.5),
    ]
    modules = [Event("jit_token_generation_model_decode(11)", 1.0, 1.0),
               Event("jit_token_generation_model_chunk(22)", 3.0, 2.0),
               Event("jit_token_generation_model_decode(11)", 6.0, 1.0),
               Event("jit__where(3)", 8.0, 0.5)]
    return spans, TraceEvents(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": modules})


@pytest.fixture
def fake(monkeypatch, tmp_path):
    spans, trace = synthetic()
    monkeypatch.setattr(ds, "dispatch_spans", lambda path: list(spans))
    monkeypatch.setattr(tr, "read", lambda path: trace)
    table = tmp_path / ds.TABLE_FILE
    table.write_text(json.dumps(TABLES))
    return spans, trace, str(table)


def test_each_dispatch_is_named_by_its_own_programs_table(fake):
    _, _, table = fake
    red = ds.reduce("x", table)
    decode, chunk = red["decode"], red["chunk"]
    assert decode["dispatches"] == 2 and chunk["dispatches"] == 1
    # fusion.1 is attention in the decode program and the KV write in the chunk program
    assert decode["by_scope"] == pytest.approx({"layer.attn": 1.0, "head": 0.75, "": 0.25})
    # an op of the loop's body is counted once, per pass, and the while not at all
    assert chunk["by_scope"] == pytest.approx(
        {"layer.kv_write": 0.6, "layer.mlp": 0.4, "layer.other": 0.1, None: 0.5})
    assert chunk["op_s"] == pytest.approx(1.6) and chunk["busy_s"] == pytest.approx(1.6)
    assert decode["op_s"] == pytest.approx(2.0)


def test_the_metrics_read_the_reduction(fake):
    _, _, table = fake
    ctx = {"trace": {}, "device_scope_table": ds.reduce("x", table)}
    ms = lambda program, scope: ds.read(
        {"kind": "scope_ms_per_dispatch", "program": program, "scope": scope}, ctx)
    assert ms("chunk", r"^layer\.kv_write$") == pytest.approx(600.0)
    assert ms("decode", r"^(head|sample|reveal)$") == pytest.approx(375.0)
    assert ms("chunk", r"^layer\.(qkv|o_proj|mlp|moe\.)") == pytest.approx(400.0)
    assert ms("decode", r"^layer\.kv_write$") == 0.0  # named, and no time under it
    # "" and the op with no entry are unscoped; layer.other is scoped
    assert ds.read({"kind": "unscoped_share"}, ctx) == pytest.approx(100 * 0.75 / 3.6)
    with pytest.raises(ValueError):
        ds.read({"kind": "nothing"}, ctx)


def test_a_dispatch_span_too_few_raises(fake):
    spans, _, table = fake
    spans.pop()
    with pytest.raises(ValueError, match="2 dispatch spans but 3 XLA Modules"):
        ds.reduce("x", table)


def test_a_span_that_names_another_program_than_ran_raises(fake):
    spans, _, table = fake
    spans[0], spans[1] = (0.9,) + spans[1][1:], (2.9,) + spans[0][1:]
    with pytest.raises(ValueError, match="not in step"):
        ds.reduce("x", table)


def test_nothing_to_read_gives_none(fake, tmp_path, monkeypatch):
    spans, trace, table = fake
    params = {"kind": "unscoped_share"}
    assert ds.read(params, {"trace": None}) is None  # no trace at all
    assert ds.reduce("x", str(tmp_path / "absent.json")) is None  # no table file
    # a program the file has no table for: its ops have no entry, its metric is None
    (tmp_path / "partial.json").write_text(json.dumps({"decode:q1:kv256": TABLES["decode:q1:kv256"]}))
    red = ds.reduce("x", str(tmp_path / "partial.json"))
    assert red["chunk"]["tabled"] == 0 and set(red["chunk"]["by_scope"]) == {None}
    ctx = {"trace": {}, "device_scope_table": red}
    assert ds.read({"kind": "scope_ms_per_dispatch", "program": "chunk", "scope": "."}, ctx) is None
    assert ds.read({"kind": "scope_ms_per_dispatch", "program": "decode", "scope": "^head$"}, ctx) == 375.0
    # spans of an older commit name no program
    monkeypatch.setattr(ds, "dispatch_spans", lambda path: [(t, None, None) for t, _, _ in spans])
    assert ds.reduce("x", table) is None
    # a host-only trace (the CPU rehearsal of --trace 2)
    monkeypatch.setattr(tr, "read", lambda path: TraceEvents())
    assert ds.reduce("x", table) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "recorded"
    run.mkdir(parents=True)
    with gzip.open(RECORDED_GZ, "rb") as src, open(run / "small.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.copy(RECORDED_TABLE, d / ds.TABLE_FILE)
    return str(d)


def test_the_recorded_chip_trace_sums_to_its_module_events(recorded):
    path = tr.find_xplane(recorded)
    spans = ds.dispatch_spans(path)
    assert len(spans) == 11 and {p for _, p, _ in spans} == {"decode", "chunk"}
    with open(os.path.join(recorded, ds.TABLE_FILE)) as f:
        assert {k for _, _, k in spans} == set(json.load(f))
    red = ds.reduce(path, os.path.join(recorded, ds.TABLE_FILE))
    assert red["decode"]["dispatches"] == 8 and red["chunk"]["dispatches"] == 3
    for acc in red.values():
        assert acc["tabled"] == acc["dispatches"]
        assert None not in acc["by_scope"]  # the trace's names ARE the compiled text's names
        # scopes + unscoped = the ops' time inside the module events, none counted twice
        assert sum(acc["by_scope"].values()) == pytest.approx(acc["op_s"])
        assert acc["op_s"] == pytest.approx(acc["busy_s"], rel=0.02)
        assert {"layer.kv_write", "layer.attn", "layer.other", "head", ""} <= set(acc["by_scope"])
    # the module events' own time holds the ops' (what is left is between ops)
    t = tr.read(path)
    chip = sorted(t.modules)[0]
    module_s = sum(e.dur for e in t.modules[chip] if ds.MODULES.match(e.name))
    op_s = sum(acc["op_s"] for acc in red.values())
    assert op_s <= module_s * 1.02 and op_s > 0.5 * module_s


def test_the_recorded_trace_through_the_readers_entry(recorded, monkeypatch):
    monkeypatch.setattr(ds, "TRACE_DIR", recorded)
    ctx = {"trace": {}}
    for name in NEW:
        with open(os.path.join(catalog.BENCH_DIR, "layer_metrics", name + ".json")) as f:
            value = ds.read(json.load(f), ctx)
        assert value is not None and value >= 0, name
    assert 0 < ctx["device_scope_table"]["chunk"]["by_scope"]["layer.kv_write"]
    # a trace without the table beside it (an older commit's) reads None
    os.rename(os.path.join(recorded, ds.TABLE_FILE), os.path.join(recorded, "moved.json"))
    try:
        assert ds.read({"kind": "unscoped_share"}, {"trace": {}}) is None
    finally:
        os.rename(os.path.join(recorded, "moved.json"), os.path.join(recorded, ds.TABLE_FILE))


def test_the_catalog_holds_with_the_twelve_entries_appended():
    cells = catalog.check_catalog()
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert tuple(m["name"] for m in bench["per_layer"][-12:]) == NEW
    for m in bench["per_layer"][-12:]:
        assert (m["layer"], m["source"], m["better"]) == ("model step", "device_trace", "lower")
        for cell in m["workloads"]:
            assert m["name"] in [p["name"] for p in cells[cell].per_layer]
            assert m["name"] not in cells[cell].spec["reports"]  # appended, not edited in


def record(out_dir: str) -> int:
    """On the chip: the steps ``record_serving_trace.py`` drives, with the
    table ``TelemetrySession.stop()`` wrote beside the trace kept when that
    script throws its profiler directory away."""
    from benchmark.selftest import record_serving_trace as rec

    os.makedirs(out_dir, exist_ok=True)
    rmtree = shutil.rmtree

    def keep_table(path, **kw):
        table = os.path.join(path, ds.TABLE_FILE)
        if os.path.exists(table):
            shutil.copy(table, os.path.join(out_dir, os.path.basename(RECORDED_TABLE)))
        rmtree(path, **kw)

    plain = os.path.join(out_dir, "small.xplane.pb")
    rec.shutil.rmtree = keep_table
    try:
        rc = rec.main(plain)
    finally:
        rec.shutil.rmtree = rmtree
    with open(plain, "rb") as f, gzip.open(os.path.join(out_dir, os.path.basename(RECORDED_GZ)), "wb") as g:
        shutil.copyfileobj(f, g)
    os.remove(plain)
    return rc


if __name__ == "__main__":
    sys.exit(record(sys.argv[1]))
