"""Kernel-contract analyzer (KERN701-705) tests: every detector proven to
FIRE on a synthetic violation and to stay SILENT on the committed tree, the
clean-tree gate pinned at exit 0, the DeviceSpec vmem_bytes field, the
tuning-table routing (kernel outputs byte-identical through the table vs the
old in-code constants), and ``legal_tiles`` as the pruned autotuner space.

The detector tests drive the PURE comparator functions (same pattern as the
cost-audit tests): no tracing, both directions, so a regression in a rule
cannot hide behind an expensive registry rebuild.
"""

import json
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.analysis import kernel_audit as ka
from neuronx_distributed_inference_tpu.analysis.findings import SEV_ERROR

pytestmark = [pytest.mark.static_analysis, pytest.mark.kernel_audit]


def _block(block_shape, array_shape, itemsize=2):
    return SimpleNamespace(
        role="in", block_shape=block_shape, array_shape=array_shape,
        dtype="bfloat16", itemsize=itemsize,
    )


# ---------------------------------------------------------------------------
# device model: the new vmem_bytes field
# ---------------------------------------------------------------------------


def test_device_specs_have_vmem_budget():
    from neuronx_distributed_inference_tpu.analysis.device_model import (
        DEVICE_REGISTRY,
        get_device,
    )

    for name, spec in DEVICE_REGISTRY.items():
        assert spec.vmem_bytes >= 16 * 1024**2, name
    # v6e (Trillium) doubles the per-core scoped VMEM vs v5e
    assert get_device("v6e").vmem_bytes == 2 * get_device("v5e").vmem_bytes
    assert get_device().vmem_bytes == 16 * 1024**2  # bench device v5e


def test_projection_tables_print_vmem():
    from neuronx_distributed_inference_tpu.analysis.device_model import (
        render_projection_tables,
    )

    assert "VMEM 16 MiB/core" in render_projection_tables()


# ---------------------------------------------------------------------------
# KERN701: static VMEM budget + census pin
# ---------------------------------------------------------------------------


def test_kern701_fires_over_budget():
    fs = ka.vmem_findings("k/s/bf16", "ops/x.py", 17 * 1024**2, 16 * 1024**2)
    assert [f.rule for f in fs] == ["KERN701"]
    assert fs[0].severity == SEV_ERROR
    assert "17.00 MiB" in fs[0].message


def test_kern701_silent_within_budget():
    assert ka.vmem_findings("k/s/bf16", "ops/x.py", 16 * 1024**2, 16 * 1024**2) == []


@pytest.mark.parametrize("asked,fires", [(None, True), (16 * 1024**2 + 1, True), (60 * 1024**2, False)])
def test_kern701_holds_a_call_to_the_scoped_limit_it_asks_for(asked, fires):
    """A call that asks the compiler for its own ``vmem_limit_bytes`` (the
    latent chunk kernel: its q, out and accumulators of every part stand in
    VMEM) is budgeted against that, not against the default."""
    fs = ka.vmem_findings("k/s/bf16", "ops/x.py", 17 * 1024**2, 16 * 1024**2, asked)
    assert [f.rule for f in fs] == (["KERN701"] if fires else [])


def test_kern701_census_drift_and_missing():
    census = {
        "a/p/bf16": {"location": "ops/a", "vmem_bytes": 10, "grid": [1],
                     "flops_per_step": 5},
        "b/p/bf16": {"location": "ops/b", "vmem_bytes": 20, "grid": [2],
                     "flops_per_step": 6},
    }
    base = {"census": {
        "a/p/bf16": {"vmem_bytes": 10, "grid": [1], "flops_per_step": 5},
        # b missing entirely; c stale
        "c/p/bf16": {"vmem_bytes": 1, "grid": [1], "flops_per_step": 1},
    }}
    fs = ka.census_findings(census, base)
    keys = {f.key for f in fs}
    assert "b/p/bf16" in keys  # missing from baseline -> error
    assert "stale/c/p/bf16" in keys  # stale baseline row -> warning
    # and a pinned-value drift fires per field
    base["census"]["a/p/bf16"]["vmem_bytes"] = 11
    fs = ka.census_findings(census, base)
    assert any(f.key == "a/p/bf16/vmem_bytes" for f in fs)
    # exact match -> silent
    base["census"]["a/p/bf16"]["vmem_bytes"] = 10
    base["census"].pop("c/p/bf16")
    base["census"]["b/p/bf16"] = {"vmem_bytes": 20, "grid": [2],
                                  "flops_per_step": 6}
    assert ka.census_findings(census, base) == []


# ---------------------------------------------------------------------------
# KERN702: Mosaic tile legality + packing contracts
# ---------------------------------------------------------------------------


def test_kern702_fires_on_bad_lane_dim():
    # last dim 96: neither a 128 multiple nor the array dim
    fs = ka.block_legality_findings(
        "k/s/bf16", "ops/x.py", [_block((8, 96), (64, 512))]
    )
    assert any("128-lane" in f.message or "last dim" in f.message for f in fs)


def test_kern702_fires_on_bad_sublane():
    # bf16 needs sublane multiples of 16; 8 is only legal for f32
    fs = ka.block_legality_findings(
        "k/s/bf16", "ops/x.py", [_block((8, 128), (64, 128), itemsize=2)]
    )
    assert [f.rule for f in fs] == ["KERN702"]
    # the same block IS legal at f32 (itemsize 4 -> sublane 8)
    assert ka.block_legality_findings(
        "k/s/f32", "ops/x.py", [_block((8, 128), (64, 128), itemsize=4)]
    ) == []


def test_kern702_fires_on_indivisible_grid():
    # block 128 over array 192: grid would be padded and read junk
    fs = ka.block_legality_findings(
        "k/s/bf16", "ops/x.py", [_block((128, 128), (192, 128))]
    )
    assert any("not divisible" in f.message for f in fs)


def test_kern702_full_array_block_is_legal():
    # block == array dims is always legal even off the lane/sublane grid
    assert ka.block_legality_findings(
        "k/s/bf16", "ops/x.py", [_block((3, 96), (3, 96))]
    ) == []


def test_kern702_packing_contracts():
    # tq=32 > RAGGED_Q_TILE=16: a tile could span two packed rows
    fs = ka.packing_contract_findings("r/m/bf16", "ops/r.py", 32, 16)
    assert any(f.key.endswith("rowspan") for f in fs)
    # the committed contract (tq=16 divides 16) is clean
    assert ka.packing_contract_findings("r/m/bf16", "ops/r.py", 16, 16) == []


# ---------------------------------------------------------------------------
# KERN703: pallas_call census <-> registry <-> fallback/tests
# ---------------------------------------------------------------------------


def _check_row(**kw):
    row = {
        "kernel": "k", "entry": "k", "fallback": "m:f", "fallback_ok": True,
        "parity_test": "tests/t.py", "parity_ok": True,
        "lowering_test": "tests/l.py", "lowering_ok": True,
    }
    row.update(kw)
    return row


def test_kern703_fires_on_unregistered_site():
    fs = ka.registry_findings(
        [("new_kernel.py", "my_kernel", 42)], {}, []
    )
    assert [f.rule for f in fs] == ["KERN703"]
    assert "unregistered pallas_call" in fs[0].message
    assert fs[0].location == "ops/new_kernel.py:42"


def test_kern703_fires_on_stale_registry_site():
    fs = ka.registry_findings(
        [], {("gone.py", "old_fn"): "old_kernel"}, []
    )
    assert any("stale registry entry" in f.message for f in fs)


def test_kern703_fires_on_broken_references():
    fs = ka.registry_findings(
        [("a.py", "f", 1)], {("a.py", "f"): "k"},
        [_check_row(fallback_ok=False, parity_ok=False, lowering_ok=False)],
    )
    assert {f.key for f in fs} == {"fallback/k", "parity/k", "lowering/k"}


def test_kern703_silent_when_all_claimed():
    fs = ka.registry_findings(
        [("a.py", "f", 1)], {("a.py", "f"): "k"}, [_check_row()]
    )
    assert fs == []


def test_kern703_ast_scan_matches_registry():
    """The live AST scan over ops/ agrees with the committed registry —
    this is the clean-tree direction of the unregistered-site detector."""
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr

    sites = {(f, fn) for f, fn, _ in kr.pallas_sites()}
    claimed = {s.site for s in kr.REGISTRY}
    assert sites == claimed


# ---------------------------------------------------------------------------
# KERN704: tuning table coverage + hand_picked drift
# ---------------------------------------------------------------------------


def _required(**kw):
    row = {
        "kernel": "k", "shape_class": "s", "dtype": "bfloat16",
        "tile_params": ("bq",), "hand_picked": {"bq": 128},
        "location": "ops/k.py",
    }
    row.update(kw)
    return row


def _table(tiles, provenance="hand_picked"):
    return {"kernels": {"k": {"s": {"bfloat16": {
        "tiles": tiles, "provenance": provenance}}}}}


def test_kern704_fires_on_missing_entry():
    fs = ka.table_findings([_required()], {"kernels": {}})
    assert [f.rule for f in fs] == ["KERN704"]
    assert "no tuning-table entry" in fs[0].message


def test_kern704_fires_on_hand_picked_drift():
    fs = ka.table_findings([_required()], _table({"bq": 256}))
    assert any(f.key == "drift/k/s/bfloat16/bq" for f in fs)
    # measured provenance is ALLOWED to differ from the in-code constant
    assert ka.table_findings([_required()], _table({"bq": 256}, "measured")) == []


def test_kern704_fires_on_bad_provenance_and_missing_param():
    fs = ka.table_findings([_required()], _table({}, provenance="guessed"))
    keys = {f.key for f in fs}
    assert "provenance/k/s/bfloat16" in keys
    assert "params/k/s/bfloat16" in keys


def test_kern704_warns_on_stale_entry():
    fs = ka.table_findings([], _table({"bq": 128}))
    assert any(f.key == "stale/k/s/bfloat16" for f in fs)


def test_kern704_silent_on_agreeing_table():
    assert ka.table_findings([_required()], _table({"bq": 128})) == []


def test_committed_table_covers_registry():
    """Both committed artifacts exist, parse, and agree with the registry's
    hand-picked constants (the in-repo direction of KERN704)."""
    table = ka.load_tuning_table()
    assert table, "analysis/tuning_table.json must be committed"
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr

    for s in kr.REGISTRY:
        if not s.tile_params:
            continue
        for c in s.cases:
            entry = table["kernels"][s.table_key][c.shape_class][c.dtype]
            assert entry["provenance"] in ("hand_picked", "measured")
            hand = kr.hand_picked_tiles(s.table_key, c.shape_class)
            if entry["provenance"] == "hand_picked" and hand:
                for p, v in hand.items():
                    assert entry["tiles"][p] == v, (s.name, c.shape_class, p)


def test_the_latent_chunk_kernels_tiles_are_pinned_in_code_registry_and_table(monkeypatch):
    """The latent chunk kernel's tiles under its OWN name: the in-code
    fallback (``latent_attention.Q_ROWS``, ``GROUP_TOKENS``), the registry's
    ``HAND_PICKED`` mirror and the committed ``measured`` entry agree, so a
    bare checkout (no table) launches the kernel the table's readers measured;
    and nothing of it is the GQA prefill kernel's."""
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr
    from neuronx_distributed_inference_tpu.ops import latent_attention as la
    from neuronx_distributed_inference_tpu.ops import paged_flash_attention as pf
    from neuronx_distributed_inference_tpu.ops import tile_defaults

    hand = kr.hand_picked_tiles(la.CHUNK_KERNEL, "blk1x32x512")
    assert hand == {"rows": la.Q_ROWS, "pages": la.GROUP_TOKENS // 32} == {"rows": 512, "pages": 32}
    entry = ka.load_tuning_table()["kernels"][la.CHUNK_KERNEL]["blk1x32x512"]["bfloat16"]
    assert entry == {"provenance": "measured", "tiles": hand}
    (spec,) = [s for s in kr.REGISTRY if s.name == la.CHUNK_KERNEL]
    assert spec.tile_params == ("rows", "pages")
    with_table = la.blocks_per_group(1, 32, 512, "bfloat16", 528)
    monkeypatch.setattr(tile_defaults, "_load_table", lambda: {})
    assert la.blocks_per_group(1, 32, 512, "bfloat16", 528) == with_table == 32
    assert pf.blocks_per_group(1, 32, 512, "bfloat16", 528) == 16 and pf.Q_ROWS == 256


# ---------------------------------------------------------------------------
# KERN705: MXU occupancy floor + dead grid axes
# ---------------------------------------------------------------------------


def _mxu_census(occ, dead):
    return {"k/s/bf16": {
        "location": "ops/k.py", "occupancy": occ, "dead_axes": dead,
        "intensity": 4.0, "bound": "memory",
    }}


def test_kern705_fires_on_unpinned_subfloor():
    fs = ka.mxu_findings(_mxu_census(0.3, []), {}, floor=0.6)
    assert [f.rule for f in fs] == ["KERN705"]
    assert "occupancy 0.300" in fs[0].message


def test_kern705_fires_on_unpinned_dead_axis():
    fs = ka.mxu_findings(_mxu_census(1.0, [2]), {}, floor=0.6)
    assert any("dead (extent-1) grid axes [2]" in f.message for f in fs)


def test_kern705_silent_when_pinned_or_clean():
    base = {"mxu_flags": {"k/s/bf16": {"occupancy": 0.3, "dead_axes": [2]}}}
    assert ka.mxu_findings(_mxu_census(0.3, [2]), base, floor=0.6) == []
    assert ka.mxu_findings(_mxu_census(0.9, []), {}, floor=0.6) == []
    # pin for a DIFFERENT value does not cover a new drop
    assert ka.mxu_findings(_mxu_census(0.2, [2]), base, floor=0.6) != []


# ---------------------------------------------------------------------------
# tile routing: table defaults are byte-identical to the old constants
# ---------------------------------------------------------------------------


def test_tile_default_override_and_fallback():
    from neuronx_distributed_inference_tpu.ops.tile_defaults import (
        tile_default,
        tile_overrides,
    )

    # unknown kernel -> the caller's fallback constant
    assert tile_default("nope", "s", "bfloat16", "bq", 99) == 99
    # the committed table serves the flash default
    assert tile_default("flash_attention", "plain", "bfloat16", "bq", 99) == 512
    with tile_overrides("flash_attention", {"bq": 256}):
        assert tile_default("flash_attention", "plain", "bfloat16", "bq", 99) == 256
    assert tile_default("flash_attention", "plain", "bfloat16", "bq", 99) == 512


def test_flash_table_default_byte_identical():
    """flash_attention with table-routed defaults (bq/bkv None) returns the
    EXACT bytes the old hard-coded constants produced."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.ops.flash_attention import (
        flash_attention_bhsd,
    )

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 256, 64), jnp.float32)
    valid = jnp.ones((1, 256), jnp.int32)
    kw = dict(scale=0.125, causal=True, interpret=True)
    out_table, _, _ = flash_attention_bhsd(q, q, q, valid, **kw)
    out_const, _, _ = flash_attention_bhsd(q, q, q, valid, bq=512, bkv=512, **kw)
    np.testing.assert_array_equal(np.asarray(out_table), np.asarray(out_const))


def test_tkg_table_default_byte_identical():
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.ops.decode_attention import (
        tkg_decode_attention,
    )

    rng = np.random.RandomState(0)
    L, B, S, Hkv, Hq, D = 2, 1, 512, 2, 4, 64
    q = jnp.asarray(rng.randn(B, 1, Hq, D), jnp.float32)
    cache = jnp.asarray(rng.randn(L, B, S, Hkv, D), jnp.float32)
    li = jnp.int32(0)
    mask = jnp.ones((B, 1, 1, S), bool)
    kw = dict(scale=0.125, n_kv=Hkv, interpret=True)
    out_table = tkg_decode_attention(q, cache, cache, li, mask, **kw)
    out_const = tkg_decode_attention(q, cache, cache, li, mask, bs=512, **kw)
    np.testing.assert_array_equal(np.asarray(out_table), np.asarray(out_const))


# ---------------------------------------------------------------------------
# legal_tiles: the pruned autotuner search space
# ---------------------------------------------------------------------------


def test_legal_tiles_flash_full_grid():
    tiles = ka.legal_tiles("flash_attention", "plain", "bfloat16")
    # every sweep combination is legal at the 8k bench shape
    assert len(tiles) == 9
    assert {"bq": 512, "bkv": 512} in tiles


def test_legal_tiles_prunes_over_budget(monkeypatch):
    # int4 matmul at N=8192 under a wider sweep than the registry commits:
    # bn=8192 (the whole width in one step) would put the double-buffered
    # weight windows over the 16 MiB budget — it must NOT be emitted
    import dataclasses

    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr

    spec = next(s for s in kr.REGISTRY if s.name == "quant_matmul")
    wide = dataclasses.replace(spec, sweep=(("bn", (512, 4096, 8192)),))
    monkeypatch.setattr(kr, "REGISTRY", tuple(wide if s is spec else s for s in kr.REGISTRY))
    tiles = ka.legal_tiles("quant_matmul", "k2048_n8192", "bfloat16")
    assert tiles == [{"bn": 512}, {"bn": 4096}]


def test_legal_tiles_enforces_packing_contract():
    # ragged: only divisors of RAGGED_Q_TILE survive, and tq=8 is sublane-
    # illegal for bf16 — exactly one candidate remains
    assert ka.legal_tiles("ragged_paged_attention", "mixed", "bfloat16") == [
        {"tq": 16}
    ]


def test_legal_tiles_dedupes_clamped_candidates():
    # bs=1024 clamps to the 512 kv bucket -> identical trace, one candidate
    tiles = ka.legal_tiles("tkg_decode_attention", "kv512", "bfloat16")
    assert tiles == [{"bs": 128}, {"bs": 256}, {"bs": 512}]


def test_legal_tiles_unknown_kernel_raises():
    with pytest.raises(KeyError):
        ka.legal_tiles("nope", "plain", "bfloat16")
    with pytest.raises(KeyError):
        ka.legal_tiles("flash_attention", "plain", "float16")


def test_sweep_scripts_source_candidates_from_legal_tiles():
    """The sweep script carries no hand-built tile list: its candidate
    sets come from legal_tiles."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    assert "legal_tiles" in (root / "scripts/decode_scaling.py").read_text()


def test_quant_matmul_tile_sweep():
    """The int4 quant-matmul bn sweep measures every gate-legal candidate
    from legal_tiles at the committed 1B shape — interpret mode on CPU, the
    identical code path hardware runs compiled."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    import decode_scaling

    sweep = decode_scaling.sweep_quant_matmul_tiles(n=1, interpret=True)
    assert set(sweep) == {"bn128", "bn256", "bn512"}
    for bn, row in sweep.items():
        assert row.get("us", 0) > 0, (bn, row)


# ---------------------------------------------------------------------------
# the gate itself: clean tree exits 0 with the committed baselines
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kernel_suite_clean_tree_exit_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "neuronx_distributed_inference_tpu.analysis",
         "--suites", "kernel", "--json"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["new"] == 0
    assert report["new_findings"] == []


def test_kernel_suite_run_inprocess_clean():
    """run() on the committed tree: no findings, and the report census
    covers every registered instantiation."""
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr

    findings = ka.run()
    assert findings == [], [f.message for f in findings]
    report = ka.last_report()
    assert report["n_registered"] == len(kr.REGISTRY)
    assert len(report["instances"]) == sum(len(s.cases) for s in kr.REGISTRY)
    text = ka.render_breakdown(report)
    assert "grouped_matmul/k2048_n2048/bfloat16" in text
