"""Kernel-contract audit (KERN701-705): static VMEM/tile-legality model over
every Pallas kernel in ``ops/``, instantiated at the committed bench shapes
through :mod:`analysis.kernel_registry`.

The repo's kernels ship tiles hand-picked with no TPU in the container. This
suite is the contract layer the ROADMAP autotuner needs: it proves — as
arithmetic, on a CPU-only host — that every committed (kernel, shape, dtype)
instantiation fits the device's scoped VMEM, is Mosaic-tile-legal, names a
native fallback plus parity coverage, and reads its tile defaults from the
committed ``tuning_table.json``; and it enumerates the LEGAL candidate space
(:func:`legal_tiles`) so hardware session zero measures only tiles that can
compile and fit.

Rules
-----
- **KERN701** static VMEM budget: 2x (double-buffered) operand/output block
  windows + ``pltpu.VMEM`` scratch vs ``DeviceSpec.vmem_bytes`` for the
  bench device, or vs the scoped limit the call itself asks the compiler for
  (``vmem_limit_bytes``) where that is more. Over-budget at any committed
  shape is an error that cannot be baselined away; the per-instance census
  (vmem bytes, grid, flops/step) is pinned in ``kernel_baseline.json`` like
  the cost census.
- **KERN702** Mosaic tile legality: block last dim a 128-lane multiple (or
  equal to the array dim), sublane multiples by dtype width (8/f32,
  16/bf16, 32/int8-fp8), block-vs-array divisibility per axis, plus the
  prose packing contract of PR 6 as arithmetic (ragged q-tile divides
  RAGGED_Q_TILE so a tile never spans rows).
- **KERN703** kernel census: every ``pl.pallas_call`` site under ``ops/``
  must be claimed by a registry entry; every entry must name an importable
  native fallback, a parity test and a TPU-lowering test that mention its
  entry point.
- **KERN704** tuning table: every registered (kernel, shape-class, dtype)
  with free tile params needs a committed ``tuning_table.json`` entry with
  valid provenance; while provenance is ``hand_picked`` the entry must
  equal the in-code fallback constants (drift check, both directions).
- **KERN705** arithmetic-intensity floor: FLOPs-weighted MXU occupancy of
  the kernel body's dots (contraction depth x output lanes vs the 128x128
  array) and dead (extent-1) grid axes, reconciled against the committed
  census — known sub-floor kernels (the D=64 half-depth family the packed
  kernel exists for) are pinned; a NEW sub-floor kernel or dead axis errors.

Workflow parity with the other suites: ``run(write_baseline=...)``,
``last_report()``, ``render_breakdown()``; regenerate baselines with
``python -m neuronx_distributed_inference_tpu.analysis --suites kernel
--write-baseline`` and review the diff like code.
"""

from __future__ import annotations

import importlib
import itertools
import json
import pathlib
from typing import Dict, List, Optional, Tuple

from neuronx_distributed_inference_tpu.analysis.findings import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
)

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "kernel_baseline.json"
TABLE_PATH = pathlib.Path(__file__).resolve().parent / "tuning_table.json"
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent

#: FLOPs-weighted MXU-occupancy floor (KERN705). 128x128 MXU: a D=64
#: attention contraction half-fills the array (0.5) — known and pinned; the
#: floor catches kernels that fall BELOW the committed family (e.g. a
#: lane-starved dot at <32 output lanes).
MXU_FLOOR = 0.6

#: sublane multiple per operand byte-width (Mosaic packing): fp32 tiles are
#: (8, 128), bf16 (16, 128), int8/fp8 (32, 128)
SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}

_LAST_REPORT: Optional[dict] = None


# ---------------------------------------------------------------------------
# baseline + tuning-table IO
# ---------------------------------------------------------------------------


def load_kernel_baseline(path: Optional[pathlib.Path] = None) -> dict:
    p = path or BASELINE_PATH
    if not p.exists():
        return {}
    with open(p) as f:
        return json.load(f)


def save_kernel_baseline(data: dict, path: Optional[pathlib.Path] = None) -> None:
    p = path or BASELINE_PATH
    with open(p, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def load_tuning_table(path: Optional[pathlib.Path] = None) -> dict:
    p = path or TABLE_PATH
    if not p.exists():
        return {}
    with open(p) as f:
        return json.load(f)


def save_tuning_table(data: dict, path: Optional[pathlib.Path] = None) -> None:
    p = path or TABLE_PATH
    with open(p, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# pure comparators (unit-testable both directions without tracing)
# ---------------------------------------------------------------------------


def _occupancy(dot_stats) -> Optional[float]:
    tot = sum(f for f, _, _ in dot_stats)
    if not tot:
        return None
    w = sum(
        f * (min(k, 128) / 128.0) * (min(n, 128) / 128.0) for f, k, n in dot_stats
    )
    return w / tot


def vmem_findings(
    key: str, location: str, vmem_bytes: int, budget: int, asked: Optional[int] = None
) -> List[Finding]:
    """KERN701 hard budget: over-budget is an error, never baselinable. A
    call that asks the compiler for a scoped limit of its own (``asked``:
    its ``vmem_limit_bytes``) is held to what it asks where that is more than
    the default; whether the chip grants it is the lowering tests' to say."""
    budget = max(budget, asked or 0)
    if vmem_bytes <= budget:
        return []
    return [
        Finding(
            rule="KERN701",
            severity=SEV_ERROR,
            location=location,
            message=(
                f"{key}: static VMEM model {vmem_bytes / 2**20:.2f} MiB exceeds "
                f"the {budget / 2**20:.0f} MiB per-core budget "
                f"(double-buffered block windows + scratch) — shrink the tile "
                f"or split the kernel"
            ),
            key=key,
        )
    ]


def census_findings(census: Dict[str, dict], baseline: dict) -> List[Finding]:
    """KERN701 census pin: the committed per-instance numbers must match the
    tree exactly (the model is arithmetic — any drift is a real change)."""
    out = []
    base = baseline.get("census", {})
    for key, row in sorted(census.items()):
        b = base.get(key)
        if b is None:
            out.append(
                Finding(
                    rule="KERN701",
                    severity=SEV_ERROR,
                    location=row["location"],
                    message=(
                        f"{key}: no committed kernel census — run "
                        f"--write-baseline and review/commit kernel_baseline.json"
                    ),
                    key=key,
                )
            )
            continue
        for fieldname in ("vmem_bytes", "grid", "flops_per_step"):
            if b.get(fieldname) != row[fieldname]:
                out.append(
                    Finding(
                        rule="KERN701",
                        severity=SEV_ERROR,
                        location=row["location"],
                        message=(
                            f"{key}: kernel census drift — {fieldname} "
                            f"{b.get(fieldname)} (committed) != {row[fieldname]} "
                            f"(tree); review and --write-baseline if intended"
                        ),
                        key=f"{key}/{fieldname}",
                    )
                )
    for key in sorted(set(base) - set(census)):
        out.append(
            Finding(
                rule="KERN701",
                severity=SEV_WARNING,
                location="analysis/kernel_baseline.json",
                message=(
                    f"{key}: stale kernel census entry (no such registered "
                    f"instance) — --write-baseline to drop it"
                ),
                key=f"stale/{key}",
            )
        )
    return out


def block_legality_findings(
    key: str,
    location: str,
    blocks,
    *,
    dtype_label: str = "",
) -> List[Finding]:
    """KERN702 per-block Mosaic legality. ``blocks`` is an iterable of
    objects with block_shape/array_shape/itemsize (BlockInfo or any stub)."""
    out = []
    for i, b in enumerate(blocks):
        bl, arr = tuple(b.block_shape), tuple(b.array_shape)
        sub = SUBLANE_BY_ITEMSIZE.get(b.itemsize, 8)
        probs = []
        if bl:
            lane_ok = bl[-1] % 128 == 0 or bl[-1] == arr[-1]
            if not lane_ok:
                probs.append(
                    f"last dim {bl[-1]} is neither a 128-lane multiple nor "
                    f"the array dim {arr[-1]}"
                )
        if len(bl) >= 2:
            sub_ok = bl[-2] % sub == 0 or bl[-2] == arr[-2]
            if not sub_ok:
                probs.append(
                    f"sublane dim {bl[-2]} is neither a multiple of {sub} "
                    f"(itemsize {b.itemsize}) nor the array dim {arr[-2]}"
                )
        for ax, (bd, ad) in enumerate(zip(bl, arr)):
            if bd and ad % bd:
                probs.append(
                    f"axis {ax}: array dim {ad} not divisible by block dim "
                    f"{bd} (padded grid would read junk)"
                )
        for p in probs:
            out.append(
                Finding(
                    rule="KERN702",
                    severity=SEV_ERROR,
                    location=location,
                    message=(
                        f"{key}: operand {i} block {bl} over array {arr}: {p}"
                    ),
                    key=f"{key}/block{i}",
                )
            )
    return out


def packing_contract_findings(
    key: str, location: str, tq: int, ragged_q_tile: int
) -> List[Finding]:
    """KERN702 packing contract (PR 6 prose, as arithmetic): row starts are
    RAGGED_Q_TILE-aligned, so a q tile never spans rows iff tq divides
    RAGGED_Q_TILE."""
    out = []
    if tq > ragged_q_tile or ragged_q_tile % tq:
        out.append(
            Finding(
                rule="KERN702",
                severity=SEV_ERROR,
                location=location,
                message=(
                    f"{key}: q tile {tq} does not divide RAGGED_Q_TILE "
                    f"{ragged_q_tile} — a tile could span two packed rows"
                ),
                key=f"{key}/rowspan",
            )
        )
    return out


def registry_findings(
    sites: List[Tuple[str, str, int]],
    claimed: Dict[Tuple[str, str], str],
    checks: List[dict],
) -> List[Finding]:
    """KERN703: unclaimed pallas_call sites, stale registry sites, fallback/
    test reference failures. ``checks`` rows: {kernel, fallback_ok, fallback,
    parity_ok, parity_test, lowering_ok, lowering_test, entry}."""
    out = []
    site_set = {(f, fn) for f, fn, _ in sites}
    for f, fn, line in sorted(sites):
        if (f, fn) not in claimed:
            out.append(
                Finding(
                    rule="KERN703",
                    severity=SEV_ERROR,
                    location=f"ops/{f}:{line}",
                    message=(
                        f"unregistered pallas_call in {fn}(): every kernel "
                        f"must be enumerated in analysis/kernel_registry.py "
                        f"with a fallback, parity test and lowering test"
                    ),
                    key=f"unregistered/{f}/{fn}",
                )
            )
    for (f, fn), kernel in sorted(claimed.items()):
        if (f, fn) not in site_set:
            out.append(
                Finding(
                    rule="KERN703",
                    severity=SEV_ERROR,
                    location=f"ops/{f}",
                    message=(
                        f"{kernel}: registry claims a pallas_call in {fn}() "
                        f"but none exists — stale registry entry"
                    ),
                    key=f"stale-site/{f}/{fn}",
                )
            )
    for row in checks:
        k = row["kernel"]
        if not row["fallback_ok"]:
            out.append(
                Finding(
                    rule="KERN703",
                    severity=SEV_ERROR,
                    location="analysis/kernel_registry.py",
                    message=(
                        f"{k}: native fallback {row['fallback']} does not "
                        f"import — every kernel must name a working fallback"
                    ),
                    key=f"fallback/{k}",
                )
            )
        if not row["parity_ok"]:
            out.append(
                Finding(
                    rule="KERN703",
                    severity=SEV_ERROR,
                    location=row["parity_test"],
                    message=(
                        f"{k}: parity test {row['parity_test']} is missing or "
                        f"never references {row['entry']}"
                    ),
                    key=f"parity/{k}",
                )
            )
        if not row["lowering_ok"]:
            out.append(
                Finding(
                    rule="KERN703",
                    severity=SEV_ERROR,
                    location=row["lowering_test"],
                    message=(
                        f"{k}: TPU lowering test {row['lowering_test']} is "
                        f"missing or never references {row['entry']}"
                    ),
                    key=f"lowering/{k}",
                )
            )
    return out


def table_findings(
    required: List[dict],
    table: dict,
) -> List[Finding]:
    """KERN704. ``required`` rows: {kernel (table key), shape_class, dtype,
    tile_params, hand_picked (dict|None), location}. Checks coverage,
    provenance validity, and hand_picked<->in-code drift both directions."""
    out = []
    kernels = table.get("kernels", {})
    seen = set()
    for row in required:
        k, sc, dt = row["kernel"], row["shape_class"], row["dtype"]
        seen.add((k, sc, dt))
        entry = kernels.get(k, {}).get(sc, {}).get(dt)
        keybase = f"{k}/{sc}/{dt}"
        if not isinstance(entry, dict):
            out.append(
                Finding(
                    rule="KERN704",
                    severity=SEV_ERROR,
                    location="analysis/tuning_table.json",
                    message=(
                        f"{keybase}: no tuning-table entry for a registered "
                        f"kernel instantiation — run --write-baseline to seed "
                        f"hand_picked defaults and commit the table"
                    ),
                    key=f"missing/{keybase}",
                )
            )
            continue
        prov = entry.get("provenance")
        if prov not in ("hand_picked", "measured"):
            out.append(
                Finding(
                    rule="KERN704",
                    severity=SEV_ERROR,
                    location="analysis/tuning_table.json",
                    message=(
                        f"{keybase}: invalid provenance {prov!r} (must be "
                        f"hand_picked or measured)"
                    ),
                    key=f"provenance/{keybase}",
                )
            )
        tiles = entry.get("tiles", {})
        missing = [p for p in row["tile_params"] if p not in tiles]
        if missing:
            out.append(
                Finding(
                    rule="KERN704",
                    severity=SEV_ERROR,
                    location="analysis/tuning_table.json",
                    message=(
                        f"{keybase}: table entry missing tile params {missing}"
                    ),
                    key=f"params/{keybase}",
                )
            )
        hand = row.get("hand_picked")
        if prov == "hand_picked" and hand:
            for p, v in hand.items():
                if p in tiles and int(tiles[p]) != int(v):
                    out.append(
                        Finding(
                            rule="KERN704",
                            severity=SEV_ERROR,
                            location="analysis/tuning_table.json",
                            message=(
                                f"{keybase}: hand_picked table value {p}="
                                f"{tiles[p]} drifted from the in-code default "
                                f"{v} — either revert, or regenerate on "
                                f"hardware and promote to measured"
                            ),
                            key=f"drift/{keybase}/{p}",
                        )
                    )
    for k, per_k in sorted(kernels.items()):
        for sc, per_sc in sorted(per_k.items()):
            for dt in sorted(per_sc):
                if (k, sc, dt) not in seen:
                    out.append(
                        Finding(
                            rule="KERN704",
                            severity=SEV_WARNING,
                            location="analysis/tuning_table.json",
                            message=(
                                f"{k}/{sc}/{dt}: tuning-table entry has no "
                                f"registered kernel instantiation — stale?"
                            ),
                            key=f"stale/{k}/{sc}/{dt}",
                        )
                    )
    return out


def mxu_findings(
    census: Dict[str, dict], baseline: dict, floor: float = MXU_FLOOR
) -> List[Finding]:
    """KERN705: sub-floor MXU occupancy / dead grid axes not pinned in the
    committed census. Pinned flags (the known D=64 half-depth family, the
    batch-1 bench grids) stay silent; anything new errors."""
    out = []
    pinned = baseline.get("mxu_flags", {})
    for key, row in sorted(census.items()):
        flags = {}
        occ = row.get("occupancy")
        if occ is not None and occ < floor:
            flags["occupancy"] = occ
        dead = row.get("dead_axes") or []
        if dead:
            flags["dead_axes"] = dead
        if not flags:
            continue
        pin = pinned.get(key)
        if pin is not None and pin.get("occupancy") == flags.get("occupancy") and pin.get("dead_axes", []) == flags.get("dead_axes", []):
            continue
        what = []
        if "occupancy" in flags:
            what.append(
                f"FLOPs-weighted MXU occupancy {flags['occupancy']:.3f} < "
                f"floor {floor} (contraction depth / output lanes under-fill "
                f"the 128x128 array)"
            )
        if "dead_axes" in flags:
            what.append(f"dead (extent-1) grid axes {flags['dead_axes']}")
        out.append(
            Finding(
                rule="KERN705",
                severity=SEV_ERROR,
                location=row["location"],
                message=(
                    f"{key}: {'; '.join(what)} — not pinned in the committed "
                    f"census (cost-audit reconciliation: intensity "
                    f"{row.get('intensity', 0):.1f} FLOP/byte, {row.get('bound')}-"
                    f"bound vs the bench device ridge); --write-baseline if "
                    f"this tile/shape trade-off is intended"
                ),
                key=key,
            )
        )
    return out


# ---------------------------------------------------------------------------
# legal-tile enumeration (KERN704's generator — the autotuner search space)
# ---------------------------------------------------------------------------


def _instance_signature(spec, case, tiles):
    """Trace the candidate; return a hashable (grid, blocks, scratch)
    signature if it passes KERN701/702, else None. The signature also
    collapses clamp-duplicates (two requested tiles that trace the same
    kernel are one candidate)."""
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr
    from neuronx_distributed_inference_tpu.analysis.device_model import get_device

    try:
        inst = kr.instantiate(spec, case, tiles=tiles)
    except ValueError:
        return None  # the wrapper itself rejects the tiling
    budget = get_device().vmem_bytes
    if vmem_findings(inst.key, "x", inst.vmem_bytes, budget, inst.vmem_limit):
        return None
    if block_legality_findings(inst.key, "x", inst.blocks):
        return None
    if spec.name == "ragged_paged_attention":
        from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
            RAGGED_Q_TILE,
        )

        if packing_contract_findings(
            inst.key, "x", tiles.get("tq", RAGGED_Q_TILE), RAGGED_Q_TILE
        ):
            return None
    return (
        tuple(inst.grid),
        tuple(tuple(b.block_shape) for b in inst.blocks),
        inst.scratch_bytes,
    )


def legal_tiles(kernel: str, shape_class: str, dtype: str) -> List[Dict[str, int]]:
    """Enumerate the tile candidates for (kernel, shape-class, dtype) that
    pass KERN701 (VMEM) and KERN702 (legality) at the committed shapes —
    the pruned search space the profile sweeps and (eventually) the
    hardware autotuner measure. Candidates come from the registry's sweep
    axes; each is instantiated through the SAME tile-lookup path a
    committed table entry would use."""
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr

    spec = next((s for s in kr.REGISTRY if s.name == kernel), None)
    if spec is None:
        raise KeyError(f"unknown kernel {kernel!r}")
    case = next(
        (
            c
            for c in spec.cases
            if c.shape_class == shape_class and c.dtype == dtype
        ),
        None,
    )
    if case is None:
        raise KeyError(f"{kernel}: no committed case {shape_class}/{dtype}")
    if not spec.sweep:
        return []
    names = [n for n, _ in spec.sweep]
    out = []
    seen_sigs = set()
    for combo in itertools.product(*(vals for _, vals in spec.sweep)):
        tiles = dict(zip(names, combo))
        sig = _instance_signature(spec, case, tiles)
        if sig is None or sig in seen_sigs:
            # illegal, or a clamp-duplicate (e.g. bs > S_kv clamps to S_kv
            # and traces the identical grid/blocks as the clamped value)
            continue
        seen_sigs.add(sig)
        out.append(tiles)
    return out


# ---------------------------------------------------------------------------
# suite entry point
# ---------------------------------------------------------------------------


def _census_row(inst, ridge: float) -> dict:
    occ = _occupancy(inst.dot_stats)
    bytes_step = inst.block_bytes_single + inst.copy_bytes
    intensity = inst.flops_per_step / bytes_step if bytes_step else 0.0
    return {
        "location": f"ops/{inst.kernel}",
        "vmem_bytes": inst.vmem_bytes,
        "vmem_limit": inst.vmem_limit,  # what the call asks for itself, or None
        "scratch_bytes": inst.scratch_bytes,
        "grid": list(inst.grid),
        "flops_per_step": inst.flops_per_step,
        "tiles": dict(inst.tiles),
        "occupancy": round(occ, 3) if occ is not None else None,
        "dead_axes": [i for i, g in enumerate(inst.grid) if g == 1],
        "intensity": round(intensity, 2),
        "bound": "compute" if intensity >= ridge else "memory",
    }


def run(
    write_baseline: bool = False,
    baseline_path: Optional[pathlib.Path] = None,
    table_path: Optional[pathlib.Path] = None,
) -> List[Finding]:
    """Run KERN701-705; returns unbaselinable findings (the census/table
    pins already encode the baseline, so everything returned is NEW)."""
    global _LAST_REPORT
    from neuronx_distributed_inference_tpu.analysis import kernel_registry as kr
    from neuronx_distributed_inference_tpu.analysis.device_model import get_device
    from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
        RAGGED_Q_TILE,
    )

    device = get_device()
    budget = device.vmem_bytes
    ridge = device.ridge_flops_per_byte

    findings: List[Finding] = []
    instances = kr.collect_instances()
    census: Dict[str, dict] = {}
    site_of = {s.name: s.site for s in kr.REGISTRY}
    for inst in instances:
        f, fn = site_of[inst.kernel]
        loc = f"ops/{f}:{fn}"
        row = _census_row(inst, ridge)
        row["location"] = loc
        census[inst.key] = row
        findings += vmem_findings(inst.key, loc, inst.vmem_bytes, budget, inst.vmem_limit)
        findings += block_legality_findings(inst.key, loc, inst.blocks)
        if inst.kernel == "ragged_paged_attention":
            findings += packing_contract_findings(
                inst.key, loc, inst.tiles.get("tq", RAGGED_Q_TILE), RAGGED_Q_TILE
            )

    # KERN703 census
    sites = kr.pallas_sites()
    claimed = {s.site: s.name for s in kr.REGISTRY}
    checks = []
    for s in kr.REGISTRY:
        mod, _, attr = s.fallback.partition(":")
        try:
            fallback_ok = hasattr(importlib.import_module(mod), attr)
        except ImportError:
            fallback_ok = False

        def _mentions(rel: str, needle: str) -> bool:
            p = REPO_ROOT / rel
            return p.exists() and needle in p.read_text()

        checks.append(
            {
                "kernel": s.name,
                "entry": s.entry,
                "fallback": s.fallback,
                "fallback_ok": fallback_ok,
                "parity_test": s.parity_test,
                "parity_ok": _mentions(s.parity_test, s.entry),
                "lowering_test": s.lowering_test,
                "lowering_ok": _mentions(s.lowering_test, s.entry),
            }
        )
    findings += registry_findings(sites, claimed, checks)

    # KERN704 tuning table
    table = load_tuning_table(table_path)
    required = []
    for s in kr.REGISTRY:
        if not s.tile_params:
            continue
        for c in s.cases:
            required.append(
                {
                    "kernel": s.table_key,
                    "shape_class": c.shape_class,
                    "dtype": c.dtype,
                    "tile_params": s.tile_params,
                    "hand_picked": kr.hand_picked_tiles(s.table_key, c.shape_class),
                    "location": f"ops/{s.site[0]}",
                }
            )
    if write_baseline:
        kernels = table.setdefault("kernels", {})
        table.setdefault(
            "comment",
            "Tile defaults per (kernel, shape-class, dtype). provenance "
            "hand_picked mirrors the in-code constants (KERN704 pins them "
            "equal); hardware sweeps promote entries to measured.",
        )
        for row in required:
            per = kernels.setdefault(row["kernel"], {}).setdefault(
                row["shape_class"], {}
            )
            if row["dtype"] not in per:
                per[row["dtype"]] = {
                    "tiles": dict(row["hand_picked"] or {}),
                    "provenance": "hand_picked",
                }
        save_tuning_table(table, table_path)
        from neuronx_distributed_inference_tpu.ops import tile_defaults

        tile_defaults.reload_table()
        table = load_tuning_table(table_path)
    findings += table_findings(required, table)

    # KERN701 census pin + KERN705 occupancy flags
    baseline = load_kernel_baseline(baseline_path)
    if write_baseline:
        mxu_flags = {}
        for key, row in census.items():
            flags = {}
            if row["occupancy"] is not None and row["occupancy"] < MXU_FLOOR:
                flags["occupancy"] = row["occupancy"]
            if row["dead_axes"]:
                flags["dead_axes"] = row["dead_axes"]
            if flags:
                mxu_flags[key] = flags
        baseline = {
            "census": {
                k: {
                    f: v
                    for f, v in row.items()
                    if f in ("vmem_bytes", "grid", "flops_per_step", "tiles",
                             "occupancy", "intensity", "bound", "scratch_bytes")
                }
                for k, row in sorted(census.items())
            },
            "mxu_flags": mxu_flags,
        }
        save_kernel_baseline(baseline, baseline_path)
    findings += census_findings(census, baseline)
    findings += mxu_findings(census, baseline)

    _LAST_REPORT = {
        "device": device.name,
        "vmem_budget": budget,
        "instances": census,
        "n_sites": len(sites),
        "n_registered": len(kr.REGISTRY),
        "findings": len(findings),
    }
    return findings


def last_report() -> Optional[dict]:
    return _LAST_REPORT


def render_breakdown(report: Optional[dict]) -> str:
    if not report:
        return ""
    lines = [
        f"kernel audit: {report['n_registered']} registered kernels over "
        f"{report['n_sites']} pallas_call sites, device {report['device']} "
        f"(VMEM budget {report['vmem_budget'] / 2**20:.0f} MiB)",
        f"{'instance':46s} {'grid':>16s} {'vmem':>9s} {'occ':>5s} "
        f"{'AI':>8s} bound",
    ]
    for key, row in sorted(report["instances"].items()):
        occ = row["occupancy"]
        lines.append(
            f"{key:46s} {str(tuple(row['grid'])):>16s} "
            f"{row['vmem_bytes'] / 2**20:8.2f}M "
            f"{occ if occ is not None else 0:5.2f} "
            f"{row['intensity']:8.1f} {row['bound']}"
        )
    return "\n".join(lines)
