"""One CLI for the static-analysis gate — shared by
``python -m neuronx_distributed_inference_tpu.analysis`` and
``scripts/run_static_analysis.py`` (one arg-parser, so the flag surface
cannot drift between the two entry points).

Runs the analysis suites and exits non-zero when any NON-BASELINED finding
exists. Designed to run on a CPU-only host (``JAX_PLATFORMS=cpu``): the
graph/shard/memory audits trace tiny tp-sharded models on 8 virtual devices.

    python -m neuronx_distributed_inference_tpu.analysis            # text
    python -m neuronx_distributed_inference_tpu.analysis --json     # JSON
    python -m ... --suites lint,flags      # skip the (slower) traced audits
    python -m ... --write-baseline         # accept current findings/censuses

An unknown ``--suites`` name is an ERROR (exit 2 with the known list) — a
typo must never select nothing and report green. ``--write-baseline`` prints
a unified diff of every baseline file it rewrote, so a regeneration is
reviewable right in the terminal before it is committed.
"""

from __future__ import annotations

import argparse
import difflib
import os
import sys
from typing import Dict, List, Optional, Tuple

from neuronx_distributed_inference_tpu.analysis import findings as findings_mod
from neuronx_distributed_inference_tpu.analysis.findings import Baseline, Finding

_ANALYSIS_DIR = os.path.dirname(__file__)
TPULINT_BASELINE = os.path.join(_ANALYSIS_DIR, "tpulint_baseline.json")

ALL_SUITES = (
    "lint", "flags", "graph", "shard", "memory", "cost", "conc", "kernel",
    "life",
)

#: every committed baseline file --write-baseline may rewrite (diffed after)
BASELINE_FILES = (
    "tpulint_baseline.json",
    "graph_baseline.json",
    "shard_baseline.json",
    "memory_baseline.json",
    "cost_baseline.json",
    "conc_baseline.json",
    "kernel_baseline.json",
    "tuning_table.json",
    "life_baseline.json",
)


def _prepare_jax_cpu():
    """Force the CPU backend with 8 virtual devices (idempotent; a no-op if
    a backend is already initialized by the embedding process)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
    import jax

    try:
        jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS") or "cpu")
    except Exception:
        pass


def build_parser() -> argparse.ArgumentParser:
    """THE arg parser for the gate — both entry points consume it."""
    parser = argparse.ArgumentParser(
        prog="python -m neuronx_distributed_inference_tpu.analysis",
        description=(
            "Static-analysis gate: tpulint + flag audit + graph audit + "
            "shard audit + memory audit + cost audit + concurrency audit + "
            "kernel audit + lifecycle audit"
        ),
    )
    parser.add_argument("--json", action="store_true", help="JSON report")
    parser.add_argument(
        "--suites",
        default=",".join(ALL_SUITES),
        help=f"comma list of {ALL_SUITES} (default: all)",
    )
    parser.add_argument(
        "--write-baseline",
        action="store_true",
        help=(
            "accept current lint findings + graph/shard/memory censuses as "
            "the baseline (prints a unified diff of every rewritten file)"
        ),
    )
    return parser


def parse_suites(parser: argparse.ArgumentParser, raw: str) -> List[str]:
    """Validate the --suites list: an unknown name errors with the known
    set (exit 2) instead of silently selecting nothing and passing."""
    suites = [s.strip() for s in raw.split(",") if s.strip()]
    unknown = set(suites) - set(ALL_SUITES)
    if unknown:
        parser.error(
            f"unknown suite(s) {sorted(unknown)}; known suites: "
            f"{', '.join(ALL_SUITES)}"
        )
    if not suites:
        parser.error(f"--suites selected nothing; known suites: {', '.join(ALL_SUITES)}")
    return suites


def run_suites(
    suites: List[str], write_baseline: bool = False
) -> Tuple[List[Finding], List[Finding], Dict]:
    """Run the requested suites; return (all findings, new findings,
    extras). ``extras`` carries suite-specific report payloads (the memory
    suite's per-bucket HBM breakdown) for the JSON/text report."""
    baselined: List[Finding] = []  # findings subject to the tpulint baseline
    unbaselined: List[Finding] = []  # graph/shard/memory/flag: always new
    extras: Dict = {}

    if "lint" in suites:
        from neuronx_distributed_inference_tpu.analysis import tpulint

        baselined.extend(tpulint.run())
    if "flags" in suites:
        from neuronx_distributed_inference_tpu.analysis import flag_audit

        unbaselined.extend(flag_audit.run())
    traced_suites = [
        s for s in ("graph", "shard", "memory", "cost", "kernel") if s in suites
    ]
    if traced_suites:
        _prepare_jax_cpu()
    if "graph" in suites:
        from neuronx_distributed_inference_tpu.analysis import graph_audit

        unbaselined.extend(graph_audit.run(write_baseline=write_baseline))
    if "shard" in suites:
        from neuronx_distributed_inference_tpu.analysis import shard_audit

        unbaselined.extend(shard_audit.run(write_baseline=write_baseline))
    if "memory" in suites:
        from neuronx_distributed_inference_tpu.analysis import memory_audit

        unbaselined.extend(memory_audit.run(write_baseline=write_baseline))
        extras["memory"] = memory_audit.last_report()
    if "cost" in suites:
        from neuronx_distributed_inference_tpu.analysis import cost_audit

        unbaselined.extend(cost_audit.run(write_baseline=write_baseline))
        extras["cost"] = cost_audit.last_report()
    if "conc" in suites:
        # pure-AST like lint: no tracing, runs in milliseconds
        from neuronx_distributed_inference_tpu.analysis import concurrency_audit

        unbaselined.extend(concurrency_audit.run(write_baseline=write_baseline))
        extras["concurrency"] = concurrency_audit.last_report()
    if "kernel" in suites:
        from neuronx_distributed_inference_tpu.analysis import kernel_audit

        unbaselined.extend(kernel_audit.run(write_baseline=write_baseline))
        extras["kernel"] = kernel_audit.last_report()
    if "life" in suites:
        # pure-AST like conc: no tracing, runs in milliseconds
        from neuronx_distributed_inference_tpu.analysis import lifecycle_audit

        unbaselined.extend(lifecycle_audit.run(write_baseline=write_baseline))
        extras["lifecycle"] = lifecycle_audit.last_report()

    all_findings = baselined + unbaselined
    if write_baseline and "lint" in suites:
        Baseline.from_findings(baselined).save(TPULINT_BASELINE)
        new = list(unbaselined)
    else:
        new = Baseline.load(TPULINT_BASELINE).filter_new(baselined) + unbaselined
    return all_findings, new, extras


def _read_baselines() -> Dict[str, str]:
    out = {}
    for name in BASELINE_FILES:
        path = os.path.join(_ANALYSIS_DIR, name)
        try:
            with open(path) as f:
                out[name] = f.read()
        except FileNotFoundError:
            out[name] = ""
    return out


def baseline_diffs(before: Dict[str, str], after: Dict[str, str]) -> str:
    """Unified diff of every baseline file a --write-baseline run rewrote —
    printed so the regeneration is reviewed like code."""
    chunks = []
    for name in BASELINE_FILES:
        old, new = before.get(name, ""), after.get(name, "")
        if old == new:
            continue
        diff = difflib.unified_diff(
            old.splitlines(keepends=True),
            new.splitlines(keepends=True),
            fromfile=f"a/analysis/{name}",
            tofile=f"b/analysis/{name}",
        )
        chunks.append("".join(diff))
    return "\n".join(chunks)


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    suites = parse_suites(parser, args.suites)

    before = _read_baselines() if args.write_baseline else None
    all_findings, new, extras = run_suites(suites, write_baseline=args.write_baseline)

    extras_chunks = []
    if "memory" in extras:
        from neuronx_distributed_inference_tpu.analysis import memory_audit

        extras_chunks.append(memory_audit.render_breakdown(extras["memory"]))
    if "cost" in extras:
        from neuronx_distributed_inference_tpu.analysis import cost_audit

        extras_chunks.append(cost_audit.render_breakdown(extras["cost"]))
    if "concurrency" in extras:
        from neuronx_distributed_inference_tpu.analysis import concurrency_audit

        extras_chunks.append(
            concurrency_audit.render_breakdown(extras["concurrency"])
        )
    if "kernel" in extras:
        from neuronx_distributed_inference_tpu.analysis import kernel_audit

        extras_chunks.append(kernel_audit.render_breakdown(extras["kernel"]))
    if "lifecycle" in extras:
        from neuronx_distributed_inference_tpu.analysis import lifecycle_audit

        extras_chunks.append(
            lifecycle_audit.render_breakdown(extras["lifecycle"])
        )
    extras_text = "\n".join(c for c in extras_chunks if c) or None
    print(
        findings_mod.render_report(
            all_findings, new, as_json=args.json, suites=suites,
            extras=extras or None, extras_text=extras_text,
        )
    )
    if args.write_baseline:
        diff = baseline_diffs(before, _read_baselines())
        if diff:
            print(
                "--write-baseline rewrote committed baselines; review this "
                "diff like code:\n" + diff,
                file=sys.stderr,
            )
    return 1 if new else 0
