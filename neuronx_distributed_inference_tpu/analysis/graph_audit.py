"""Jaxpr/HLO contract auditor for the compiled sub-model programs.

For each registered sub-model tag × bucket, trace a TINY tp-sharded model on
the CPU mesh (no accelerator needed; 8 virtual devices, same GSPMD path as
hardware) and assert the graph invariants the AOT latency model relies on:

- **GRAPH201 collective-census** — per-phase counts of the partitioner's
  collectives (all-reduce / all-gather / reduce-scatter / collective-permute
  / all-to-all in the compiled HLO) must match the committed baseline
  (``analysis/graph_baseline.json``). A new collective in the decode graph is
  a silent latency regression even when numerics are identical; a missing
  one usually means a sharding constraint stopped propagating.
- **GRAPH202 census-bucket-variance** — the census must be IDENTICAL across
  buckets of one tag: buckets only change constants, never the communication
  pattern.
- **GRAPH203 f32-upcast-in-decode** — in a bf16 config, no
  ``convert_element_type`` bf16→f32 inside the decode layer scan except from
  the allowlisted files (norm/softmax/rope/sampling compute in f32 by
  design; ``cast_logits_fp32`` is outside the scan).
- **GRAPH204 missing-donation** — KV-cache donation must survive to lowering
  (``tf.aliasing_output`` / ``jax.buffer_donor`` attrs on the cache leaves);
  otherwise every decode step double-buffers the whole cache. The memory
  audit (MEM401, ``memory_audit.py``) carries this further: the COMPILED
  executable's ``input_output_alias`` table must actually alias every
  donated cache leaf.
- **GRAPH205 bucket-skeleton-drift** — the jaxpr equation skeleton (the
  recursive sequence of primitive names) must be identical across buckets of
  one tag: same program, different constants, exactly the frozen-executable
  contract.

Program construction (tiny 2-layer models, CPU compile, a few seconds per
tag) lives in :mod:`.programs` and is SHARED with the shard and memory
audits — the three suites trace each program family once per process.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional, Tuple

from neuronx_distributed_inference_tpu.analysis import programs
from neuronx_distributed_inference_tpu.analysis.findings import (
    Finding,
    SEV_ERROR,
)
from neuronx_distributed_inference_tpu.analysis.programs import (  # noqa: F401
    COLLECTIVE_OPS,
    TAG_CONTEXT_ENCODING,
    TAG_CONTEXT_ENCODING_KVQ8,
    TAG_FUSED_SPECULATION,
    TAG_FUSED_SPECULATION_KVQ8,
    TAG_TOKEN_GENERATION,
    TAG_TOKEN_GENERATION_KVQ8,
    tiny_config as _tiny_config,
)

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "graph_baseline.json"

# Files allowed to upcast bf16 -> f32 inside the decode scan: numerically
# deliberate (fp32 softmax/norm/rope/sampling), mirrored by config flags
# (attention_softmax_fp32) or reference parity. kvcache/block_kvcache are the
# int8/fp8 cache write path: the running-absmax + quantize math runs in f32
# by design (the CACHE itself stays in codes — GRAPH203 would catch a
# dequantized-cache materialization coming from any other file).
F32_UPCAST_ALLOWLIST = (
    "norm.py",
    "attention.py",
    "rope.py",
    "sampling.py",
    "decode_attention.py",
    "ragged_paged_attention.py",
    "masks.py",
    "quant.py",
    "kvcache.py",
    "block_kvcache.py",
)

AUDIT_TAGS = programs.COMMITTED_TAGS


# ---------------------------------------------------------------------------
# jaxpr walks
# ---------------------------------------------------------------------------


def _skeleton(jaxpr) -> Tuple:
    """Recursive primitive-name skeleton of a (closed) jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        sub = []
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                sub.append(_skeleton(inner))
        out.append((eqn.primitive.name, tuple(sub)))
    return tuple(out)


def _eqn_source_file(eqn) -> Optional[str]:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return frame.file_name
    except Exception:
        pass
    return None


def _walk_scan_upcasts(jaxpr, hits: List[Tuple[str, Optional[str]]], in_scan: bool = False):
    """Collect bf16->f32 convert_element_type eqns inside scan bodies."""
    import jax.numpy as jnp

    for eqn in jaxpr.eqns:
        if in_scan and eqn.primitive.name == "convert_element_type":
            src_dtype = eqn.invars[0].aval.dtype
            dst_dtype = eqn.params.get("new_dtype")
            if src_dtype == jnp.bfloat16 and dst_dtype == jnp.float32:
                hits.append((str(eqn), _eqn_source_file(eqn)))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _walk_scan_upcasts(
                    inner, hits, in_scan=in_scan or eqn.primitive.name == "scan"
                )


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def load_census_baseline(path: Optional[pathlib.Path] = None) -> Dict[str, Dict[str, int]]:
    p = path or BASELINE_PATH
    try:
        with open(p) as f:
            return json.load(f).get("census", {})
    except FileNotFoundError:
        return {}


def save_census_baseline(census: Dict[str, Dict[str, int]], path: Optional[pathlib.Path] = None):
    p = path or BASELINE_PATH
    with open(p, "w") as f:
        json.dump({"census": census}, f, indent=2, sort_keys=True)
        f.write("\n")


def run(
    write_baseline: bool = False,
    baseline_path: Optional[pathlib.Path] = None,
    tags: Tuple[str, ...] = AUDIT_TAGS,
) -> List[Finding]:
    """Run the graph audit over the requested tags; return findings."""
    findings: List[Finding] = []
    results = programs.collect_programs(tuple(tags))

    baseline = load_census_baseline(baseline_path)
    observed_census: Dict[str, Dict[str, int]] = {}

    for tag, per_bucket in results.items():
        buckets = sorted(per_bucket)
        # -- GRAPH204 donation ---------------------------------------------
        for bucket in buckets:
            rec = per_bucket[bucket]
            if rec.donation_count < rec.n_cache_leaves:
                findings.append(
                    Finding(
                        rule="GRAPH204",
                        severity=SEV_ERROR,
                        location=f"{tag}/{bucket}",
                        message=(
                            f"KV-cache donation missing: {rec.donation_count} "
                            f"aliased/donor buffers in the lowering, expected "
                            f"≥ {rec.n_cache_leaves} cache leaves — decode "
                            f"would double-buffer the cache"
                        ),
                        key=tag,
                    )
                )
        # -- GRAPH202/201 census -------------------------------------------
        censuses = {b: per_bucket[b].census for b in buckets}
        ref_bucket = buckets[0]
        for b in buckets[1:]:
            if censuses[b] != censuses[ref_bucket]:
                findings.append(
                    Finding(
                        rule="GRAPH202",
                        severity=SEV_ERROR,
                        location=f"{tag}/{b}",
                        message=(
                            f"collective census differs across buckets: "
                            f"{censuses[ref_bucket]} (bucket {ref_bucket}) vs "
                            f"{censuses[b]} (bucket {b}) — buckets must only "
                            f"change constants, never the communication "
                            f"pattern"
                        ),
                        key=tag,
                    )
                )
        observed_census[tag] = censuses[ref_bucket]
        # under --write-baseline the observed census IS the new contract:
        # drift vs the old file is being accepted, not reported
        expected = None if write_baseline else baseline.get(tag)
        if expected is not None and expected != censuses[ref_bucket]:
            regressed = {
                op: (expected.get(op, 0), censuses[ref_bucket].get(op, 0))
                for op in set(expected) | set(censuses[ref_bucket])
                if expected.get(op, 0) != censuses[ref_bucket].get(op, 0)
            }
            findings.append(
                Finding(
                    rule="GRAPH201",
                    severity=SEV_ERROR,
                    location=f"{tag}/{ref_bucket}",
                    message=(
                        f"collective census drifted from baseline "
                        f"(op: expected -> got): {regressed} — regenerate "
                        f"with --write-baseline only if the change is "
                        f"intentional"
                    ),
                    key=tag,
                )
            )
        # -- GRAPH205 skeleton ---------------------------------------------
        skels = {b: _skeleton(per_bucket[b].jaxpr.jaxpr) for b in buckets}
        for b in buckets[1:]:
            if skels[b] != skels[ref_bucket]:
                findings.append(
                    Finding(
                        rule="GRAPH205",
                        severity=SEV_ERROR,
                        location=f"{tag}/{b}",
                        message=(
                            f"jaxpr equation skeleton differs between "
                            f"buckets {ref_bucket} and {b} — the per-bucket "
                            f"programs must share one structure (only "
                            f"constants may differ)"
                        ),
                        key=tag,
                    )
                )
        # -- GRAPH203 f32 upcasts in decode scan ---------------------------
        if tag in (
            TAG_TOKEN_GENERATION,
            TAG_FUSED_SPECULATION,
            TAG_TOKEN_GENERATION_KVQ8,
            TAG_FUSED_SPECULATION_KVQ8,
            programs.TAG_MIXED_STEP,
        ):
            hits: List[Tuple[str, Optional[str]]] = []
            _walk_scan_upcasts(per_bucket[ref_bucket].jaxpr.jaxpr, hits)
            for eqn_str, src in hits:
                base = pathlib.Path(src).name if src else "<unknown>"
                if src is not None and base in F32_UPCAST_ALLOWLIST:
                    continue
                if src is None:
                    # no user frame (jax-internal rewrite): not actionable
                    continue
                findings.append(
                    Finding(
                        rule="GRAPH203",
                        severity=SEV_ERROR,
                        location=f"{tag}/{ref_bucket}",
                        message=(
                            f"bf16→f32 upcast inside the decode layer scan "
                            f"from {base} (not in the logits/norm allowlist): "
                            f"{eqn_str[:120]}"
                        ),
                        key=tag,
                    )
                )

    if write_baseline:
        # merge over the existing file so auditing a tags SUBSET never
        # deletes the other tags' committed censuses
        merged = dict(baseline)
        merged.update(observed_census)
        save_census_baseline(merged, baseline_path)
    return findings
