"""Device time of the step programs by the PROGRAM's own names: the scopes
``telemetry/device_scopes.py::DEVICE_SCOPES`` names, joined to the trace's
ops through the table the program writes beside the trace it started
(``device_scopes.json``: ``{"<program>:q<q>:kv<kv>": {"module": ..., "ops":
{instruction name: scope}}}``).

    {"reader": "device_scope", "kind": "scope_ms_per_dispatch",
     "program": "chunk" | "decode", "scope": "<regex over scope names>"}
    {"reader": "device_scope", "kind": "unscoped_share"}

On the first chip's plane the i-th ``XLA Modules`` event of a step program
(``MODULES``) pairs with the i-th ``serving.*.dispatch`` span (``DISPATCH``);
their numbers must agree over the whole trace, or the reader raises, as
``program_span.check_dispatches`` does. The span's ``program``, ``q`` and
``kv`` (on the ``TraceAnnotation`` since they are given at entry) say which
table names the ``XLA Ops`` events inside the module event's interval:
instruction names are numbered per compiled program, and a slice runs a
dozen. A ``while`` / ``call`` / ``conditional`` event holds the ops of its
body, which are there too: containers are skipped (``trace_reduce.CONTAINER``).

- ``scope_ms_per_dispatch``: device ms in the ops of ``program`` whose scope
  matches ``scope``, per dispatch of that program in the slice.
- ``unscoped_share``: of the device time of both programs' ops, the share (%)
  under scope "" or with no table entry (``layer.other`` counts as scoped).

No device plane, no table file, no dispatch span that names its program (an
older commit), no table for the program -> None.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Tuple

from .. import catalog, trace_reduce

TRACE_DIR = os.path.join(catalog.REPO_DIR, ".bench_cache", "trace")
TABLE_FILE = "device_scopes.json"
MODULES = re.compile(r"^jit_token_generation_model_(decode|chunk)\(")
DISPATCH = re.compile(r"^serving\.(decode|prefill_chunk)\.dispatch$")


def dispatch_spans(path: str) -> List[Tuple[float, Optional[str], Optional[str]]]:
    """(start, program, table key) of every dispatch span of the split step,
    by start time; program and key are None on a span that names no
    program."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if DISPATCH.match(e.name):
                    stats = dict(e.stats)
                    if "program" in stats:
                        program = str(stats["program"])
                        key = f"{program}:q{int(stats['q'])}:kv{int(stats['kv'])}"
                        out.append((e.start_ns * 1e-9, program, key))
                    else:
                        out.append((e.start_ns * 1e-9, None, None))
    out.sort(key=lambda s: s[0])
    return out


def reduce(path: str, table_path: str) -> Optional[Dict[str, dict]]:
    """``{program: {"dispatches", "tabled", "op_s", "busy_s", "by_scope":
    {scope: seconds}}}`` over the whole trace; in ``by_scope``, "" holds the
    ops under no scope and None those with no table entry. ``op_s`` is the
    sum over the leaf ops inside the program's module events, ``busy_s`` the
    union of their intervals (equal unless ops are counted twice)."""
    if not os.path.exists(table_path):
        return None
    tr = trace_reduce.read(path)
    if not tr.modules:
        return None
    with open(table_path) as f:
        tables = json.load(f)
    spans = dispatch_spans(path)
    if not spans or any(key is None for _, _, key in spans):
        return None
    chip = sorted(tr.modules)[0]
    execs = sorted((e for e in tr.modules[chip] if MODULES.match(e.name)), key=lambda e: e.start)
    if len(execs) != len(spans):
        raise ValueError(
            f"device_scope: {len(spans)} dispatch spans but {len(execs)} XLA Modules events "
            f"match {MODULES.pattern!r}: the host spans and the device events of this trace "
            f"do not cover the same dispatches"
        )
    ops = sorted(tr.ops.get(chip, []), key=lambda e: e.start)
    out: Dict[str, dict] = {}
    i = 0
    for ex, (_, program, key) in zip(execs, spans):
        table = tables.get(key)
        if table is not None and not ex.name.startswith(table["module"] + "("):
            raise ValueError(
                f"device_scope: the dispatch span says {key} ({table['module']}) where the "
                f"device ran {ex.name}: spans and module events are not in step"
            )
        acc = out.setdefault(program, {"dispatches": 0, "tabled": 0, "op_s": 0.0,
                                       "intervals": [], "by_scope": {}})
        acc["dispatches"] += 1
        acc["tabled"] += table is not None
        names = table["ops"] if table is not None else {}
        while i < len(ops) and ops[i].start < ex.start:
            i += 1
        while i < len(ops) and ops[i].start < ex.end:
            op = ops[i]
            i += 1
            if trace_reduce.CONTAINER.match(op.name):
                continue
            scope = names.get(op.name)
            acc["by_scope"][scope] = acc["by_scope"].get(scope, 0.0) + op.dur
            acc["op_s"] += op.dur
            acc["intervals"].append((op.start, op.end))
    for acc in out.values():
        acc["busy_s"] = trace_reduce.total(trace_reduce.union(acc.pop("intervals")))
    return out


def read(params: dict, ctx: dict) -> Optional[float]:
    if ctx.get("trace") is None:
        return None
    if "device_scope_table" not in ctx:  # one reduction a run
        try:
            path = trace_reduce.find_xplane(TRACE_DIR)
        except FileNotFoundError:
            path = None
        ctx["device_scope_table"] = path and reduce(path, os.path.join(TRACE_DIR, TABLE_FILE))
    table = ctx["device_scope_table"]
    if not table:
        return None
    kind = params["kind"]
    if kind == "scope_ms_per_dispatch":
        acc = table.get(params["program"])
        if acc is None or not acc["tabled"]:
            return None
        rx = re.compile(params["scope"])
        seconds = sum(s for scope, s in acc["by_scope"].items() if scope and rx.search(scope))
        return seconds / acc["dispatches"] * 1e3
    if kind == "unscoped_share":
        total = sum(acc["op_s"] for acc in table.values())
        if total <= 0 or not any(acc["tabled"] for acc in table.values()):
            return None
        unscoped = sum(s for acc in table.values()
                       for scope, s in acc["by_scope"].items() if not scope)
        return 100.0 * unscoped / total
    raise ValueError(f"unknown device_scope reader kind {kind!r}")
