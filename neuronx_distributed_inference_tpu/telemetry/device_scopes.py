"""The device side of a step program under the program's own names.

A profiler trace names a device op by its HLO instruction (``fusion.146``),
a number the compiler gave and gives anew with every compile. The program
names the PARTS of a step instead: a closed vocabulary, :data:`DEVICE_SCOPES`,
applied with ``jax.named_scope`` where the work is traced (``models/base.py``,
``modules/moe.py``, the hybrid and latent-attention layers). A scope is a
name at trace time: it reaches the ``op_name`` metadata of the compiled
program's instructions and nothing else — same instructions, same memory.

The trace itself carries no metadata, so the join is made by the program:
:func:`scope_table` reads one compiled program's text
(``compiled.as_text()``) into ``{instruction name: scope}``, and a recording
:class:`~.tracing.TelemetrySession` writes the tables of the programs it saw
dispatched to ``<profile_dir>/device_scopes.json`` beside the trace
(:data:`TABLE_FILE`; docs/OBSERVABILITY.md "Device scopes"). :func:`time_by_scope`
is the operator's reduction of the two (``utils/profiling.summarize_trace``).
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

#: every name a device op can be given. ``layer.other`` is the one no
#: ``named_scope`` applies: an instruction of the layer loop's body under no
#: scope (the scan's own slices of a layer's weights and caches, the carry's
#: copies). "" = under no scope and outside the loop.
DEVICE_SCOPES = (
    "embed",
    "layer.norm",
    "layer.qkv",
    "layer.latent_proj",
    "layer.absorb",
    "layer.indexer",
    "layer.select",
    "layer.kv_write",
    "layer.attn",
    "layer.o_proj",
    "layer.mlp",
    "layer.post_norm",
    "layer.moe.router",
    "layer.moe.experts",
    "layer.shared_mlp",
    "layer.ssm",
    "layer.kda",
    "layer.power",
    "layer.other",
    "loop.norm",
    "head",
    "sample",
    "reveal",
)
LAYER_OTHER = "layer.other"
_APPLIED = frozenset(DEVICE_SCOPES) - {LAYER_OTHER}

#: the KINDS of attention layer a stack may mix (models/mellum.py: layers
#: that attend a window beside layers that attend the whole context). A kind
#: is a scope INSIDE ``layer.attn`` (``layer.attn.window``): an op under it
#: is still ``layer.attn`` in a table's ``ops``, so what reads the attention
#: of a program reads the sum, and the table names its kind beside it
#: (``kinds``: :func:`scope_table`). A model of one kind applies none.
ATTN_WINDOW, ATTN_FULL = "window", "full"
ATTN_KINDS = (ATTN_WINDOW, ATTN_FULL)
_KIND_SCOPES = frozenset(f"layer.attn.{kind}" for kind in ATTN_KINDS)

#: the file a recording session writes beside the trace
TABLE_FILE = "device_scopes.json"

#: ops that only hold other ops (their time is their bodies'): as
#: ``benchmark/harness/trace_reduce.CONTAINER`` has them
CONTAINER = re.compile(r"(while|call|conditional)([.\d]|$)")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_FUSED = re.compile(r"\bkind=k\w+, calls=%?([\w.\-]+)")
_LOOP = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def table_key(program: str, q: int, kv: int) -> str:
    """``decode:q1:kv512``: which step program a dispatch launched."""
    return f"{program}:q{int(q)}:kv{int(kv)}"


def scope_of(op_name: str) -> str:
    """The innermost :data:`DEVICE_SCOPES` component of an instruction's
    ``op_name`` (``jit(f)/while/body/closed_call/layer.kv_write/scatter``);
    ``layer.other`` for one under no scope inside a loop body, else ""."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in _APPLIED:
            return part
    return LAYER_OTHER if "while" in parts else ""


def kind_of(op_name: str) -> str:
    """The ``layer.attn.<kind>`` component of an instruction's ``op_name``
    (:data:`ATTN_KINDS`), "" where it has none."""
    return next((p for p in reversed(op_name.split("/")) if p in _KIND_SCOPES), "")


def scope_table(hlo_text: str) -> dict:
    """``{"module": <HloModule name>, "ops": {instruction: scope}}`` of one
    compiled program, and, for a program whose stack names KINDS of attention
    layer, ``"kinds": {instruction: "layer.attn.<kind>"}`` of the ops that
    carry one (absent for every other program): every instruction that can run as a device op of its
    own (those of the entry, of loop bodies and of called computations; not
    the insides of a fusion, which run as the fusion) but the containers. A
    fusion has the metadata the compiler printed on it: its root's. Where it
    printed none of the program's (a fusion it built out of a scatter's
    expansion, a copy it put in for a layout; a ``ragged_dot`` it rewrote
    carries its own name, ``ragged-dot-none``, and no path) the instruction
    is named by what consumes it: its first user in its computation that is
    under a scope; failing that it is ``layer.other`` in a loop's own
    computation and "" elsewhere."""
    fused, loops = set(_FUSED.findall(hlo_text)), set(_LOOP.findall(hlo_text))
    module, ops, kinds = "", {}, {}
    own, reads, in_loop = None, {}, False  # of the computation being read

    def close():
        """Name the finished computation's instructions."""
        users = {}
        for name, refs in reads.items():
            for ref in refs:
                if ref in own and ref != name:
                    users.setdefault(ref, []).append(name)

        def consumed_as(name, depth=3):
            for user in users.get(name, ()):
                scope = own[user]
                if scope is None and depth:
                    scope = consumed_as(user, depth - 1)
                if scope in _APPLIED:
                    return scope
            return None

        for name, scope in own.items():
            if CONTAINER.match(name):
                continue
            if scope is None:
                scope = consumed_as(name) or ""
            ops[name] = scope or (LAYER_OTHER if in_loop else "")

    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _COMPUTATION.match(line)
        if m:
            if own:
                close()
            own = None if m.group(1) in fused else {}
            reads, in_loop = {}, m.group(1) in loops
            continue
        m = _INSTRUCTION.match(line) if own is not None else None
        if m is None:
            continue
        op_name = _OP_NAME.search(line)
        op_name = op_name.group(1) if op_name else ""
        scope = scope_of(op_name)
        # an op_name of the program's own is a scope or a path from its jit
        own[m.group(1)] = scope if scope in _APPLIED or "jit(" in op_name else None
        if kind_of(op_name):
            kinds[m.group(1)] = kind_of(op_name)
        reads[m.group(1)] = _REF.findall(line[m.end():].split(", metadata=", 1)[0])
    if own:
        close()
    table = {"module": module, "ops": ops}
    if kinds:
        table["kinds"] = {name: kind for name, kind in kinds.items() if name in ops}
    return table


def write_tables(profile_dir: str, tables: Dict[str, dict]) -> None:
    """Write the tables beside the trace; no file when there are none."""
    if tables:
        with open(os.path.join(profile_dir, TABLE_FILE), "w") as f:
            json.dump(tables, f)


def read_tables(profile_dir: str) -> Optional[Dict[str, dict]]:
    path = os.path.join(profile_dir, TABLE_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def time_by_scope(data, tables: Dict[str, dict]) -> dict:
    """Device seconds by scope of the step programs in a trace
    (``jax.profiler.ProfileData``), on the first device plane:
    ``{module: {scope: seconds}}``, "" holding what no table entry names.

    The i-th execution of a program the tables name pairs with the i-th
    ``serving.*.dispatch`` span, whose ``program`` / ``q`` / ``kv`` fields
    say which table applies (the numberings of two compiled programs are
    independent). A trace whose spans and executions differ in number
    raises ValueError."""
    modules = {t["module"] for t in tables.values()}
    keys: List[str] = []
    ops = execs = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            spans = []
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serving.") and e.name.endswith(".dispatch"):
                        stats = dict(e.stats)
                        if "program" in stats:
                            spans.append((e.start_ns, table_key(
                                stats["program"], stats["q"], stats["kv"])))
            keys += [k for _, k in sorted(spans)]
        elif plane.name.startswith("/device:") and ops is None:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines and "XLA Modules" in lines:
                ops = sorted((e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                             for e in lines["XLA Ops"].events)
                execs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name.split("(", 1)[0])
                               for e in lines["XLA Modules"].events)
    if ops is None:
        return {}
    execs = [x for x in execs if x[2] in modules]
    if len(execs) != len(keys):
        raise ValueError(
            f"device scopes: {len(keys)} dispatch spans carry a program but {len(execs)} "
            f"executions of {sorted(modules)} are in the trace"
        )
    out: Dict[str, Dict[str, float]] = {}
    i = 0
    for (t0, t1, module), key in zip(execs, keys):
        names = tables.get(key, {}).get("ops", {})
        sums = out.setdefault(module, {})
        while i < len(ops) and ops[i][0] < t0:
            i += 1
        while i < len(ops) and ops[i][0] < t1:
            start, end, name = ops[i]
            i += 1
            if not CONTAINER.match(name):
                scope = names.get(name, "")
                sums[scope] = sums.get(scope, 0.0) + (end - start) * 1e-9
    return out


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; the
    instruction's own name stands before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")
