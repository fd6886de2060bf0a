"""tpulint: AST rules specific to this codebase.

The rules encode contracts the runtime relies on but Python cannot enforce:

- **TPU101 host-sync-under-trace** (error): ``jax.device_get``,
  ``block_until_ready`` or ``.item()`` inside a jit-traced function body. At
  trace time these force the tracer to a concrete value (ConcretizationError
  at best, a silent constant-fold at worst); they belong in host loops only.
- **TPU102 host-sync-census** (warning, baselined): EVERY host-sync call in
  the package, counted per file. The committed baseline pins the count — the
  batched ``jax.device_get((tokens, logits))`` work in runtime/ stays pinned
  so a new per-field fetch in a hot loop fails the lint. Calls inside the
  hot-path function sets of :data:`HOT_PATH_BUCKETS` additionally count
  against separately-pinned per-file buckets — the serving ``step()`` hot
  path (:data:`SERVING_STEP_HOT_PATH`, ``::step-hot-path``) and the
  router's placement/failover loop (:data:`ROUTER_HOT_PATH`,
  ``::route-hot-path``, pinned at ZERO) — so a blocking fetch added to a
  per-step loop trips the gate on its own: the pipelined ragged dispatch
  depends on the step path staying fetch-free outside the designated
  consume points, and the multi-replica router would serialize every
  replica behind one device.
- **TPU103 host-time-under-trace** (error): ``time.time()`` /
  ``time.perf_counter()`` / ``print`` under trace — they execute ONCE at
  trace time and then lie forever.
- **TPU104 pallas-missing-interpret** (error): a ``pallas_call`` site
  without the ``interpret=`` kwarg, i.e. a kernel outside the
  ``ops/kernel_mode.py`` plumbing. Such a kernel cannot run on the CPU test
  mesh and cannot be forced to compile for the AOT Mosaic-lowering tests
  (the r1/r3 bench-only crash class).
- **TPU105 mutable-default-arg** (error): a list/dict/set literal default
  argument anywhere in the package.
- **TPU106 np-under-trace** (warning, baselined): ``np.asarray``/``np.array``
  inside a traced body. Legitimate on trace-time-static values (bucket
  tables, permutations) — those sites carry a pragma or a baseline entry —
  but on a traced value it synchronizes or crashes.
- **TPU107 metric-recording-under-trace** (error): a telemetry call inside a
  jit-traced body — any reference to a symbol imported from the
  ``telemetry`` package, or a ``.inc(...)``/``.observe(...)`` metric-method
  call. Python under trace runs ONCE per compile, so a metric recorded
  there counts compiles, not steps — it would lie forever (TPU103's
  failure mode) AND any telemetry that *read* a traced value would force a
  host sync (TPU101's). Recording belongs in host loops, on values the
  step's existing batched fetch already landed; this rule is the static
  half of the zero-device-round-trip telemetry contract
  (docs/OBSERVABILITY.md).
- **TPU109 module-level-mutable-state** (warning, baselined — zero entries
  expected): a dict/list/set (literal or ``dict()``/``list()``/``set()``/
  ``deque()``/``defaultdict()`` call) assigned at module level in
  ``runtime/`` that any function then WRITES (subscript assignment, a
  mutating method call, or a ``global`` rebind). Import-time mutable state
  written from functions is the classic hidden-shared-state smell the
  concurrency audit's census rules (CONC601) key off: it has no owning
  object, so no confinement argument covers it — under thread-per-replica
  stepping it is a cross-replica race waiting to happen. Put the state on
  an owning class (where the CONC601 ownership model classifies it) or
  suppress with a written-down justification (e.g. a decoration-time-only
  registry).
- **TPU110 silent-swallow** (warning, baselined — zero entries expected):
  a bare ``except:`` or ``except Exception/BaseException:`` handler whose
  body is only ``pass`` in ``runtime/`` or ``telemetry/``. A swallowed
  failure on a serving or observability path is an invisible leak — the
  containment story (typed degradation, loud failure) depends on every
  broad catch either handling or re-raising. Catch the typed class or let it
  propagate. The lifecycle audit (LIFE803) carries the ERROR-level version
  for runtime/.
- **TPU108 large-unsharded-constant** (warning, baselined — zero entries
  expected): a ``jnp.zeros/ones/full/arange/eye/...`` call with a
  STATICALLY-known element count ≥ 2**20 inside a jit-traced body, not
  wrapped in a sharding constraint (``with_sharding_constraint`` /
  ``constrain`` / ``device_put``). GSPMD replicates unconstrained
  constants, so a large table materialized in-graph silently costs
  model-group× its HBM — this catches it at the AST, before the shard
  audit (GRAPH301/302) ever sees a compile. Census format shared with
  TPU102 (per-file counts against the committed baseline).

Traced-body detection: a function is *traced* when it is (a) decorated with
``jax.jit`` (possibly through ``partial``), (b) referenced anywhere inside a
``jax.jit(...)`` call's arguments (covers ``jax.jit(partial(forward, ...))``
and the retrace-guard ``trace_marker`` wrappers, resolved across modules
through the import graph), (c) defined inside a traced function, or (d)
reachable from a traced function through package-internal calls/references
(fixpoint propagation — ``forward -> model_logits -> decoder_layer`` all
count). This overapproximates (a function used both host-side and in-graph
counts as traced), which is the correct direction for a contract check.

Suppression: ``# tpulint: ignore[TPU101]`` (or a bare ``# tpulint: ignore``)
on the offending line or its enclosing ``def`` line.
"""

from __future__ import annotations

import ast
import pathlib
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from neuronx_distributed_inference_tpu.analysis.findings import (
    CONTAINER_MUTATORS,
    Finding,
    SEV_ERROR,
    SEV_WARNING,
)

PACKAGE = "neuronx_distributed_inference_tpu"

HOST_SYNC_ATTRS = {"device_get", "block_until_ready", "item"}
HOST_TIME_FUNCS = {"time", "perf_counter", "monotonic"}
NP_SYNC_FUNCS = {"asarray", "array"}
# telemetry recording: the package prefix (import-based detection) and the
# metric mutator names distinctive enough to flag bare (heuristic half —
# catches `self.tel.inc/observe`-style calls the import map cannot resolve)
TELEMETRY_PKG = PACKAGE + "/telemetry"
METRIC_RECORD_ATTRS = {"inc", "observe"}

# TPU108: jnp array creators whose result REPLICATES when unconstrained
# under GSPMD (the *_like variants inherit their prototype's sharding and
# are exempt), and the element-count threshold above which a replicated
# constant is an HBM problem worth flagging (2**20 elems = 4 MiB in f32,
# PER DEVICE, times the model-group size).
JNP_ARRAY_CREATORS = {"zeros", "ones", "full", "empty", "arange", "eye", "linspace"}
TPU108_ELEM_THRESHOLD = 1 << 20
# wrappers that give the fresh array a placement, silencing TPU108
SHARDING_WRAPPERS = {"with_sharding_constraint", "constrain", "device_put"}

# TPU109: constructors whose module-level result is mutable shared state
# (the write-counting mutator set is findings.CONTAINER_MUTATORS, shared
# with the concurrency audit's CONC601 census), and the package subtree the
# rule audits (the serving runtime — where the thread-per-replica router
# makes hidden module state an actual race)
MUTABLE_CONSTRUCTORS = {"dict", "list", "set", "deque", "defaultdict",
                        "OrderedDict", "Counter"}
TPU109_SCOPE_PREFIX = PACKAGE + "/runtime/"

_PRAGMA_RE = re.compile(r"#\s*tpulint:\s*ignore(?:\[([A-Z0-9, ]+)\])?")

#: ServingSession step() hot-path functions (runtime/serving.py): every
#: method a scheduler tick runs through. Host-sync calls inside them get a
#: SECOND TPU102 census finding keyed `<file>::step-hot-path`, pinned
#: separately by the baseline, so a future blocking `jax.device_get` added
#: to the per-step loop (outside the designated consume points) fails the
#: gate even when the file-level count is rebalanced. The speculative
#: session's accept/reject fetch in `_step_inner` is the one designated
#: (baselined) entry.
SERVING_STEP_HOT_PATH = {
    "step",
    "_step_inner",
    "_ragged_step",
    "_schedule_mixed",
    "_build_mixed_descriptors",
    "_consume_ragged",
    "_note_acceptance",
    "_dispatch_decode",
    "_consume",
    # a chunk pass and its two halves: the step dispatches it, dispatches the
    # decode pass behind it, and only then waits for its tokens
    "_prefill_chunks",
    "_dispatch_chunks",
    "_commit_chunks",
}

#: ServingRouter per-tick functions (runtime/router.py): the placement /
#: health / failover loop over N replicas. Pure host bookkeeping by
#: contract — a blocking device fetch here would serialize EVERY replica
#: behind one device, so its census bucket
#: (`runtime/router.py::route-hot-path`) is pinned at ZERO entries.
ROUTER_HOT_PATH = {
    "step",
    "_place_pending",
    "_candidates",
    "_sync_terminals",
    "_failover_request",
    "_failover_replica",
    "_publish_gauges",
    "run_to_completion",
    # thread-per-replica stepping (router_threading): the stepping phase +
    # the worker protocol — router.py-side code here must stay fetch-free
    # (the per-replica session's designated consume points live in
    # serving.py's own bucket; a fetch in the worker loop or the barrier
    # would re-serialize every replica behind one device)
    "_step_replicas",
    "run",
    "dispatch",
    "wait_done",
    "join_step",
}

#: WorkloadDriver per-tick functions (workload/driver.py): the open-loop
#: admission / chaos / commit-attribution loop wrapped around every router
#: (or session) step. Pure host bookkeeping by contract — commit counts
#: are read from host-side request records, never fetched — so its census
#: bucket (`workload/driver.py::drive-hot-path`) is pinned at ZERO entries.
DRIVER_HOT_PATH = {
    "step",
    "run",
    "_admit_due",
    "_maybe_kill",
    "_record_step",
    "_committed_of",
    "_has_live_work",
    "_backlog_depth",
}

#: ServingRouter disaggregated hand-off functions (runtime/router.py): the
#: prefill-tier placement path. The ONE designated hand-off sync (the
#: payload finiteness reduce) lives in runtime/disaggregated.py's
#: validate_handoff_payload — router.py-side hand-off code is pure host
#: bookkeeping, so its census bucket
#: (`runtime/router.py::handoff-hot-path`) is pinned at ZERO entries.
ROUTER_HANDOFF_HOT_PATH = {
    "_bind_replica",
    "_handoff",
    "_local_prefill",
    "_pick_prefill",
    "_publish_tier_gauges",
}

#: per-file hot-path census buckets: {relpath suffix: tuple of (bucket
#: label, function-name set, human description of why a fetch there is a
#: bug)} — a file may pin SEVERAL independent buckets (router.py pins the
#: placement loop and the hand-off path separately)
HOT_PATH_BUCKETS = {
    "runtime/serving.py": (
        (
            "step-hot-path",
            SERVING_STEP_HOT_PATH,
            "a blocking fetch here stalls the pipelined serving loop; "
            "consume points only",
        ),
    ),
    "runtime/router.py": (
        (
            "route-hot-path",
            ROUTER_HOT_PATH,
            "a blocking fetch in the placement loop serializes every replica "
            "behind one device; the router is host bookkeeping only",
        ),
        (
            "handoff-hot-path",
            ROUTER_HANDOFF_HOT_PATH,
            "a blocking fetch in the hand-off path would stall every "
            "placement behind one transfer; the designated hand-off sync "
            "lives in disaggregated.validate_handoff_payload",
        ),
    ),
    "workload/driver.py": (
        (
            "drive-hot-path",
            DRIVER_HOT_PATH,
            "a blocking fetch in the open-loop driver would bill device "
            "waits as workload time; the driver reads host-side commit "
            "records only",
        ),
    ),
}


@dataclass
class _FuncInfo:
    module: str  # module path relative to repo root
    name: str  # bare name
    node: ast.AST  # FunctionDef / AsyncFunctionDef / Lambda
    refs: Set[Tuple[str, str]] = field(default_factory=set)  # resolved (module, name)
    traced: bool = False


class _ModuleIndex:
    """Per-module: source, pragma lines, import map, function table."""

    def __init__(self, path: pathlib.Path, relpath: str, root: pathlib.Path):
        self.path = path
        self.relpath = relpath
        self.source = path.read_text()
        self.tree = ast.parse(self.source, filename=str(path))
        self.pragmas = self._collect_pragmas()
        # local name -> fully-resolved in-package module relpath (aliases for
        # `import pkg.x as y` and symbols for `from pkg.x import f`)
        self.import_modules: Dict[str, str] = {}
        self.import_symbols: Dict[str, Tuple[str, str]] = {}
        self._collect_imports(root)
        self.functions: Dict[str, List[_FuncInfo]] = {}
        # simple name -> assigned RHS expressions, so the two-step pattern
        # `step = partial(forward, ...); jax.jit(step)` still seeds `forward`
        self.assignments: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.assignments.setdefault(t.id, []).append(node.value)

    def _collect_pragmas(self) -> Dict[int, Set[str]]:
        pragmas: Dict[int, Set[str]] = {}
        for i, line in enumerate(self.source.splitlines(), start=1):
            m = _PRAGMA_RE.search(line)
            if m:
                rules = m.group(1)
                pragmas[i] = (
                    {r.strip() for r in rules.split(",")} if rules else {"*"}
                )
        return pragmas

    def _mod_to_relpath(self, dotted: str, root: pathlib.Path) -> Optional[str]:
        if not dotted.startswith(PACKAGE):
            return None
        p = root / (dotted.replace(".", "/") + ".py")
        if p.is_file():
            return str(p.relative_to(root))
        p = root / dotted.replace(".", "/") / "__init__.py"
        if p.is_file():
            return str(p.relative_to(root))
        return None

    def _collect_imports(self, root: pathlib.Path):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    rp = self._mod_to_relpath(a.name, root)
                    if rp:
                        self.import_modules[a.asname or a.name.split(".")[-1]] = rp
            elif isinstance(node, ast.ImportFrom) and node.module:
                mod = self._mod_to_relpath(node.module, root)
                for a in node.names:
                    if mod:
                        sub = self._mod_to_relpath(f"{node.module}.{a.name}", root)
                        if sub:
                            # `from pkg.x import submodule`
                            self.import_modules[a.asname or a.name] = sub
                        else:
                            self.import_symbols[a.asname or a.name] = (mod, a.name)

    def suppressed(self, line: int, rule: str, def_line: Optional[int] = None) -> bool:
        for ln in (line, def_line):
            if ln is None:
                continue
            rules = self.pragmas.get(ln)
            if rules and ("*" in rules or rule in rules):
                return True
        return False


def _names_in(expr: ast.AST) -> List[ast.AST]:
    """Every Name / module-attribute reference inside an expression tree."""
    out = []
    for n in ast.walk(expr):
        if isinstance(n, ast.Name):
            out.append(n)
        elif isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name):
            out.append(n)
    return out


def _static_elem_count(call: ast.Call) -> Optional[int]:
    """Element count of a jnp array-creating call when it is statically
    decidable from literal arguments (positional OR keyword — a
    ``jnp.zeros(shape=(4096, 4096))`` is just as provably large); None when
    shape flows from variables (the conservative direction for a lint: only
    flag what is PROVABLY large)."""
    name = call.func.attr if isinstance(call.func, ast.Attribute) else None
    kwargs = {k.arg: k.value for k in call.keywords if k.arg}

    def arg(pos: int, kw: str):
        if pos < len(call.args):
            return call.args[pos]
        return kwargs.get(kw)

    def _lit_int(node) -> Optional[int]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        return None

    def _shape_count(node) -> Optional[int]:
        if node is None:
            return None
        one = _lit_int(node)
        if one is not None:
            return one
        if isinstance(node, (ast.Tuple, ast.List)):
            total = 1
            for el in node.elts:
                d = _lit_int(el)
                if d is None:
                    return None
                total *= d
            return total
        return None

    if name in ("zeros", "ones", "full", "empty"):
        return _shape_count(arg(0, "shape"))
    if name == "arange":
        # arange(stop) / arange(start, stop[, step]) with literal ints
        nodes = [arg(0, "start"), arg(1, "stop"), arg(2, "step")]
        vals = [None if n is None else _lit_int(n) for n in nodes]
        if nodes[0] is None or vals[0] is None:
            return None
        if nodes[1] is None:
            return max(0, vals[0])  # arange(stop)
        if vals[1] is None:
            return None
        step = 1 if nodes[2] is None else vals[2]
        if not step:
            return None
        return max(0, -(-(vals[1] - vals[0]) // step))
    if name == "eye":
        n = _lit_int(arg(0, "N")) if arg(0, "N") is not None else None
        m_node = arg(1, "M")
        m = _lit_int(m_node) if m_node is not None else n
        return None if n is None or m is None else n * m
    if name == "linspace":
        num_node = arg(2, "num")
        return 50 if num_node is None else _lit_int(num_node)
    return None


def _is_jit_expr(expr: ast.AST) -> bool:
    """Does this expression mention jax.jit (directly or through partial)?"""
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr == "jit":
            return True
        if isinstance(n, ast.Name) and n.id == "jit":
            return True
    return False


def _is_jit_call(call: ast.Call) -> bool:
    """A DIRECT ``jax.jit(...)`` / ``jit(...)`` call — not a chained
    ``jax.jit(fn).lower(...)`` whose args are abstract values, not traced
    functions."""
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "jit") or (
        isinstance(f, ast.Name) and f.id == "jit"
    )


def _local_bindings(fn_node: ast.AST) -> Set[str]:
    """Names bound inside a function (params + assignments + comprehension
    targets): references to these are data flow, not module-function refs."""
    out: Set[str] = set()
    args = fn_node.args
    for a in (
        list(args.posonlyargs)
        + list(args.args)
        + list(args.kwonlyargs)
        + ([args.vararg] if args.vararg else [])
        + ([args.kwarg] if args.kwarg else [])
    ):
        out.add(a.arg)
    for n in ast.walk(fn_node):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign, ast.For, ast.comprehension)):
            targets = (
                n.targets
                if isinstance(n, ast.Assign)
                else [getattr(n, "target", None)]
            )
            for t in targets:
                if t is None:
                    continue
                for x in ast.walk(t):
                    if isinstance(x, ast.Name):
                        out.add(x.id)
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            for x in ast.walk(n.optional_vars):
                if isinstance(x, ast.Name):
                    out.add(x.id)
        elif (
            isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
            and n is not fn_node
        ):
            # nested defs bind their name locally; references to them are
            # covered by the nested-def propagation rule, and resolving the
            # bare name module-wide would drag in unrelated same-name defs
            out.add(n.name)
    return out


class _Linter:
    def __init__(self, root: pathlib.Path, files: List[pathlib.Path]):
        self.root = root
        self.modules: Dict[str, _ModuleIndex] = {}
        for f in files:
            rel = str(f.relative_to(root))
            try:
                self.modules[rel] = _ModuleIndex(f, rel, root)
            except SyntaxError as e:  # pragma: no cover - repo code parses
                raise RuntimeError(f"tpulint: cannot parse {rel}: {e}") from e
        self.findings: List[Finding] = []

    # ---- pass 1: function tables + traced roots --------------------------

    def index_functions(self):
        for rel, mod in self.modules.items():
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    info = _FuncInfo(module=rel, name=node.name, node=node)
                    mod.functions.setdefault(node.name, []).append(info)

    def resolve(self, mod: _ModuleIndex, node: ast.AST) -> List[_FuncInfo]:
        """Resolve a Name / module-attr reference to package functions."""
        if isinstance(node, ast.Name):
            # imported symbols win over same-named local defs: a function-
            # local `from models.base import forward` shadows a module-level
            # method named `forward` at its use sites
            if node.id in mod.import_symbols:
                target_mod, name = mod.import_symbols[node.id]
                target = self.modules.get(target_mod)
                if target:
                    return target.functions.get(name, [])
            if node.id in mod.functions:
                return mod.functions[node.id]
        elif isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
            target_rel = mod.import_modules.get(node.value.id)
            target = self.modules.get(target_rel) if target_rel else None
            if target:
                return target.functions.get(node.attr, [])
        return []

    def seed_traced(self):
        for rel, mod in self.modules.items():
            for infos in mod.functions.values():
                for info in infos:
                    for dec in getattr(info.node, "decorator_list", []):
                        if _is_jit_expr(dec):
                            info.traced = True
            def mark_expr(expr, seen):
                for ref in _names_in(expr):
                    for target in self.resolve(mod, ref):
                        target.traced = True
                    # chase `name = <expr>` one assignment at a time so
                    # `step = partial(forward, ...); jax.jit(step)` seeds
                    # `forward` (cycle-guarded via `seen`)
                    if isinstance(ref, ast.Name) and ref.id not in seen:
                        seen.add(ref.id)
                        for rhs in mod.assignments.get(ref.id, []):
                            mark_expr(rhs, seen)

            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call) and _is_jit_call(node)):
                    continue
                # every function referenced anywhere in the jit call's args
                # is (transitively) a traced root
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    mark_expr(arg, set())

    def collect_refs(self):
        for rel, mod in self.modules.items():
            for infos in mod.functions.values():
                for info in infos:
                    local = _local_bindings(info.node)
                    for n in ast.walk(info.node):
                        if isinstance(n, ast.Call):
                            for ref in _names_in(n.func) + [
                                r
                                for a in list(n.args) + [k.value for k in n.keywords]
                                for r in _names_in(a)
                            ]:
                                if isinstance(ref, ast.Name) and ref.id in local:
                                    continue
                                for t in self.resolve(mod, ref):
                                    info.refs.add((t.module, t.name))

    def propagate_traced(self):
        changed = True
        while changed:
            changed = False
            for mod in self.modules.values():
                for infos in mod.functions.values():
                    for info in infos:
                        if not info.traced:
                            continue
                        # nested defs of a traced function are traced
                        for n in ast.walk(info.node):
                            if isinstance(
                                n, (ast.FunctionDef, ast.AsyncFunctionDef)
                            ) and n is not info.node:
                                for cand in mod.functions.get(n.name, []):
                                    if cand.node is n and not cand.traced:
                                        cand.traced = True
                                        changed = True
                        for tm, tn in info.refs:
                            target = self.modules.get(tm)
                            if not target:
                                continue
                            for cand in target.functions.get(tn, []):
                                if not cand.traced:
                                    cand.traced = True
                                    changed = True

    def traced_functions(self) -> List[Tuple[_ModuleIndex, _FuncInfo]]:
        out = []
        for mod in self.modules.values():
            for infos in mod.functions.values():
                for info in infos:
                    if info.traced:
                        out.append((mod, info))
        return out

    # ---- pass 2: rules ---------------------------------------------------

    def _emit(self, mod, node, rule, severity, message, def_line=None, key=None):
        line = getattr(node, "lineno", 0)
        if mod.suppressed(line, rule, def_line):
            return
        self.findings.append(
            Finding(
                rule=rule,
                severity=severity,
                location=f"{mod.relpath}:{line}",
                message=message,
                key=key if key is not None else mod.relpath,
            )
        )

    def rule_host_sync_census(self):
        for mod in self.modules.values():
            # [(bucket label, note, [(line_lo, line_hi), ...]), ...] — a
            # file may pin several independent buckets (router.py pins the
            # placement loop AND the hand-off path)
            hot_buckets = []
            for suffix, buckets in HOT_PATH_BUCKETS.items():
                if not mod.relpath.endswith(suffix):
                    continue
                for label, names, note in buckets:
                    ranges = []
                    for name, infos in mod.functions.items():
                        if name not in names:
                            continue
                        for info in infos:
                            node = info.node
                            ranges.append(
                                (node.lineno,
                                 getattr(node, "end_lineno", node.lineno))
                            )
                    hot_buckets.append((label, note, ranges))
                    # a renamed/removed hot-path function must not silently
                    # disarm the gate (the baseline only fails on count
                    # INCREASES, so a bucket quietly dropping to 0 is
                    # invisible) — a stale name is a loud, non-baselined
                    # error instead
                    for name in sorted(names - set(mod.functions)):
                        self._emit(
                            mod, mod.tree, "TPU102", SEV_ERROR,
                            f"the {label} census names `{name}` but {suffix} "
                            f"defines no such function — the hot-path census "
                            f"is stale (a renamed per-step method would "
                            f"silently escape the gate); update the set in "
                            f"analysis/tpulint.py",
                            key=f"{mod.relpath}::{label}-stale",
                        )
            for n in ast.walk(mod.tree):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                name = None
                if isinstance(f, ast.Attribute) and f.attr in (
                    "device_get",
                    "block_until_ready",
                ):
                    name = f.attr
                elif isinstance(f, ast.Name) and f.id in (
                    "device_get",
                    "block_until_ready",
                ):
                    # `from jax import device_get; device_get(x)` must not
                    # slip past the pinned census
                    name = f.id
                if not name:
                    continue
                self._emit(
                    mod, n, "TPU102", SEV_WARNING,
                    f"host-sync call `{name}` (census; the baseline pins "
                    f"this file's count — batch fetches into one "
                    f"device_get per step)",
                )
                line = getattr(n, "lineno", 0)
                for bucket, hot_note, ranges in hot_buckets:
                    if not any(a <= line <= b for a, b in ranges):
                        continue
                    # separately-pinned bucket per HOT_PATH_BUCKETS: a NEW
                    # blocking fetch inside step/route/handoff-reachable
                    # code trips this gate even if the per-file count is
                    # rebalanced elsewhere in the file (ISSUE 8/10/15; the
                    # pipelined ragged path consumes via np.asarray on an
                    # async-copied array, deliberately NOT a census name).
                    self._emit(
                        mod, n, "TPU102", SEV_WARNING,
                        f"host-sync call `{name}` inside the {bucket} "
                        f"functions (separately-pinned census bucket — "
                        f"{hot_note})",
                        key=f"{mod.relpath}::{bucket}",
                    )

    def _body_nodes(self, info: _FuncInfo):
        """Nodes of this function body, excluding nested defs (they are
        linted as their own traced functions)."""
        nested = [
            n
            for n in ast.walk(info.node)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n is not info.node
        ]
        skip = set()
        for nd in nested:
            skip.update(id(x) for x in ast.walk(nd))
            skip.discard(id(nd))
        for n in ast.walk(info.node):
            if id(n) not in skip:
                yield n

    def rule_under_trace(self):
        for mod, info in self.traced_functions():
            def_line = info.node.lineno
            for n in self._body_nodes(info):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                if isinstance(f, ast.Attribute):
                    if f.attr in HOST_SYNC_ATTRS:
                        # dict.items() etc. have different names; `.item()` on
                        # anything inside a traced body is the bug
                        self._emit(
                            mod, n, "TPU101", SEV_ERROR,
                            f"host-sync `.{f.attr}(...)` inside jit-traced "
                            f"`{info.name}` — forces a device round-trip/"
                            f"concretization at trace time; move it to the "
                            f"host loop",
                            def_line=def_line,
                        )
                    elif (
                        isinstance(f.value, ast.Name)
                        and f.value.id in ("time",)
                        and f.attr in HOST_TIME_FUNCS
                    ):
                        self._emit(
                            mod, n, "TPU103", SEV_ERROR,
                            f"`time.{f.attr}()` inside jit-traced "
                            f"`{info.name}` — executes once at trace time; "
                            f"use utils/profiling.py host-side",
                            def_line=def_line,
                        )
                    elif (
                        isinstance(f.value, ast.Name)
                        and f.value.id in ("np", "numpy")
                        and f.attr in NP_SYNC_FUNCS
                    ):
                        self._emit(
                            mod, n, "TPU106", SEV_WARNING,
                            f"`np.{f.attr}` inside jit-traced `{info.name}` — "
                            f"fine on trace-time constants (baseline/pragma "
                            f"it), a sync or crash on traced values",
                            def_line=def_line,
                        )
                elif isinstance(f, ast.Name) and f.id == "print":
                    self._emit(
                        mod, n, "TPU103", SEV_ERROR,
                        f"`print` inside jit-traced `{info.name}` — runs once "
                        f"at trace time; use jax.debug.print",
                        def_line=def_line,
                    )
                elif isinstance(f, ast.Name) and f.id in (
                    "device_get",
                    "block_until_ready",
                ):
                    # bare-imported forms of the host-sync calls
                    self._emit(
                        mod, n, "TPU101", SEV_ERROR,
                        f"host-sync `{f.id}(...)` inside jit-traced "
                        f"`{info.name}` — forces a device round-trip/"
                        f"concretization at trace time; move it to the "
                        f"host loop",
                        def_line=def_line,
                    )

    def rule_telemetry_under_trace(self):
        """TPU107: no metric recording under a jit trace. Two detectors:
        references to symbols imported from the telemetry package (resolved
        through the import maps), and bare ``.inc(...)``/``.observe(...)``
        metric-mutator calls (the heuristic half for sessions reached
        through attributes the import map cannot see)."""
        for mod, info in self.traced_functions():
            def_line = info.node.lineno
            local = _local_bindings(info.node)
            for n in self._body_nodes(info):
                if isinstance(n, ast.Call):
                    f = n.func
                    if isinstance(f, ast.Attribute) and f.attr in METRIC_RECORD_ATTRS:
                        self._emit(
                            mod, n, "TPU107", SEV_ERROR,
                            f"metric `.{f.attr}(...)` inside jit-traced "
                            f"`{info.name}` — Python under trace runs once "
                            f"per compile, so this records compiles, not "
                            f"steps; record in the host loop on the step's "
                            f"existing batched fetch",
                            def_line=def_line,
                        )
                if isinstance(n, ast.Name) and n.id not in local:
                    tgt = mod.import_symbols.get(n.id)
                    if tgt and tgt[0].startswith(TELEMETRY_PKG):
                        self._emit(
                            mod, n, "TPU107", SEV_ERROR,
                            f"telemetry symbol `{n.id}` referenced inside "
                            f"jit-traced `{info.name}` — recording (or even "
                            f"resolving a session) belongs in host loops "
                            f"only; under trace it runs once and lies",
                            def_line=def_line,
                        )
                elif isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name):
                    rel = mod.import_modules.get(n.value.id)
                    if rel and rel.startswith(TELEMETRY_PKG):
                        self._emit(
                            mod, n, "TPU107", SEV_ERROR,
                            f"telemetry module access "
                            f"`{n.value.id}.{n.attr}` inside jit-traced "
                            f"`{info.name}` — recording belongs in host "
                            f"loops only; under trace it runs once and lies",
                            def_line=def_line,
                        )

    def rule_large_unsharded_constants(self):
        """TPU108: statically-sized jnp array creation ≥ the element
        threshold inside a traced body, with no sharding wrapper anywhere
        above it in the expression."""
        for mod, info in self.traced_functions():
            def_line = info.node.lineno
            wrapped: Set[int] = set()
            for n in self._body_nodes(info):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                name = f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", None)
                if name in SHARDING_WRAPPERS:
                    for sub in ast.walk(n):
                        wrapped.add(id(sub))
            for n in self._body_nodes(info):
                if not isinstance(n, ast.Call) or id(n) in wrapped:
                    continue
                f = n.func
                if not (
                    isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "jnp"
                    and f.attr in JNP_ARRAY_CREATORS
                ):
                    continue
                count = _static_elem_count(n)
                if count is None or count < TPU108_ELEM_THRESHOLD:
                    continue
                self._emit(
                    mod, n, "TPU108", SEV_WARNING,
                    f"`jnp.{f.attr}` creates {count} elements inside "
                    f"jit-traced `{info.name}` with no sharding constraint — "
                    f"GSPMD replicates unconstrained constants, so this "
                    f"costs model-group× its HBM; wrap it in "
                    f"with_sharding_constraint (or build it host-side and "
                    f"device_put it sharded)",
                    def_line=def_line,
                )

    def rule_module_mutable_state(self):
        """TPU109: a module-level dict/list/set in runtime/ written from any
        function in the module — shared state with no owning object, i.e.
        nothing the concurrency audit's confinement census can classify."""
        for mod in self.modules.values():
            if not mod.relpath.startswith(TPU109_SCOPE_PREFIX):
                continue
            mutables: Set[str] = set()
            for node in mod.tree.body:
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, ast.AnnAssign) and node.value is not None:
                    targets = [node.target]
                else:
                    continue
                v = node.value
                is_mutable = isinstance(
                    v, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                        ast.ListComp, ast.SetComp)
                )
                if isinstance(v, ast.Call):
                    fn = v.func
                    name = fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", None)
                    is_mutable = is_mutable or name in MUTABLE_CONSTRUCTORS
                if not is_mutable:
                    continue
                for t in targets:
                    if isinstance(t, ast.Name):
                        mutables.add(t.id)
            if not mutables:
                continue
            for infos in mod.functions.values():
                for info in infos:
                    # names bound as PLAIN locals (params / bare-Name
                    # assignments / loop targets). _local_bindings is the
                    # wrong tool here: it walks subscript-assignment
                    # targets too, so `REGISTRY[k] = v` would mark REGISTRY
                    # itself local and hide exactly the write this rule
                    # exists to catch.
                    local: Set[str] = set()
                    args = info.node.args
                    for a in (
                        list(args.posonlyargs) + list(args.args)
                        + list(args.kwonlyargs)
                        + ([args.vararg] if args.vararg else [])
                        + ([args.kwarg] if args.kwarg else [])
                    ):
                        local.add(a.arg)
                    declared_global: Set[str] = set()
                    for n in self._body_nodes(info):
                        if isinstance(n, ast.Global):
                            declared_global.update(n.names)
                        elif isinstance(n, ast.Assign):
                            for t in n.targets:
                                if isinstance(t, ast.Name):
                                    local.add(t.id)
                        elif isinstance(n, (ast.AnnAssign, ast.NamedExpr)):
                            # `x: Dict = {}` / `(x := ...)` bind locals
                            # exactly like a plain assignment
                            if isinstance(n.target, ast.Name):
                                local.add(n.target.id)
                        elif isinstance(n, (ast.For, ast.comprehension)):
                            for x in ast.walk(n.target):
                                if isinstance(x, ast.Name):
                                    local.add(x.id)
                        elif isinstance(n, ast.withitem) and n.optional_vars:
                            for x in ast.walk(n.optional_vars):
                                if isinstance(x, ast.Name):
                                    local.add(x.id)
                    local -= declared_global

                    def emit(n, name, how, info=info):
                        self._emit(
                            mod, n, "TPU109", SEV_WARNING,
                            f"module-level mutable `{name}` (assigned at "
                            f"import time) is written from `{info.name}` "
                            f"({how}) — hidden shared state with no owning "
                            f"object: no thread-confinement argument covers "
                            f"it (CONC601 census), and under "
                            f"thread-per-replica router stepping it is a "
                            f"cross-replica race; move it onto an owning "
                            f"class or suppress with a justification",
                            def_line=info.node.lineno,
                            key=f"{mod.relpath}::{name}",
                        )

                    for n in self._body_nodes(info):
                        if isinstance(n, (ast.Assign, ast.AugAssign)):
                            tgts = (
                                n.targets if isinstance(n, ast.Assign)
                                else [n.target]
                            )
                            for t in tgts:
                                if (
                                    isinstance(t, ast.Subscript)
                                    and isinstance(t.value, ast.Name)
                                    and t.value.id in mutables
                                    and t.value.id not in local
                                ):
                                    emit(n, t.value.id, "subscript assignment")
                                elif (
                                    isinstance(t, ast.Name)
                                    and t.id in mutables
                                    and t.id in declared_global
                                ):
                                    emit(n, t.id, "global rebind")
                        elif isinstance(n, ast.Call) and isinstance(
                            n.func, ast.Attribute
                        ):
                            recv = n.func.value
                            if (
                                n.func.attr in CONTAINER_MUTATORS
                                and isinstance(recv, ast.Name)
                                and recv.id in mutables
                                and recv.id not in local
                            ):
                                emit(n, recv.id, f".{n.func.attr}() call")

    def rule_pallas_interpret(self):
        for mod in self.modules.values():
            for n in ast.walk(mod.tree):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                is_pallas = (isinstance(f, ast.Name) and f.id == "pallas_call") or (
                    isinstance(f, ast.Attribute) and f.attr == "pallas_call"
                )
                if not is_pallas:
                    continue
                if not any(k.arg == "interpret" for k in n.keywords):
                    self._emit(
                        mod, n, "TPU104", SEV_ERROR,
                        "`pallas_call` without `interpret=` — every kernel "
                        "must plumb ops/kernel_mode.kernel_interpret() so the "
                        "CPU mesh can run it and the AOT lowering tests can "
                        "force-compile it",
                    )

    def rule_mutable_defaults(self):
        for mod in self.modules.values():
            for infos in mod.functions.values():
                for info in infos:
                    args = info.node.args
                    for default in list(args.defaults) + [
                        d for d in args.kw_defaults if d is not None
                    ]:
                        if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                            self._emit(
                                mod, default, "TPU105", SEV_ERROR,
                                f"mutable default argument in `{info.name}` — "
                                f"shared across calls; use None + in-body "
                                f"default",
                                def_line=info.node.lineno,
                            )

    def rule_silent_swallow(self):
        """TPU110: `except: pass` / `except Exception: pass` in runtime/ or
        telemetry/ — a silently swallowed failure on a serving or
        observability path."""
        for mod in self.modules.values():
            if not (
                "runtime/" in mod.relpath or "telemetry/" in mod.relpath
            ):
                continue
            for n in ast.walk(mod.tree):
                if not isinstance(n, ast.ExceptHandler):
                    continue
                broad = n.type is None or (
                    isinstance(n.type, ast.Name)
                    and n.type.id in ("Exception", "BaseException")
                )
                silent = all(
                    isinstance(s, ast.Pass)
                    or (isinstance(s, ast.Expr)
                        and isinstance(s.value, ast.Constant))
                    for s in n.body
                )
                if broad and silent:
                    what = (
                        n.type.id if isinstance(n.type, ast.Name)
                        else "bare except"
                    )
                    self._emit(
                        mod, n, "TPU110", SEV_WARNING,
                        f"silent-swallow `except {what}: pass` — a broad "
                        f"catch that discards the failure hides leaks and "
                        f"corruption on a runtime/telemetry path; catch the "
                        f"typed class or re-raise",
                        key=f"{mod.relpath}::silent-swallow",
                    )

    def run(self) -> List[Finding]:
        self.index_functions()
        self.seed_traced()
        self.collect_refs()
        self.propagate_traced()
        self.rule_under_trace()
        self.rule_telemetry_under_trace()
        self.rule_large_unsharded_constants()
        self.rule_host_sync_census()
        self.rule_pallas_interpret()
        self.rule_mutable_defaults()
        self.rule_module_mutable_state()
        self.rule_silent_swallow()
        self.findings.sort(key=lambda f: (f.location, f.rule))
        return self.findings


def package_files(root: Optional[pathlib.Path] = None) -> Tuple[pathlib.Path, List[pathlib.Path]]:
    """(repo root, package .py files). The analysis package itself is linted
    too — it must obey its own rules."""
    if root is None:
        root = pathlib.Path(__file__).resolve().parents[2]
    pkg = root / PACKAGE
    return root, sorted(pkg.rglob("*.py"))


def run(root: Optional[pathlib.Path] = None, files: Optional[List[pathlib.Path]] = None) -> List[Finding]:
    """Lint the package (or an explicit file list, for fixture tests)."""
    resolved_root, pkg_files = package_files(root)
    if files is not None:
        pkg_files = files
    return _Linter(resolved_root, pkg_files).run()


def lint_paths(paths: List[pathlib.Path], root: pathlib.Path) -> List[Finding]:
    """Lint arbitrary snippet files (test fixtures) relative to ``root``."""
    return _Linter(root, [p.resolve() for p in paths]).run()
