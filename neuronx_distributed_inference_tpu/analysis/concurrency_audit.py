"""Concurrency-contract analyzer (CONC6xx): the host threading model of the
serving stack as a statically audited, baseline-pinned contract.

The thread-per-replica router (``TpuConfig.router_threading``,
runtime/router.py) is only safe under a specific confinement model: ONLY
``ReplicaHandle.step()`` runs on worker threads; placement, admission,
failover harvesting, terminal sync and every gauge stay on the router
thread, which blocks on the per-step barrier while workers run — so
per-replica objects are touched by at most one thread at a time, and the
only state crossing replicas (the shared telemetry session and its metric
instruments) must be lock-protected. A dynamic test suite cannot reliably
catch a violation of that model (a data race is a probability, not a
behavior), so — in the tradition of the graph (PR 1), shard/memory (PR 5)
and cost (PR 11) contracts — this suite proves the model over the AST +
traced call graph and pins the resulting census to
``analysis/conc_baseline.json``:

- **CONC601 shared-mutable-state census** — every attribute/container WRITE
  site in runtime/router.py, runtime/replica.py, runtime/serving.py,
  runtime/faults.py and telemetry/ is classified:

  - ``replica-step-confined`` — a write to replica-owned state (session,
    handle, request, injector, app/cache, worker cell) reachable from the
    worker entry points: safe because each replica owns its objects and is
    stepped by one thread.
  - ``router-thread`` — a write NOT reachable from any worker entry: it can
    only execute on the router/driver thread (placement, admission,
    harvesting — phases the barrier serializes against the workers).
  - ``lock-protected`` — syntactically inside a ``with <lock>:`` region.
  - ``init-confined`` — ``self.*`` writes inside the owner's
    ``__init__``/``__post_init__`` (the object is unpublished).

  Anything else — shared (telemetry/registry) state written from a worker
  path without a lock, router-owned state written from a worker path, a
  write whose owner the analyzer cannot resolve, a module global mutated
  from a worker path — is an ERROR finding with zero baseline budget. The
  classified census is pinned: new shared state (a new attribute, or an
  existing write drifting to a different classification) trips the gate.
- **CONC602 lock discipline** — locks are acquired only via ``with`` (bare
  ``.acquire()``/``.release()`` is an error); nested acquisition must follow
  the single global order **router (0) → replica/session (1) → telemetry
  session (2) → metric instrument (3)** — for every ``with <lock>`` region
  the traced call graph is walked and a reachable acquisition of a
  lower-or-equal level is a cycle risk (same-identity re-entry is allowed
  only for locks constructed as ``threading.RLock``); and no BLOCKING call
  (``jax.device_get`` / ``block_until_ready``, an in-flight ``.result()`` /
  ``np.asarray`` fetch, ``time.sleep``, ``.join()``/``.wait()``, file or
  socket I/O) may execute while holding a router-level (level-0) lock — a
  block under the router lock would stall every replica.
- **CONC603 telemetry atomicity** — every Counter/Gauge/Histogram mutation
  must go through the registry's atomic ``inc``/``set``/``observe``: a
  read-modify-write on instrument internals (``.value``/``.sum``/
  ``.count``/``._value``/bucket lists) anywhere outside the locked
  instrument methods in telemetry/metrics.py is an error. (``+=`` on a
  Python float is multiple bytecodes; the GIL does not make it atomic.)
- **CONC604 JAX-object thread-ownership census** — replica device state
  (``kv_cache``, params, the in-flight ``_pending``/``_draft_prop`` device
  arrays, the runners) is touched only by the replica's confinement set
  (session + handle). ``ServingRouter`` code reaching through
  ``h.session.<attr>`` may only read committed host-side snapshots: the
  touched-attribute census is baseline-pinned (a NEW router→session touch
  is reviewed like a collective), and touching a device-state attribute is
  an error outright.

Like the other suites: ``python -m neuronx_distributed_inference_tpu.analysis
--suites conc`` exits 0 on a clean tree, ``--write-baseline`` regenerates
``conc_baseline.json`` and prints the unified diff, and the ``--json``
report carries a ``"concurrency"`` section with the classification
breakdown. Suppression: ``# conc: ignore[CONC601]`` on the offending line
or its ``def`` line. See docs/STATIC_ANALYSIS.md "Concurrency audit".
"""

from __future__ import annotations

import ast
import pathlib
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from neuronx_distributed_inference_tpu.analysis.findings import (
    Baseline,
    CONTAINER_MUTATORS,
    Finding,
    SEV_ERROR,
    SEV_WARNING,
)

PACKAGE = "neuronx_distributed_inference_tpu"
BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "conc_baseline.json"

#: the audited surface — the serving host layers the threaded router makes
#: concurrent, matched by relpath suffix so fixture trees audit identically
SCOPE_SUFFIXES = (
    "runtime/router.py",
    "runtime/replica.py",
    "runtime/serving.py",
    "runtime/faults.py",
    "telemetry/__init__.py",
    "telemetry/metrics.py",
    "telemetry/tracing.py",
    # the open-loop workload driver (ISSUE 14): it steps the router — and
    # under router_threading its spec accept-gate closure is CALLED from
    # replica workers — so its write sites join the census like the
    # router's own
    "workload/driver.py",
    # the disaggregated KV hand-off (ISSUE 15): extract/inject/validate run
    # on the router thread during the placement phase, writing the prefill
    # and decode apps' caches — their write sites join the census so a
    # future worker-reachable hand-off cannot slip in unclassified
    "runtime/disaggregated.py",
    # the observability layer (ISSUE 19): the span store and SLO monitor
    # are written from replica workers (via TelemetrySession record hooks)
    # AND read by the ops-server scrape thread, so both join the census as
    # SHARED; the ops server itself adds a third thread kind to the model
    # (its handler threads, entered at do_GET)
    "telemetry/spans.py",
    "telemetry/slo_monitor.py",
    "telemetry/ops_server.py",
)

# ---------------------------------------------------------------------------
# ownership model: which class owns a write decides what discipline it needs
# ---------------------------------------------------------------------------

#: per-replica objects: each replica owns exactly one of each, and the
#: barrier guarantees at most one thread (its worker, or the router between
#: barriers) touches them at a time. ``TpuApplication`` is the pseudo-class
#: for ``session.app``/``session.draft`` (the per-replica model application
#: holding params + the donated KV cache).
#: PrefillReplicaHandle/DisaggregatedPipeline (ISSUE 15) carry the replica
#: discipline: a tier member's app/health is touched by exactly one thread
#: at a time — the router thread, synchronously, during the placement
#: phase's hand-off (workers never run hand-offs; CONC604 keeps it so)
REPLICA_OWNED = frozenset({
    "ServingSession", "SpeculativeServingSession", "ReplicaHandle",
    "Request", "FaultInjector", "RequestTrace", "TpuApplication",
    "_ReplicaStepWorker", "WatchdogError",
    "PrefillReplicaHandle", "DisaggregatedPipeline",
    "_HealthStateMachine",  # the shared health-machine base of both handles
    # a step-timeline span lives on the stack of the thread that opened it
    # (``with tel.span(...)``), and each thread has its own stack of them
    "_Span", "_SpanStack",
})

#: router-global objects: written ONLY by the router thread — a write
#: reachable from a worker entry is an error, not a census entry.
#: WorkloadDriver/VirtualClock/WorkloadResult (workload/driver.py) run the
#: open-loop admission/scoring loop on the SAME thread the router's
#: placement phases run on (the driver calls router.step() between its own
#: phases), so they carry the router-thread discipline.
ROUTER_OWNED = frozenset({
    "ServingRouter", "RouterRequest",
    "WorkloadDriver", "VirtualClock", "WorkloadResult",
    # the ops server's lifecycle state (thread handle, bound port) is
    # written only by whoever starts/stops it — the router/driver thread;
    # its handler threads read the registry/snapshot callbacks but never
    # write OpsServer attributes (CONC601 keeps it so)
    "OpsServer",
})

#: state shared ACROSS replicas: every worker thread records into one
#: telemetry session / registry, so worker-reachable writes must be
#: lock-protected
SHARED = frozenset({
    "TelemetrySession", "MetricsRegistry", "_Family",
    "Counter", "Gauge", "Histogram",
    # ISSUE 19: span timelines + SLO windows are recorded from worker
    # threads through the session's record hooks and scraped by the ops
    # server's handler threads — every mutation must hold their own lock
    "SpanStore", "SloMonitor",
})

#: the worker thread entry points — the ONLY code the thread-per-replica
#: pool runs. Everything transitively reachable from these is the
#: "replica step thread" set W.
WORKER_ENTRIES = (
    ("ReplicaHandle", "step"),
    ("_ReplicaStepWorker", "run"),
    # the ops server's per-connection handler threads (ThreadingHTTPServer)
    # — everything a scrape can reach must carry worker discipline
    ("_OpsHandler", "do_GET"),
)

# ---------------------------------------------------------------------------
# type environment: how receiver expressions resolve to owner classes.
# Deliberately repo-specific configuration (like tpulint's hot-path sets) —
# the analyzer is a contract for THIS codebase, not a general type checker.
# ---------------------------------------------------------------------------

#: (owner class or "*", attribute) -> class of that attribute
ATTR_TYPES = {
    ("*", "session"): "ServingSession",
    ("*", "tel"): "TelemetrySession",
    ("*", "faults"): "FaultInjector",
    ("*", "registry"): "MetricsRegistry",
    ("*", "app"): "TpuApplication",
    ("*", "draft"): "TpuApplication",
    ("_ReplicaStepWorker", "handle"): "ReplicaHandle",
    ("WorkloadDriver", "result"): "WorkloadResult",
    ("WorkloadDriver", "clock"): "VirtualClock",
    ("*", "prefill_app"): "TpuApplication",
    ("*", "decode_app"): "TpuApplication",
    ("*", "spans"): "SpanStore",
    ("*", "slo_monitor"): "SloMonitor",
}

#: (owner class or "*", container attribute) -> element/value class
ELEM_TYPES = {
    ("ServingRouter", "replicas"): "ReplicaHandle",
    ("ServingRouter", "alive_replicas"): "ReplicaHandle",
    ("ServingRouter", "prefill_replicas"): "PrefillReplicaHandle",
    ("ServingRouter", "alive_prefill_replicas"): "PrefillReplicaHandle",
    ("ServingRouter", "requests"): "RouterRequest",
    ("ServingRouter", "rejected"): "RouterRequest",
    ("ServingRouter", "pending"): "RouterRequest",
    ("ServingRouter", "_workers"): "_ReplicaStepWorker",
    ("ServingSession", "slots"): "Request",
    ("ServingSession", "active"): "Request",
    ("ServingSession", "decoding"): "Request",
    ("ServingSession", "prefilling"): "Request",
    ("ServingSession", "_readmit"): "Request",
    ("ServingSession", "requests"): "Request",
    ("ServingSession", "rejected"): "Request",
    ("ReplicaHandle", "owned"): "RouterRequest",
    ("TelemetrySession", "traces"): "RequestTrace",
    ("TelemetrySession", "completed"): "RequestTrace",
    ("MetricsRegistry", "_families"): "_Family",
}

#: last-resort receiver-name hints (an explicit annotation or an inferred
#: assignment always wins); the census keeps the analyzer honest — a
#: mis-hinted owner shows up as census drift
VAR_NAME_HINTS = {
    "req": "Request", "r": "Request", "sreq": "Request", "victim": "Request",
    "rreq": "RouterRequest",
    "h": "ReplicaHandle", "handle": "ReplicaHandle",
    "tr": "RequestTrace",
    "sess": "ServingSession", "session": "ServingSession",
    "fam": "_Family", "tel": "TelemetrySession",
    "router": "ServingRouter",
    "w": "_ReplicaStepWorker",
    "app": "TpuApplication", "draft_app": "TpuApplication",
    "drv": "WorkloadDriver", "vc": "VirtualClock",
    "mon": "SloMonitor", "store": "SpanStore",
    "ph": "PrefillReplicaHandle",
    "pre": "TpuApplication", "dec": "TpuApplication",
    "pipe": "DisaggregatedPipeline",
}

#: container-mutating method names (a call through these IS a write) —
#: shared with tpulint's TPU109 so lint and audit agree on what a write is
MUTATORS = CONTAINER_MUTATORS

#: lock acquisition hierarchy: nested ``with <lock>`` must strictly
#: INCREASE in level (router outermost, metric instruments innermost; the
#: registry may hold its lock while copying a family's child table, and a
#: family holds its lock while minting a child instrument)
LOCK_LEVELS = {
    "ServingRouter": 0, "RouterRequest": 0,
    "WorkloadDriver": 0, "VirtualClock": 0, "WorkloadResult": 0,
    "ReplicaHandle": 1, "ServingSession": 1, "SpeculativeServingSession": 1,
    "Request": 1, "FaultInjector": 1, "_ReplicaStepWorker": 1,
    "PrefillReplicaHandle": 1, "DisaggregatedPipeline": 1,
    "_HealthStateMachine": 1,
    "TelemetrySession": 2,
    # the span store and SLO monitor sit BELOW the session: record hooks
    # take the session lock then the store/monitor lock, never the reverse
    # (export snapshots under the session lock copy, serialize outside)
    "SpanStore": 3, "SloMonitor": 3, "OpsServer": 2,
    "MetricsRegistry": 3,
    "_Family": 4,
    "Counter": 5, "Gauge": 5, "Histogram": 5,
}
#: fallback lock level by scope file when the lock's owner class is unknown
MODULE_LOCK_LEVELS = {
    "workload/driver.py": 0,
    "runtime/router.py": 0,
    "runtime/replica.py": 1,
    "runtime/disaggregated.py": 1,
    "runtime/serving.py": 1,
    "runtime/faults.py": 1,
    "telemetry/tracing.py": 2,
    "telemetry/__init__.py": 2,
    "telemetry/spans.py": 3,
    "telemetry/slo_monitor.py": 3,
    "telemetry/ops_server.py": 2,
    "telemetry/metrics.py": 3,
}

#: calls that can block (device sync, sleeps, thread joins, file/socket IO)
#: — forbidden while holding a router-level lock (CONC602)
BLOCKING_ATTRS = frozenset({
    "device_get", "block_until_ready", "item", "result", "join", "wait",
    "sleep", "asarray", "array", "acquire", "read", "write", "recv", "send",
    "connect",
})
BLOCKING_NAMES = frozenset({"open", "device_get", "block_until_ready",
                            "sleep", "input"})

#: CONC603: instrument-internal attributes no call site may read-modify-write
INSTRUMENT_INTERNALS = frozenset({"value", "sum", "count", "_value"})
INSTRUMENT_BUCKETS = frozenset({"counts", "buckets"})
INSTRUMENT_CLASSES = frozenset({"Counter", "Gauge", "Histogram", "_Family"})

#: CONC604: replica device state the router must never reach through
#: ``h.session.<attr>`` (stepping included: it belongs to the handle/worker)
DEVICE_STATE_ATTRS = frozenset({
    "kv_cache", "params", "_pending", "_draft_prop", "mixed_runner",
    "draft", "app_params", "token_generation_model",
    "context_encoding_model", "step", "_step_inner",
})

_PRAGMA_RE = re.compile(r"#\s*conc:\s*ignore(?:\[([A-Z0-9, ]+)\])?")

#: set by :func:`run` — the classification breakdown the CLI embeds in --json
_LAST_REPORT: Dict = {}


# ---------------------------------------------------------------------------
# module / function indexing
# ---------------------------------------------------------------------------


@dataclass
class _Func:
    module: str  # scope-relative path (matched suffix)
    cls: str  # "" for module-level functions
    name: str
    node: ast.AST
    bases: Tuple[str, ...] = ()
    calls: Set[Tuple[str, str]] = field(default_factory=set)  # (cls, name)
    worker: bool = False  # reachable from a WORKER_ENTRY

    @property
    def key(self) -> Tuple[str, str]:
        return (self.cls, self.name)

    @property
    def qual(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclass
class _LockRegion:
    func: "_Func"
    identity: Tuple[str, str]  # (owner class or <module...>, attr/name)
    level: int
    lineno: int
    end_lineno: int
    node: ast.With


class _Module:
    def __init__(self, path: pathlib.Path, scope_rel: str):
        self.path = path
        self.rel = scope_rel
        self.source = path.read_text()
        self.tree = ast.parse(self.source, filename=str(path))
        self.pragmas = self._collect_pragmas()
        # module-level names assigned at import time (the TPU109 smell's
        # census side) — writes through them from functions are module-
        # global writes
        self.module_globals: Set[str] = set()
        for node in self.tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    self.module_globals.add(t.id)

    def _collect_pragmas(self) -> Dict[int, Set[str]]:
        out: Dict[int, Set[str]] = {}
        for i, line in enumerate(self.source.splitlines(), start=1):
            m = _PRAGMA_RE.search(line)
            if m:
                rules = m.group(1)
                out[i] = {r.strip() for r in rules.split(",")} if rules else {"*"}
        return out

    def suppressed(self, line: int, rule: str, def_line: Optional[int] = None) -> bool:
        for ln in (line, def_line):
            if ln is None:
                continue
            rules = self.pragmas.get(ln)
            if rules and ("*" in rules or rule in rules):
                return True
        return False


def _ann_to_type(ann, classes: Set[str]) -> Tuple[Optional[str], Optional[str]]:
    """(scalar type, container element type) from an annotation node."""
    if isinstance(ann, ast.Name) and ann.id in classes:
        return ann.id, None
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str) and ann.value in classes:
        return ann.value, None
    if isinstance(ann, ast.Subscript):
        # List[Request] / Sequence[ReplicaHandle] / Dict[str, Request]
        sl = ann.slice
        elts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
        for e in reversed(elts):  # Dict value type wins
            t, _ = _ann_to_type(e, classes)
            if t:
                return None, t
    return None, None


class _Analyzer:
    def __init__(self, files: List[Tuple[pathlib.Path, str]]):
        self.modules: List[_Module] = [_Module(p, rel) for p, rel in files]
        self.findings: List[Finding] = []
        # class -> (module, bases); method tables per class
        self.class_bases: Dict[str, Tuple[str, ...]] = {}
        self.methods: Dict[Tuple[str, str], List[_Func]] = {}
        self.funcs: List[_Func] = []
        self.lock_kinds: Dict[Tuple[str, str], str] = {}  # identity -> lock|rlock
        self._index()
        self._build_env_and_calls()
        self._mark_worker_set()

    # ---- indexing --------------------------------------------------------

    def _index(self):
        for mod in self.modules:
            for node in mod.tree.body:
                if isinstance(node, ast.ClassDef):
                    bases = tuple(
                        b.id for b in node.bases if isinstance(b, ast.Name)
                    )
                    self.class_bases[node.name] = bases
                    for sub in node.body:
                        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                            self._add_func(mod, node.name, sub, bases)
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._add_func(mod, "", node, ())
        # lock kinds: self.<attr> = threading.Lock()/RLock() anywhere
        for f in self.funcs:
            for n in ast.walk(f.node):
                if not (isinstance(n, ast.Assign) and isinstance(n.value, ast.Call)):
                    continue
                v = n.value.func
                kind = None
                if isinstance(v, ast.Attribute) and v.attr in ("Lock", "RLock"):
                    kind = "rlock" if v.attr == "RLock" else "lock"
                elif isinstance(v, ast.Name) and v.id in ("Lock", "RLock"):
                    kind = "rlock" if v.id == "RLock" else "lock"
                if kind is None:
                    continue
                for t in n.targets:
                    if (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        self.lock_kinds[(f.cls, t.attr)] = kind

    def _add_func(self, mod: _Module, cls: str, node, bases):
        f = _Func(module=mod.rel, cls=cls, name=node.name, node=node, bases=bases)
        f._mod = mod  # type: ignore[attr-defined]
        self.funcs.append(f)
        self.methods.setdefault((cls, node.name), []).append(f)
        # nested defs (dispatch closures): indexed as their own functions in
        # the same class context, with an implicit call edge from the parent
        for sub in ast.walk(node):
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub is not node
            ):
                nf = _Func(module=mod.rel, cls=cls, name=sub.name, node=sub,
                           bases=bases)
                nf._mod = mod  # type: ignore[attr-defined]
                self.funcs.append(nf)
                self.methods.setdefault((cls, sub.name), []).append(nf)
                f.calls.add((cls, sub.name))

    def _hierarchy(self, cls: str) -> Set[str]:
        """cls + its in-scope bases + in-scope subclasses (method resolution
        fans out over the whole hierarchy: the conservative direction)."""
        out = {cls}
        # bases (transitive)
        frontier = [cls]
        while frontier:
            c = frontier.pop()
            for b in self.class_bases.get(c, ()):
                if b not in out:
                    out.add(b)
                    frontier.append(b)
        # subclasses
        changed = True
        while changed:
            changed = False
            for c, bases in self.class_bases.items():
                if c not in out and any(b in out for b in bases):
                    out.add(c)
                    changed = True
        return out

    # ---- type environment ------------------------------------------------

    def _elem_type(self, owner: Optional[str], attr: str) -> Optional[str]:
        if owner:
            for c in self._hierarchy(owner):
                t = ELEM_TYPES.get((c, attr))
                if t:
                    return t
        return ELEM_TYPES.get(("*", attr))

    def _attr_type(self, owner: Optional[str], attr: str) -> Optional[str]:
        if owner:
            for c in self._hierarchy(owner):
                t = ATTR_TYPES.get((c, attr))
                if t:
                    return t
        return ATTR_TYPES.get(("*", attr))

    def _expr_type(self, f: _Func, env: Dict[str, str], expr) -> Optional[str]:
        if isinstance(expr, ast.Name):
            if expr.id == "self" and f.cls:
                return f.cls
            t = env.get(expr.id)
            if t:
                return t
            return VAR_NAME_HINTS.get(expr.id)
        if isinstance(expr, ast.Attribute):
            base = self._expr_type(f, env, expr.value)
            return self._attr_type(base, expr.attr)
        if isinstance(expr, ast.Subscript):
            v = expr.value
            if isinstance(v, ast.Attribute):
                base = self._expr_type(f, env, v.value)
                return self._elem_type(base, v.attr)
            if isinstance(v, ast.Name):
                return env.get("<elem>" + v.id)
            return None
        if isinstance(expr, ast.Call):
            fn = expr.func
            if isinstance(fn, ast.Name) and fn.id == "default_session":
                return "TelemetrySession"
            if isinstance(fn, ast.Attribute):
                if fn.attr in ("get", "pop", "popleft"):
                    # dict.get / dict.pop / deque.popleft yield the element
                    return self._expr_type(
                        f, env, ast.Subscript(value=fn.value, slice=ast.Constant(value=0))
                    )
                # constructor-ish call through a class name
            if isinstance(fn, ast.Name) and fn.id in self.class_bases:
                return fn.id
        return None

    def _build_env(self, f: _Func) -> Dict[str, str]:
        """name -> class for locals (annotations, inferred assignments,
        iteration over typed containers); '<elem>name' entries carry the
        element type of locally-bound container aliases."""
        env: Dict[str, str] = {}
        classes = set(self.class_bases) | {"TpuApplication", "RequestTrace"}
        args = f.node.args
        for a in list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs):
            if a.annotation is not None:
                t, elem = _ann_to_type(a.annotation, classes)
                if t:
                    env[a.arg] = t
                elif elem:
                    env["<elem>" + a.arg] = elem
        # two passes so chains like alive = self.alive_replicas; for h in
        # alive resolve regardless of textual order
        for _ in range(2):
            for n in ast.walk(f.node):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 and isinstance(
                    n.targets[0], ast.Name
                ):
                    name = n.targets[0].id
                    t = self._expr_type(f, env, n.value)
                    if t:
                        env[name] = t
                    elif isinstance(n.value, ast.Attribute):
                        base = self._expr_type(f, env, n.value.value)
                        elem = self._elem_type(base, n.value.attr)
                        if elem:
                            env["<elem>" + name] = elem
                elif isinstance(n, (ast.For, ast.comprehension)):
                    tgt = n.target
                    it = n.iter
                    # unwrap enumerate(...) / list()/sorted()/reversed() /
                    # .items()/.values() wrappers, any nesting order;
                    # enumerate and .items() shift the element to the
                    # SECOND tuple target
                    second_of_tuple = False
                    for _unwrap in range(3):
                        if not isinstance(it, ast.Call):
                            break
                        fn = it.func
                        if isinstance(fn, ast.Name) and fn.id in (
                            "enumerate", "list", "sorted", "reversed"
                        ) and it.args:
                            if fn.id == "enumerate":
                                second_of_tuple = True
                            it = it.args[0]
                        elif isinstance(fn, ast.Attribute) and fn.attr in (
                            "items", "values"
                        ):
                            if fn.attr == "items":
                                second_of_tuple = True
                            it = fn.value
                        else:
                            break
                    elem = None
                    if isinstance(it, ast.Attribute):
                        base = self._expr_type(f, env, it.value)
                        elem = self._elem_type(base, it.attr)
                    elif isinstance(it, ast.Name):
                        elem = env.get("<elem>" + it.id)
                    if elem is None:
                        continue
                    if isinstance(tgt, ast.Name) and not second_of_tuple:
                        env[tgt.id] = elem
                    elif isinstance(tgt, ast.Tuple) and len(tgt.elts) == 2 and isinstance(
                        tgt.elts[1], ast.Name
                    ):
                        env[tgt.elts[1].id] = elem
        return env

    # ---- call graph + worker reachability --------------------------------

    def _build_env_and_calls(self):
        self._envs: Dict[int, Dict[str, str]] = {}
        # unique method names: a receiver of unknown type still resolves
        # when exactly one scope class defines the method
        by_name: Dict[str, List[Tuple[str, str]]] = {}
        for (cls, name), fns in self.methods.items():
            by_name.setdefault(name, []).append((cls, name))
        for f in self.funcs:
            env = self._build_env(f)
            self._envs[id(f)] = env
            for n in ast.walk(f.node):
                if not isinstance(n, ast.Call):
                    continue
                fn = n.func
                if isinstance(fn, ast.Name):
                    if (("", fn.id)) in self.methods:
                        f.calls.add(("", fn.id))
                    continue
                if not isinstance(fn, ast.Attribute):
                    continue
                m = fn.attr
                recv = fn.value
                if isinstance(recv, ast.Name) and recv.id == "self" and f.cls:
                    for c in self._hierarchy(f.cls):
                        if (c, m) in self.methods:
                            f.calls.add((c, m))
                    continue
                t = self._expr_type(f, env, recv)
                if t:
                    hit = False
                    for c in self._hierarchy(t):
                        if (c, m) in self.methods:
                            f.calls.add((c, m))
                            hit = True
                    if hit:
                        continue
                # unique-name fallback (never into a different module's
                # same-named module-level function)
                cands = [k for k in by_name.get(m, []) if k[0] != ""]
                if len(cands) == 1:
                    f.calls.add(cands[0])

    def _mark_worker_set(self):
        frontier: List[_Func] = []
        for cls, name in WORKER_ENTRIES:
            for f in self.methods.get((cls, name), []):
                f.worker = True
                frontier.append(f)
        while frontier:
            f = frontier.pop()
            for key in f.calls:
                for g in self.methods.get(key, []):
                    if not g.worker:
                        g.worker = True
                        frontier.append(g)

    # ---- lock regions ----------------------------------------------------

    def _lock_identity(self, f: _Func, env, ctx) -> Optional[Tuple[str, str]]:
        if isinstance(ctx, ast.Attribute) and re.search(r"lock", ctx.attr, re.I):
            owner = self._expr_type(f, env, ctx.value)
            return (owner or f"<module:{f.module}>", ctx.attr)
        if isinstance(ctx, ast.Name) and re.search(r"lock", ctx.id, re.I):
            return (f"<module:{f.module}>", ctx.id)
        return None

    def _lock_level(self, identity: Tuple[str, str], module: str) -> int:
        owner = identity[0]
        if owner in LOCK_LEVELS:
            return LOCK_LEVELS[owner]
        for suffix, level in MODULE_LOCK_LEVELS.items():
            if module.endswith(suffix):
                return level
        return 1

    def _lock_regions(self) -> List[_LockRegion]:
        out = []
        for f in self.funcs:
            env = self._envs[id(f)]
            for n in ast.walk(f.node):
                if not isinstance(n, ast.With):
                    continue
                for item in n.items:
                    ident = self._lock_identity(f, env, item.context_expr)
                    if ident is None:
                        continue
                    out.append(_LockRegion(
                        func=f, identity=ident,
                        level=self._lock_level(ident, f.module),
                        lineno=n.lineno,
                        end_lineno=getattr(n, "end_lineno", n.lineno),
                        node=n,
                    ))
        return out

    # ---- emission --------------------------------------------------------

    def _emit(self, f: _Func, node, rule, severity, message, key):
        line = getattr(node, "lineno", 0)
        mod: _Module = f._mod  # type: ignore[attr-defined]
        if mod.suppressed(line, rule, getattr(f.node, "lineno", None)):
            return
        self.findings.append(Finding(
            rule=rule, severity=severity,
            location=f"{f.module}:{line}", message=message, key=key,
        ))

    # ---- CONC601: shared-mutable-state census ----------------------------

    def _write_sites(self, f: _Func):
        """Yield (node, owner, attr) for attribute/container writes in f's
        own body (nested defs are their own functions)."""
        env = self._envs[id(f)]
        mod: _Module = f._mod  # type: ignore[attr-defined]
        declared_global: Set[str] = set()
        nested = set()
        for n in ast.walk(f.node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n is not f.node:
                for x in ast.walk(n):
                    nested.add(id(x))
                nested.discard(id(n))

        def owner_of(expr) -> Optional[str]:
            return self._expr_type(f, env, expr)

        def classify_target(t):
            if isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    yield from classify_target(e)
                return
            if isinstance(t, ast.Attribute):
                yield t, owner_of(t.value), t.attr
            elif isinstance(t, ast.Subscript):
                v = t.value
                if isinstance(v, ast.Attribute):
                    yield t, owner_of(v.value), v.attr
                elif isinstance(v, ast.Name):
                    if v.id in mod.module_globals:
                        yield t, "<module>", v.id
                    elif v.id in env or v.id in VAR_NAME_HINTS:
                        tname = env.get(v.id) or VAR_NAME_HINTS.get(v.id)
                        if tname in self.class_bases or tname in REPLICA_OWNED | ROUTER_OWNED | SHARED:
                            yield t, tname, "<subscript>"
                    # plain local container: thread-private, skip
            elif isinstance(t, ast.Name):
                if t.id in declared_global:
                    yield t, "<module>", t.id

        for n in ast.walk(f.node):
            if isinstance(n, ast.Global):
                declared_global.update(n.names)
        for n in ast.walk(f.node):
            if id(n) in nested:
                continue
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    if t is None:
                        continue
                    yield from classify_target(t)
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr not in MUTATORS:
                    continue
                recv = n.func.value
                # drill through dict.setdefault(...).append(...) chains
                if (
                    isinstance(recv, ast.Call)
                    and isinstance(recv.func, ast.Attribute)
                    and recv.func.attr in ("setdefault", "get")
                ):
                    recv = recv.func.value
                if isinstance(recv, ast.Attribute):
                    yield n, owner_of(recv.value), recv.attr
                elif isinstance(recv, ast.Name):
                    if recv.id in mod.module_globals:
                        yield n, "<module>", recv.id
                    # local container (rows.sort(...)): thread-private, skip

    def rule_census(self, regions: List[_LockRegion]):
        by_func_regions: Dict[int, List[_LockRegion]] = {}
        for r in regions:
            by_func_regions.setdefault(id(r.func), []).append(r)
        for f in self.funcs:
            f_regions = by_func_regions.get(id(f), [])
            for node, owner, attr in self._write_sites(f):
                line = getattr(node, "lineno", 0)
                locked = any(r.lineno <= line <= r.end_lineno for r in f_regions)
                cls = self._classify(f, owner, attr, locked)
                if cls is None:
                    self._emit(
                        f, node, "CONC601", SEV_ERROR,
                        f"unclassified shared write `{owner}.{attr}` in "
                        f"`{f.qual}`: "
                        + self._why_unclassified(f, owner)
                        + " — protect it with a lock, move it off the "
                        "worker path, or teach the analyzer its owner "
                        "(docs/STATIC_ANALYSIS.md \"Concurrency audit\")",
                        key=f"{f.module}::{owner}.{attr}::unclassified",
                    )
                else:
                    self._emit(
                        f, node, "CONC601", SEV_WARNING,
                        f"write census: `{owner}.{attr}` in `{f.qual}` "
                        f"[{cls}]",
                        key=f"{f.module}::{owner}.{attr}::{cls}",
                    )

    def _why_unclassified(self, f: _Func, owner) -> str:
        if owner is None:
            return ("the write target's owner cannot be resolved, so its "
                    "thread-confinement cannot be proven")
        if owner == "<module>":
            return ("module-global state mutated on a replica step thread "
                    "without a lock")
        if owner in SHARED:
            return ("state shared across replica threads written on a "
                    "worker-reachable path without a lock")
        if owner in ROUTER_OWNED:
            return ("router-thread-owned state written on a worker-reachable "
                    "path (the router thread owns placement/failover state)")
        return "ownership class is not in the analyzer's model"

    def _classify(self, f: _Func, owner, attr, locked: bool) -> Optional[str]:
        if locked:
            return "lock-protected"
        if owner is None:
            return None
        if owner == "<module>":
            return None if f.worker else "router-thread"
        init_confined = (
            f.name in ("__init__", "__post_init__")
            and f.cls
            and owner in self._hierarchy(f.cls)
        )
        if init_confined:
            return "init-confined"
        if owner in SHARED:
            return None if f.worker else "router-thread"
        if owner in ROUTER_OWNED:
            return None if f.worker else "router-thread"
        if owner in REPLICA_OWNED:
            return "replica-step-confined" if f.worker else "router-thread"
        return None

    # ---- CONC602: lock discipline ----------------------------------------

    def rule_lock_discipline(self, regions: List[_LockRegion]):
        # (a) explicit acquire()/release() anywhere
        for f in self.funcs:
            for n in ast.walk(f.node):
                if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)):
                    continue
                if n.func.attr in ("acquire", "release") and isinstance(
                    n.func.value, (ast.Attribute, ast.Name)
                ):
                    name = (
                        n.func.value.attr
                        if isinstance(n.func.value, ast.Attribute)
                        else n.func.value.id
                    )
                    if re.search(r"lock", name, re.I):
                        self._emit(
                            f, n, "CONC602", SEV_ERROR,
                            f"`{name}.{n.func.attr}()` in `{f.qual}` — locks "
                            f"are acquired only via `with` (an exception "
                            f"between acquire and release leaks the lock "
                            f"and wedges every replica thread)",
                            key=f"{f.module}::acquire-release",
                        )
        # (b) ordering + re-entry + (c) blocking under the router lock,
        # over the traced call graph
        for r in regions:
            reach = self._reachable_from_region(r)
            # direct nested with-regions in the same function
            inner = [
                r2 for r2 in regions
                if r2 is not r and r2.func is r.func
                and r.lineno <= r2.lineno <= r.end_lineno
            ]
            inner += [r2 for r2 in regions if id(r2.func) in reach and r2.func is not r.func]
            for r2 in inner:
                if r2.identity == r.identity:
                    if self.lock_kinds.get(r.identity, "lock") != "rlock":
                        self._emit(
                            r.func, r.node, "CONC602", SEV_ERROR,
                            f"re-entrant acquisition of non-reentrant lock "
                            f"`{r.identity[0]}.{r.identity[1]}` (held at "
                            f"{r.func.qual}:{r.lineno}, re-acquired at "
                            f"{r2.func.qual}:{r2.lineno}) — deadlock; use "
                            f"threading.RLock or restructure",
                            key=f"{r.func.module}::lock-reentry",
                        )
                elif r2.level <= r.level:
                    self._emit(
                        r.func, r.node, "CONC602", SEV_ERROR,
                        f"lock-order violation: holding level-{r.level} "
                        f"`{r.identity[0]}.{r.identity[1]}` "
                        f"({r.func.qual}:{r.lineno}) can acquire "
                        f"level-{r2.level} `{r2.identity[0]}.{r2.identity[1]}` "
                        f"({r2.func.qual}:{r2.lineno}) — the global order is "
                        f"router(0) -> replica(1) -> telemetry session(2) -> "
                        f"registry(3) -> family(4) -> instrument(5), "
                        f"strictly increasing (cycle risk)",
                        key=f"{r.func.module}::lock-order",
                    )
            if r.level == 0:
                self._check_blocking(r, reach)

    def _reachable_from_region(self, r: _LockRegion) -> Set[int]:
        """ids of functions transitively callable from inside the region."""
        start: Set[Tuple[str, str]] = set()
        for n in ast.walk(r.node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            f = r.func
            env = self._envs[id(f)]
            if isinstance(fn, ast.Name) and ("", fn.id) in self.methods:
                start.add(("", fn.id))
            elif isinstance(fn, ast.Attribute):
                recv = fn.value
                if isinstance(recv, ast.Name) and recv.id == "self" and f.cls:
                    for c in self._hierarchy(f.cls):
                        if (c, fn.attr) in self.methods:
                            start.add((c, fn.attr))
                else:
                    t = self._expr_type(f, env, recv)
                    if t:
                        for c in self._hierarchy(t):
                            if (c, fn.attr) in self.methods:
                                start.add((c, fn.attr))
        seen: Set[int] = set()
        frontier: List[_Func] = []
        for key in start:
            for g in self.methods.get(key, []):
                if id(g) not in seen:
                    seen.add(id(g))
                    frontier.append(g)
        while frontier:
            g = frontier.pop()
            for key in g.calls:
                for h in self.methods.get(key, []):
                    if id(h) not in seen:
                        seen.add(id(h))
                        frontier.append(h)
        return seen

    def _check_blocking(self, r: _LockRegion, reach: Set[int]):
        funcs = [f for f in self.funcs if id(f) in reach]
        scopes = [(r.func, r.node)] + [(g, g.node) for g in funcs]
        for g, scope in scopes:
            for n in ast.walk(scope):
                if not isinstance(n, ast.Call):
                    continue
                fn = n.func
                name = None
                if isinstance(fn, ast.Attribute) and fn.attr in BLOCKING_ATTRS:
                    name = fn.attr
                elif isinstance(fn, ast.Name) and fn.id in BLOCKING_NAMES:
                    name = fn.id
                if not name:
                    continue
                self._emit(
                    g, n, "CONC602", SEV_ERROR,
                    f"blocking call `{name}(...)` reachable while holding "
                    f"router-level lock `{r.identity[0]}.{r.identity[1]}` "
                    f"(acquired {r.func.qual}:{r.lineno}) — a block under "
                    f"the router lock stalls every replica; fetch/sleep/IO "
                    f"outside it",
                    key=f"{r.func.module}::blocking-under-router-lock",
                )

    # ---- CONC603: telemetry atomicity ------------------------------------

    def rule_instrument_atomicity(self, regions: List[_LockRegion]):
        by_func_regions: Dict[int, List[_LockRegion]] = {}
        for r in regions:
            by_func_regions.setdefault(id(r.func), []).append(r)
        for f in self.funcs:
            in_metrics = f.module.endswith("telemetry/metrics.py")
            inside_instrument = in_metrics and f.cls in INSTRUMENT_CLASSES
            f_regions = by_func_regions.get(id(f), [])
            for n in ast.walk(f.node):
                if not isinstance(n, (ast.Assign, ast.AugAssign)):
                    continue
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    hit = None
                    if isinstance(t, ast.Attribute) and t.attr in INSTRUMENT_INTERNALS:
                        hit = t.attr
                    elif isinstance(t, ast.Subscript) and isinstance(
                        t.value, ast.Attribute
                    ) and t.value.attr in INSTRUMENT_BUCKETS:
                        hit = t.value.attr
                    if hit is None:
                        continue
                    line = getattr(n, "lineno", 0)
                    locked = any(
                        r.lineno <= line <= r.end_lineno for r in f_regions
                    )
                    if inside_instrument and (locked or f.name == "__init__"):
                        continue  # the atomic mutator itself
                    self._emit(
                        f, n, "CONC603", SEV_ERROR,
                        f"read-modify-write on instrument internal "
                        f"`.{hit}` in `{f.qual}` — metric mutations must go "
                        f"through the registry's atomic inc()/set()/"
                        f"observe() (a bare `+=` from a replica thread "
                        f"loses updates; the GIL does not make it atomic)",
                        key=f"{f.module}::instrument-internals",
                    )

    # ---- CONC604: router -> session touch census -------------------------

    def rule_session_touches(self):
        for f in self.funcs:
            if f.cls != "ServingRouter" or not f.module.endswith(
                "runtime/router.py"
            ):
                continue
            parents: Dict[int, ast.AST] = {}
            for n in ast.walk(f.node):
                for child in ast.iter_child_nodes(n):
                    parents[id(child)] = n
            for n in ast.walk(f.node):
                if not (isinstance(n, ast.Attribute) and n.attr == "session"):
                    continue
                p = parents.get(id(n))
                touched = None
                if isinstance(p, ast.Attribute) and p.value is n:
                    touched = p.attr
                if touched is None:
                    self._emit(
                        f, n, "CONC604", SEV_WARNING,
                        f"router touch census: bare `session` reference in "
                        f"`{f.qual}`",
                        key=f"{f.module}::session.<bare>",
                    )
                    continue
                if touched in DEVICE_STATE_ATTRS:
                    self._emit(
                        f, n, "CONC604", SEV_ERROR,
                        f"ServingRouter.{f.name} touches replica device "
                        f"state `session.{touched}` — the router may only "
                        f"read committed host-side snapshots; device state "
                        f"belongs to the replica's confinement set "
                        f"(session + handle + worker)",
                        key=f"{f.module}::session.{touched}::device-state",
                    )
                    continue
                if touched == "app":
                    gp = parents.get(id(p))
                    sub = gp.attr if (
                        isinstance(gp, ast.Attribute) and gp.value is p
                    ) else None
                    if sub != "config":
                        self._emit(
                            f, n, "CONC604", SEV_ERROR,
                            f"ServingRouter.{f.name} reaches "
                            f"`session.app.{sub or '<bare>'}` — only the "
                            f"frozen `session.app.config` read is a "
                            f"host-side snapshot; everything else on the "
                            f"app is replica device state",
                            key=f"{f.module}::session.app::device-state",
                        )
                        continue
                    touched = "app.config"
                self._emit(
                    f, n, "CONC604", SEV_WARNING,
                    f"router touch census: `session.{touched}` read in "
                    f"`{f.qual}` (host-side snapshot allowlist; a new "
                    f"entry here is reviewed like a new collective)",
                    key=f"{f.module}::session.{touched}",
                )

    # ---- driver ----------------------------------------------------------

    def run(self) -> List[Finding]:
        regions = self._lock_regions()
        self.rule_census(regions)
        self.rule_lock_discipline(regions)
        self.rule_instrument_atomicity(regions)
        self.rule_session_touches()
        self.findings.sort(key=lambda f: (f.rule, f.key, f.location))
        return self.findings


# ---------------------------------------------------------------------------
# entry points (mirrors graph/shard/memory audit shape)
# ---------------------------------------------------------------------------


def _scope_files(root: Optional[pathlib.Path] = None) -> List[Tuple[pathlib.Path, str]]:
    if root is None:
        root = pathlib.Path(__file__).resolve().parents[2]
    pkg = root / PACKAGE
    out = []
    for suffix in SCOPE_SUFFIXES:
        p = pkg / suffix
        if p.is_file():
            out.append((p, suffix))
    return out


def _match_scope(path: pathlib.Path) -> Optional[str]:
    s = str(path)
    for suffix in SCOPE_SUFFIXES:
        if s.endswith(suffix):
            return suffix
    # fixture fallback: match by basename so tmp-dir snippets audit as the
    # file they stand in for
    for suffix in SCOPE_SUFFIXES:
        if path.name == pathlib.Path(suffix).name:
            return suffix
    return None


def audit_paths(paths: List[pathlib.Path]) -> List[Finding]:
    """Audit arbitrary snippet files (test fixtures): each file is scoped by
    suffix/basename match against :data:`SCOPE_SUFFIXES` and the RAW
    findings (census entries included, no baseline filtering) come back."""
    files = []
    for p in paths:
        rel = _match_scope(p)
        if rel is None:
            raise ValueError(
                f"{p}: not a recognizable scope file (expected one of "
                f"{SCOPE_SUFFIXES} by suffix or basename)"
            )
        files.append((p, rel))
    return _Analyzer(files).run()


def _build_report(findings: List[Finding]) -> Dict:
    classifications: Dict[str, int] = {}
    census: Dict[str, int] = {}
    touches: Dict[str, int] = {}
    errors = 0
    for f in findings:
        if f.severity == SEV_ERROR:
            errors += 1
            continue
        if f.rule == "CONC601":
            cls = f.key.rsplit("::", 1)[-1]
            classifications[cls] = classifications.get(cls, 0) + 1
            census[f.key] = census.get(f.key, 0) + 1
        elif f.rule == "CONC604":
            touches[f.key] = touches.get(f.key, 0) + 1
    return {
        "write_sites": sum(classifications.values()),
        "classifications": dict(sorted(classifications.items())),
        "errors": errors,
        "census": dict(sorted(census.items())),
        "session_touches": dict(sorted(touches.items())),
        "worker_entries": [f"{c}.{m}" for c, m in WORKER_ENTRIES],
    }


def last_report() -> Dict:
    return _LAST_REPORT


def render_breakdown(report: Optional[Dict] = None) -> str:
    rep = report if report is not None else _LAST_REPORT
    if not rep:
        return ""
    lines = [
        "concurrency write-site census "
        f"({rep['write_sites']} classified sites; worker entries: "
        f"{', '.join(rep['worker_entries'])}):"
    ]
    for cls, n in rep["classifications"].items():
        lines.append(f"  {cls:>22}: {n}")
    if rep["session_touches"]:
        lines.append(
            "router->session host-snapshot touches: "
            + ", ".join(
                k.split("::", 1)[1] for k in rep["session_touches"]
            )
        )
    return "\n".join(lines)


def run(write_baseline: bool = False) -> List[Finding]:
    """Audit the real tree against ``conc_baseline.json``; returns the NEW
    (gate-failing) findings. Errors (unclassified/shared/ordering/device-
    state findings) are never baselined — only the classified census and
    the router->session touch allowlist are."""
    global _LAST_REPORT
    findings = _Analyzer(_scope_files()).run()
    _LAST_REPORT = _build_report(findings)
    warnings = [f for f in findings if f.severity == SEV_WARNING]
    errors = [f for f in findings if f.severity == SEV_ERROR]
    if write_baseline:
        Baseline.from_findings(warnings).save(BASELINE_PATH)
        return errors
    return Baseline.load(BASELINE_PATH).filter_new(warnings) + errors
