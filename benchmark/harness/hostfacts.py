"""What the HOST did while the window ran: facts, never metrics.

A one-chip machine shares its host's CPU cores, and the serving loop's host
work lies on a step's critical path, so a rate read on the host's clock
falls when a neighbour takes the cores (PR 34's refusal round: one run of
``granite-4.0-h-micro.decode`` read 1.3% under its set with the device's own
step time unchanged). These facts say, for ONE run, whether the host was
held up and by what: they go on the run's ``window`` line and under the key
``host`` of its last line, which the driver ignores.

Taken at the two ends of ``LoadDriver.run()`` from the kernel's own
counters (``/proc/stat``, ``/proc/pressure/cpu``, the cgroup's ``cpu.stat``,
``getrusage``; one that a sandbox does not show is left out) and, inside
it, only from the garbage collector's callbacks (two clock reads per
collection). A ``--trace 0`` and a ``--trace 2`` run do the same.
"""

from __future__ import annotations

import gc
import os
import resource
import time
from typing import Dict, List, Optional, Sequence, Tuple

#: the /proc/stat fields of the ``cpu`` line, in order
_STAT = ("user", "nice", "system", "idle", "iowait", "irq", "softirq", "steal")


def _proc_stat() -> Optional[Dict[str, int]]:
    try:
        with open("/proc/stat") as f:
            parts = f.readline().split()
    except OSError:
        return None
    if not parts or parts[0] != "cpu":
        return None
    return {k: int(v) for k, v in zip(_STAT, parts[1:])}


def _pressure_us() -> Optional[int]:
    """Microseconds, so far, in which some runnable task of the machine
    waited for a core (``some total=`` of /proc/pressure/cpu)."""
    try:
        with open("/proc/pressure/cpu") as f:
            for line in f:
                if line.startswith("some"):
                    return int(line.rsplit("total=", 1)[1])
    except (OSError, ValueError, IndexError):
        return None
    return None


def _throttled() -> Optional[Dict[str, int]]:
    """The container's CPU quota at work: periods in which its threads were
    stopped for having used the quota up, and the microseconds they stood
    (cgroup v2 ``cpu.stat``, else v1's)."""
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            with open(path) as f:
                stat = dict(line.split()[:2] for line in f if len(line.split()) >= 2)
        except OSError:
            continue
        if "nr_throttled" in stat:
            us = stat.get("throttled_usec") or int(stat.get("throttled_time", 0)) // 1000
            return {"periods": int(stat["nr_throttled"]), "us": int(us)}
    return None


class HostWatch:
    """``start()`` before the window opens, ``stop()`` when the driver's
    ``run()`` has returned; ``stop()`` gives the facts."""

    def __init__(self):
        self._gc_t0 = 0.0
        #: per generation: collections, seconds in them, the longest
        self.gc = [[0, 0.0, 0.0] for _ in range(3)]
        self._at: Dict[str, object] = {}

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._gc_t0
        row = self.gc[info["generation"]]
        row[0] += 1
        row[1] += took
        row[2] = max(row[2], took)

    def _snapshot(self) -> Dict[str, object]:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {"wall": time.perf_counter(), "cpu": time.process_time(),
                "stat": _proc_stat(), "pressure_us": _pressure_us(), "throttled": _throttled(),
                "switched": ru.ru_nvcsw, "preempted": ru.ru_nivcsw}

    def start(self):
        self._at = self._snapshot()
        gc.callbacks.append(self._on_gc)

    def stop(self) -> Dict[str, object]:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        a, b = self._at, self._snapshot()
        wall = b["wall"] - a["wall"]
        out: Dict[str, object] = {
            "wall_s": wall,
            "cores": os.cpu_count(),
            #: CPU seconds of this process (every thread) per second of wall
            "process_cores_busy": (b["cpu"] - a["cpu"]) / wall if wall > 0 else None,
            #: times a thread of this process gave its core up / had it taken
            "switched": b["switched"] - a["switched"],
            "preempted": b["preempted"] - a["preempted"],
            "gc": {f"gen{g}": {"collections": n, "seconds": s, "longest_s": m}
                   for g, (n, s, m) in enumerate(self.gc)},
        }
        if a["stat"] is not None and b["stat"] is not None:
            d = {k: b["stat"][k] - a["stat"][k] for k in b["stat"]}
            total = sum(d.values())
            if total > 0:
                busy = total - d.get("idle", 0) - d.get("iowait", 0)
                #: of the machine's cores together: the share that ran
                #: anything (this process included), and the share the
                #: hypervisor gave to someone else while a task here waited
                out["machine_busy_share"] = busy / total
                out["machine_steal_share"] = d.get("steal", 0) / total
        if a["pressure_us"] is not None and b["pressure_us"] is not None:
            out["cpu_pressure_s"] = (b["pressure_us"] - a["pressure_us"]) / 1e6
        if a["throttled"] is not None and b["throttled"] is not None:
            out["quota_throttled"] = {"periods": b["throttled"]["periods"] - a["throttled"]["periods"],
                                      "seconds": (b["throttled"]["us"] - a["throttled"]["us"]) / 1e6}
        return out


#: upper edges, in medians, of the classes ``step_facts`` sorts steps into
STEP_CLASSES = (1.25, 2.0, 3.0, 4.0)


def step_facts(spans: Sequence[Tuple[str, float, float]], window_s: float,
               slowest: int = 5) -> Dict[str, object]:
    """The window's ``step`` spans by their length: quantiles, the slowest
    few with the time they began, and ``by_median``: per class of length in
    medians (up to 1.25, 2, 3, 4, beyond) the steps in it and their seconds.
    A decode cell's steps are of two kinds, plain ones (the median) and those
    that carry a prompt's chunk pass (3 to 4 medians); a stalled plain step
    falls between them, where a quiet run has next to none, and a stalled
    chunk step beyond them."""
    steps: List[Tuple[float, float]] = [
        (t1 - t0, t0) for name, t0, t1 in spans if name == "step" and 0.0 <= t0 <= window_s]
    if not steps:
        return {"count": 0}
    d = sorted(x for x, _ in steps)
    q = lambda p: d[min(len(d) - 1, int(p * (len(d) - 1) + 0.5))] * 1e3
    median = d[len(d) // 2]
    classes = [[0, 0.0] for _ in range(len(STEP_CLASSES) + 1)]
    for x in d:
        row = classes[sum(x > edge * median for edge in STEP_CLASSES)]
        row[0] += 1
        row[1] += x
    names = [f"to_{e:g}" for e in STEP_CLASSES] + [f"over_{STEP_CLASSES[-1]:g}"]
    return {
        "count": len(d),
        "ms": {"p50": q(0.5), "p90": q(0.9), "p99": q(0.99), "max": d[-1] * 1e3},
        "by_median": {n: {"steps": c, "seconds": t} for n, (c, t) in zip(names, classes)},
        "slowest": [[round(t0, 3), x * 1e3] for x, t0 in sorted(steps, reverse=True)[:slowest]],
    }
