"""``run.py`` end to end on the CPU at each configuration's tiny
``rehearsal`` preset: every cell, the four-chip cell on four virtual
devices. The last line names the device ``cpu`` and carries counts only;
without ``--rehearsal`` a CPU is refused and no result line is printed."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import catalog

RUN = os.path.join(catalog.BENCH_DIR, "run.py")


def run(cell, *extra, devices=1, rehearsal=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable, RUN, "--workload", cell, "--seed", "4000000123", "--seconds", "3",
           "--rehearsal", str(rehearsal), *extra]
    return subprocess.run(cmd, env=env, cwd=catalog.REPO_DIR, capture_output=True, text=True,
                          timeout=900)


def cells():
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        return [(w["name"], w["chips"]) for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell,chips", cells())
def test_rehearsal_of_every_cell(cell, chips):
    p = run(cell, "--trace", "0", devices=chips)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "host", "compared"]
    assert last["host"]["steps"]["count"] > 0 and last["host"]["wall_s"] > 0  # harness/hostfacts.py
    assert all(number <= limit for number, limit in last["compared"].values())
    shown = [l.split()[1].rstrip(":") for l in p.stderr.splitlines()[-len(last["compared"]):]]
    assert shown == list(last["compared"])  # the same, beside their limits, end standard error
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == chips
    assert all(m["unit"] == "count" for m in last["metrics"].values())
    window = [json.loads(l) for l in p.stdout.splitlines() if l.startswith('{"phase": "window"')][0]
    assert window["compiled_in_window"] == 0
    assert window["tokens_counted"] == window["tokens_stamped"]


def test_traced_rehearsal_starts_and_stops_the_profiler():
    p = run(cells()[0][0], "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is True


def test_a_cpu_is_refused_outside_rehearsal():
    p = run(cells()[0][0], "--trace", "0", rehearsal=0)
    assert p.returncode != 0
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())
