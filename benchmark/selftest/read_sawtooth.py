#!/usr/bin/env python3
"""One run of a cell, summarised as if its window had closed at each of its
last step ends (PERF.md section 6, PR 26's method): how far ``out_tok_s``
moves from one step end to the next.

    python3 benchmark/selftest/read_sawtooth.py --workload granite-4.0-h-micro.decode \
        --seed 2800000201 --seconds 52 --last 2.5 --out <file.json>

``run.py`` is run as it is (same process, ``--trace 0``); this script only
listens to ``stats.summarize`` for the window's records. A closed loop at
full occupancy commits tokens at step ends only, so the rate over [0, T] for
every step end T of the last ``--last`` seconds is what the run would have
reported had the clock cut it there. Prints one JSON line after ``run.py``'s
own: the step ends, the rates, the largest move between neighbours and the
largest across a step longer than five times the median step."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=52.0)
    ap.add_argument("--last", type=float, default=2.5)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmark import run
    from benchmark.harness import stats

    held = {}
    summarize = stats.summarize

    def listen(records, window_s, *a, **kw):
        held.setdefault("records", list(records))
        held.setdefault("window_s", window_s)
        return summarize(records, window_s, *a, **kw)

    stats.summarize = listen
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed), "--seconds",
                   str(args.seconds), "--trace", "0", "--rehearsal", str(args.rehearsal)])
    if rc or "records" not in held:
        return rc or 3
    commits = sorted((t, n) for r in held["records"] for t, n in r.commits if t > 0.0)
    ends = sorted({t for t, _ in commits if t <= held["window_s"]})
    total, k, rates = 0, 0, []
    for end in ends:
        while k < len(commits) and commits[k][0] <= end:
            total += commits[k][1]
            k += 1
        rates.append(total / end)
    first = next(i for i, t in enumerate(ends) if t >= held["window_s"] - args.last)
    steps = [b - a for a, b in zip(ends, ends[1:])]
    median = sorted(steps)[len(steps) // 2]
    moves = [(ends[i], (ends[i] - ends[i - 1]) * 1e3, 100.0 * (rates[i] / rates[i - 1] - 1.0))
             for i in range(max(first, 1), len(ends))]
    line = {"sawtooth": args.workload, "seed": args.seed, "window_s": held["window_s"],
            "step_ends": len(ends), "median_step_ms": median * 1e3,
            "last": [{"end_s": e, "step_ms": ms, "out_tok_s": rates[ends.index(e)], "move_pct": mv}
                     for e, ms, mv in moves],
            "largest_move_pct": max((abs(mv) for _, _, mv in moves), default=None),
            "largest_move_over_a_long_step_pct": max(
                (abs(mv) for _, ms, mv in moves if ms > 5e3 * median), default=None),
            "long_steps_ms": sorted({round(s * 1e3, 1) for s in steps if s > 5 * median})[-12:],
            "largest_move_anywhere_after_10s_pct": max(
                (abs(100.0 * (rates[i] / rates[i - 1] - 1.0)) for i in range(1, len(ends)) if ends[i] > 10.0),
                default=None)}
    print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(line, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
