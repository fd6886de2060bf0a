#!/usr/bin/env python3
"""Read, on the chip and at a configuration's own size, the two numbers
``correct.K`` is set from: err / floor of the program over many seeds, and
err / floor of the CONTROL — the reference itself computed in fp8-e4m3, the
nearest precision below bf16, put in the program's place.

    python3 benchmark/selftest/read_ratio.py --config qwen3-1p7b --chips 1 \
        --max-prompt 256,6144 --seeds 20 --first-seed 2600000001 --control 3 --out <file.jsonl>

One process, one set of compiled programs: per seed the weights are made
anew, the probe of ``correct.serve_probe`` is served at the kv width the
given longest prompt ends in (the widths given are taken in turn, seed after
seed) and ``correct.judge`` reads the rows (of a reference that plans passes:
its passes; every row then carries the margins too, the reveal's last). For the first ``--control``
seeds the same prompts and tokens go through the reference at
``rounding=float8_e4m3fn`` and ``judge`` is asked again with those logits as
the served ones. One JSON line per seed; the last line sums up. Exits 1 if
the program failed the rule on any seed or the control passed it on any."""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a file name under benchmark/configs, without .json")
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--max-prompt", required=True, help="longest prompts of the cells, comma-separated")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="the configuration's tiny preset on the CPU: tries this script, reads nothing")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from benchmark.harness import catalog, correct, device, system

    with open(os.path.join(catalog.BENCH_DIR, "configs", args.config + ".json")) as f:
        cfg = system.resolve_config(json.load(f), bool(args.rehearsal))
    try:
        devices, _, info = device.find_chips(args.chips, rehearsal=bool(args.rehearsal))
    except device.DeviceError as e:
        print(f"read_ratio: {e}", file=sys.stderr)
        return 2
    system.configure_cache()
    degree = cfg["tpu_config"].get("tp_degree", 1)
    widths = [int(w) for w in args.max_prompt.split(",")]
    reference = correct.load_reference(cfg)
    geo = reference.geometry(system.model_attrs(cfg), degree)
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        out = open(args.out, "w")
    program, control, bad = [], [], 0

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    for k in range(args.seeds):
        seed, max_prompt = args.first_seed + 2 * k, widths[k % len(widths)]
        t0 = time.perf_counter()
        app = system.build_app(cfg, devices, seed)
        params, pspecs = system.make_weights(app, seed, cfg.get("weights"))
        t1 = time.perf_counter()
        prompts, chosen, served, choices, plans = correct.serve_probe(cfg, devices, seed, params, pspecs, max_prompt)
        t2 = time.perf_counter()
        try:
            facts, ok = correct.judge(cfg, params, degree, prompts, chosen, served, choices, plans), True
        except correct.CorrectnessError as e:
            facts, ok = {"error": str(e), **e.facts}, False
        t3 = time.perf_counter()
        bad += not ok
        program += [r["ratio"] for r in facts["rows"]]
        line = dict(seed=seed, max_prompt=max_prompt, ok=ok, weights_s=t1 - t0, probe_s=t2 - t1,
                    judge_s=t3 - t2, rows=facts["rows"], error=facts.get("error"))
        if k < args.control:
            # a reference that replays follows the served routes at fp8 too
            follow = lambda r: {"choices": choices[r]} if getattr(reference, "CHOICES", False) else {}
            fp8 = [reference.reference_logits(params, geo, *correct.reference_args(p, chosen[r], plans and plans[r]),
                                              rounding=jnp.float8_e4m3fn, **follow(r))
                   for r, p in enumerate(prompts)]
            try:
                facts8, passed = correct.judge(cfg, params, degree, prompts, chosen, fp8, choices, plans), True
            except correct.CorrectnessError as e:
                facts8, passed = e.facts, False
            bad += passed
            control += [r["ratio"] for r in facts8["rows"]]
            line.update(control_passed=passed, control_rows=facts8["rows"])
        say(**line)
        del app, params, served
        gc.collect()
    say(summary=True, config=args.config, device=info, K=correct.K, seeds=args.seeds, readings=len(program),
        program_ratio_max=max(program), program_ratio_min=min(program),
        control_ratio_min=min(control) if control else None,
        control_ratio_max=max(control) if control else None, bad=bad)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
