#!/usr/bin/env python3
"""Read, on the chip and at a configuration's own size, how ``correct`` reads
named FAULTS of the model: the reference itself, with one part of its
equations wrong (``fault=``, one of the reference module's ``FAULTS``) and
rounded as the bf16 twin is, put in the program's place.

    python3 benchmark/selftest/read_faults.py --config mellum2-12b-a2.5b --chips 1 \
        --max-prompt 15360 --faults window_ignored,default_rope_in_full --seeds 1 \
        --first-seed 2600000001 --out <file.jsonl>

``read_ratio.py`` reads the program and the fp8 control; this reads what a
rule of one number cannot be set without: that the parts of THIS model the
configuration was chosen for (a window, a second rotary table) reach the
compared logits at the cell's own context. Per seed the weights are made
anew, the probe of ``correct.serve_probe`` is served (its tokens and, for a
reference that replays, its choices are what every control follows) and per
fault ``correct.judge`` is asked with the faulty logits as the served ones.
One JSON line per (seed, fault); the last line sums up. Exits 1 if a fault
passed the rule on any seed."""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a file name under benchmark/configs, without .json")
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--max-prompt", type=int, required=True, help="longest prompt of the cell")
    ap.add_argument("--faults", required=True, help="comma-separated, of the reference module's FAULTS")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, required=True)
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
        print(f"read_faults: {e}", file=sys.stderr)
        return 2
    system.configure_cache()
    degree = cfg["tpu_config"].get("tp_degree", 1)
    reference = correct.load_reference(cfg)
    faults = args.faults.split(",")
    unknown = set(faults) - set(getattr(reference, "FAULTS", ()))
    if unknown:
        print(f"read_faults: {sorted(unknown)} are no FAULTS of {reference.__name__}", file=sys.stderr)
        return 2
    geo = reference.geometry(system.model_attrs(cfg), degree)
    out = open(args.out, "w") if args.out and not os.makedirs(
        os.path.dirname(os.path.abspath(args.out)), exist_ok=True) else None
    passed, least = 0, {}

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    for k in range(args.seeds):
        seed = args.first_seed + 2 * k
        app = system.build_app(cfg, devices, seed)
        params, pspecs = system.make_weights(app, seed, cfg.get("weights"))
        prompts, chosen, served, choices, plans = correct.serve_probe(
            cfg, devices, seed, params, pspecs, args.max_prompt)
        follow = lambda r: {"choices": choices[r]} if getattr(reference, "CHOICES", False) else {}
        for fault in faults:
            faulty = [reference.reference_logits(
                params, geo, *correct.reference_args(p, chosen[r], plans and plans[r]),
                rounding=jnp.bfloat16, fault=fault, **follow(r)) for r, p in enumerate(prompts)]
            try:
                facts, ok = correct.judge(cfg, params, degree, prompts, chosen, faulty, choices, plans), True
            except correct.CorrectnessError as e:
                facts, ok = e.facts, False
            passed += ok
            ratios = [r["ratio"] for r in facts["rows"]]
            least[fault] = min([least.get(fault, float("inf"))] + ratios[:1])
            say(seed=seed, fault=fault, passed=ok, prompts=[len(p) for p in prompts], ratios=ratios)
        del app, params, served
        gc.collect()
    say(summary=True, config=args.config, device=info, K=correct.K, seeds=args.seeds,
        least_ratio_at_the_long_prompt=least, passed=passed)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
