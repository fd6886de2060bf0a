#!/usr/bin/env python3
"""The chunk-to-chunk carry of a state-space configuration, held to account
on the chip at the configuration's own width.

    python3 benchmark/selftest/read_slow_decay.py --config granite-4.0-h-micro --chips 1 \
        --seed 2800000301 --prompt 1024 --out <file.json>

``system.make_weights`` draws every leaf N(0, 0.02): ``A_log`` ~ 0 and
``dt_bias`` ~ 0 give every head a decay of ~0.5 a token, under which a lost
carry between two prefill chunks has died out long before the probed
position — the cell's own ``correct`` probe cannot see one. Here the weights
are made as the cell makes them and then ``A_log``, ``dt_bias`` and the
depthwise conv weight are overwritten with the PUBLISHED initialisation
(``A_log = log(1..heads)``; dt log-uniform in 1e-3..1e-1 through the inverse
softplus; the conv taps uniform in +-1/sqrt(d_conv), ``nn.Conv1d``'s own):
slow-decay heads, and x, B, C of a size at which what the state holds, not
the ``D x`` skip, is most of a layer's output (with N(0, 0.02) taps the
skip is 40 times the state's share and a lost carry moves nothing). A
prompt of ``--prompt`` tokens goes through the probe application in chunks of
the session's chunk size, then four decode steps, teacher-forced
(``correct._forced_logits``), and the logits at the last prompt position and
after each forced token are judged by ``correct.judge``'s own rule against
the float32 reference and its bf16 twin. With ``--zero-carry 1`` the probe's
recurrent state is zeroed before the LAST chunk: the control, which the rule
must fail. One JSON line; exits 1 if the sound pass fails the rule or the
control passes it."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def published_init(params, seed: int):
    """The same tree with the recurrence's published initialisation."""
    import jax.numpy as jnp
    import numpy as np

    mixer = dict(params["layers"]["mamba"]["mixer"])
    layers, heads = mixer["A_log"].shape
    dt = np.exp(np.random.default_rng([int(seed), 11]).uniform(np.log(1e-3), np.log(1e-1), (layers, heads)))
    like = lambda a, ref: jnp.asarray(a, ref.dtype)
    mixer["A_log"] = like(np.tile(np.log(np.arange(1, heads + 1.0)), (layers, 1)), mixer["A_log"])
    mixer["dt_bias"] = like(dt + np.log(-np.expm1(-dt)), mixer["dt_bias"])
    w = mixer["conv1d"]["weight"]  # (layers, taps, channels)
    bound = w.shape[1] ** -0.5
    taps = np.random.default_rng([int(seed), 12]).uniform(-bound, bound, w.shape)
    mixer["conv1d"] = dict(mixer["conv1d"], weight=like(taps, w))
    out = dict(params)
    out["layers"] = dict(params["layers"])
    out["layers"]["mamba"] = dict(params["layers"]["mamba"], mixer=mixer)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--zero-carry", type=int, choices=(0, 1), default=1,
                    help="also run the control: the state zeroed before the last chunk")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark.harness import catalog, correct, device, system

    with open(os.path.join(catalog.BENCH_DIR, "configs", args.config + ".json")) as f:
        cfg = system.resolve_config(json.load(f), bool(args.rehearsal))
    try:
        devices, _, info = device.find_chips(args.chips, rehearsal=bool(args.rehearsal))
    except device.DeviceError as e:
        print(f"read_slow_decay: {e}", file=sys.stderr)
        return 2
    system.configure_cache()
    vocab = system.model_attrs(cfg)["vocab_size"]
    n = min(args.prompt, cfg["tpu_config"]["seq_len"] - correct.PROBE_DECODE_STEPS - 2)
    app = system.build_app(cfg, devices, args.seed)
    params, pspecs = system.make_weights(app, args.seed)
    params = published_init(params, args.seed)
    over = correct.probe_overrides(cfg, n)
    probe = system.build_app(cfg, devices, args.seed, tpu_overrides=over["tpu"],
                             chunked_overrides=over["chunked"])
    rng = np.random.default_rng([int(args.seed), 7])
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)]
    chosen = [[int(t) for t in rng.integers(0, vocab, size=correct.PROBE_DECODE_STEPS + 1)]]
    width = correct.probe_width(cfg, n)
    line = {"config": args.config, "device": info, "seed": args.seed, "prompt": n, "kv_width": width,
            "chunk": probe.config.tpu_config.chunked_prefill_config.kernel_q_tile_size}

    def judged(served):
        try:
            return True, correct.judge(cfg, params, 1, prompts, chosen, served)["rows"][0]
        except correct.CorrectnessError as e:
            return False, e.facts["rows"][0]

    system.give_weights(probe, params, pspecs)
    sound = correct._forced_logits(probe, prompts, chosen, width)
    ok, row = judged(sound)
    line["sound"] = {"passes": ok, **{k: row[k] for k in ("err", "floor", "scale", "ratio", "rms_ratio")}}
    bad = not ok
    if args.zero_carry:
        from neuronx_distributed_inference_tpu.runtime.faults import fill_slot_state

        chunk = line["chunk"]
        head = (n - 1) // chunk * chunk  # tokens before the last chunk
        probe.init_kv_cache()
        # all chunks but the last, as _forced_logits runs them; then the state is lost
        _prefill(probe, prompts[0][:head], 0, width)
        probe.kv_cache = fill_slot_state(probe.kv_cache, [0], 0.0)
        served = _rest(probe, prompts[0], head, chosen[0], width)
        ok, row = judged([served])
        line["carry_zeroed"] = {"passes": ok, **{k: row[k] for k in ("err", "floor", "ratio")},
                                "moved_by": float(np.abs(served - sound[0]).max())}
        bad = bad or ok
    print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(line, f, indent=1)
    return 1 if bad else 0


def _prefill(probe, prompt, start: int, width: int):
    """The chunks of ``prompt`` from position ``start`` (a chunk boundary) on,
    on the cache as it stands: ``correct._forced_logits``'s chunk calls for
    one row. Returns the logits at the last prompt position."""
    import numpy as np

    tc = probe.config.tpu_config
    bs, chunk = tc.pa_block_size, tc.chunked_prefill_config.kernel_q_tile_size
    table = (1 + np.arange(width // bs))[None, :].astype(np.int32)
    seq = np.zeros((1,), np.int32)
    last = None
    for head in range(start, len(prompt), chunk):
        n = min(chunk, len(prompt) - head)
        at = head + np.arange(n)
        ids, pos = np.zeros((1, chunk), np.int32), (head + np.arange(chunk))[None, :].astype(np.int32)
        sm, mask = np.full((1, chunk), -1, np.int32), np.zeros((1, width), np.int32)
        ids[0, :n] = prompt[head : head + n]
        sm[0, :n] = table[0, at // bs] * bs + at % bs
        mask[0, : head + n] = 1
        _, logits = probe.forward(ids, pos, seq, attention_mask=mask, slot_mapping=sm,
                                  block_table=table, phase="tkg")
        last = np.asarray(logits[0, n - 1], np.float32)
    return last


def _rest(probe, prompt, head: int, forced, width: int):
    """The chunks of ``prompt`` from ``head`` on and the decode steps, on the
    cache as it stands: ``correct._forced_logits``'s calls for one row,
    started in the middle."""
    import numpy as np

    from benchmark.harness.correct import PROBE_DECODE_STEPS

    table = (1 + np.arange(width // probe.config.tpu_config.pa_block_size))[None, :].astype(np.int32)
    seq = np.zeros((1,), np.int32)
    got = [_prefill(probe, prompt, head, width)]
    for step in range(PROBE_DECODE_STEPS):
        p = np.asarray([[len(prompt) + step]], np.int32)
        m = (np.arange(width)[None, :] <= p).astype(np.int32)
        _, logits = probe.forward(np.asarray([[forced[step]]], np.int32), p, seq,
                                  attention_mask=m, block_table=table, phase="tkg")
        got.append(np.asarray(logits[0, 0], np.float32))
    return np.stack(got)


if __name__ == "__main__":
    sys.exit(main())
