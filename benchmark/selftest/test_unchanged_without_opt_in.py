"""A configuration that opts into nothing new is judged as it was: at
rehearsal size on the CPU, ``serve_probe`` + ``judge`` of ``qwen3-1p7b``
(dense, no choices) and of ``zaya1-8b`` (replayed, margins) return the facts
the PARENT's returned, array for array and digit for digit. The parent's
``_forced_pass``, ``serve_probe`` and ``judge`` (commit b3bc804, PR 36) are
kept below word for word but for the names they call (``frozen_*``; the
harness's helpers that did not change through ``correct.``)."""

import json
import os
import time
from typing import List

import numpy as np
import pytest

from benchmark.harness import catalog, correct, system
from benchmark.harness.correct import K, PROBE_DECODE_STEPS, PROBE_SHORT_PROMPT, CorrectnessError

SEED = 4000000637


def frozen_forced_pass(probe, prompts: List[np.ndarray], forced: List[List[int]], width: int):
    """Teacher-forced pass through ``app.forward`` on the paged cache: the
    prompt in chunks of the session's chunk size, then one decode step per
    forced token. Row r owns blocks 1 + r*per_row ... (block 0 is the
    program's garbage block). Returns (logits, choices): per prompt the
    (1 + steps, V) logits at the last prompt position and after each forced
    token, and, where ``forward`` returns a third value (module docstring,
    "A model that chooses"), per prompt a dict ``name -> (len(prompt) +
    steps, ...)`` of the choices made at every token of the row; else None."""
    tc = probe.config.tpu_config
    bs = tc.pa_block_size
    per_row = width // bs
    chunk = tc.chunked_prefill_config.kernel_q_tile_size
    B = len(prompts)
    table = np.stack([1 + r * per_row + np.arange(per_row) for r in range(B)]).astype(np.int32)
    seq_ids = np.arange(B, dtype=np.int32)
    slot = lambda r, pos: table[r, pos // bs] * bs + pos % bs
    got = [[] for _ in prompts]
    chose = [{} for _ in prompts]  # row -> name -> pieces in token order

    def keep(aux, r, n):
        for name, a in (aux[0] if aux else {}).items():
            chose[r].setdefault(name, []).append(np.asarray(a[r, :n]))

    longest = max(len(p) for p in prompts)
    for start in range(0, longest, chunk):
        ids = np.zeros((B, chunk), np.int32)
        pos = np.zeros((B, chunk), np.int32)
        sm = np.full((B, chunk), -1, np.int32)
        mask = np.zeros((B, width), np.int32)
        rows = seq_ids.copy()
        ends, live = {}, {}
        for r, p in enumerate(prompts):
            n = min(chunk, len(p) - start)
            pos[r] = start + np.arange(chunk)
            if n <= 0:
                rows[r] = -1
                continue
            live[r] = n
            ids[r, :n] = p[start : start + n]
            sm[r, :n] = slot(r, start + np.arange(n))
            mask[r, : start + n] = 1
            if start + n == len(p):
                ends[r] = n - 1
        _, logits, *aux = probe.forward(ids, pos, rows, attention_mask=mask, slot_mapping=sm,
                                        block_table=table, phase="tkg")
        for r, idx in ends.items():
            got[r].append(np.asarray(logits[r, idx], np.float32))
        for r, n in live.items():
            keep(aux, r, n)
    for step in range(PROBE_DECODE_STEPS):
        ids = np.asarray([[f[step]] for f in forced], np.int32)
        pos = np.asarray([[len(p) + step] for p in prompts], np.int32)
        mask = (np.arange(width)[None, :] <= pos).astype(np.int32)
        _, logits, *aux = probe.forward(ids, pos, seq_ids, attention_mask=mask,
                                        block_table=table, phase="tkg")
        for r in range(B):
            got[r].append(np.asarray(logits[r, 0], np.float32))
            keep(aux, r, 1)
    choices = [{name: np.concatenate(parts) for name, parts in row.items()} for row in chose]
    return [np.stack(g) for g in got], choices if any(choices) else None


def frozen_serve_probe(cfg: dict, devices, seed: int, params, pspecs, max_prompt: int):
    """(prompts, chosen, served, choices): the two probe prompts, per prompt
    the tokens that follow it (the long prompt's from the seed, the short
    prompt's as the probe session chose them), the served logits
    (1 + PROBE_DECODE_STEPS, V) at the last prompt position and after each
    of the first PROBE_DECODE_STEPS of them, and the choices the forced pass
    returned per row (None where the program returns none). The probe
    application is gone when this returns."""
    vocab = system.model_attrs(cfg)["vocab_size"]
    over = correct.probe_overrides(cfg, max_prompt)
    probe = system.build_app(cfg, devices, seed, tpu_overrides=over["tpu"],
                             chunked_overrides=over["chunked"])
    system.give_weights(probe, params, pspecs)
    rng = np.random.default_rng([int(seed), 7])
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
               for n in (max_prompt, PROBE_SHORT_PROMPT)]
    budget = PROBE_DECODE_STEPS + 1
    try:
        chosen = [[int(t) for t in rng.integers(0, vocab, size=budget)],
                  correct._session_tokens(probe, prompts[1:], budget)[0][0]]
        probe.init_kv_cache()
        served, choices = frozen_forced_pass(probe, prompts, chosen, correct.probe_width(cfg, max_prompt))
    finally:
        probe.params = probe.kv_cache = None
    return prompts, chosen, served, choices


def frozen_judge(cfg: dict, params, degree: int, prompts, chosen, served, choices=None) -> dict:
    """``served`` against the float32 reference and its bf16 twin, row by
    row (module docstring); with a reference that replays (``CHOICES``),
    both follow ``choices`` (per row, ``_forced_pass``'s) and every choosing
    layer is held to its margin. Raises CorrectnessError; returns the facts
    it read: per row ``err``, ``floor``, ``scale``, ``ratio`` = err / floor
    and the same ratio of root mean squares (steadier than a ratio of
    maxima; printed, not judged), and per choosing layer ``regret``,
    ``score_floor`` and the decisions that are not float32's own."""
    reference = correct.load_reference(cfg)
    geo = reference.geometry(system.model_attrs(cfg), degree)
    budget = PROBE_DECODE_STEPS + 1
    replay = bool(getattr(reference, "CHOICES", False))
    facts = {"K": K, "reference": reference.__name__.rsplit(".", 1)[-1],
             "prompts": [len(p) for p in prompts], "rows": []}
    if replay and choices is None:
        raise CorrectnessError(
            "the configuration's reference replays choices and the program returned none", facts)
    errors = []
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    for r, p in enumerate(prompts):
        tokens, positions = correct.probe_row(p, chosen[r])
        follow = {"choices": choices[r]} if replay else {}
        t0 = time.perf_counter()
        ref = reference.reference_logits(params, geo, tokens, positions, **follow)
        t1 = time.perf_counter()
        twin = reference.twin_logits(params, geo, tokens, positions, **follow)
        t2 = time.perf_counter()
        got = np.asarray(served[r], np.float32)
        err, floor = float(np.abs(got - ref).max()), float(np.abs(twin - ref).max())
        # how far below the reference's best each token the session chose is
        regret = None
        if r > 0:  # the short prompt's tokens are the session's
            regret = float(max(ref[k].max() - ref[k, chosen[r][k]] for k in range(budget)))
        row = {"prompt": len(p), "err": err, "floor": floor,
               "scale": float(np.abs(ref).max()),
               "ratio": err / floor if floor > 0 else None,
               "limit": K * floor, "session_token_regret": regret,
               "rms_ratio": rms(got - ref) / max(rms(twin - ref), 1e-30),
               "ref32_s": t1 - t0, "twin_s": t2 - t1}
        facts["rows"].append(row)
        if not np.isfinite(got).all():
            errors.append(f"prompt {r}: non-finite logits from the served model")
        if not err <= K * floor:
            errors.append(f"prompt {r}: max logit error {err:.4g} > {K} x the bf16 twin's {floor:.4g}")
        if regret is not None and regret > K * floor:
            errors.append(
                f"prompt {r}: a token the session chose is {regret:.4g} below the "
                f"reference's best, more than {K} x the bf16 twin's error {floor:.4g}"
            )
        if replay:
            margins, score_floor, *differing = reference.choice_margins(params, geo, tokens, choices[r])
            margins, score_floor = (np.asarray(a, np.float64).ravel() for a in (margins, score_floor))
            row.update(choice_regret=margins.tolist(), choice_score_floor=score_floor.tolist(),
                       choice_limit=(2 * K * score_floor).tolist(), choices_s=time.perf_counter() - t2)
            if differing:
                row["choices_not_float32s"] = np.asarray(differing[0]).ravel().astype(int).tolist()
            for l in np.flatnonzero(~(margins <= 2 * K * score_floor)):
                errors.append(
                    f"prompt {r}: margin: a choice of choosing layer {l} scores {margins[l]:.4g} "
                    f"below the best candidate, more than 2 x {K} x the bf16 twin's score error "
                    f"{score_floor[l]:.4g}")
    if errors:
        raise CorrectnessError("; ".join(errors), facts)
    return facts


TIMES = ("ref32_s", "twin_s", "choices_s")


@pytest.mark.parametrize("name,max_prompt", [("qwen3-1p7b", 300), ("zaya1-8b", 256)])
def test_a_configuration_that_opts_into_nothing_reads_the_parents_facts(name, max_prompt):
    import jax

    with open(os.path.join(catalog.BENCH_DIR, "configs", name + ".json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    assert "reserved_token_ids" not in cfg and not getattr(correct.load_reference(cfg), "PASSES", False)
    devices = jax.devices()[:1]
    app = system.build_app(cfg, devices, SEED)
    params, pspecs = system.make_weights(app, SEED, cfg.get("weights"))
    then = frozen_serve_probe(cfg, devices, SEED, params, pspecs, max_prompt)
    *now, plans = correct.serve_probe(cfg, devices, SEED, params, pspecs, max_prompt)
    assert plans is None
    for a, b in zip(now[:3], then[:3]):  # prompts, chosen, served: array for array
        assert len(a) == len(b) == 2
        for x, y in zip(a, b):
            assert np.asarray(x).dtype == np.asarray(y).dtype and np.array_equal(x, y)
    assert (now[3] is None) == (then[3] is None) == (name == "qwen3-1p7b")
    for row_now, row_then in zip(now[3] or [], then[3] or []):
        assert row_now.keys() == row_then.keys()
        assert all(np.array_equal(row_now[k], row_then[k]) for k in row_now)
    facts_then = frozen_judge(cfg, params, 1, *then)
    facts_now = correct.judge(cfg, params, 1, *now)
    strip = lambda facts: [{k: v for k, v in row.items() if k not in TIMES} for row in facts["rows"]]
    assert strip(facts_now) == strip(facts_then)  # err, floor, scale, ratio, regrets, margins: every digit
    assert {k: v for k, v in facts_now.items() if k != "rows"} == {k: v for k, v in facts_then.items() if k != "rows"}
    assert correct.compared(facts_now, [], 0, 7, 7) == correct.compared(facts_then, [], 0, 7, 7)
