"""What decides ``correct``.

(a) Logits of the served model against the plain reference
    (``reference.py``), outside the window, at the configuration's full
    width, on two prompts made from the seed: one as long as the longest
    prompt of the cell's traffic, one of a single partial chunk. A probe
    application — the cell's own configuration (``seq_len``, kv buckets,
    block size) and the very same weight arrays, but few slots, a block
    pool for two rows and ``output_logits`` on (the served application
    keeps it off: 78 MB of float32 logits per slot per prefill chunk would
    come out of the block pool) — takes the short prompt through
    ``ServingSession.add_request()/step()`` on the paged cache, chunked
    prefill and 1-ahead decode as served. Both prompts are then
    teacher-forced through ``app.forward()`` (the program's public one-pass
    entry point, same programs, block placement chosen here): every chunk
    pass with its prior KV at the kv bucket the cell's longest prompt ends
    in, then four decode steps, fed the tokens the session chose (short
    prompt) or four tokens from the seed (long prompt: a second pass of
    all its chunks, through the session, would double the probe's time).
    The logits at the last prompt position and the four decode steps
    after it are read, and the same tokens go through the reference.
      - logits:  max|served - ref| <= LOGIT_TOL * max|ref|
      - session (short prompt): every token the session chose is, by the REFERENCE's
        logits, within LOGIT_TOL * max|ref| of the best token at its
        position. Random weights give near-flat logits, so equality of
        argmax is not asked (PR 21: two paths agreed on 4 of 8 requests).
(b) In the window: every finished request has exactly its budget of tokens,
    all inside the vocabulary; none ended FAILED.
(c) No compilation inside the window (``system.CompileLog``).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import reference, system

#: max|served - ref| <= LOGIT_TOL * max|ref|. The served model multiplies in
#: bf16 and rounds every activation to bf16 (8 mantissa bits, eps 0.4%);
#: through 28 layers the roundings random-walk to a few percent of the logit
#: scale. Read on the chip against this float32 reference (PR 22, Qwen3-1.7B,
#: scale 4.3-4.8, some 80 prompts of 100 to 6144 tokens over 40 seeds):
#: 2.9-3.7%, the same for a 6144-token prompt at the 8192 bucket (3.3%) as
#: for 100 tokens (3.0%) — the size PR 21 read between two bf16 paths of the
#: 1B (2.6-2.9%). The bound sits 0.8 points above the largest reading: the
#: maximum over 5 x 151936 logits moves little from seed to seed. A fault
#: that matters (a mis-masked tile, a dropped head, a norm weight not
#: applied, fp8 in place of bf16) moves logits by tens of percent of scale.
LOGIT_TOL = 0.045

PROBE_SHORT_PROMPT = 100  # one partial chunk of the default 128
PROBE_DECODE_STEPS = 4
PROBE_SLOTS = 8


class CorrectnessError(AssertionError):
    """The served model and the reference disagree. ``facts``: what was read
    before the verdict."""

    def __init__(self, message: str, facts: dict = None):
        super().__init__(message)
        self.facts = facts or {}


def probe_width(cfg: dict, max_prompt: int) -> int:
    """The kv bucket the cell's longest prompt ends in, decode steps included."""
    need = max_prompt + PROBE_DECODE_STEPS + 1
    buckets = sorted(cfg["tpu_config"]["token_generation_buckets"])
    return next((b for b in buckets if b >= need), buckets[-1])


def probe_overrides(cfg: dict, max_prompt: int) -> Dict[str, dict]:
    per_row = probe_width(cfg, max_prompt) // cfg["tpu_config"]["pa_block_size"]
    return {
        "tpu": dict(batch_size=PROBE_SLOTS, output_logits=True, pa_num_blocks=1 + 2 * per_row),
        "chunked": dict(max_num_seqs=PROBE_SLOTS),
    }


def _session_tokens(probe, prompts: List[np.ndarray], budget: int) -> List[List[int]]:
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    session = ServingSession(probe)
    for i, p in enumerate(prompts):
        if not session.add_request(f"probe-{i}", p, max_new_tokens=budget):
            raise CorrectnessError(f"the probe session refused prompt {i}")
    for _ in range(sum(len(p) for p in prompts) + 64):
        if not session.active:
            break
        session.step()
    out = []
    for i in range(len(prompts)):
        req = session.requests[f"probe-{i}"]
        if req.status != "finished" or len(req.generated) != budget:
            raise CorrectnessError(
                f"probe request {i}: {req.status} with {len(req.generated)} of {budget} tokens"
            )
        out.append([int(t) for t in req.generated])
    return out


def _forced_logits(probe, prompts: List[np.ndarray], forced: List[List[int]],
                   width: int) -> List[np.ndarray]:
    """Teacher-forced pass through ``app.forward`` on the paged cache: the
    prompt in chunks of the session's chunk size, then one decode step per
    forced token. Row r owns blocks 1 + r*per_row ... (block 0 is the
    program's garbage block). Returns per prompt the (1 + steps, V) logits
    at the last prompt position and after each forced token."""
    tc = probe.config.tpu_config
    bs = tc.pa_block_size
    per_row = width // bs
    chunk = tc.chunked_prefill_config.kernel_q_tile_size
    B = len(prompts)
    table = np.stack([1 + r * per_row + np.arange(per_row) for r in range(B)]).astype(np.int32)
    seq_ids = np.arange(B, dtype=np.int32)
    slot = lambda r, pos: table[r, pos // bs] * bs + pos % bs
    got = [[] for _ in prompts]
    longest = max(len(p) for p in prompts)
    for start in range(0, longest, chunk):
        ids = np.zeros((B, chunk), np.int32)
        pos = np.zeros((B, chunk), np.int32)
        sm = np.full((B, chunk), -1, np.int32)
        mask = np.zeros((B, width), np.int32)
        rows = seq_ids.copy()
        ends = {}
        for r, p in enumerate(prompts):
            n = min(chunk, len(p) - start)
            pos[r] = start + np.arange(chunk)
            if n <= 0:
                rows[r] = -1
                continue
            ids[r, :n] = p[start : start + n]
            sm[r, :n] = slot(r, start + np.arange(n))
            mask[r, : start + n] = 1
            if start + n == len(p):
                ends[r] = n - 1
        _, logits = probe.forward(ids, pos, rows, attention_mask=mask, slot_mapping=sm,
                                  block_table=table, phase="tkg")
        for r, idx in ends.items():
            got[r].append(np.asarray(logits[r, idx], np.float32))
    for step in range(PROBE_DECODE_STEPS):
        ids = np.asarray([[f[step]] for f in forced], np.int32)
        pos = np.asarray([[len(p) + step] for p in prompts], np.int32)
        mask = (np.arange(width)[None, :] <= pos).astype(np.int32)
        _, logits = probe.forward(ids, pos, seq_ids, attention_mask=mask,
                                  block_table=table, phase="tkg")
        for r in range(B):
            got[r].append(np.asarray(logits[r, 0], np.float32))
    return [np.stack(g) for g in got]


def check_model(cfg: dict, devices, seed: int, params, pspecs, degree: int,
                max_prompt: int) -> dict:
    """Part (a), with one prompt of ``max_prompt`` tokens (the longest of
    the cell's traffic). Raises CorrectnessError; returns the facts it read."""
    attrs = system.model_attrs(cfg)
    geo = reference.Geometry.from_config(attrs, degree)
    over = probe_overrides(cfg, max_prompt)
    lengths = (max_prompt, PROBE_SHORT_PROMPT)
    probe = system.build_app(cfg, devices, seed, tpu_overrides=over["tpu"],
                             chunked_overrides=over["chunked"])
    system.give_weights(probe, params, pspecs)
    rng = np.random.default_rng([int(seed), 7])
    prompts = [rng.integers(0, geo.vocab, size=n).astype(np.int32) for n in lengths]
    budget = PROBE_DECODE_STEPS + 1
    try:
        chosen = [[int(t) for t in rng.integers(0, geo.vocab, size=budget)],
                  _session_tokens(probe, prompts[1:], budget)[0]]
        probe.init_kv_cache()
        served = _forced_logits(probe, prompts, chosen, probe_width(cfg, max_prompt))
    finally:
        probe.params = probe.kv_cache = None
    facts = {"tolerance": LOGIT_TOL, "prompts": list(lengths), "rows": []}
    errors = []
    for r, p in enumerate(prompts):
        tokens = list(p) + chosen[r][:PROBE_DECODE_STEPS]
        positions = [len(p) - 1 + k for k in range(budget)]
        ref = reference.reference_logits(params, geo, tokens, positions)
        cmp = reference.compare(served[r], ref)
        # how far below the reference's best each token the session chose is
        regret = 0.0
        if r > 0:  # the short prompt's tokens are the session's
            regret = float(max(ref[k].max() - ref[k, chosen[r][k]] for k in range(budget)))
        facts["rows"].append({**cmp, "prompt": len(p), "session_token_regret": regret if r else None})
        if not cmp["finite"]:
            errors.append(f"prompt {r}: non-finite logits from the served model")
        if cmp["max_abs_err"] > LOGIT_TOL * cmp["scale"]:
            errors.append(
                f"prompt {r}: max logit error {cmp['max_abs_err']:.4g} > "
                f"{LOGIT_TOL} x reference scale {cmp['scale']:.4g}"
            )
        if regret > LOGIT_TOL * cmp["scale"]:
            errors.append(
                f"prompt {r}: a token the session chose is {regret:.4g} below the "
                f"reference's best, more than {LOGIT_TOL} x scale {cmp['scale']:.4g}"
            )
    if errors:
        raise CorrectnessError("; ".join(errors), facts)
    return facts


def check_window(records, session, vocab: int) -> List[str]:
    """Part (b): the faults found in the window's requests (empty = none)."""
    faults = []
    for rec in records:
        if rec.failed:
            faults.append(f"{rec.req_id}: {rec.failed}")
        req = session.requests.get(rec.req_id)
        if req is None:
            continue
        gen = req.generated
        if any(t < 0 or t >= vocab for t in gen):
            faults.append(f"{rec.req_id}: token outside the vocabulary")
        if rec.finished and len(gen) != rec.budget:
            faults.append(f"{rec.req_id}: finished with {len(gen)} of {rec.budget} tokens")
    return faults
