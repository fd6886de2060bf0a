"""What decides ``correct``.

(a) Logits of the served model against the plain reference the
    configuration names (``references/<cfg["reference"]>.py``, ``dense`` where
    it names none), outside the window, at the configuration's full
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
    after it are read, and the same tokens go through the reference twice:
    in float32 (``ref32``) and as its bf16 twin (``twin``: the same
    equations with the roundings a faultless bf16 deployment of this depth
    and tensor-parallel degree states). Per probe row
        err   = max|served - ref32|
        floor = max|twin - ref32|      the noise a sound bf16 model shows HERE
        scale = max|ref32|
      - logits:  err <= K * floor
      - session (short prompt): every token the session chose is, by ref32,
        within K * floor of the best token at its position. Random weights
        give near-flat logits, so equality of argmax is not asked (PR 21:
        two paths agreed on 4 of 8 requests).
    No constant of scale: depth, width and layout move err and floor alike.

    A model that chooses (an expert layer's router, any argmax or top-k
    inside the step): replay, then margin. The hidden state of a sound bf16
    model is some per cent of scale off float32 by its last layers; a top-1
    choice among near-tied scores flips under that (a few decisions of a
    thousand at 4 layers on the CPU; more with depth), so the served model,
    ``ref32`` and ``twin`` would each take another route, and err and floor
    would both be maxima over a handful of flips
    (``selftest/test_correct_choices.py`` reads the ratio that gives: 0.2
    to 32 over 20 seeds of a sound program). So
    three parties opt in, each by name, and a configuration that does not
    pays not one extra pass:
      - the configuration file names, under ``probe_tpu_config``, the option
        of the program that makes the step return its choices (laid over
        the probe application only, as ``output_logits`` is);
      - the program's ``forward`` then returns a third value beside
        ``(tokens, logits)``: a dict ``name -> int array (B, S, ...)`` of the
        choices made at every position of the pass (an expert layer:
        ``(B, S, L_moe, k)``; index E where a router may skip). The harness
        keeps, per probe row, the choices of EVERY token of the row (prompt
        chunks and decode steps, padding dropped by the bookkeeping that
        picks the logits): the K and V of context tokens depend on their
        routes too;
      - the reference module sets ``CHOICES = True`` and is called as
        ``reference_logits(params, geo, tokens, positions, choices=row)``
        and ``twin_logits(..., choices=row)``, ``row`` a dict
        ``name -> (len(tokens), ...)``: both FOLLOW the served selection and
        compute everything else (scores, affinities, experts) themselves,
        so ``floor`` is rounding noise alone and ``err <= K * floor`` keeps
        its meaning and its K. It also gives
        ``choice_margins(params, geo, tokens, choices) -> (regret,
        score_floor[, differing])``, one number per choosing layer, on the
        replayed path: ``regret[l]`` the most, over tokens, by which the
        served choice's selection score lies below the best candidate's in
        float32; ``score_floor[l]`` the largest |twin's selection score -
        float32's| over tokens and candidates; ``differing[l]`` (printed,
        not judged) the decisions that are not float32's own.
      - margin:  regret[l] <= 2 * K * score_floor[l]  for every layer l.
        Why 2 K: a model that takes the argmax of scores s + e picks j over
        the best i only if s[i] - s[j] < e[j] - e[i] <= 2 max|e|, and a
        sound model's e is within K of the twin's, as its logits are.
    A reference that sets ``CHOICES`` while the probe returned no third
    value is a CorrectnessError, not a silent fall-back to three routes.
    The session rule is read on the replayed ``ref32``.

    A model whose step is a block (a step fills several positions at once,
    some of them holding a mask token, sees them both ways, and a token is
    predicted AT its position): the probe drives passes the reference
    plans. Three parties opt in, each by name; a reference that does not is
    called exactly as above:
      - the reference module sets ``PASSES = True`` and gives
        ``probe_budget(geo) -> int`` (the tokens the probe session is asked
        for and the seed draws for the long prompt, in place of
        PROBE_DECODE_STEPS + 1) and ``probe_passes(geo, prompt, following,
        revealed_at=None) -> (prefill_len, [pass, ...])``: how many of the
        prompt's tokens the chunk passes carry (their one read is then at
        ``prefill_len - 1``) and the passes that follow them, each
        ``{"ids": (q,), "positions": (q,), "read": [indices of the pass
        whose logits are compared], "chosen": [per read, the token the row
        reveals there, or -1]}`` (further keys are the module's own).
        ``following`` are the tokens after the prompt (the seed's, or the
        session's) and ``revealed_at`` the session's record below (None for
        the long prompt: the module makes an order from ``geo`` and the
        tokens alone, so that the run is a function of ``--seed``). The
        module, not this file, knows what a mask token is, what a pass that
        commits is and which position predicts which token;
      - the program: a finished request of the probe session carries
        ``revealed_at``, one int per generated token: the ordinal, within
        its block, of the pass that revealed it. The option that makes the
        session record it is named under ``probe_tpu_config``. A ``PASSES``
        reference whose session returned none is a CorrectnessError;
      - ``_forced_pass`` runs the chunk passes over ``prompt[:prefill_len]``
        and then every planned pass through the same ``forward``: ids
        ``(rows, q)``, their positions, ``attention_mask = arange(width) <=
        the pass's last position``, ``slot_mapping`` = the slots of its
        positions, the block table; a row whose plan has ended sits out
        (``seq_ids`` -1, no slot), as in the chunk passes. The logits are
        kept at ``read`` and, where ``forward`` returns a third value, the
        choices of EVERY token of every pass, in pass order.
      - ``reference_logits(params, geo, prompt, passes[, choices=])`` and
        ``twin_logits(...)`` are called once a row and give ``(1 + reads,
        V)``; ``err``, ``floor``, ``K`` and ``err <= K * floor`` are what
        they are.
      - session, where it was predicted: for every read of the short
        prompt's row with ``chosen >= 0``, that token is, by ref32 AT THAT
        READ, within K * floor of the best. (The rule above with the
        position the reference names in place of "the one before".)
      - the reveal is a choice and is held as one: with ``CHOICES`` too,
        ``choice_margins(params, geo, prompt, passes, choices)`` may return
        more choosing layers than the model has; the module appends one for
        the reveal (``regret`` the most, over passes that reveal, by which
        the float32 confidence of a revealed position lies under the best
        position's still masked after it; ``score_floor`` max |twin's
        confidence - float32's|), held by the margin rule as it stands.
(b) In the window: every finished request has exactly its budget of tokens,
    all inside the vocabulary and none of them an id the configuration
    reserves; none ended FAILED.
(c) No compilation inside the window (``system.CompileLog``).

``compared`` lists every number of (a), (b) and (c) beside its limit, for
the run's last lines.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List

import numpy as np

from . import system

#: err <= K * floor, ONE number for every configuration: depth, width and
#: tensor-parallel layout move ``err`` and ``floor`` alike, so their ratio is
#: a property of the program and not of the model. Read on the chip with
#: ``selftest/read_ratio.py`` and in the cells' own runs (PR 26, TPU v5 lite):
#:   Qwen3-1.7B, one chip, 28 layers: 40 rows over 20 seeds (prompts of 256
#:     and 6144 tokens, the kv widths of the decode and the longprompt cell,
#:     and the 100-token session prompt): err / floor 0.859 - 1.243, median
#:     1.04 (err 2.6 - 3.9% of scale, as PR 22 read it);
#:   Qwen3-14B at tp = 4, four chips, 40 layers: 24 rows over 12 seeds (prompts
#:     of 2048 tokens, the chat cell's kv width, and 100): 0.957 - 1.208 (err
#:     5.0 - 6.5% of scale: over the old constant 0.045, with nothing at fault);
#:   the CONTROL, the reference itself at fp8-e4m3 (the nearest precision
#:     below bf16) in the program's place, same prompts and tokens:
#:     16.8 - 38.9 on the 1.7B (8 rows), 15.0 - 25.2 on the 14B (6 rows).
#: K is the largest sound reading plus the share PR 22 left above its own
#: largest (0.8 of 3.7: 1.243 x 1.22 = 1.51), held to the 1.5 the issue allows;
#: the control's smallest is ten times that. A ratio of two maxima over
#: 5 x 151936 logits swings by some 10% from seed to seed; the ratio of the
#: root mean squares, printed beside it, read 0.985 - 1.159 on the same 64 rows.
#: What the rule fails at a small size on the CPU (``selftest/test_correct.py``):
#: a norm weight not applied 14 - 16, a KV head dropped 98 - 114, the mask off
#: by one tile 122 - 161, fp8 in place of bf16 18 - 26.
K = 1.5

PROBE_SHORT_PROMPT = 100  # one partial chunk of the default 128
PROBE_DECODE_STEPS = 4
PROBE_SLOTS = 8


class CorrectnessError(AssertionError):
    """The served model and the reference disagree. ``facts``: what was read
    before the verdict."""

    def __init__(self, message: str, facts: dict = None):
        super().__init__(message)
        self.facts = facts or {}


def _planner(cfg: dict):
    """(reference, its geometry) where the configuration's reference plans
    passes (module docstring, "A model whose step is a block"), else None."""
    reference = load_reference(cfg)
    if not getattr(reference, "PASSES", False):
        return None
    degree = cfg["tpu_config"].get("tp_degree", 1)
    return reference, reference.geometry(system.model_attrs(cfg), degree)


def probe_budget(cfg: dict) -> int:
    """The tokens that follow a probe prompt: PROBE_DECODE_STEPS + 1, or
    what a reference that plans passes asks for."""
    planner = _planner(cfg)
    return PROBE_DECODE_STEPS + 1 if planner is None else int(planner[0].probe_budget(planner[1]))


def probe_width(cfg: dict, max_prompt: int) -> int:
    """The kv bucket the cell's longest prompt ends in, the tokens that
    follow it included."""
    need = max_prompt + probe_budget(cfg)
    buckets = sorted(cfg["tpu_config"]["token_generation_buckets"])
    return next((b for b in buckets if b >= need), buckets[-1])


def probe_overrides(cfg: dict, max_prompt: int) -> Dict[str, dict]:
    per_row = probe_width(cfg, max_prompt) // cfg["tpu_config"]["pa_block_size"]
    return {
        "tpu": dict(cfg.get("probe_tpu_config") or {}, batch_size=PROBE_SLOTS, output_logits=True,
                    pa_num_blocks=1 + 2 * per_row),
        "chunked": dict(max_num_seqs=PROBE_SLOTS),
    }


def load_reference(cfg: dict):
    """The module of the plain reference this configuration names."""
    return importlib.import_module(
        "benchmark.harness.references." + cfg.get("reference", "dense"))


def _session_tokens(probe, prompts: List[np.ndarray], budget: int):
    """(generated, revealed_at), per prompt: the tokens the probe session
    generated, and the request's ``revealed_at`` where the program keeps one
    (module docstring, "A model whose step is a block"), else None."""
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    session = ServingSession(probe)
    for i, p in enumerate(prompts):
        if not session.add_request(f"probe-{i}", p, max_new_tokens=budget):
            raise CorrectnessError(f"the probe session refused prompt {i}")
    for _ in range(sum(len(p) for p in prompts) + 64):
        if not session.active:
            break
        session.step()
    out, revealed = [], []
    for i in range(len(prompts)):
        req = session.requests[f"probe-{i}"]
        if req.status != "finished" or len(req.generated) != budget:
            raise CorrectnessError(
                f"probe request {i}: {req.status} with {len(req.generated)} of {budget} tokens"
            )
        out.append([int(t) for t in req.generated])
        at = getattr(req, "revealed_at", None)
        revealed.append(None if at is None else [int(k) for k in at])
    return out, revealed


def _forced_pass(probe, prompts: List[np.ndarray], forced: List[List[int]], width: int,
                 plans=None):
    """Teacher-forced pass through ``app.forward`` on the paged cache: the
    prompt in chunks of the session's chunk size, then one decode step per
    forced token. Row r owns blocks 1 + r*per_row ... (block 0 is the
    program's garbage block). Returns (logits, choices): per prompt the
    (1 + steps, V) logits at the last prompt position and after each forced
    token, and, where ``forward`` returns a third value (module docstring,
    "A model that chooses"), per prompt a dict ``name -> (len(prompt) +
    steps, ...)`` of the choices made at every token of the row; else None.

    With ``plans`` (per prompt ``probe_passes``'s ``(prefill_len, passes)``;
    module docstring, "A model whose step is a block") the chunks carry
    ``prompt[:prefill_len]`` and the planned passes take the decode steps'
    place, ``forced`` unused: the logits are (1 + reads, V), at
    ``prefill_len - 1`` and at every read in pass order, the choices
    ``name -> (prefill_len + the passes' tokens, ...)``."""
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

    if plans is not None:
        if min(prefill_len for prefill_len, _ in plans) < 1:
            raise CorrectnessError("a plan whose chunk passes carry no token has no first read")
        prompts = [p[:prefill_len] for p, (prefill_len, _) in zip(prompts, plans)]
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
    if plans is None:
        for step in range(PROBE_DECODE_STEPS):
            ids = np.asarray([[f[step]] for f in forced], np.int32)
            pos = np.asarray([[len(p) + step] for p in prompts], np.int32)
            mask = (np.arange(width)[None, :] <= pos).astype(np.int32)
            _, logits, *aux = probe.forward(ids, pos, seq_ids, attention_mask=mask,
                                            block_table=table, phase="tkg")
            for r in range(B):
                got[r].append(np.asarray(logits[r, 0], np.float32))
                keep(aux, r, 1)
    else:
        for k in range(max(len(passes) for _, passes in plans)):
            live = {r: passes[k] for r, (_, passes) in enumerate(plans) if k < len(passes)}
            q = max(len(p["ids"]) for p in live.values())
            ids = np.zeros((B, q), np.int32)
            pos = np.zeros((B, q), np.int32)
            sm = np.full((B, q), -1, np.int32)
            mask = np.zeros((B, width), np.int32)
            rows = np.full(B, -1, np.int32)  # a row whose plan has ended sits out
            for r, p in live.items():
                at = np.asarray(p["positions"], np.int32)
                n = len(at)
                rows[r] = r
                ids[r, :n] = p["ids"]
                pos[r] = np.concatenate([at, at[-1] + 1 + np.arange(q - n)])
                sm[r, :n] = slot(r, at)
                mask[r, : at.max() + 1] = 1
            _, logits, *aux = probe.forward(ids, pos, rows, attention_mask=mask, slot_mapping=sm,
                                            block_table=table, phase="tkg")
            for r, p in live.items():
                if len(p["read"]):
                    got[r].extend(np.asarray(logits[r, np.asarray(p["read"])], np.float32))
                keep(aux, r, len(p["ids"]))
    choices = [{name: np.concatenate(parts) for name, parts in row.items()} for row in chose]
    return [np.stack(g) for g in got], choices if any(choices) else None


def _forced_logits(probe, prompts: List[np.ndarray], forced: List[List[int]],
                   width: int) -> List[np.ndarray]:
    """The logits of ``_forced_pass``."""
    return _forced_pass(probe, prompts, forced, width)[0]


def serve_probe(cfg: dict, devices, seed: int, params, pspecs, max_prompt: int):
    """(prompts, chosen, served, choices, plans): the two probe prompts, per
    prompt the tokens that follow it (the long prompt's from the seed, the
    short prompt's as the probe session chose them), the served logits
    (1 + PROBE_DECODE_STEPS, V) at the last prompt position and after each
    of the first PROBE_DECODE_STEPS of them, the choices the forced pass
    returned per row (None where the program returns none) and, for a
    reference that plans passes, per prompt its ``(prefill_len, passes)``
    (the served logits are then ``_forced_pass``'s (1 + reads, V); else
    None). The probe application is gone when this returns."""
    from .traffic import draw_ids

    vocab = system.model_attrs(cfg)["vocab_size"]
    reserved = cfg.get("reserved_token_ids", ())
    planner, budget = _planner(cfg), probe_budget(cfg)
    over = probe_overrides(cfg, max_prompt)
    probe = system.build_app(cfg, devices, seed, tpu_overrides=over["tpu"],
                             chunked_overrides=over["chunked"])
    system.give_weights(probe, params, pspecs)
    rng = np.random.default_rng([int(seed), 7])
    prompts = [draw_ids(rng, vocab, n, reserved).astype(np.int32)
               for n in (max_prompt, PROBE_SHORT_PROMPT)]
    try:
        seeded = [int(t) for t in draw_ids(rng, vocab, budget, reserved)]
        session, revealed_at = _session_tokens(probe, prompts[1:], budget)
        chosen, plans = [seeded, session[0]], None
        if planner is not None:
            if revealed_at[0] is None:
                raise CorrectnessError("the configuration's reference plans passes and the probe "
                                       "session's request carries no revealed_at")
            reference, geo = planner
            plans = [reference.probe_passes(geo, prompts[0], chosen[0]),
                     reference.probe_passes(geo, prompts[1], chosen[1], revealed_at[0])]
        probe.init_kv_cache()
        served, choices = _forced_pass(probe, prompts, chosen, probe_width(cfg, max_prompt), plans)
    finally:
        probe.params = probe.kv_cache = None
    return prompts, chosen, served, choices, plans


def probe_row(prompt, chosen):
    """(tokens, positions) of one probe row as the reference sees it: the
    prompt with the first PROBE_DECODE_STEPS tokens that followed it, read at
    the last prompt position and after each of those tokens."""
    tokens = list(prompt) + list(chosen[:PROBE_DECODE_STEPS])
    return tokens, [len(prompt) - 1 + k for k in range(PROBE_DECODE_STEPS + 1)]


def reference_args(prompt, chosen, plan=None) -> tuple:
    """What a reference's two passes are handed after ``geo``: ``probe_row``'s
    (tokens, positions), or (prompt, passes) of a row that was planned."""
    return probe_row(prompt, chosen) if plan is None else (list(prompt), plan[1])


def judge(cfg: dict, params, degree: int, prompts, chosen, served, choices=None,
          plans=None) -> dict:
    """``served`` against the float32 reference and its bf16 twin, row by
    row (module docstring); with a reference that replays (``CHOICES``),
    both follow ``choices`` (per row, ``_forced_pass``'s) and every choosing
    layer is held to its margin; with one that plans passes (``PASSES``),
    ``plans`` are ``serve_probe``'s and a session's token is judged at the
    read that predicted it. Raises CorrectnessError; returns the facts
    it read: per row ``err``, ``floor``, ``scale``, ``ratio`` = err / floor
    and the same ratio of root mean squares (steadier than a ratio of
    maxima; printed, not judged), and per choosing layer ``regret``,
    ``score_floor`` and the decisions that are not float32's own."""
    reference = load_reference(cfg)
    geo = reference.geometry(system.model_attrs(cfg), degree)
    budget = PROBE_DECODE_STEPS + 1
    replay = bool(getattr(reference, "CHOICES", False))
    facts = {"K": K, "reference": reference.__name__.rsplit(".", 1)[-1],
             "prompts": [len(p) for p in prompts], "rows": []}
    if replay and choices is None:
        raise CorrectnessError(
            "the configuration's reference replays choices and the program returned none", facts)
    if bool(getattr(reference, "PASSES", False)) != (plans is not None):
        raise CorrectnessError("a reference that plans passes, and it alone, is judged by its plans", facts)
    errors = []
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    for r, p in enumerate(prompts):
        args = reference_args(p, chosen[r], plans and plans[r])
        follow = {"choices": choices[r]} if replay else {}
        t0 = time.perf_counter()
        ref = reference.reference_logits(params, geo, *args, **follow)
        t1 = time.perf_counter()
        twin = reference.twin_logits(params, geo, *args, **follow)
        t2 = time.perf_counter()
        got = np.asarray(served[r], np.float32)
        if not got.shape == ref.shape == twin.shape:
            raise CorrectnessError(f"prompt {r}: served logits {got.shape}, the reference's "
                                   f"{ref.shape}, its twin's {twin.shape}", facts)
        err, floor = float(np.abs(got - ref).max()), float(np.abs(twin - ref).max())
        # per compared position the token the row put there (-1: none): a token after each
        # position read, or, planned, the one the plan says was revealed AT the read
        picks = chosen[r][:budget] if plans is None else [-1] + [c for q in args[1] for c in q["chosen"]]
        # how far below the reference's best each token the session chose is
        regret = None
        if r > 0:  # the short prompt's tokens are the session's
            if max(picks) < 0:
                raise CorrectnessError(f"prompt {r}: the plan reveals none of the session's tokens", facts)
            regret = float(max(ref[k].max() - ref[k, c] for k, c in enumerate(picks) if c >= 0))
        row = {"prompt": len(p), "err": err, "floor": floor,
               "scale": float(np.abs(ref).max()),
               "ratio": err / floor if floor > 0 else None,
               "limit": K * floor, "session_token_regret": regret,
               "rms_ratio": rms(got - ref) / max(rms(twin - ref), 1e-30),
               "ref32_s": t1 - t0, "twin_s": t2 - t1}
        if plans is not None:
            row.update(prefill_len=plans[r][0], passes=len(args[1]), reads=len(picks) - 1)
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
            margins, score_floor, *differing = reference.choice_margins(
                params, geo, *(args if plans else args[:1]), choices[r])
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


def check_model(cfg: dict, devices, seed: int, params, pspecs, degree: int,
                max_prompt: int) -> dict:
    """Part (a), with one prompt of ``max_prompt`` tokens (the longest of
    the cell's traffic). Raises CorrectnessError; returns the facts it read."""
    return judge(cfg, params, degree, *serve_probe(cfg, devices, seed, params, pspecs, max_prompt))


def check_window(records, session, vocab: int, reserved=()) -> List[str]:
    """Part (b): the faults found in the window's requests (empty = none);
    ``reserved`` are the configuration's ``reserved_token_ids``."""
    faults = []
    reserved = set(reserved)
    for rec in records:
        if rec.failed:
            faults.append(f"{rec.req_id}: {rec.failed}")
        req = session.requests.get(rec.req_id)
        if req is None:
            continue
        gen = req.generated
        if any(t < 0 or t >= vocab for t in gen):
            faults.append(f"{rec.req_id}: token outside the vocabulary")
        for t in sorted(reserved.intersection(gen)):
            faults.append(f"{rec.req_id}: reserved token {t} among its generated tokens")
        if rec.finished and len(gen) != rec.budget:
            faults.append(f"{rec.req_id}: finished with {len(gen)} of {rec.budget} tokens")
    return faults


def compared(model_facts: dict, faults: List[str], compiled_in_window: int,
             tokens_counted: int, tokens_stamped: int) -> Dict[str, List[float]]:
    """name -> [number, limit] of everything ``correct`` was decided by: per
    probe row the logit error and the session's regret against K x floor
    and, where choices were replayed, each choosing layer's regret against
    2 K x its score floor; then the window's exact comparisons."""
    out = {}
    for r, row in enumerate(model_facts.get("rows", [])):
        out[f"row{r}.logit_err"] = [row["err"], row["limit"]]
        if row["session_token_regret"] is not None:
            out[f"row{r}.session_regret"] = [row["session_token_regret"], row["limit"]]
        for l, pair in enumerate(zip(row.get("choice_regret", []), row.get("choice_limit", []))):
            out[f"row{r}.choice_regret.{l}"] = list(pair)
    out["window_faults"] = [len(faults), 0]
    out["compiled_in_window"] = [compiled_in_window, 0]
    out["tokens_not_stamped"] = [abs(tokens_counted - tokens_stamped), 0]
    return out
