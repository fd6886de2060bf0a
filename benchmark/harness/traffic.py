"""One general, seeded traffic generator; a mix is a data file of parameters.

A mix (``benchmark/traffic/<mix>.json``) gives arrivals and tenants:

    {"shape_seed": 1, "pool": 64,
     "arrivals": {"kind": "poisson" | "constant" | "onoff" | "diurnal",
                  "period_s": 5.0, "on_share": 0.25, "floor": 0.25},
     "tenants": [{"name": "t", "weight": 1.0, "shared_prefix_len": 0,
                  "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                             "min": 32, "max": 2048},
                  "output": {"dist": "uniform", "min": 16, "max": 512}}]}

Length distributions: ``lognormal`` (median, sigma), ``uniform``, ``zipf``
(``a``; min + Zipf - 1) and ``fixed`` (``value``), all clipped to [min, max].
Arrival envelopes scale one mean rate: ``poisson``/``constant`` are flat,
``onoff`` spends ``on_share`` of each ``period_s`` at mean/on_share and the
rest silent, ``diurnal`` is a sinusoid between ``floor`` x peak and the peak.
The rate itself (open loop) or the number of clients (closed loop) belongs to
the cell, not to the mix.

Steadiness rule (the driver's bounds depend on it): the SCHEDULE of the work
— which request has which prompt and output length, in which order, due
when — is drawn from the mix's own ``shape_seed`` and is the same in every
run. ``--seed`` draws the token ids (and the weights). A window of the
system as it is holds some tens of requests; with the seed also permuting
their order (the first design of PR 22) a median over them moved by 6-12%
between seeds, from the order alone.

Ids a configuration reserves (``reserved_token_ids`` in its file: a mask
token, a pad) never appear in a prompt: ``draw_ids`` draws over the
vocabulary without them. A configuration that reserves none draws the ids it
always drew, bit for bit.

``first_round`` (optional, ``"mid_decode"`` by default) says where in their
life the requests already in flight at the opening of the window are: see
``Traffic.first_round``.

The arithmetic follows ``neuronx_distributed_inference_tpu/workload/
generator.py`` (seeded envelope x lognormal/Zipf lengths x tenant prefixes x
sha256 digest), moved from its virtual one-step clock to seconds.
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

ARRIVAL_KINDS = ("poisson", "constant", "onoff", "diurnal")
LENGTH_DISTS = ("lognormal", "uniform", "zipf", "fixed")
FIRST_ROUNDS = ("mid_decode", "mid_prefill")


#: least budget of a request of the first round: enough tokens inside the
#: window for its time per output token to be taken (stats.TPOT_MIN_TOKENS)
FIRST_ROUND_FLOOR = 16


class TrafficError(ValueError):
    """A mix or a cell asks for traffic this generator cannot make."""


def draw_ids(rng: np.random.Generator, vocab_size: int, size: int,
             reserved: Sequence[int] = ()) -> np.ndarray:
    """``size`` token ids, uniform over the vocabulary without ``reserved``:
    ONE ``integers`` call over ``vocab_size - len(reserved)`` values, each
    then shifted past every reserved id at or below it. With nothing
    reserved that is the call every generator here made before."""
    reserved = sorted(int(r) for r in reserved)
    if len(set(reserved)) != len(reserved) or any(not 0 <= r < vocab_size for r in reserved):
        raise TrafficError(f"reserved ids {reserved}: distinct ids inside the vocabulary, please")
    if len(reserved) >= vocab_size:
        raise TrafficError("every id of the vocabulary is reserved")
    ids = rng.integers(0, vocab_size - len(reserved), size=size)
    for r in reserved:  # ascending: an id shifted past one reserved id is tested against the next
        ids = ids + (ids >= r)
    return ids


def draw_lengths(spec: dict, n: int, rng: np.random.RandomState) -> np.ndarray:
    """``n`` lengths from one distribution spec, clipped to [min, max]."""
    dist = spec.get("dist")
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 0 < lo <= hi:
        raise TrafficError(f"bad length bounds {lo}..{hi}")
    if dist == "lognormal":
        x = np.rint(rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n))
    elif dist == "uniform":
        x = rng.randint(lo, hi + 1, size=n)
    elif dist == "zipf":
        x = lo + rng.zipf(spec["a"], size=n) - 1
    elif dist == "fixed":
        x = np.full(n, spec["value"])
    else:
        raise TrafficError(f"unknown length dist {dist!r}; known: {LENGTH_DISTS}")
    return np.clip(x, lo, hi).astype(np.int64)


def envelope(arrivals: dict, t: np.ndarray) -> np.ndarray:
    """Relative arrival rate at times ``t`` (seconds); its mean over a whole
    period is 1, so the cell's rate stays the MEAN rate under every kind."""
    kind = arrivals.get("kind", "poisson")
    if kind in ("poisson", "constant"):
        return np.ones_like(t, dtype=np.float64)
    period = float(arrivals["period_s"])
    if kind == "onoff":
        share = float(arrivals["on_share"])
        return np.where((t % period) < share * period, 1.0 / share, 0.0)
    if kind == "diurnal":
        floor = float(arrivals.get("floor", 0.25))
        depth = 0.5 * (1.0 + np.sin(2.0 * math.pi * t / period))
        return (floor + (1.0 - floor) * depth) / (floor + (1.0 - floor) * 0.5)
    raise TrafficError(f"unknown arrival kind {kind!r}; known: {ARRIVAL_KINDS}")


def arrival_times(arrivals: dict, unit_gaps: np.ndarray, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of ``len(unit_gaps)`` arrivals. The gaps
    (unit-mean exponentials for ``poisson``, ones for ``constant``) are laid
    end to end on a clock that the envelope stretches: equal areas under the
    envelope get equal shares of the arrivals. The first request is due at
    t=0 and the gaps fill the window exactly, whatever their order."""
    u = np.concatenate([[0.0], np.cumsum(unit_gaps)[:-1]]) / float(np.sum(unit_gaps))
    grid = np.linspace(0.0, seconds, max(2, int(seconds * 1000) + 1))
    mass = np.concatenate([[0.0], np.cumsum(envelope(arrivals, grid[:-1]))])
    if mass[-1] <= 0:
        raise TrafficError("arrival envelope is zero over the whole window")
    return np.interp(u, mass / mass[-1], grid)


@dataclass(frozen=True)
class RequestShape:
    tenant: int
    prompt_len: int
    output_len: int


@dataclass(frozen=True)
class TrafficRequest:
    index: int
    req_id: str
    tenant: str
    due_s: Optional[float]  # None in a closed loop: due when a client frees up
    input_ids: np.ndarray
    max_new_tokens: int


class Traffic:
    """The requests of one run. ``shapes`` and ``due`` are fixed at
    construction; token ids are drawn per request on demand, so a closed
    loop can cycle its pool for as long as the window lasts without ever
    sending the same prompt twice."""

    def __init__(self, mix: dict, *, seed: int, vocab_size: int, loop: str,
                 seconds: float, rate_rps: Optional[float] = None,
                 max_prompt_len: Optional[int] = None, reserved_ids: Sequence[int] = ()):
        self.mix = mix
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.reserved_ids = tuple(reserved_ids)
        self.loop = loop
        tenants = mix["tenants"]
        if not tenants:
            raise TrafficError("a mix needs at least one tenant")
        for t in tenants:
            if t.get("shared_prefix_len", 0) >= t["prompt"]["min"]:
                raise TrafficError(
                    f"tenant {t['name']!r}: shared_prefix_len must leave a suffix token"
                )
            if max_prompt_len is not None and t["prompt"]["max"] > max_prompt_len:
                raise TrafficError(
                    f"tenant {t['name']!r}: prompts up to {t['prompt']['max']} tokens "
                    f"exceed what the configuration admits ({max_prompt_len})"
                )
        self.tenants = tenants
        if loop == "open":
            if not rate_rps or rate_rps <= 0:
                raise TrafficError("an open loop needs rate_rps > 0")
            n = max(1, int(round(rate_rps * seconds)))
        elif loop == "closed":
            n = int(mix.get("pool", 256))
        else:
            raise TrafficError(f"unknown loop {loop!r}; known: open, closed")
        shape_rng = np.random.RandomState(int(mix["shape_seed"]) % (2**32))
        weights = np.asarray([t.get("weight", 1.0) for t in tenants], np.float64)
        which = shape_rng.choice(len(tenants), size=n, p=weights / weights.sum())
        prompt = np.zeros(n, np.int64)
        output = np.zeros(n, np.int64)
        for i, t in enumerate(tenants):
            idx = np.flatnonzero(which == i)
            prompt[idx] = draw_lengths(t["prompt"], len(idx), shape_rng)
            output[idx] = draw_lengths(t["output"], len(idx), shape_rng)
        kind = mix.get("arrivals", {}).get("kind", "poisson")
        gaps = np.ones(n) if kind == "constant" else shape_rng.exponential(1.0, size=n)
        self.first_round_kind = mix.get("first_round", "mid_decode")
        if self.first_round_kind not in FIRST_ROUNDS:
            raise TrafficError(
                f"unknown first_round {self.first_round_kind!r}; known: {FIRST_ROUNDS}"
            )
        self.shapes: List[RequestShape] = [
            RequestShape(int(which[j]), int(prompt[j]), int(output[j])) for j in range(n)
        ]
        self.due: Optional[np.ndarray] = None
        if loop == "open":
            self.due = arrival_times(mix.get("arrivals", {}), gaps, seconds)
        # --seed draws the token ids. SeedSequence takes any non-negative
        # integer, so seeds beyond 2**31 need no folding.
        self._prefix: Dict[int, np.ndarray] = {
            i: self._ids([self.seed, 1, i], int(t.get("shared_prefix_len", 0)))
            for i, t in enumerate(tenants)
        }

    def _ids(self, key: List[int], size: int) -> np.ndarray:
        """``size`` prompt ids from the generator ``key`` seeds."""
        return draw_ids(np.random.default_rng(key), self.vocab_size, size, self.reserved_ids)

    def __len__(self) -> int:
        return len(self.shapes)

    def request(self, index: int) -> TrafficRequest:
        """Request number ``index`` of the run; a closed loop wraps round its
        pool of shapes, with fresh token ids each time."""
        shape = self.shapes[index % len(self.shapes)]
        if self.loop == "open" and index >= len(self.shapes):
            raise IndexError(index)
        prefix = self._prefix[shape.tenant]
        suffix = self._ids([self.seed, 2, index], shape.prompt_len - len(prefix))
        tenant = self.tenants[shape.tenant]["name"]
        return TrafficRequest(
            index=index, req_id=f"{tenant}-{index:06d}", tenant=tenant,
            due_s=None if self.due is None else float(self.due[index]),
            input_ids=np.concatenate([prefix, suffix]).astype(np.int32),
            max_new_tokens=shape.output_len,
        )

    def first_round(self, n: int) -> List[TrafficRequest]:
        """``n`` requests that are already running when the window opens (a
        closed loop's clients; an open loop's steady-state occupancy), so
        that the window measures a loaded system and not one filling up.
        What is in flight in a steady state is not a fair draw of the mix:
        a request twice as long is in flight twice as long, and each is
        somewhere in its life. Under ``"first_round": "mid_decode"`` (the
        default; requests that spend their life decoding) the output is
        drawn in proportion to its length (from 16 candidates) and request
        k keeps the fraction (k+1)/n of it; the driver prefills these before
        the window. Under ``"mid_prefill"`` (long prompts, short answers)
        the same is done to the PROMPT: request k has the last (k+1)/n of a
        prompt still to prefill when the window opens (the driver only
        admits it), so the clients of a closed loop meet the window out of
        step, as they are after many rounds. A cut length is at least
        FIRST_ROUND_FLOOR tokens. Everything but the token ids comes from
        the mix's ``shape_seed``: the same first round in every run."""
        rng = np.random.RandomState((int(self.mix["shape_seed"]) + 1) % (2**32))
        weights = np.asarray([t.get("weight", 1.0) for t in self.tenants], np.float64)
        which = rng.choice(len(self.tenants), size=n, p=weights / weights.sum())
        frac = (rng.permutation(n) + 1.0) / n
        cut, whole = (("output", "prompt") if self.first_round_kind == "mid_decode"
                      else ("prompt", "output"))
        out = []
        for k in range(n):
            tenant = int(which[k])
            t = self.tenants[tenant]
            prefix = self._prefix[tenant]
            lengths = {whole: int(draw_lengths(t[whole], 1, rng)[0])}
            cand = draw_lengths(t[cut], 16, rng).astype(np.float64)
            drawn = float(rng.choice(cand, p=cand / cand.sum()))
            lengths[cut] = max(FIRST_ROUND_FLOOR, int(round(drawn * frac[k])))
            suffix = self._ids([self.seed, 4, k], max(1, lengths["prompt"] - len(prefix)))
            out.append(TrafficRequest(
                index=-1 - k, req_id=f"{t['name']}-first-{k:04d}", tenant=t["name"], due_s=None,
                input_ids=np.concatenate([prefix, suffix]).astype(np.int32),
                max_new_tokens=lengths["output"],
            ))
        return out

    def digest(self) -> str:
        """sha256 over the first pool of requests as they would be sent:
        the same seed gives the same digest, another seed another."""
        h = hashlib.sha256()
        for i in range(len(self.shapes)):
            r = self.request(i)
            due = -1 if r.due_s is None else int(round(r.due_s * 1e6))
            h.update(f"{r.req_id}:{due}:{r.max_new_tokens}:".encode())
            h.update(r.input_ids.tobytes())
        return h.hexdigest()

    def bounds(self) -> dict:
        """The largest prompt and whole context any request of this traffic
        can reach: what the cell's warm-up has to cover, and no more."""
        return {
            "max_prompt": max(t["prompt"]["max"] for t in self.tenants),
            "max_context": max(t["prompt"]["max"] + t["output"]["max"] for t in self.tenants),
        }

    def summary(self) -> dict:
        p = np.asarray([s.prompt_len for s in self.shapes])
        o = np.asarray([s.output_len for s in self.shapes])
        out = {
            "requests": len(self.shapes), "loop": self.loop,
            "prompt_tokens": {"mean": float(p.mean()), "p50": float(np.median(p)), "max": int(p.max())},
            "output_tokens": {"mean": float(o.mean()), "p50": float(np.median(o)), "max": int(o.max())},
        }
        if self.due is not None and len(self.due) > 1:
            out["last_due_s"] = float(self.due[-1])
        return out


def scale_mix(mix: dict, max_context: int) -> dict:
    """The mix with every length divided by one whole factor so that its
    longest context fits ``max_context`` — for the CPU rehearsal's tiny
    preset only; a cell on the chip runs its mix as written."""
    import copy

    longest = max(t["prompt"]["max"] + t["output"]["max"] for t in mix["tenants"])
    f = -(-longest // max_context)
    if f <= 1:
        return mix
    out = copy.deepcopy(mix)
    for t in out["tenants"]:
        t["shared_prefix_len"] = t.get("shared_prefix_len", 0) // f
        for spec in (t["prompt"], t["output"]):
            spec["min"] = max(1, spec["min"] // f)
            spec["max"] = max(spec["min"], spec["max"] // f)
            for key in ("median", "value"):
                if key in spec:
                    spec[key] = max(1, spec[key] // f)
        t["prompt"]["min"] = max(t["prompt"]["min"], t["shared_prefix_len"] + 1)
        t["prompt"]["max"] = max(t["prompt"]["max"], t["prompt"]["min"])
    return out
