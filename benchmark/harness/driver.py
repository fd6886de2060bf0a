"""A wall-clock load driver for ``ServingSession``: open or closed loop.

One thread, one loop: release what is due, offer it to ``add_request()``,
call ``step()``, stamp the tokens ``step()`` hands back. The session is
driven through the two calls an online caller has and through nothing else.
A request's clock starts when it was DUE, not when this loop got round to
it; how late the loop ran is reported (``late``), so a starved generator is
not read as a fast server.

``workload/driver.py`` of the program does the same on a virtual clock (one
step == one second); its numbers are step counts. This one reads seconds.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

from .roofline import qk_pairs
from .stats import RequestRecord
from .traffic import Traffic, TrafficRequest

#: add_request refusals that mean "no room now": the request stays in the
#: driver's backlog and is offered again after the next step
RETRY_REASONS = ("no_slot", "kv_blocks", "backlog")

#: the traced phase of a ``--trace 2`` run lets the session come back to the
#: occupancy it had when the window closed before the slice starts, but
#: waits no longer than this
SETTLE_LIMIT_S = 6.0

#: appended to the ids of the requests the traced phase sends again (the
#: first round, an open loop's further arrivals): no id of the window recurs
PHASE_TAG = "-traced"

#: after the window closes the loop keeps stepping until every request that
#: was due has its first token (a long prompt due at the window's end still
#: has all its chunk passes to run), but no longer than this
DRAIN_LIMIT_S = 60.0


class LoadDriver:
    def __init__(self, session, traffic: Traffic, *, loop: str, seconds: float,
                 clients: int = 0, prestart: int = 0, traced: bool = False,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.session = session
        self.traffic = traffic
        self.loop = loop
        self.seconds = float(seconds)
        #: the window as it was: from its opening to the return of the step
        #: that was running when ``seconds`` were over. Rates and times per
        #: token are taken over it, so that no step is cut in two.
        self.window_s = float(seconds)
        self.clients = int(clients)
        self.prestart = int(prestart)
        self.traced = bool(traced)  # spans onto the profiler's clock + per-step work samples
        self.clock = clock
        self.sleep = sleep
        self.records: Dict[str, RequestRecord] = {}
        self.spans: List[Tuple[str, float, float]] = []
        self.samples: Dict[str, List[Tuple[float, float]]] = {
            "decoding_rows": [], "backlog": [], "live_kv_tokens": [],
            "prefill_qk_pairs": [], "prefill_tokens": [],
        }
        self._backlog: Deque[Tuple[TrafficRequest, RequestRecord]] = deque()
        self._next = 0  # next traffic index to release
        self._open: Dict[str, RequestRecord] = {}  # admitted, not yet finished
        self._seen: Dict[str, int] = {}  # tokens already stamped per request
        self._t0: Optional[float] = None
        self._occupancy_at_close = 0  # requests in the session when the window closed
        self._annotation = None
        if self.traced:
            self._trace_spans()

    def _trace_spans(self):
        """From here on the driver's spans are also ``TraceAnnotation``s
        (on the profiler's clock) and each step's work is sampled."""
        import jax.profiler

        self.traced = True
        self._annotation = jax.profiler.TraceAnnotation

    # ---- clock and spans ---------------------------------------------------

    def now(self) -> float:
        return self.clock() - self._t0

    @contextlib.contextmanager
    def span(self, name: str):
        ann = self._annotation("bench." + name) if self._annotation else contextlib.nullcontext()
        t0 = self.now()
        with ann:
            yield
        self.spans.append((name, t0, self.now()))

    # ---- one request -------------------------------------------------------

    def _release(self, req: TrafficRequest, due_s: Optional[float]):
        rec = RequestRecord(index=req.index, req_id=req.req_id, prompt_len=len(req.input_ids),
                            budget=req.max_new_tokens, due_s=due_s)
        self.records[req.req_id] = rec
        self._backlog.append((req, rec))

    def _admit(self):
        """Offer the backlog, oldest first, until the session has no room."""
        while self._backlog:
            req, rec = self._backlog[0]
            now = self.now()
            if rec.sent_s is None:
                rec.sent_s = now
            verdict = self.session.add_request(
                req.req_id, req.input_ids, max_new_tokens=req.max_new_tokens
            )
            if verdict:
                rec.admitted_s = self.now()
                self._open[req.req_id] = rec
                self._seen[req.req_id] = 0
                self._backlog.popleft()
                continue
            if getattr(verdict, "reason", None) in RETRY_REASONS:
                return
            rec.failed = f"refused:{getattr(verdict, 'reason', None)}"
            self._backlog.popleft()

    def _step(self) -> int:
        """One ``step()``; stamp what it produced. Returns requests finished."""
        results = self.session.step()
        t = self.now()
        done = 0
        requests = self.session.requests
        for rid in results:
            rec = self._open.get(rid)
            if rec is None:
                continue
            have = len(requests[rid].generated)
            if have > self._seen[rid]:
                rec.commits.append((t, have - self._seen[rid]))
                self._seen[rid] = have
        for rid in [r for r in self._open if requests[r].finished]:
            rec = self._open.pop(rid)
            req = requests[rid]
            # a request can end on a step that does not list it (a late
            # consume): stamp what is left before closing its record
            if len(req.generated) > self._seen[rid]:
                rec.commits.append((t, len(req.generated) - self._seen[rid]))
            if getattr(req, "status", "finished") != "finished":
                rec.failed = f"{req.status}:{getattr(req, 'fail_reason', None)}"
            else:
                rec.finished = True
            done += 1
        return done

    def _sample_work(self, now: float):
        """What the step about to run has to do, from the session's public
        view of its rows: the live context the decode attention must read,
        and the query-key pairs and tokens of the prefill chunks."""
        s = self.session
        self.samples["live_kv_tokens"].append((now, float(sum(r.pos + 1 for r in s.decoding))))
        chunk = s.chunk_size
        pairs, tokens = 0.0, 0
        for r in s.prefilling[: s.max_prefill_seqs]:
            n = min(chunk, r.prompt_len - r.prefill_pos)
            pairs += qk_pairs(r.prefill_pos, n)
            tokens += n
        self.samples["prefill_qk_pairs"].append((now, pairs))
        self.samples["prefill_tokens"].append((now, float(tokens)))

    def _has_work(self) -> bool:
        s = self.session
        return bool(s.active) or bool(getattr(s, "_readmit", ()))

    # ---- the run -----------------------------------------------------------

    def fill(self):
        """Before the window: ``prestart`` requests are sent (the traffic's
        ``first_round``: a closed loop's clients, an open loop's steady
        occupancy). A first round in mid-decode is stepped until no prompt
        is left to prefill; one in mid-prefill is only admitted. These
        requests have no due time and are in no latency sample; the tokens
        they get inside the window count."""
        self._t0 = self.clock()
        for req in self.traffic.first_round(self.prestart):
            self._release(req, None)
        self._admit()
        prefill_first = self.traffic.first_round_kind == "mid_decode"
        while self._backlog or (prefill_first and self.session.prefilling):
            self._step()
            self._admit()

    def run(self, on_tick: Optional[Callable[[float], None]] = None) -> float:
        """Open the window (after ``fill()``, where the cell has a first
        round), offer the traffic for ``seconds``; the window closes
        (``window_s``) when the step that was running then returns. Then
        step on until every due request has its first token. ``on_tick(now)`` is
        called once per loop turn (the traced run starts and stops the
        profiler from it). Returns the wall time of the whole call."""
        in_flight_at_open = len(self._open)
        # rebase: the window opens now; what fill() stamped moves before 0
        t_open = self.clock()
        if self._t0 is not None:
            shift = t_open - self._t0
            for rec in self.records.values():
                rec.commits = [(t - shift, n) for t, n in rec.commits]
                for k in ("sent_s", "admitted_s"):
                    if getattr(rec, k) is not None:
                        setattr(rec, k, getattr(rec, k) - shift)
        self._t0 = t_open
        self.spans.clear()
        idle_clients = 0
        if self.loop == "closed":
            idle_clients = max(0, self.clients - in_flight_at_open)
        n_open = len(self.traffic) if self.loop == "open" else None
        drain_from = None
        while True:
            now = self.now()
            if on_tick is not None:
                on_tick(now)
            sending = now < self.seconds
            if self.loop == "open":
                # every arrival is due inside the window; one that fell due
                # during the window's last step is released after it
                while self._next < n_open and self.traffic.due[self._next] <= now:
                    self._release(self.traffic.request(self._next), float(self.traffic.due[self._next]))
                    self._next += 1
            elif sending:
                for _ in range(idle_clients):
                    self._release(self.traffic.request(self._next), now)
                    self._next += 1
                idle_clients = 0
            if not sending and drain_from is None:
                drain_from = self.window_s = now
                self._occupancy_at_close = len(self.session.active)
            if self._backlog and (sending or drain_from is not None):
                with self.span("admit"):
                    self._admit()
            if self._has_work():
                if self.traced and now <= self.seconds:
                    self._sample_work(now)
                with self.span("step"):
                    finished = self._step()
                if self.loop == "closed":
                    idle_clients += finished
                if now <= self.seconds:
                    self.samples["decoding_rows"].append((now, float(len(self.session.decoding))))
                    self.samples["backlog"].append((now, float(len(self._backlog))))
            elif sending and self.loop == "open" and self._next < n_open:
                with self.span("wait_for_arrival"):
                    self.sleep(max(0.0, min(float(self.traffic.due[self._next]), self.seconds) - self.now()))
            elif sending:
                with self.span("wait_for_arrival"):
                    self.sleep(max(0.0, min(0.001, self.seconds - self.now())))
            if drain_from is not None:
                waiting = self._backlog or any(
                    not r.commits for r in self._open.values() if r.due_s is not None
                )
                if not waiting or now - drain_from > DRAIN_LIMIT_S:
                    break
        for _, rec in self._backlog:
            rec.failed = rec.failed or "never_admitted"
        for rec in self._open.values():
            if rec.due_s is not None and not rec.commits:
                rec.failed = "no_first_token"
        return self.clock() - t_open

    def trace_phase(self, profiler, arrivals: Optional[Traffic] = None) -> dict:
        """After ``run()`` (``--trace 2``): a few seconds more of the same
        traffic for the traced slice. The loop re-opens — a closed loop's
        idle clients send again (a first round in mid-prefill is sent
        again, under new ids), an open loop goes on with ``arrivals`` (a
        Traffic of its own at the cell's rate, due times from the start of
        this phase, ids tagged) — and runs until the session holds what it
        held when the window closed, nothing waits in the backlog and, in a
        closed loop whose first round is in mid-decode, no prompt is left
        to prefill (what ``fill()`` waits for: the clients that went idle
        during the drain send together, which the window's steady state
        does not), but no longer than SETTLE_LIMIT_S. Then ``profiler.arm(now)`` and
        ``profiler.tick(now)`` once per turn until it has stopped. The
        phase has no drain: what is in flight at its end is abandoned.
        Nothing here touches what the window measured: ``window_s``, the
        window's spans and the records of its requests are read before
        this is called. Returns the phase's own facts."""
        self._trace_spans()
        t_phase = self.now()
        backlog0 = len(self._backlog)
        sent = 0
        if self.loop == "closed":
            idle_clients = max(0, self.clients - len(self._open) - len(self._backlog))
            if self.traffic.first_round_kind == "mid_prefill":
                for req in self.traffic.first_round(self.prestart)[:idle_clients]:
                    self._release(dataclasses.replace(req, req_id=req.req_id + PHASE_TAG), None)
                    idle_clients -= 1
                    sent += 1
        prefill_first = self.loop == "closed" and self.traffic.first_round_kind == "mid_decode"
        settled = None
        steps = 0
        while profiler.stopped is None:
            now = self.now()
            if settled is not None:
                profiler.tick(now)
                if profiler.stopped is not None:
                    break
            if self.loop == "open":
                while sent < len(arrivals) and t_phase + float(arrivals.due[sent]) <= now:
                    req = arrivals.request(sent)
                    self._release(dataclasses.replace(req, req_id=req.req_id + PHASE_TAG),
                                  t_phase + float(arrivals.due[sent]))
                    sent += 1
            else:
                for _ in range(idle_clients):
                    self._release(self.traffic.request(self._next), now)
                    self._next += 1
                    sent += 1
                idle_clients = 0
            if self._backlog:
                with self.span("admit"):
                    self._admit()
            if self._has_work():
                self._sample_work(now)
                with self.span("step"):
                    finished = self._step()
                steps += 1
                if self.loop == "closed":
                    idle_clients += finished
            else:
                with self.span("wait_for_arrival"):
                    self.sleep(0.001)
            if settled is None and (
                (not self._backlog and len(self.session.active) >= self._occupancy_at_close
                 and not (prefill_first and self.session.prefilling))
                or now - t_phase > SETTLE_LIMIT_S
            ):
                settled = self.now()
                profiler.arm(settled)
        def step_ms(t0, t1):
            d = [b - a for name, a, b in self.spans if name == "step" and t0 <= a < t1]
            return {"count": len(d), "mean_ms": sum(d) / len(d) * 1e3} if d else {"count": 0}

        # telemetry is on from t_phase, the profiler only during the slice:
        # the two step times tell the cost of the one from that of the other
        sliced = (profiler.started, profiler.stopped)
        return {"phase_s": self.now() - t_phase, "settle_s": settled - t_phase, "steps": steps,
                "step_ms_settling": step_ms(t_phase, sliced[0]), "step_ms_slice": step_ms(*sliced),
                "sent": sent, "backlog_at_start": backlog0,
                "occupancy_at_close": self._occupancy_at_close,
                "occupancy_at_slice": len(self.session.active)}
