"""The load driver on a fake session and a fake clock: the window ends with
a step, the first round in mid-prefill is admitted and not stepped, and the
trace reader counts the work of exactly the traced steps."""

import json
import os
import types

import pytest

from benchmark.harness import catalog, stats
from benchmark.harness.driver import LoadDriver
from benchmark.harness.readers import trace as trace_reader
from benchmark.harness.traffic import Traffic


class FakeSession:
    """Every step takes ``step_s`` on the fake clock; a prompt prefills in
    one step and every decoding request gets one token per step."""

    chunk_size, max_prefill_seqs = 128, 8

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.requests, self.prefilling, self.decoding = {}, [], []
        self.steps = 0

    @property
    def active(self):
        return self.prefilling + self.decoding

    def add_request(self, req_id, ids, max_new_tokens):
        req = types.SimpleNamespace(req_id=req_id, generated=[], finished=False, status="active",
                                    budget=max_new_tokens, prompt_len=len(ids), prefill_pos=0, pos=0)
        self.requests[req_id] = req
        self.prefilling.append(req)
        return True

    def step(self):
        self.steps += 1
        self.clock.t += self.step_s
        out = {}
        for r in self.decoding + self.prefilling:
            r.generated.append(1)
            out[r.req_id] = 1
            if len(r.generated) >= r.budget:
                r.finished, r.status = True, "finished"
        self.decoding = [r for r in self.decoding + self.prefilling if not r.finished]
        self.prefilling = []
        return out


def mix(name):
    with open(os.path.join(catalog.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def drive(step_s, seconds=20.0):
    clock = types.SimpleNamespace(t=100.0)
    session = FakeSession(clock, step_s)
    traffic = Traffic(mix("decode"), seed=1, vocab_size=100, loop="closed", seconds=seconds)
    driver = LoadDriver(session, traffic, loop="closed", seconds=seconds, clients=4, prestart=4,
                        clock=lambda: clock.t, sleep=lambda s: None)
    driver.fill()
    driver.run()
    return driver, session


@pytest.mark.parametrize("step_s", [0.3, 0.303, 0.7])
def test_the_window_ends_with_a_step_and_a_rate_is_over_all_of_it(step_s):
    driver, _ = drive(step_s)
    steps = round(driver.window_s / step_s)
    assert driver.window_s == pytest.approx(steps * step_s) and 20.0 <= driver.window_s < 20.0 + step_s
    s = stats.summarize(list(driver.records.values()), driver.window_s)
    # 4 rows, a token each per step, no step cut in two: the rate is exact,
    # and 1% more time per step is 1% less rate (not 0% or one step's worth)
    assert s["out_tok_s"] == pytest.approx(4 / step_s)
    assert s["tpot_p95_ms"] == pytest.approx(step_s * 1e3)


def test_a_first_round_in_mid_prefill_is_admitted_and_not_stepped():
    clock = types.SimpleNamespace(t=0.0)
    session = FakeSession(clock, 0.5)
    traffic = Traffic(mix("longprompt"), seed=1, vocab_size=100, loop="closed", seconds=12.0)
    driver = LoadDriver(session, traffic, loop="closed", seconds=12.0, clients=6, prestart=6,
                        clock=lambda: clock.t, sleep=lambda s: None)
    driver.fill()
    assert session.steps == 0 and len(session.prefilling) == 6
    driver.run()
    due = [r for r in driver.records.values() if r.due_s is not None]
    assert due and all(r.first_token_s is not None for r in due)  # drained to every first token


def test_a_share_counts_the_work_of_exactly_the_traced_steps():
    samples = {"live_kv_tokens": [(t, 10.0) for t in (1.0, 2.0, 3.0, 4.0, 5.0)]}
    ctx = {"slice": (2.0, 5.0), "samples": samples, "trace": {"span_counts": {"step": 3}}}
    assert trace_reader._slice_sum(ctx, "live_kv_tokens") == 30.0  # 2, 3, 4: not the stop turn's
    ctx["trace"]["span_counts"]["step"] = 4
    with pytest.raises(ValueError):
        trace_reader._slice_sum(ctx, "live_kv_tokens")


def test_an_arrival_due_during_the_last_step_is_still_attempted():
    clock = types.SimpleNamespace(t=0.0)
    session = FakeSession(clock, 0.7)
    traffic = Traffic(mix("chat"), seed=1, vocab_size=100, loop="open", seconds=10.0, rate_rps=2.0)
    driver = LoadDriver(session, traffic, loop="open", seconds=10.0,
                        clock=lambda: clock.t, sleep=lambda s: setattr(clock, "t", clock.t + s))
    driver.run()
    assert traffic.due[-1] > 10.0 - 0.7  # falls due while the window's last step runs
    s = stats.summarize(list(driver.records.values()), driver.window_s)
    assert s["attempted"] == len(traffic) == 20 and s["failed"] == 0 and s["ttft_n"] == 20
