"""The traced phase of a ``--trace 2`` run on the fake session and clock of
``test_driver.py``: each kind of loop re-opens under ids the window never
used, the slice starts when the session is settled, the steps sampled in
the slice are the steps run in it, and nothing the window measured moves."""

import types

from benchmark.harness import stats
from benchmark.harness.driver import PHASE_TAG, SETTLE_LIMIT_S, LoadDriver
from benchmark.harness.traffic import Traffic

from .test_driver import FakeSession, mix

SLICE_S = 3.0


class FakeProfiler:
    """``run.Profiler`` without the device: armed by the driver, it starts
    at the next tick and stops SLICE_S later."""

    def __init__(self):
        self.start_at = self.stop_at = float("inf")
        self.started = self.stopped = None

    def arm(self, now):
        self.start_at, self.stop_at = now, now + SLICE_S

    def tick(self, now):
        if self.started is None and now >= self.start_at:
            self.started = now
        elif self.started is not None and self.stopped is None and now >= self.stop_at:
            self.stopped = now


def window_and_phase(name, loop, step_s=0.5, seconds=12.0, **kw):
    clock = types.SimpleNamespace(t=0.0)
    session = FakeSession(clock, step_s)
    traffic = Traffic(mix(name), seed=1, vocab_size=100, loop=loop, seconds=seconds,
                      rate_rps=kw.get("rate_rps"))
    driver = LoadDriver(session, traffic, loop=loop, seconds=seconds,
                        clients=kw.get("clients", 0), prestart=kw.get("prestart", 0),
                        clock=lambda: clock.t, sleep=lambda s: setattr(clock, "t", clock.t + s))
    if driver.prestart:
        driver.fill()
    driver.run()
    before = set(driver.records)
    window_s = driver.window_s
    summary = stats.summarize(list(driver.records.values()), window_s)
    arrivals = None
    if loop == "open":
        arrivals = Traffic(mix(name), seed=2, vocab_size=100, loop="open",
                           seconds=SETTLE_LIMIT_S + 2 * SLICE_S, rate_rps=kw["rate_rps"])
    profiler = FakeProfiler()
    facts = driver.trace_phase(profiler, arrivals)
    # the window is what it was; of its numbers only the count of finished
    # requests could move (run.py takes them all before the phase)
    again = stats.summarize([driver.records[r] for r in before], window_s)
    assert driver.window_s == window_s
    assert {k: v for k, v in again.items() if k != "finished"} == {
        k: v for k, v in summary.items() if k != "finished"}
    return driver, session, profiler, facts, before


def check_slice(driver, profiler, facts):
    t0, t1 = profiler.started, profiler.stopped
    assert t0 >= driver.window_s and t1 - t0 >= SLICE_S
    sampled = [t for t, _ in driver.samples["live_kv_tokens"] if t0 <= t < t1]
    stepped = [a for name, a, _ in driver.spans if name == "step" and t0 <= a < t1]
    assert len(sampled) == len(stepped) == facts["step_ms_slice"]["count"] > 0
    assert not [t for t, _ in driver.samples["live_kv_tokens"] if t < driver.window_s]
    assert all(t <= driver.seconds for t, _ in driver.samples["decoding_rows"])


def test_a_closed_loop_sends_again_under_new_indices_and_settles_first():
    # steps short enough for requests of 256-768 tokens to end inside the phase
    driver, session, profiler, facts, before = window_and_phase(
        "decode", "closed", step_s=0.01, clients=4, prestart=4)
    after = set(driver.records) - before
    assert after and not {r for r in after if r in before}
    assert facts["sent"] == len(after) and not any(PHASE_TAG in r for r in after)
    assert facts["occupancy_at_slice"] >= facts["occupancy_at_close"] == 4
    assert facts["settle_s"] <= SETTLE_LIMIT_S + 0.5
    check_slice(driver, profiler, facts)


def test_a_first_round_in_mid_prefill_is_sent_again_under_tagged_ids():
    driver, session, profiler, facts, before = window_and_phase(
        "longprompt", "closed", clients=6, prestart=6)
    again = [r for r in driver.records if r.endswith(PHASE_TAG)]
    assert len(again) == 6 and {r[: -len(PHASE_TAG)] for r in again} <= before
    assert all(driver.records[r].due_s is None for r in again)
    check_slice(driver, profiler, facts)


def test_an_open_loop_goes_on_with_arrivals_of_its_own():
    driver, session, profiler, facts, before = window_and_phase(
        "chat", "open", step_s=0.7, seconds=10.0, rate_rps=2.0)
    after = set(driver.records) - before
    assert after and all(r.endswith(PHASE_TAG) for r in after)
    dues = sorted(driver.records[r].due_s for r in after)
    assert dues[0] >= driver.window_s  # due from the start of the phase, not of the window
    assert len(after) == facts["sent"] >= int(2.0 * SLICE_S) - 2  # the cell's rate goes on
    assert len(driver.traffic) == 20  # the window's own schedule is what it was
    check_slice(driver, profiler, facts)
