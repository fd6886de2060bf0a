"""The order of a split step: every pass the step holds is dispatched before
the step waits for any pass's tokens (chunk dispatch, decode dispatch, chunk
commit, decode consume), and that order serves what the order before it
served (chunk dispatch, chunk commit, decode dispatch, decode consume), step
for step: the device sees the same programs on the same operands in the same
order. Small paged apps on the CPU, a dense one and a block-step one,
``async_mode`` on and off.
"""

from typing import Dict

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import make_random_hf_state_dict
from tests.test_sdar_reference import VOCAB as BLOCK_VOCAB, make_app as make_block_app
from tests.test_telemetry import _paged_config as paged_config


class CommitFirstSession(ServingSession):
    """The split step as it ran before the decode pass went out behind the
    chunk pass: the chunk pass is waited for and committed BEFORE the decode
    rows are drawn and dispatched."""

    def _step_inner(self) -> Dict[str, int]:
        results: Dict[str, int] = {}
        if self.chunked and self.prefilling:
            batch = self.prefilling[: self.max_prefill_seqs]
            self._commit_chunks(
                self._dispatch_chunks(batch, self.chunk_size, preempt=True), results
            )
        # a prompt's first token is this step's; its row decodes next step
        active = [r for r in self.decoding if r.req_id not in results]
        pend, self._pending = self._pending, None
        current = {
            id(req): p for req, p, _s, e, *_ in (pend[1] if pend else ())
            if e == req.epoch and not req.finished and not req.preempted
        }
        if self.blocks is not None:
            rows, chained = self.blocks.plan(active)
        else:
            rows = [(r, current[id(r)] + 1 if id(r) in current else r.pos) for r in active]
            chained = [r.slot for r in active if id(r) in current]
        ahead = None
        if rows:
            out, snap = self._dispatch_decode(rows, (pend[0], chained) if chained else None)
            if out is not None:
                ahead = (self._step_ids(out), snap)
        if self.async_decode:
            self._pending = ahead
        else:
            pend = ahead
        if pend is not None:
            self._consume(pend, results)
        return results


@pytest.fixture(scope="module")
def dense_app():
    cfg = paged_config(tpu=dict(seq_len=128))  # four slots, chunks of 16, room for a long prompt
    return TpuModelForCausalLM(None, cfg).load(state_dict=make_random_hf_state_dict(cfg))


@pytest.fixture(scope="module")
def block_app():
    return make_block_app()[0]


@pytest.fixture
def app_of(request, monkeypatch):
    """(kind, async_mode) -> the kind's app, its config saying that mode."""

    def get(kind: str, async_mode: bool):
        app = request.getfixturevalue(f"{kind}_app")
        monkeypatch.setattr(app.config.tpu_config, "async_mode", async_mode)
        return app

    return get


def _arrivals(kind: str):
    """{step: [(req_id, prompt, max_new_tokens)]}: two requests decode while
    a long prompt is chunked in beside them, then one whose only token is a
    chunk pass's, then one more long prompt."""
    vocab, chunk = (BLOCK_VOCAB - 1, 32) if kind == "block" else (128, 16)
    rng = np.random.default_rng(49)
    prompt = lambda n: rng.integers(1, vocab, size=n)
    return {
        0: [("d0", prompt(5), 20), ("d1", prompt(chunk + 3), 24)],
        4: [("long", prompt(4 * chunk + 5), 9)],
        6: [("one", prompt(chunk + 7), 1)],
        9: [("late", prompt(2 * chunk + 1), 6)],
    }


def _serve(session, arrivals, limit=120):
    """Every step's results as an ordered list, and every request's tokens."""
    steps = []
    for k in range(limit):
        for rid, ids, budget in arrivals.get(k, ()):
            assert session.add_request(rid, ids, max_new_tokens=budget)
        if k > max(arrivals) and not (session.active or session._readmit):
            break
        steps.append(list(session.step().items()))
    else:
        raise AssertionError("the session did not drain")
    return steps, {rid: list(r.generated) for rid, r in session.requests.items()}


@pytest.mark.parametrize("async_mode", [True, False], ids=["async", "sync"])
@pytest.mark.parametrize("kind", ["dense", "block"])
def test_decode_behind_chunk_serves_what_commit_first_served(app_of, kind, async_mode):
    """Each step's results, in order, and each request's tokens are those of
    the commit-first order; a finished prompt's first token stands before the
    step's decode tokens, and its request takes no decode row in that step."""
    app = app_of(kind, async_mode)
    arrivals = _arrivals(kind)
    served = {}
    for cls in (CommitFirstSession, ServingSession):
        app.init_kv_cache()
        session = cls(app)
        assert session.async_decode is async_mode
        served[cls] = _serve(session, arrivals)
        assert all(r.status == "finished" for r in session.requests.values())
    steps, tokens = served[ServingSession]
    assert (steps, tokens) == served[CommitFirstSession]
    assert [len(tokens[r]) for r in ("d0", "d1", "long", "one", "late")] == [20, 24, 9, 1, 6]
    mixed = [s for s in steps if len(s) > 1 and "long" in dict(s)]
    assert mixed, "no step held the long prompt's token beside decode tokens"
    if kind == "dense":
        # the step that ends long's prompt reports its first token FIRST
        first = next(s for s in steps if "long" in dict(s))
        assert first[0][0] == "long" and len(first) > 1


def _spans_by_step(tel):
    by_step = {}
    for e in tel.events:
        if e["type"] == "span" and "step" in e:
            by_step.setdefault(e["step"], []).append(e)
    return by_step


@pytest.mark.parametrize("async_mode", [True, False], ids=["async", "sync"])
def test_the_decode_pass_is_dispatched_before_the_chunk_pass_is_waited_for(app_of, async_mode):
    """In a step that holds a chunk pass and decode rows the decode dispatch
    ends before the chunk pass's fetch wait begins, and the step counts as
    one with a decode pass behind it; a step with a chunk pass alone counts
    as a chunk step only; a step with decode rows alone does not count."""
    app = app_of("dense", async_mode)
    app.init_kv_cache()
    arrivals = _arrivals("dense")
    # nobody decodes yet while this prompt is chunked in: chunk-only steps
    arrivals[0] = [("d1", arrivals[0][1][1], 24)]
    with TelemetrySession() as tel:
        _serve(ServingSession(app, telemetry=tel), arrivals)
    kinds = {"behind": 0, "none": 0, "decode_only": 0}
    for step, spans in _spans_by_step(tel).items():
        named = lambda name: [e for e in spans if e["name"] == name]
        outer, = named("serving.step")
        chunk, decode = named("serving.prefill_chunk.dispatch"), named("serving.decode.dispatch")
        waits = named("serving.prefill_chunk.fetch_wait")
        assert len(waits) == (1 if chunk else 0)
        if not chunk:
            assert "decode_behind_chunk" not in outer
            kinds["decode_only"] += bool(decode)
            continue
        assert outer["decode_behind_chunk"] is bool(decode)
        kinds["behind" if decode else "none"] += 1
        # dispatch order on the host is chunk, decode; then the waits in that order
        assert max(e["t1"] for e in chunk) <= waits[0]["t0"]
        for e in decode:
            assert chunk[-1]["t1"] <= e["t0"] and e["t1"] <= waits[0]["t0"]
        for e in named("serving.fetch_wait"):
            assert waits[0]["t1"] <= e["t0"]
    assert min(kinds.values()) >= 1, kinds
    snap = tel.registry.snapshot()
    counted = [snap[name]["samples"][0]["value"]
               for name in ("nxdi_chunk_steps_total", "nxdi_chunk_steps_decode_behind_total")]
    assert counted == [kinds["behind"] + kinds["none"], kinds["behind"]]


class ExhaustBehindChunk(FaultInjector):
    """The pool as a step's decode rows find it while a request that finishes
    at the same step's chunk commit still holds its blocks: every allocation
    of ``step`` AFTER its chunk pass went out fails."""

    def __init__(self, step: int):
        super().__init__()
        self.step, self.chunk_out = step, None

    def on_dispatch(self, session, label):
        super().on_dispatch(session, label)
        if label == "prefill_chunk":
            self.chunk_out = session._step_index

    def pool_exhausted(self, session) -> bool:
        hit = session._step_index == self.step == self.chunk_out
        if hit:
            self._fired(self.step, "exhaust_pool")
        return hit


@pytest.mark.parametrize("async_mode", [True, False], ids=["async", "sync"])
def test_a_decode_row_preempted_behind_a_finishing_chunk_resumes_byte_identically(
    app_of, async_mode
):
    """A request with one output token finishes at a chunk commit, which now
    comes after the step's decode rows asked for their blocks: the rows that
    found the pool exhausted are preempted, the request still finishes with
    its token in that step, and the rows resume with the tokens of a calm run."""
    app = app_of("dense", async_mode)
    arrivals = _arrivals("dense")
    del arrivals[9]

    def run(injector=None):
        app.init_kv_cache()
        session = ServingSession(app, fault_injector=injector)
        steps, tokens = _serve(session, arrivals)
        return session, steps, tokens

    calm, calm_steps, golden = run()
    ends = next(k for k, s in enumerate(calm_steps) if "one" in dict(s))  # 0-based
    shaken, steps, tokens = run(ExhaustBehindChunk(ends + 1))
    assert tokens == golden
    assert dict(steps[ends])["one"] == golden["one"][0]  # in the step it ended in
    evicted = {rid for rid, r in shaken.requests.items() if r.preemptions}
    assert evicted and evicted <= {"d0", "d1", "long"} and "one" not in evicted
    assert all(r.status == "finished" for r in shaken.requests.values())
    assert not any(r.preemptions for r in calm.requests.values())
