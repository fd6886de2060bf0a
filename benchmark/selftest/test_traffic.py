"""Same seed, same traffic; another seed, the same schedule with other tokens."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog
from benchmark.harness.traffic import Traffic, TrafficError, arrival_times, draw_ids, envelope, scale_mix

BIG = 4_000_000_123  # beyond 32 signed bits, as the driver's seeds are


def mix(name):
    with open(os.path.join(catalog.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def chat(seed, **kw):
    args = dict(seed=seed, vocab_size=1000, loop="open", seconds=40.0, rate_rps=2.0)
    return Traffic(mix("chat"), **{**args, **kw})


def test_same_seed_same_digest_other_seed_other_digest():
    assert chat(7).digest() == chat(7).digest()
    assert chat(7).digest() != chat(8).digest()
    assert chat(BIG).digest() == chat(BIG).digest()
    assert chat(BIG).digest() != chat(BIG + 2**32).digest()


def sizes(requests):
    return [(len(r.input_ids), r.max_new_tokens) for r in requests]


def test_every_seed_replays_one_schedule_with_other_tokens():
    a, b = chat(1), chat(BIG)
    assert a.shapes == b.shapes and np.array_equal(a.due, b.due)
    assert a.digest() != b.digest()  # the tokens are the seed's
    assert a.due[0] == 0.0 and a.due[-1] < 40.0 and len(a) == 80
    assert len({s.prompt_len for s in a.shapes}) > 40  # a schedule, not one size
    fa, fb = a.first_round(8), b.first_round(8)
    assert sizes(fa) == sizes(fb)
    assert not np.array_equal(fa[0].input_ids, fb[0].input_ids)


def test_first_round_mid_decode_cuts_outputs_and_mid_prefill_cuts_prompts():
    a = chat(1).first_round(20)
    assert all(r.due_s is None and r.max_new_tokens >= 16 for r in a)
    assert len({r.max_new_tokens for r in a}) > 10  # budgets spread over a life
    m = mix("longprompt")
    assert m["first_round"] == "mid_prefill"
    t = Traffic(m, seed=3, vocab_size=500, loop="closed", seconds=40.0)
    first = t.first_round(6)
    ten = m["tenants"][0]
    assert all(r.max_new_tokens == ten["output"]["value"] for r in first)  # whole
    lengths = sorted(len(r.input_ids) for r in first)
    assert lengths[0] < ten["prompt"]["min"] and lengths[-1] >= ten["prompt"]["min"]
    assert len(set(lengths)) == 6  # out of step
    with pytest.raises(TrafficError):
        Traffic({**m, "first_round": "sideways"}, seed=3, vocab_size=500, loop="closed", seconds=4.0)


def test_lengths_respect_the_mix_bounds():
    for name, loop in (("chat", "open"), ("decode", "closed"), ("longprompt", "closed")):
        m = mix(name)
        t = Traffic(m, seed=3, vocab_size=500, loop=loop, seconds=30.0, rate_rps=3.0)
        ten = m["tenants"][0]
        for i in range(len(t)):
            r = t.request(i)
            assert ten["prompt"]["min"] <= len(r.input_ids) <= ten["prompt"]["max"]
            assert ten["output"]["min"] <= r.max_new_tokens <= ten["output"]["max"]
            assert 0 <= r.input_ids.min() and r.input_ids.max() < 500


def test_a_closed_loop_cycles_its_pool_with_fresh_tokens():
    t = Traffic(mix("longprompt"), seed=5, vocab_size=1000, loop="closed", seconds=40.0)
    n = len(t)
    a, b = t.request(0), t.request(n)
    assert len(a.input_ids) == len(b.input_ids) and a.req_id != b.req_id
    assert not np.array_equal(a.input_ids, b.input_ids)


@pytest.mark.parametrize("arrivals", [
    {"kind": "poisson"}, {"kind": "constant"},
    {"kind": "onoff", "period_s": 5.0, "on_share": 0.25},
    {"kind": "diurnal", "period_s": 20.0, "floor": 0.25},
])
def test_every_envelope_keeps_the_mean_rate_and_fills_the_window(arrivals):
    gaps = np.random.RandomState(0).exponential(1.0, size=400)
    due = arrival_times(arrivals, gaps, 40.0)
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 40.0
    t = np.linspace(0, 40.0, 40001)[:-1]
    assert envelope(arrivals, t).mean() == pytest.approx(1.0, rel=0.02)
    if arrivals["kind"] == "onoff":  # nothing is due while the source is off
        assert np.all((due % 5.0) <= 0.25 * 5.0 + 1e-6)


def test_shared_prefix_and_zipf_and_tenant_weights():
    m = {"shape_seed": 1, "arrivals": {"kind": "poisson"}, "tenants": [
        {"name": "a", "weight": 3.0, "shared_prefix_len": 8,
         "prompt": {"dist": "uniform", "min": 16, "max": 32},
         "output": {"dist": "zipf", "a": 2.0, "min": 4, "max": 64}},
        {"name": "b", "weight": 1.0,
         "prompt": {"dist": "fixed", "value": 20, "min": 20, "max": 20},
         "output": {"dist": "lognormal", "median": 10, "sigma": 0.5, "min": 2, "max": 40}}]}
    t = Traffic(m, seed=9, vocab_size=100, loop="open", seconds=10.0, rate_rps=40.0)
    reqs = [t.request(i) for i in range(len(t))]
    a = [r for r in reqs if r.tenant == "a"]
    assert 0.6 < len(a) / len(reqs) < 0.9
    assert all(np.array_equal(r.input_ids[:8], a[0].input_ids[:8]) for r in a)
    assert len({tuple(r.input_ids[8:12]) for r in a}) > 1


def test_bad_mixes_are_refused():
    with pytest.raises(TrafficError):
        Traffic(mix("chat"), seed=1, vocab_size=10, loop="open", seconds=5.0)  # no rate
    with pytest.raises(TrafficError):
        chat(1, max_prompt_len=1000)  # the mix's prompts go to 2048
    with pytest.raises(TrafficError):
        Traffic(mix("chat"), seed=1, vocab_size=10, loop="sideways", seconds=5.0, rate_rps=1.0)


def test_scale_mix_fits_the_rehearsal_preset():
    m = scale_mix(mix("longprompt"), 510)
    ten = m["tenants"][0]
    assert ten["prompt"]["max"] + ten["output"]["max"] <= 510
    assert scale_mix(mix("decode"), 100000) is not None


def test_without_reserved_ids_every_prompt_is_the_parents_bit_for_bit():
    """The ids ``Traffic`` drew before a configuration could reserve any: one
    ``integers`` call over the whole vocabulary per prefix, request and
    first-round request, from these generators."""
    t = Traffic(mix("decode"), seed=BIG, vocab_size=151936, loop="closed", seconds=40.0)
    for index in (0, 5, len(t) + 3):
        then = np.random.default_rng([BIG, 2, index]).integers(0, 151936, size=len(t.request(index).input_ids))
        assert t.request(index).input_ids.dtype == np.int32
        assert np.array_equal(t.request(index).input_ids, then.astype(np.int32))
    for k, r in enumerate(t.first_round(6)):
        then = np.random.default_rng([BIG, 4, k]).integers(0, 151936, size=len(r.input_ids))
        assert np.array_equal(r.input_ids, then.astype(np.int32))
    m = mix("chat")
    m["tenants"][0]["shared_prefix_len"] = 8
    c = Traffic(m, seed=7, vocab_size=1000, loop="open", seconds=10.0, rate_rps=2.0)
    prefix = np.random.default_rng([7, 1, 0]).integers(0, 1000, size=8)
    assert np.array_equal(c.request(0).input_ids[:8], prefix)


def test_reserved_ids_are_in_no_prompt_over_a_million_drawn_ids_and_the_rest_stay_uniform():
    reserved = [0, 17, 18, 499]
    t = Traffic(mix("longprompt"), seed=BIG, vocab_size=500, loop="closed", seconds=40.0,
                reserved_ids=reserved)
    counts, n = np.zeros(500, np.int64), 0
    for r in t.first_round(8) + [t.request(i) for i in range(400)]:
        counts += np.bincount(r.input_ids, minlength=500)
        n += len(r.input_ids)
        if n > 10**6:
            break
    assert n > 10**6 and not counts[reserved].any()
    rest = np.delete(counts, reserved)
    assert rest.min() > 0.9 * n / 496 and rest.max() < 1.1 * n / 496
    ids = draw_ids(np.random.default_rng(3), 10, 1000, [9, 3, 4])  # any order, the edges too
    assert set(ids) == {0, 1, 2, 5, 6, 7, 8}
    for bad in ([3, 3], [10], [-1], list(range(10))):
        with pytest.raises(TrafficError):
            draw_ids(np.random.default_rng(3), 10, 5, bad)
