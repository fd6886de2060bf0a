"""``system.warm_up`` asks the application: one that has ``warm_serving`` is
warmed through it, with the shapes ``reachable_shapes`` lists; one that serves
through the ragged mixed step by its own ``warmup()``; any other by the loop
that knows the split step (every program once, a decode step twice: ids from
the host, then ids chained on the device)."""

import dataclasses
import types

import numpy as np

from benchmark.harness import system

SHAPES = [(1, 256), (1, 512), (128, 256)]


@dataclasses.dataclass
class Inputs:
    input_ids: np.ndarray
    bucket: int
    q_len: object


class SplitStep:
    """``app.token_generation_model`` as the loop drives it; records every call."""

    def __init__(self):
        self.calls = []

    def example_inputs(self, bucket, q_len=None):
        return Inputs(np.zeros((4, q_len or 1), np.int32), bucket, q_len)

    def __call__(self, params, cache, inputs, sampling):
        self.calls.append((inputs.bucket, inputs.q_len, int(inputs.input_ids[0, 0])))
        return types.SimpleNamespace(tokens=np.full(inputs.input_ids.shape, 7, np.int32), cache=cache + 1)


def test_an_application_with_warm_serving_is_warmed_through_it():
    asked = []
    app = types.SimpleNamespace(warm_serving=asked.append, mixed_step_model=None,
                                token_generation_model=None)  # touching the split step would raise
    system.warm_up(app, iter(SHAPES))
    assert asked == [SHAPES]


def test_one_without_is_warmed_by_the_loop_that_knows_the_split_step():
    tkg = SplitStep()
    app = types.SimpleNamespace(mixed_step_model=None, token_generation_model=tkg, params=None, kv_cache=0)
    system.warm_up(app, SHAPES)
    # a decode step twice (host ids 0, then the step's own token 7 chained), a chunk once
    assert tkg.calls == [(256, None, 0), (256, None, 7), (512, None, 0), (512, None, 7), (256, 128, 0)]
    assert app.kv_cache == 5


def test_the_ragged_mixed_step_keeps_its_own_warm_up():
    done = []
    app = types.SimpleNamespace(mixed_step_model=object(), warmup=lambda: done.append(True),
                                token_generation_model=None)
    system.warm_up(app, SHAPES)
    assert done == [True]
