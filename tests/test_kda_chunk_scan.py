"""``ops/kda_chunk_scan.py``: the sub-chunk recurrence of the chunked delta
rule on the STACKED state in place, held to the scan of ``modules/kda.
kda_chunk`` and to the recurrence token by token (``kda_step``). CPU, the
kernel in interpret mode.

What the kernel promises beyond the numbers: a row with no valid position
moves nothing (its state bit for bit, its slot never dereferenced, its ``o``
zeros whatever lies in the state), a ``reset`` row starts from zero whatever
stood there, every other layer and slot of the stack is where it was, and the
gate in ``ops/kernel_mode.py`` sends what the kernel cannot serve to the scan.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules import kda, ssm
from neuronx_distributed_inference_tpu.ops import kernel_mode
from neuronx_distributed_inference_tpu.ops.kda_chunk_scan import kda_chunk_scan

ATOL = 2e-5  # the chunked form's against the recurrence (tests/test_kimi_linear_reference.py)


def _inputs(seed, R, Q, H, D, strong=False):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = kda.l2_normalize(n(R, Q, H, D), 1e-6) * D ** -0.5
    k = kda.l2_normalize(n(R, Q, H, D), 1e-6)
    g = -jnp.asarray(rng.uniform(1e-3, 6.0 if strong else 0.3, (R, Q, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (R, Q, H)), jnp.float32)
    return q, k, n(R, Q, H, D), g, beta


def _stack(seed, L, S, H, D):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((L, S, H, D, D)), jnp.float32)


def _prefix(n_valid, Q):
    return jnp.asarray(np.arange(Q)[None, :] < np.asarray(n_valid)[:, None])


def _by_the_scan(stack, li, x, valid, reset, slots, sub):
    """``kda_mixer``'s other path: the rows' state gathered, ``kda_chunk``'s
    scan, the rows' state scattered back."""
    s = ssm.rows_state(stack, li, reset, slots)
    o, s = kda.kda_chunk(*x, s, valid, chunk_size=sub)
    return o, ssm.put_rows_state(stack, s, li, slots)


def _token_by_token(x, state, valid):
    q, k, v, g, beta = x
    outs = []
    for t in range(q.shape[1]):
        o, state = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state, valid[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), state


def _same_but(new, old, li, slots):
    """Every (layer, slot) of the stack but ``li`` x ``slots`` is bit for bit
    what it was."""
    new, old = np.asarray(new), np.asarray(old)
    untouched = np.ones(old.shape[:2], bool)
    untouched[li, list(slots)] = False
    return np.array_equal(new[untouched], old[untouched], equal_nan=True)


@pytest.mark.parametrize("Q,sub", [(8, 16), (16, 16), (32, 16), (64, 16), (128, 16), (32, 8), (64, 64)])
def test_the_kernel_is_the_scan_and_the_recurrence_at_every_chunk_width(Q, sub):
    """The widths the warm-up compiles (8 as one sub-chunk of 8), all rows
    live from a non-zero state, slots out of order, under decays strong
    enough that ``e^{-G}`` alone would overflow; two heads a tile."""
    R, H, D, li = 3, 4, 128, 1
    x = _inputs(Q, R, Q, H, D, strong=True)
    stack = _stack(Q + 1, 3, 6, H, D)
    slots = jnp.asarray([4, 0, 3], jnp.int32)
    valid, reset = jnp.ones((R, Q), bool), jnp.zeros((R,), bool)
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, reset, slots, chunk_size=sub,
                            heads_per_block=2, interpret=True)
    want_o, want = _by_the_scan(stack, li, x, valid, reset, slots, sub)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want), rtol=0, atol=ATOL)
    step_o, step_s = _token_by_token(x, stack[li, slots], valid)
    np.testing.assert_allclose(np.asarray(o), np.asarray(step_o), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, slots]), np.asarray(step_s), rtol=0, atol=ATOL)
    assert _same_but(new, stack, li, [4, 0, 3])


@pytest.mark.parametrize("hb", [4, 16])
def test_the_kernel_is_the_scan_at_the_published_widths(hb):
    """32 heads of 128, 8 rows of 128 positions, one live row of 8: the
    cell's chunk dispatch after a request finished."""
    R, Q, H, D, li = 8, 128, 32, 128, 2
    x = _inputs(5, R, Q, H, D)
    stack = _stack(6, 3, 9, H, D)
    slots = jnp.asarray([9, 9, 9, 5, 9, 9, 9, 9], jnp.int32)  # 9: no slot (an empty row's)
    valid = _prefix([0, 0, 0, 128, 0, 0, 0, 0], Q)
    reset = jnp.zeros((R,), bool)
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, reset, slots, heads_per_block=hb,
                            interpret=True)
    want_o, want = _by_the_scan(stack, li, x, valid, reset, slots, 16)
    np.testing.assert_allclose(np.asarray(o[3]), np.asarray(want_o[3]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, 5]), np.asarray(want[li, 5]), rtol=0, atol=ATOL)
    assert not np.asarray(o)[[0, 1, 2, 4, 5, 6, 7]].any()
    assert _same_but(new, stack, li, [5])


@pytest.mark.parametrize("live_rows", [[r] for r in range(8)] + [[], [0, 7], [1, 2, 5], list(range(8))])
def test_a_row_with_no_valid_position_moves_nothing(live_rows):
    """ONE live row of 8 in each position, none, some, all: the empty rows
    name a slot that is not there, NaN is planted in the state of every slot
    no live row owns (and in every other layer's), and still every live row
    is the scan's, every other byte of the stack is where it was and an empty
    row's ``o`` is zeros."""
    R, Q, H, D, S, li = 8, 32, 4, 128, 12, 0
    x = _inputs(31, R, Q, H, D)
    own = np.array([7, 2, 11, 0, 5, 9, 3, 6])
    slots = np.where(np.isin(np.arange(R), live_rows), own, S + 3)  # an empty row: out of range
    stack = np.array(_stack(32, 2, S, H, D))
    dead = np.setdiff1d(np.arange(S), own[live_rows])
    stack[li, dead[::2]] = np.nan
    stack[li, dead[1::2]] = np.inf
    stack[1, ::3] = np.nan
    stack = jnp.asarray(stack)
    valid = _prefix(np.where(np.isin(np.arange(R), live_rows), Q, 0), Q)
    reset = jnp.zeros((R,), bool)
    slots = jnp.asarray(slots, jnp.int32)
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, reset, slots, heads_per_block=2,
                            interpret=True)
    assert np.isfinite(np.asarray(o)).all()
    assert not np.asarray(o)[np.setdiff1d(np.arange(R), live_rows)].any()
    assert _same_but(new, stack, li, own[live_rows])
    if live_rows:
        want_o, want = _by_the_scan(stack, li, x, valid, reset, slots, 16)
        np.testing.assert_allclose(np.asarray(o)[live_rows], np.asarray(want_o)[live_rows], rtol=0, atol=ATOL)
        np.testing.assert_allclose(np.asarray(new[li])[own[live_rows]], np.asarray(want[li])[own[live_rows]],
                                   rtol=0, atol=ATOL)


@pytest.mark.parametrize("prefix", [1, 15, 16, 17, 127])
def test_a_partly_valid_last_chunk_stops_at_its_prefix(prefix):
    """A prompt's last chunk: the state after ``prefix`` positions is the
    recurrence's over those alone, and the outputs up to there are its."""
    R, Q, H, D, li = 2, 128, 2, 128, 0
    x = _inputs(prefix, R, Q, H, D)
    stack = _stack(prefix + 1, 1, 2, H, D)
    valid = _prefix([prefix, Q], Q)
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, jnp.zeros((R,), bool), None, interpret=True)
    step_o, step_s = _token_by_token(x, stack[li], valid)
    np.testing.assert_allclose(np.asarray(o[0, :prefix]), np.asarray(step_o[0, :prefix]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(o[1]), np.asarray(step_o[1]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li]), np.asarray(step_s), rtol=0, atol=ATOL)
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("prefix", [256, 130, 128, 3])
def test_a_whole_prompt_walks_its_tiles_with_the_state_resident(prefix):
    """256 positions are two tiles of 128: the state a head stays in VMEM
    from the first to the second (row 1, ``prefix`` valid positions from a
    reset), around an empty row whose slot is not there and a full one."""
    R, Q, H, D, li = 3, 256, 4, 128, 0
    x = _inputs(prefix, R, Q, H, D)
    stack = _stack(prefix + 1, 1, 4, H, D)
    slots = jnp.asarray([7, 2, 0], jnp.int32)
    valid, reset = _prefix([0, prefix, Q], Q), jnp.asarray([False, True, False])
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, reset, slots, heads_per_block=2, interpret=True)
    start = jnp.stack([stack[li, 0] * 0, stack[li, 2] * 0, stack[li, 0]])
    step_o, step_s = _token_by_token(x, start, valid)
    np.testing.assert_allclose(np.asarray(o[1, :prefix]), np.asarray(step_o[1, :prefix]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(o[2]), np.asarray(step_o[2]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, [2, 0]]), np.asarray(step_s[1:]), rtol=0, atol=ATOL)
    assert not np.asarray(o[0]).any() and _same_but(new, stack, li, [2, 0])


@pytest.mark.parametrize("old", [3e38, np.inf, np.nan])
def test_a_reset_row_starts_from_zero_whatever_stood_there(old):
    """Row 0 resets over a state that is not finite (or would overflow under
    any product), row 1 carries its state on, row 2 is marked ``reset`` and
    holds nothing: its state, not finite either, stays bit for bit."""
    R, Q, H, D, li = 3, 32, 2, 128, 1
    x = _inputs(3, R, Q, H, D)
    stack = np.array(_stack(4, 2, 3, H, D))
    stack[li, 0] = old
    stack[li, 2] = old
    stack = jnp.asarray(stack)
    valid, reset = _prefix([Q, 20, 0], Q), jnp.asarray([True, False, True])
    o, new = kda_chunk_scan(stack, jnp.int32(li), *x, valid, reset, None, interpret=True)
    start = stack[li].at[0].set(0.0)
    step_o, step_s = _token_by_token(x, start, valid)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(new[li, :2])).all()
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(step_o[0]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(o[1, :20]), np.asarray(step_o[1, :20]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, :2]), np.asarray(step_s[:2]), rtol=0, atol=ATOL)
    assert _same_but(new, stack, li, [0, 1])


def test_the_state_carried_from_chunk_to_chunk_is_the_recurrence():
    """Two chunks of 64 through the stack, the second from what the first
    left there (and a second row that joins at the second chunk, from zero),
    against one pass token by token."""
    R, Q, H, D, li = 2, 128, 2, 128, 1
    x = _inputs(11, R, Q, H, D)
    stack = _stack(12, 2, 4, H, D)
    slots = jnp.asarray([3, 1], jnp.int32)
    halves = [tuple(a[:, lo:lo + 64] for a in x) for lo in (0, 64)]
    first = jnp.asarray([[True] * 64, [False] * 64])
    o1, mid = kda_chunk_scan(stack, jnp.int32(li), *halves[0], first, jnp.zeros((R,), bool), slots,
                             interpret=True)
    o2, new = kda_chunk_scan(mid, jnp.int32(li), *halves[1], jnp.ones((R, 64), bool),
                             jnp.asarray([False, True]), slots, interpret=True)
    want_o, want_s = _token_by_token(x, stack[li, slots][:1], jnp.ones((1, Q), bool))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)[0]), np.asarray(want_o[0]),
                               rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, 3]), np.asarray(want_s[0]), rtol=0, atol=ATOL)
    late = tuple(a[1:, 64:] for a in x)
    late_o, late_s = _token_by_token(late, jnp.zeros((1, H, D, D)), jnp.ones((1, 64), bool))
    np.testing.assert_allclose(np.asarray(o2[1]), np.asarray(late_o[0]), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(new[li, 1]), np.asarray(late_s[0]), rtol=0, atol=ATOL)
    assert _same_but(new, stack, li, [3, 1])


@pytest.mark.parametrize("head_dim,q_len,sub,shards,compiled,taken", [
    (128, 128, 16, 1, False, True), (128, 8, 16, 1, False, True), (128, 16, 16, 1, False, True),
    (128, 32, 16, 1, False, True), (128, 64, 16, 1, False, True), (256, 128, 64, 1, False, True),
    (32, 128, 16, 1, False, False),  # the rehearsal model's heads: not whole lane rows
    (128, 40, 16, 1, False, False),  # a last sub-chunk that would be padded
    (128, 4, 16, 1, False, False),  # a sub-chunk under the sublane tile
    (128, 128, 16, 4, False, False),  # a pallas_call has no partitioning rule
    (128, 128, 16, 1, True, False),  # compiled kernels asked for off the chip
])
def test_the_gate_takes_what_the_kernel_serves(head_dim, q_len, sub, shards, compiled, taken):
    """``use_kda_chunk_scan``: on the chip, or off it where kernels run
    interpreted; one shard; whole lane rows; whole sub-chunks of whole
    sublane tiles."""
    with kernel_mode.force_compiled_kernels() if compiled else contextlib.nullcontext():
        assert kernel_mode.use_kda_chunk_scan(head_dim, q_len, sub, shards) is taken


@pytest.mark.parametrize("head_dim", [32, 128])
def test_the_mixer_takes_the_kernel_where_the_gate_does_and_the_scan_answers_elsewhere(head_dim, monkeypatch):
    """``kda_mixer`` over a chunk with slots: at head_dim 128 the stacked
    state goes through the kernel (``rows_state`` / ``put_rows_state`` are not
    called), at 32 through the scan; either way the mixer's output and state
    are the scan's."""
    from neuronx_distributed_inference_tpu.ops import kda_chunk_scan as kernel

    spec = kda.KDASpec(num_heads=2, head_dim=head_dim, gate_rank=16)
    R, Q, hidden, S = 3, 16, 64, 5
    rng = np.random.default_rng(head_dim)
    w = lambda *s: {"weight": jnp.asarray(rng.standard_normal(s) * s[0] ** -0.5, jnp.float32)}
    m = dict(qkv_proj=w(hidden, spec.conv_dim), conv1d=w(spec.conv_kernel, spec.conv_dim),
             f_a_proj=w(hidden, 16), f_b_proj=w(16, spec.d_inner), g_a_proj=w(hidden, 16),
             g_b_proj=w(16, spec.d_inner), b_proj=w(hidden, 2), o_proj=w(spec.d_inner, hidden),
             o_norm={"weight": jnp.ones((head_dim,), jnp.float32)},
             dt_bias=jnp.zeros((spec.d_inner,), jnp.float32), A_log=jnp.zeros((2,), jnp.float32))
    x = jnp.asarray(rng.standard_normal((R, Q, hidden)), jnp.float32)
    state = kda.init_delta_state(spec, 2, S, jnp.float32)
    state = kda.DeltaState(conv=state.conv, ssm=_stack(1, 2, S, 2, head_dim))
    valid, reset = _prefix([Q, 0, 9], Q), jnp.asarray([False, False, True])
    slots = jnp.asarray([4, S, 1], jnp.int32)
    calls = []
    real = kernel.kda_chunk_scan
    monkeypatch.setattr(kernel, "kda_chunk_scan", lambda *a, **kw: calls.append(1) or real(*a, **kw))
    out, new = kda.kda_mixer(m, x, state, jnp.int32(1), valid, reset, spec, slots=slots)
    assert bool(calls) == (head_dim == 128)
    monkeypatch.setattr(kernel_mode, "use_kda_chunk_scan", lambda *a: False)
    want_out, want = kda.kda_mixer(m, x, state, jnp.int32(1), valid, reset, spec, slots=slots)
    live = np.asarray(valid)[:, :, None]
    np.testing.assert_allclose(np.asarray(out) * live, np.asarray(want_out) * live, rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(new.ssm), np.asarray(want.ssm), rtol=0, atol=ATOL)
    assert np.array_equal(np.asarray(new.conv), np.asarray(want.conv))


def test_a_chunk_pass_counts_its_rows_where_the_layers_take_the_kernel():
    """``nxdi_kda_chunk_rows_total{kind}``: at head_dim 128 every chunk
    dispatch counts 4 program rows x 6 KDA layers, the prefilling row
    ``advanced`` and the three that hold nothing ``skipped``; the served
    tokens are those of the same model with the scan in every layer; at
    head_dim 32 (the scan) the family is not fed."""
    from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
    from tests.conftest import drain
    from tests.test_kimi_linear_reference import ATTRS, make_config

    def serve(head_dim):
        lin = dict(ATTRS["linear_attn_config"], head_dim=head_dim, num_heads=2)
        app = TpuModelForCausalLM(None, make_config(dict(ATTRS, linear_attn_config=lin))).load(
            random_weights=True)
        with TelemetrySession() as tel:
            s = ServingSession(app, telemetry=tel)
            s.add_request("a", np.arange(1, 40, dtype=np.int32), max_new_tokens=4)  # 39 tokens: 3 chunks of 16
            drain(s)
            out = list(s.requests["a"].generated)
            snap = tel.registry.snapshot()
        rows = {x["labels"]["kind"]: x["value"]
                for x in snap.get("nxdi_kda_chunk_rows_total", {"samples": []})["samples"]}
        return app, out, rows

    _, out, rows = serve(128)
    assert rows == {"advanced": 3 * 6, "skipped": 3 * 3 * 6}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernel_mode, "use_kda_chunk_scan", lambda *a, **kw: False)
        _, want, none = serve(128)
    assert out == want and not any(none.values())
    assert not any(serve(32)[2].values())
