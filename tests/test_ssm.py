"""modules/ssm.py: the chunked state-space-dual form, the one-token step and
the sequential recurrence are one function; masked positions and rows leave
conv tail and state bit-identical; the Pallas decode update
(ops/ssm_state_update.py, interpret mode here) is the one-token step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import ssm

R, H, P, N = 3, 4, 8, 16


def _inputs(Q, G=1, seed=0, slow=True):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, B, C = f(R, Q, H, P), f(R, Q, G, N), f(R, Q, G, N)
    # slow-decay heads (the published initialisation: dt in 1e-3..1e-1, A = -(1..H))
    # hold a lost carry to account; fast ones forget it within a few tokens
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (R, Q, H)) if slow else rng.uniform(0.3, 1.0, (R, Q, H)),
                     jnp.float32)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    state = f(R, H, P, N)
    return x, B, C, dt, A, state


def _sequential(x, B, C, dt, A, state, valid):
    """The recurrence, token by token, in numpy float64."""
    x, B, C, dt, A, s = (np.asarray(a, np.float64) for a in (x, B, C, dt, A, state))
    G = B.shape[2]
    ys = np.zeros(x.shape)
    for r in range(x.shape[0]):
        for t in range(x.shape[1]):
            if not valid[r, t]:
                continue
            Bh, Ch = np.repeat(B[r, t], H // G, 0), np.repeat(C[r, t], H // G, 0)
            s[r] = np.exp(dt[r, t] * A)[:, None, None] * s[r] + (
                dt[r, t][:, None] * x[r, t])[:, :, None] * Bh[:, None, :]
            ys[r, t] = np.einsum("hpn,hn->hp", s[r], Ch)
    return ys, s


@pytest.mark.parametrize("Q,chunk,G", [(12, 4, 1), (12, 5, 1), (12, 256, 1), (7, 3, 2), (1, 256, 1)])
def test_chunk_form_is_the_sequential_recurrence(Q, chunk, G):
    x, B, C, dt, A, state = _inputs(Q, G)
    valid = np.ones((R, Q), bool)
    y, new = ssm.mamba2_chunk(x, B, C, dt, A, state, jnp.asarray(valid), chunk_size=chunk)
    y_ref, s_ref = _sequential(x, B, C, dt, A, state, valid)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new), s_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("G", [1, 2])
def test_step_repeated_is_the_chunk(G):
    Q = 9
    x, B, C, dt, A, state = _inputs(Q, G, seed=1)
    valid = jnp.ones((R, Q), bool)
    y_c, s_c = ssm.mamba2_chunk(x, B, C, dt, A, state, valid, chunk_size=4)
    s, ys = state, []
    for t in range(Q):
        y_t, s = ssm.mamba2_step(x[:, t], B[:, t], C[:, t], dt[:, t], A, s, jnp.ones((R,), bool))
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)), np.asarray(y_c), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_c), rtol=2e-4, atol=2e-4)


def test_a_chunk_split_in_two_carries_its_state():
    """Two passes of 5 and 7 positions equal one of 12 (slow-decay heads: a
    zeroed carry would show)."""
    x, B, C, dt, A, state = _inputs(12, seed=2)
    ones = lambda q: jnp.ones((R, q), bool)
    y, s = ssm.mamba2_chunk(x, B, C, dt, A, state, ones(12))
    y1, s1 = ssm.mamba2_chunk(x[:, :5], B[:, :5], C[:, :5], dt[:, :5], A, state, ones(5))
    y2, s2 = ssm.mamba2_chunk(x[:, 5:], B[:, 5:], C[:, 5:], dt[:, 5:], A, s1, ones(7))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=2e-4, atol=2e-4)
    y2z, _ = ssm.mamba2_chunk(x[:, 5:], B[:, 5:], C[:, 5:], dt[:, 5:], A, jnp.zeros_like(s1), ones(7))
    assert float(jnp.abs(y2z - y2).max()) > 0.1  # the carry is not small here


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_masked_positions_and_rows_leave_the_state_bit_identical(form):
    Q = 8
    x, B, C, dt, A, state = _inputs(Q, seed=3)
    n = np.array([5, 0, Q])  # row 0 padded after 5, row 1 idle, row 2 full
    valid = np.arange(Q)[None, :] < n[:, None]
    if form == "chunk":
        y, new = ssm.mamba2_chunk(x, B, C, dt, A, state, jnp.asarray(valid), chunk_size=3)
        _, ref = ssm.mamba2_chunk(x[:, :5], B[:, :5], C[:, :5], dt[:, :5], A, state,
                                  jnp.ones((R, 5), bool), chunk_size=3)
        np.testing.assert_allclose(np.asarray(new[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-6)
    else:
        _, new = ssm.mamba2_step(x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A, state, jnp.asarray(n > 0))
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))  # bit for bit


def test_conv_tail_shifts_by_the_count_of_real_tokens():
    K, Cdim, Q = 4, 6, 8
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.standard_normal((R, Q, Cdim)), jnp.bfloat16)
    tail = jnp.asarray(rng.standard_normal((K - 1, R, Cdim)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((K, Cdim)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((Cdim,)), jnp.float32)
    n = np.array([5, 0, 2])
    out, new_tail = ssm.causal_conv(xs, tail, w, b, jnp.asarray(n, jnp.int32))
    assert np.array_equal(np.asarray(new_tail[:, 1], np.float32), np.asarray(tail[:, 1], np.float32))
    window = np.concatenate([np.swapaxes(np.asarray(tail, np.float32), 0, 1),
                             np.asarray(xs, np.float32)], 1)
    for r in range(R):
        assert np.array_equal(np.asarray(new_tail[:, r], np.float32), window[r, n[r] : n[r] + K - 1])
    ref = sum(np.asarray(w)[k] * window[:, k : k + Q] for k in range(K)) + np.asarray(b)
    np.testing.assert_allclose(np.asarray(out), ref / (1 + np.exp(-ref)), rtol=1e-5, atol=1e-5)
    # two passes (5 then 3 tokens of row 0) see what one pass of 8 sees
    out_a, tail_a = ssm.causal_conv(xs[:, :5], tail, w, b, jnp.full((R,), 5, jnp.int32))
    out_b, _ = ssm.causal_conv(xs[:, 5:], tail_a, w, b, jnp.full((R,), 3, jnp.int32))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([out_a, out_b], 1)), np.asarray(out),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("heads_per_block", [2, 4])
def test_pallas_state_update_is_the_step(heads_per_block):
    from neuronx_distributed_inference_tpu.ops.ssm_state_update import ssm_state_update

    L, li = 3, 1
    x, B, C, dt, A, _ = _inputs(1, seed=5)
    rng = np.random.default_rng(6)
    stacked = jnp.asarray(rng.standard_normal((L, R, H, P, N)), jnp.float32)
    valid = jnp.asarray([True, False, True])
    reset = jnp.asarray([False, False, True])
    y, new = ssm_state_update(
        stacked, jnp.int32(li), x[:, 0], B[:, 0, 0], C[:, 0, 0], dt[:, 0], A, valid, reset,
        heads_per_block=heads_per_block, interpret=True,
    )
    start = jnp.where(reset[:, None, None, None], 0.0, stacked[li])
    y_ref, s_ref = ssm.mamba2_step(x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A, start, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[li]), np.asarray(s_ref), rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[li, 1]), np.asarray(stacked[li, 1]))  # the idle row
    for other in (0, 2):  # the other layers are not touched
        assert np.array_equal(np.asarray(new[other]), np.asarray(stacked[other]))


def _held_to_the_step(heads, p, n, groups, rows=4, L=2, li=1, dirty=False, seed=0, bf16_bc=False, **kernel_kw):
    """One call of the kernel against ``mamba2_step``: row 1 invalid, row 2
    reset (``dirty``: its old state holds large and non-finite values, which a
    select and not a product must drop); ``kernel_kw``: ``heads_per_block``."""
    from neuronx_distributed_inference_tpu.ops.ssm_state_update import ssm_state_update

    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x, B, C = f(rows, heads, p), f(rows, groups, n), f(rows, groups, n)
    if bf16_bc:  # as the serving path hands them over
        x, B, C = (a.astype(jnp.bfloat16) for a in (x, B, C))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, heads)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (heads,)), jnp.float32)
    stacked = np.asarray(rng.standard_normal((L, rows, heads, p, n)), np.float32)
    if dirty:
        stacked[li, 2] = np.resize(np.array([3e38, np.inf, np.nan], np.float32), stacked[li, 2].shape)
    stacked = jnp.asarray(stacked)
    valid = jnp.asarray([True, False, True, True][:rows])
    reset = jnp.asarray([False, False, True, False][:rows])
    y, new = ssm_state_update(stacked, jnp.int32(li), x, B, C, dt, A, valid, reset,
                              interpret=True, **kernel_kw)
    start = jnp.where(reset[:, None, None, None], 0.0, stacked[li])
    y_ref, s_ref = ssm.mamba2_step(x, B, C, dt, A, start, valid)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(new[li])).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[li]), np.asarray(s_ref), rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[li, 1]), np.asarray(stacked[li, 1]))  # the invalid row
    for other in range(L):  # the other layers are not touched
        if other != li:
            assert np.array_equal(np.asarray(new[other]), np.asarray(stacked[other]), equal_nan=True)


@pytest.mark.parametrize("groups,heads_per_block", [(1, 4), (2, 2), (2, 4), (8, 1), (8, 2), (8, 8)])
def test_pallas_state_update_with_groups_is_the_step(groups, heads_per_block):
    """B and C of (rows, G, N): head h reads group h // (heads / G), whether a
    head block lies inside one group or covers whole groups; an invalid row's
    state is rewritten bit for bit, a reset row starts from zero."""
    from neuronx_distributed_inference_tpu.ops.ssm_state_update import pick_heads_per_block

    _held_to_the_step(8, 8, 16, groups, seed=60 + groups, heads_per_block=heads_per_block)
    # the block the kernel picks for itself lies inside a group or covers whole ones
    hb = pick_heads_per_block(64, groups=groups)
    assert 64 % hb == 0 and (hb % (64 // groups) == 0 or (64 // groups) % hb == 0)
    assert pick_heads_per_block(64, groups=1) == pick_heads_per_block(64) == 16


@pytest.mark.parametrize("dirty", [False, True], ids=["clean", "nonfinite_reset_row"])
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("heads_per_block", [16, 32, 64])
def test_pallas_state_update_is_the_step_at_every_tile_the_table_can_give(heads_per_block, groups, dirty):
    """64 heads at each heads-a-tile the registry sweeps (16 / 32 / 64), inside
    one group of B/C (2 groups, 16 heads a tile) or over 1, 2, 4 or 8 whole
    groups: the state as today, ``y`` at today's tolerance; a reset row whose
    old state holds 3e38, inf and NaN starts from zero."""
    _held_to_the_step(64, 8, 16, groups, dirty=dirty, seed=70 + groups, heads_per_block=heads_per_block)


@pytest.mark.parametrize("heads", [16, 32, 64])
def test_pallas_state_update_takes_its_tile_from_the_table(heads):
    """No ``heads_per_block``: the tile is what the tuning table gives the
    call's shape under the kernel's name (here through ``tile_overrides``, the
    lookup a committed entry takes), held to the kernel's rule; the result is
    the keyword's, bit for bit."""
    from neuronx_distributed_inference_tpu.ops import ssm_state_update as su
    from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_overrides

    rng = np.random.default_rng(80)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows, H, p, n, G = 2, 64, 8, 16, 8
    args = (f(2, rows, H, p, n), jnp.int32(0), f(rows, H, p), f(rows, G, n), f(rows, G, n),
            jnp.asarray(rng.uniform(0.01, 0.5, (rows, H)), jnp.float32), -jnp.ones((H,), jnp.float32),
            jnp.ones((rows,), bool), jnp.zeros((rows,), bool))
    raw = su.ssm_state_update.__wrapped__  # the jitted wrapper keys its trace on shapes alone
    with tile_overrides(su.KERNEL, {"heads": heads}):
        assert su.heads_wanted(H, G, p, n) == heads
        y_t, new_t = raw(*args, interpret=True)
    assert su.heads_wanted(H, G, p, n) == su.DEFAULT_HEADS_PER_BLOCK  # no entry for this shape
    y_k, new_k = raw(*args, heads_per_block=heads, interpret=True)
    assert np.array_equal(np.asarray(y_t), np.asarray(y_k)) and np.array_equal(np.asarray(new_t), np.asarray(new_k))
    # a head count the wanted tile does not divide, or groups it would split, falls to the rule
    assert su.pick_heads_per_block(24, 32, groups=1) == 24 and su.pick_heads_per_block(48, 32, groups=1) == 24
    assert su.pick_heads_per_block(48, 32, groups=3) == 16 and su.pick_heads_per_block(6, 64, groups=2) == 6


@pytest.mark.parametrize("groups,dirty", [(1, False), (8, True)], ids=["granite-4.0-h", "nemotron_h"])
def test_pallas_state_update_at_the_published_shapes(groups, dirty):
    """64 heads of 64 over a state of 128, one group (Granite-4.0-H) and eight
    (``nemotron_h``), B, C and x in bfloat16 as the serving path hands them
    over, the tile the committed table's (32 heads at both shapes: measured)."""
    from neuronx_distributed_inference_tpu.ops import ssm_state_update as su
    from neuronx_distributed_inference_tpu.ops.tile_defaults import table_entry

    entry = table_entry(su.KERNEL, f"h64g{groups}x64x128", "float32")
    assert entry == {"provenance": "measured", "tiles": {"heads": 32}}
    assert su.pick_heads_per_block(64, su.heads_wanted(64, groups, 64, 128), groups=groups) == 32
    _held_to_the_step(64, 64, 128, groups, rows=3, L=1, li=0, dirty=dirty, seed=90 + groups, bf16_bc=True)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_gated_norm_by_group_is_its_equation(groups):
    rng = np.random.default_rng(7)
    d = 64
    y = jnp.asarray(rng.standard_normal((3, 5, d)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((3, 5, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    got = np.asarray(ssm.gated_rms_norm(y, z, w, 1e-5, groups=groups))
    v = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    parts = v.reshape(3, 5, groups, d // groups)
    want = (parts / np.sqrt(np.mean(parts ** 2, -1, keepdims=True) + 1e-5)).reshape(3, 5, d) * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if groups > 1:  # and it is not the one-group norm
        assert np.abs(got - np.asarray(ssm.gated_rms_norm(y, z, w, 1e-5))).max() > 1e-2
