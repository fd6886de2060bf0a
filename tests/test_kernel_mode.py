"""Consolidated kernel dispatch gates (ISSUE 17 small fix): one tested
predicate per kernel in ops/kernel_mode.py.

Every kernel/native auto-gate lives in ONE module with a shared tri-state
convention (None = auto, True = force with shape guards + a warning on
fallback, False = off). These tests pin each predicate in isolation so a
change to one kernel's auto condition cannot silently flip another's — in
particular, the cache-reading attention kernels (ragged since ISSUE 17,
paged flash and TKG since ISSUE 33) launch per head shard and ask only that
the head counts divide the degree, which must NOT loosen the single-shard
requirement on the contiguous flash / MoE gates, whose pallas_calls still
meet a sharded operand bare.

The suite runs on the CPU harness, so ``on_tpu()`` is False throughout:
auto paths that require TPU are asserted off here and force-enabled paths
(the shape-guard logic) carry the rest.
"""

import numpy as np
import pytest

import jax

from neuronx_distributed_inference_tpu.modules.attention import AttnSpec
from neuronx_distributed_inference_tpu.modules.moe import MoESpec
from neuronx_distributed_inference_tpu.ops import kernel_mode as km


def _spec(**kw):
    return AttnSpec(num_heads=8, num_kv_heads=2, head_dim=64, **kw)


def test_on_tpu_and_single_shard():
    assert km.on_tpu() is False  # the CPU harness
    assert km.single_shard(_spec())
    assert not km.single_shard(_spec(model_parallel=2))


def test_heads_divide():
    assert km.heads_divide(_spec())  # degree 1 divides anything
    assert km.heads_divide(_spec(model_parallel=2))  # 8 / 2 heads over 2
    assert not km.heads_divide(_spec(model_parallel=4))  # 2 kv heads over 4
    assert not km.heads_divide(_spec(model_parallel=3))


def test_flash_shape_ok():
    assert km.flash_shape_ok(_spec(), 128)
    assert not km.flash_shape_ok(_spec(), 127)  # not 128-tiled
    assert not km.flash_shape_ok(_spec(), 64)  # below one tile
    assert not km.flash_shape_ok(
        AttnSpec(num_heads=8, num_kv_heads=2, head_dim=80), 128
    )  # head_dim not lane-aligned


def test_use_flash_tristate():
    # auto requires TPU: off on this host even for a legal shape
    assert not km.use_flash(_spec(), 128)
    # force honors the shape guards (warns on fallback)
    assert km.use_flash(_spec(use_flash_kernel=True), 128)
    assert not km.use_flash(_spec(use_flash_kernel=True), 100)
    assert not km.use_flash(_spec(use_flash_kernel=False), 128)
    # force-enable ignores the single-shard auto condition deliberately
    assert km.use_flash(_spec(use_flash_kernel=True, model_parallel=2), 128)


def test_use_packed_pairs_small_heads():
    assert km.use_packed(_spec())  # D=64: auto-on
    assert not km.use_packed(
        AttnSpec(num_heads=8, num_kv_heads=2, head_dim=128)
    )  # full-lane heads don't pack
    assert not km.use_packed(
        AttnSpec(num_heads=1, num_kv_heads=1, head_dim=64)
    )  # nothing to pair
    assert not km.use_packed(_spec(use_packed_heads=False))


def test_use_tkg_shape_guards_and_auto():
    forced = _spec(use_tkg_kernel=True)
    assert km.use_tkg(forced, q_len=1, kv_width=512)
    assert km.use_tkg(forced, q_len=1, kv_width=128)  # force: short kv ok
    assert not km.use_tkg(forced, q_len=32, kv_width=512)  # not decode-sized
    assert not km.use_tkg(forced, q_len=1, kv_width=96)  # unaligned kv
    assert not km.use_tkg(_spec(use_tkg_kernel=False), 1, 512)
    # auto requires TPU + kv_width >= 512
    assert not km.use_tkg(_spec(), 1, 512)
    # a sharded mesh is served per head shard: no single-shard condition,
    # but the head counts must divide the degree, forced or not
    assert km.use_tkg(_spec(use_tkg_kernel=True, model_parallel=2), 1, 512)
    assert not km.use_tkg(_spec(use_tkg_kernel=True, model_parallel=4), 1, 512)
    odd_d = AttnSpec(
        num_heads=8, num_kv_heads=2, head_dim=80, use_tkg_kernel=True
    )
    assert not km.use_tkg(odd_d, 1, 512)


def test_use_paged_flash_prefill_only():
    forced = _spec(use_flash_kernel=True)
    assert km.use_paged_flash(forced, q_len=64)
    assert km.use_paged_flash(forced, q_len=8)  # force: small chunks ok
    assert not km.use_paged_flash(forced, q_len=4)  # decode-sized: TKG's job
    assert not km.use_paged_flash(_spec(use_flash_kernel=False), 64)
    assert not km.use_paged_flash(_spec(), 64)  # auto requires TPU
    assert km.use_paged_flash(_spec(use_flash_kernel=True, model_parallel=2), 64)
    assert not km.use_paged_flash(
        _spec(use_flash_kernel=True, model_parallel=4), 64
    )  # 2 kv heads do not divide 4: native, not a shard_map error


def test_auto_gates_on_a_sharded_mesh(monkeypatch):
    """What the four-chip cell takes (Qwen3-14B, 40 / 8 heads of 128 over
    tp = 4, every kernel option at its default) once the backend is a TPU:
    both paged kernels and the decode kernel; the contiguous flash prefill,
    which has no per-shard launch, keeps its single-shard condition."""
    monkeypatch.setattr(km, "on_tpu", lambda: True)
    tp4 = AttnSpec(num_heads=40, num_kv_heads=8, head_dim=128, model_parallel=4)
    tp1 = AttnSpec(num_heads=40, num_kv_heads=8, head_dim=128)
    for spec in (tp1, tp4):
        assert km.use_tkg(spec, q_len=1, kv_width=2048)
        assert not km.use_tkg(spec, q_len=1, kv_width=256)  # auto: kv >= 512
        assert km.use_paged_flash(spec, q_len=128)
        assert not km.use_paged_flash(spec, q_len=32)  # auto: q >= 64
        assert km.use_ragged(spec, total_q=128)
    assert km.use_flash(tp1, 128) and not km.use_flash(tp4, 128)
    odd = AttnSpec(num_heads=40, num_kv_heads=8, head_dim=128, model_parallel=16)
    assert not km.use_tkg(odd, 1, 2048) and not km.use_paged_flash(odd, 128)


def _moe_spec(**kw):
    return MoESpec(num_experts=4, top_k=2, **kw)


def test_use_grouped_matmul_serves_plain_experts_on_one_shard(monkeypatch):
    """The grouped-matmul kernel's gate: no option forces it; it rests on the
    entry (plain weights in the rows' dtype, widths on the lanes, at most a
    bias), the mesh (ep = 1, one model-parallel shard) and the backend."""
    bf16 = np.dtype("float32")  # any dtype: the gate compares, it does not compute
    w = lambda *s: {"weight": np.ones(s, bf16)}
    plain = {"gate_proj": w(4, 128, 256), "up_proj": w(4, 128, 256), "down_proj": w(4, 256, 128)}
    assert not km.use_grouped_matmul(_moe_spec(), plain, bf16)  # the CPU harness
    monkeypatch.setattr(km, "on_tpu", lambda: True)
    assert km.use_grouped_matmul(_moe_spec(), plain, bf16)
    stacked = {k: w(6, *v["weight"].shape) for k, v in plain.items()}  # (L, E, in, out)
    assert km.use_grouped_matmul(_moe_spec(), stacked, bf16)
    biased = {k: dict(v, bias=np.ones((4, v["weight"].shape[-1]), bf16)) for k, v in plain.items()}
    assert km.use_grouped_matmul(_moe_spec(), biased, bf16)
    assert not km.use_grouped_matmul(_moe_spec(), plain, np.dtype("float16"))  # a cast would copy
    scaled = dict(plain, up_proj=dict(plain["up_proj"], scale=np.ones((4, 256))))
    assert not km.use_grouped_matmul(_moe_spec(), scaled, bf16)
    narrow = dict(plain, down_proj=w(4, 256, 48))  # off the 128 lanes
    assert not km.use_grouped_matmul(_moe_spec(), narrow, bf16)
    assert not km.use_grouped_matmul(_moe_spec(ep_degree=2), plain, bf16)
    assert not km.use_grouped_matmul(_moe_spec(model_parallel=2), plain, bf16)


def test_grouped_beats_dense_from_the_shapes():
    """Passes over a layer's expert weights: dense ``rows / 240`` once
    arithmetic bounds it, grouped a pass an expert a visit."""
    assert km.grouped_beats_dense(16, 1, 1024) and km.grouped_beats_dense(16, 1, 512)
    assert km.grouped_beats_dense(128, 8, 1024) and km.grouped_beats_dense(64, 6, 512)
    assert km.grouped_beats_dense(8, 2, 1024)
    assert not km.grouped_beats_dense(16, 1, 240)  # the stream bounds dense: one pass
    assert not km.grouped_beats_dense(8, 4, 512)  # half the experts a token: 2.9 passes to 2.1
    assert not km.grouped_beats_dense(4, 2, 1024)  # 4.8 passes to 4.3


def test_use_ragged_allows_sharded_meshes():
    """The ISSUE 17 gate change: NO single-shard condition — the dispatch
    launches per head shard — but head counts must divide the
    model-parallel degree so a hand-built spec degrades to native."""
    forced = _spec(use_flash_kernel=True)
    assert km.use_ragged(forced, total_q=64)
    assert km.use_ragged(forced, total_q=64) and km.use_ragged(
        _spec(use_flash_kernel=True, model_parallel=2), 64
    )
    assert not km.use_ragged(_spec(use_flash_kernel=True, model_parallel=3), 64)
    assert not km.use_ragged(forced, total_q=65)  # not q-tile aligned
    assert not km.use_ragged(_spec(use_flash_kernel=False), 64)
    assert not km.use_ragged(_spec(), 64)  # auto requires TPU


def test_kernel_interpret_and_force_compiled():
    assert km.kernel_interpret()  # CPU host: interpret
    with km.force_compiled_kernels():
        assert not km.kernel_interpret()
    assert km.kernel_interpret()


# ---------------------------------------------------------------------------
# int4 quant matmul gate (ISSUE 17 tentpole b)
# ---------------------------------------------------------------------------


def test_use_quant_matmul_mode_stack():
    # auto requires TPU
    assert not km.use_quant_matmul(8, 512, 512)
    with km.quant_matmul_mode(True):
        assert km.use_quant_matmul(8, 512, 512)
        with km.quant_matmul_mode(False):  # inner override wins
            assert not km.use_quant_matmul(8, 512, 512)
        assert km.use_quant_matmul(8, 512, 512)
    assert not km.use_quant_matmul(8, 512, 512)
    with pytest.raises(ValueError):
        km.set_quant_matmul_mode("yes")
    with pytest.raises(ValueError):
        with km.quant_matmul_mode("on"):
            pass


def test_use_quant_matmul_shape_guards():
    with km.quant_matmul_mode(True):
        assert km.use_quant_matmul(64, 512, 512)
        assert not km.use_quant_matmul(65, 512, 512)  # not decode-sized
        assert not km.use_quant_matmul(8, 512, 500)  # n not lane-aligned
        assert not km.use_quant_matmul(8, 128, 512)  # k < one double-group
        assert km.use_quant_matmul(8, 128, 512, group=64)


def test_use_quant_matmul_refuses_model_sharded_mesh():
    """pallas_call has no GSPMD rule: under any model-sharded ambient mesh
    (tp/ep/cp/dp axes > 1) even the FORCED mode falls back to the native
    GSPMD-shardable int4 path."""
    from neuronx_distributed_inference_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp_degree=2)
    with km.quant_matmul_mode(True):
        assert km.use_quant_matmul(8, 512, 512)
        with jax.set_mesh(mesh):
            assert not km.use_quant_matmul(8, 512, 512)
        assert km.use_quant_matmul(8, 512, 512)
