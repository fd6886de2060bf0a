"""A padded position of a paged chunk pass is routed to no expert, in every
builder with routed experts on the shared layer path.

``models/base.expert_positions`` hands a paged chunk pass's expert layers the
pass's real positions (``real_positions``: a live row's fed prefix), so the
grouped sort puts a padded position's rows in no group. Held here, per builder
at test widths (kimi's, sdar's, zaya's, glm-5's held share, mellum's two kinds
of layer), on a chunk pass with a full row, a row with a padded tail and a row
that sits out: the logits at every real position and everything the pass
leaves in the cache equal, bit for bit, the same pass traced without the mask;
the groups hold the real positions' rows and no other; overwriting the padded
positions' token ids changes nothing; a decode pass and a context-encoding
pass trace with ``valid=None``, the decode program's jaxpr the unmasked
tree's.
"""

import contextlib

import numpy as np
import pytest

import jax

from benchmark.harness import system
from neuronx_distributed_inference_tpu.models import base, glm_moe_dsa, mellum, zaya
from neuronx_distributed_inference_tpu.modules import moe
from tests import (
    test_deepseek_reference as kimi_t,
    test_glm_dsa_reference as glm_t,
    test_mellum_reference as mellum_t,
    test_sdar_reference as sdar_t,
    test_zaya_reference as zaya_t,
)

SEED = 6000000017


def _cfg(model, chunk, block, slots, **tpu):
    return dict(
        model,
        tpu_config=dict(
            dtype="float32", tp_degree=1, batch_size=slots, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=block, pa_num_blocks=64, is_chunked_prefill=True,
            output_logits=True, output_choices=True, **tpu,
        ),
        chunked_prefill=dict(max_num_seqs=slots, kernel_q_tile_size=chunk),
    )


# name -> (configuration, weight rules, positions a chunk row, pool block,
# experts held, experts per token)
BUILDERS = {
    "kimi": (_cfg(kimi_t.MODEL, 32, 16, 4), kimi_t.WEIGHTS, 32, 16, 8, 2),
    "sdar": (_cfg(dict(sdar_t.ATTRS, block_length=4), 32, 16, 4, fused_qkv=True),
             sdar_t.RULES, 32, 16, 8, 2),
    "zaya": (_cfg(zaya_t.ATTRS, 16, 16, 4), [], 16, 16, 8, 1),
    # 8 of 16 published experts held: a real position's choice of an expert
    # held elsewhere is in no group either
    "glm-5": (_cfg(dict(glm_t.MODEL, expert_share={"first": 0, "of": 2}), 16, 8, 4),
              glm_t.WEIGHTS, 16, 8, 8, 2),
    "mellum": (_cfg(mellum_t.MODEL, 16, 4, 4, fused_qkv=True), mellum_t.WEIGHTS, 16, 4, 8, 2),
}
#: the builders whose application also compiles a context-encoding program
#: that runs (sdar refuses whole-prompt context encoding; the others serve chunks alone)
HAS_CTE = ("kimi",)


def _grouped_at_chunks(monkeypatch):
    """At test widths every pass is ``dense`` off the chip: send a pass of a
    chunk's width through the grouped strategy the served shapes take."""
    real = moe.expert_path
    monkeypatch.setattr(
        moe, "expert_path",
        lambda spec, experts, q_len, rows, dtype: (
            "ragged_dot" if q_len >= 8 else real(spec, experts, q_len, rows, dtype)
        ),
    )


@contextlib.contextmanager
def _without_the_mask():
    """The tree before this mechanism: no pass hands its experts a mask."""
    mods = (base, glm_moe_dsa, mellum, zaya)
    saved = [m.expert_positions for m in mods]
    for m in mods:
        m.expert_positions = lambda inputs, phase: None
    try:
        yield
    finally:
        for m, fn in zip(mods, saved):
            m.expert_positions = fn


def _app(name):
    cfg, rules = BUILDERS[name][:2]
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED, rules))
    return app


def _chunk_pass(name, ids_at_padding=0):
    """Three rows of a chunk program: slot 0 feeds a whole chunk, slot 2 the
    first 5 positions of one (a padded tail), the third row sits out."""
    S, block = BUILDERS[name][2:4]
    rng = np.random.default_rng(SEED)
    ids = rng.integers(1, 200, size=(3, S)).astype(np.int32)
    fed = np.array([S, 5, 0])
    real = np.arange(S)[None, :] < fed[:, None]
    ids = np.where(real, ids, ids_at_padding).astype(np.int32)
    pos = np.tile(np.arange(S, dtype=np.int32), (3, 1))
    seq = np.array([0, 2, -1], np.int32)
    blocks_a_row = 256 // block
    table = 1 + np.arange(3 * blocks_a_row, dtype=np.int32).reshape(3, blocks_a_row) % 60
    table[1] = 1 + (np.arange(blocks_a_row) + 20) % 60
    sm = np.take_along_axis(table, pos // block, axis=1) * block + pos % block
    sm = np.where(real, sm, -1).astype(np.int32)
    mask = (np.arange(256)[None, :] < fed[:, None]).astype(np.int32)
    return dict(input_ids=ids, position_ids=pos, seq_ids=seq, attention_mask=mask,
                slot_mapping=sm, block_table=table, phase="tkg"), real


def _run(app, call, sorts):
    app.init_kv_cache()
    call = dict(call)
    del sorts[:]
    out = app.forward(call.pop("input_ids"), call.pop("position_ids"), call.pop("seq_ids"), **call)
    jax.effects_barrier()
    cache = [np.asarray(leaf) for leaf in jax.tree.leaves(app.kv_cache)]
    return out[1], out[2], cache, list(sorts)


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_a_padded_position_of_a_chunk_pass_is_routed_to_no_expert(name, monkeypatch):
    _grouped_at_chunks(monkeypatch)
    S, _, held, top_k = BUILDERS[name][2:]
    sorts, layers_seen = [], []
    real_sort, real_layer = moe._sorted_dispatch, moe.moe_layer

    def sort_spy(aff, k, first=0, held=None, valid=None):
        got = real_sort(aff, k, first, held, valid)
        jax.debug.callback(
            lambda st, se, sizes: sorts.append((np.asarray(st), np.asarray(se), np.asarray(sizes))),
            got[0], got[1], got[3],
        )
        return got

    def layer_spy(params, hidden, spec, *args, valid=None, **kw):
        layers_seen.append((hidden.shape[1], valid is not None))
        return real_layer(params, hidden, spec, *args, valid=valid, **kw)

    monkeypatch.setattr(moe, "_sorted_dispatch", sort_spy)
    monkeypatch.setattr(moe, "moe_layer", layer_spy)
    monkeypatch.setattr(zaya, "moe_layer", layer_spy)

    call, real = _chunk_pass(name)
    app = _app(name)
    logits, aux, cache, masked_sorts = _run(app, call, sorts)
    tkg = app.token_generation_model
    rows = tkg.chunk_rows
    # every expert layer of the chunk pass was handed the mask, and the runner says so
    assert layers_seen and all(s == S and m for s, m in layers_seen)
    assert tkg.masked_sort_shapes == {(rows, S)}

    # (b) the groups hold the real positions' rows and no row of a padded one
    assert masked_sorts
    flat_real = np.zeros(rows * S, bool)
    flat_real[: real.size] = real.reshape(-1)
    for st, se, sizes in masked_sorts:
        in_group = se < held
        assert sizes.shape == (held,) and int(sizes.sum()) == int(in_group.sum())
        assert flat_real[st[in_group]].all()
        # sorted: the rows of no group come last
        assert in_group[: int(sizes.sum())].all()
        if name == "glm-5":  # a held share: the real positions' hits on the held experts
            assert 0 < int(sizes.sum()) < int(real.sum()) * top_k
        else:
            assert int(sizes.sum()) == int(real.sum()) * top_k

    # (c) the padded positions' token ids decide nothing
    other, _ = _chunk_pass(name, ids_at_padding=7)
    assert (other["input_ids"] != call["input_ids"]).any()
    logits_c, aux_c, cache_c, sorts_c = _run(app, other, sorts)
    np.testing.assert_array_equal(logits_c[real], logits[real])
    for a, b in zip(cache_c, cache):
        np.testing.assert_array_equal(a, b)
    assert sorted(int(s[2].sum()) for s in sorts_c) == sorted(int(s[2].sum()) for s in masked_sorts)

    # a decode pass and a context-encoding pass hand their experts no mask
    del layers_seen[:]
    decode = tkg.example_inputs(tkg.buckets[0])
    masked_decode = str(tkg.trace_program(app.params, app.kv_cache, decode, None)[0].jaxpr)
    assert layers_seen and not any(m for _, m in layers_seen)
    if name in HAS_CTE:
        del layers_seen[:]
        cte = app.context_encoding_model
        cte.trace_program(app.params, app.kv_cache, cte.example_inputs(cte.buckets[-1]), None)
        assert layers_seen and not any(m for _, m in layers_seen)
        assert cte.masked_sort_shapes == set()
    assert tkg.masked_sort_shapes == {(rows, S)}

    # (a) the same pass traced without the mask: every real position's logits
    # and choices, and all the pass left in the cache, bit for bit
    with _without_the_mask():
        del layers_seen[:]
        plain = _app(name)
        logits_p, aux_p, cache_p, plain_sorts = _run(plain, call, sorts)
        assert layers_seen and not any(m for _, m in layers_seen)
        assert plain.token_generation_model.masked_sort_shapes == set()
        plain_decode = str(
            plain.token_generation_model.trace_program(plain.params, plain.kv_cache, decode, None)[0].jaxpr
        )
    np.testing.assert_array_equal(logits[real], logits_p[real])
    for key in aux:
        np.testing.assert_array_equal(aux[key][real], aux_p[key][real])
    for a, b in zip(cache, cache_p):
        np.testing.assert_array_equal(a, b)
    # without it every position is in a group
    # (under a held share: where the padded positions' one choice is held here)
    routed = [int(s[2].sum()) for s in plain_sorts]
    assert sum(routed) >= sum(int(s[2].sum()) for s in masked_sorts)
    if name != "glm-5":
        assert set(routed) == {rows * S * top_k}
    # the decode program is the unmasked tree's
    assert masked_decode == plain_decode


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "traced_without_the_mask"])
def test_the_sorted_rows_counter_splits_a_pass_by_what_its_program_was_traced_with(masked, monkeypatch):
    """``nxdi_moe_sorted_rows_total{program, kind}``: a grouped chunk pass's
    rows of real positions (``live``: what ``nxdi_moe_rows_routed_total``
    counts) and of padded ones (``padding``), the second only for a program
    whose expert layers were handed the mask; a dense pass (decode) sorts
    nothing and counts nothing."""
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
    from tests.conftest import drain

    _grouped_at_chunks(monkeypatch)
    layers, top_k = 3, BUILDERS["kimi"][5]
    with contextlib.nullcontext() if masked else _without_the_mask():
        app = _app("kimi")
        app.init_kv_cache()
        tel = TelemetrySession(enabled=True)
        s = ServingSession(app, telemetry=tel)
        rng = np.random.default_rng(11)
        s.add_request("a", rng.integers(0, 512, size=40), max_new_tokens=3)
        s.add_request("b", rng.integers(0, 512, size=10), max_new_tokens=3)
        drain(s)
    snap = tel.registry.snapshot()
    sorted_rows = {(x["labels"]["program"], x["labels"]["kind"]): x["value"]
                   for x in snap["nxdi_moe_sorted_rows_total"]["samples"]}
    routed = {x["labels"]["program"]: x["value"] for x in snap["nxdi_moe_rows_routed_total"]["samples"]}
    assert routed["chunk"] == 50 * layers * top_k and routed["decode"] > 0
    # the positions the chunk dispatches ran beside the real ones (prefill.padded_share's)
    padding = snap["nxdi_prefill_padded_tokens_total"]["samples"][0]["value"] * layers * top_k
    assert padding > routed["chunk"]
    assert sorted_rows == {("chunk", "live"): routed["chunk"],
                           ("chunk", "padding"): padding if masked else 0}
