"""The paged chunk program is ``CHUNK_ROWS`` rows wide and its rows are
addressed by slot: a pass over n prefilling requests is ceil(n / 8)
dispatches of ONE program per (q bucket, kv bucket), whatever the slot count
and whichever slots the requests sit in.

44 slots, small widths, CPU, seeded random weights; the dense Qwen3 builder
and the granite hybrid builder (whose state-space layers gather and write
back their per-slot state by ``seq_ids`` in the chunk program).
"""

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.analysis import RetraceGuard
from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig, TpuConfig
from neuronx_distributed_inference_tpu.models import get_model_builder
from neuronx_distributed_inference_tpu.modules.autobucketing import generate_chunk_q_buckets
from neuronx_distributed_inference_tpu.ops.kernel_mode import CHUNK_ROWS
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

SLOTS = 44
CHUNK = 16
BUDGET = 4
#: admission order: the first requests land in slots 5, 17, 40, ...
ORDER = [5, 17, 40, 2, 33, 11, 26, 43, 0, 21, 38, 8, 30, 14, 3, 41, 19, 27, 9, 36]
ORDER += [s for s in range(SLOTS) if s not in ORDER]
#: simultaneously prefilling requests -> dispatches of the first pass
CASES = {3: 1, 8: 1, 9: 2, 20: 3}

COMMON = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
    rms_norm_eps=1e-5, hidden_act="silu", rope_theta=10000, tie_word_embeddings=True,
)
ATTRS = {
    "qwen3": dict(COMMON, model_type="qwen3", intermediate_size=128, num_hidden_layers=2,
                  head_dim=16, max_position_embeddings=256),
    "granitemoehybrid": dict(
        COMMON, model_type="granitemoehybrid", shared_intermediate_size=128,
        intermediate_size=128, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        attention_multiplier=0.2, embedding_multiplier=3.0, residual_multiplier=0.5,
        logits_scaling=2.0, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=256, mamba_expand=2,
        mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
        position_embedding_type="nope", num_local_experts=0, num_experts_per_tok=0,
    ),
}


def make_app(model_type):
    tc = TpuConfig(
        dtype="float32", batch_size=SLOTS, seq_len=128, enable_bucketing=True,
        context_encoding_buckets=[128], token_generation_buckets=[64, 128],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_block_size=16, pa_num_blocks=160, is_chunked_prefill=True, fused_qkv=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
    )
    attrs = ATTRS[model_type]
    cfg = get_model_builder(model_type).config_cls(
        tc, load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()]
    )
    return TpuModelForCausalLM(None, cfg).load(random_weights=True)


def prompts(n):
    """Lengths 3..41 across the q ladder and the chunk boundary (one to
    three passes a request), the same for every case so that the goldens
    are shared."""
    rng = np.random.default_rng(29)
    return [rng.integers(1, 512, size=3 + (7 * i) % 39).astype(np.int32) for i in range(20)][:n]


class ScatteredSession(ServingSession):
    """Hands out free slots in ``ORDER`` instead of ascending."""

    @property
    def free_slots(self):
        return sorted(super().free_slots, key=ORDER.index)


def drain(sess, limit=100):
    for _ in range(limit):
        if not sess.active:
            return
        sess.step()
    raise AssertionError("the session did not drain")


@pytest.fixture(scope="module", params=sorted(ATTRS))
def served(request):
    """(application, {prompt index: tokens of the request served alone})."""
    app = make_app(request.param)
    alone = {}
    for i, p in enumerate(prompts(20)):
        app.init_kv_cache()
        sess = ServingSession(app)
        assert sess.add_request(f"r{i}", p, max_new_tokens=BUDGET)
        drain(sess)
        alone[i] = list(sess.requests[f"r{i}"].generated)
        assert len(alone[i]) == BUDGET
    return app, alone


class StateWatch:
    """Around every dispatch of the chunk program: the recurrent state of
    the slots that sit the pass out, before and after."""

    def __init__(self, app):
        self.runner = app.token_generation_model
        self.orig = self.runner._fn
        self.dispatches = 0

    def __enter__(self):
        def watch(params, cache, inputs, rng=None):
            state = getattr(cache, "state", None)
            if inputs.slot_mapping is None or state is None:
                return self.orig(params, cache, inputs, rng)
            assert inputs.input_ids.shape[0] == CHUNK_ROWS
            out_of_pass = np.setdiff1d(np.arange(SLOTS), np.asarray(inputs.seq_ids))
            before = (np.asarray(state.conv)[:, :, out_of_pass], np.asarray(state.ssm)[:, out_of_pass])
            out = self.orig(params, cache, inputs, rng)
            after = out.cache.state
            assert np.array_equal(before[0], np.asarray(after.conv)[:, :, out_of_pass])
            assert np.array_equal(before[1], np.asarray(after.ssm)[:, out_of_pass])
            self.dispatches += 1
            return out

        self.runner._fn = watch
        return self

    def __exit__(self, *exc):
        self.runner._fn = self.orig


@pytest.mark.parametrize("n", sorted(CASES))
def test_scattered_simultaneous_prefill_is_the_request_served_alone(served, n):
    """n requests admitted at once into slots 5, 17, 40, ... prefill in
    ceil(n / 8) dispatches a pass and give the tokens each gives alone; in a
    hybrid model no dispatch touches the state of a slot outside it; the
    counters say what the program ran over."""
    app, alone = served
    app.init_kv_cache()
    tel = TelemetrySession()
    sess = ScatteredSession(app, telemetry=tel)
    # two requests already decoding, so that slots with live state sit the
    # passes out
    for i in (18, 19):
        assert sess.add_request(f"e{i}", prompts(20)[i][:9], max_new_tokens=BUDGET + 2)
    sess.step()
    assert [r.slot for r in sess.active] == sorted(ORDER[:2]) and not sess.prefilling
    ps = prompts(n)
    for i, p in enumerate(ps):
        assert sess.add_request(f"r{i}", p, max_new_tokens=BUDGET)
    assert sorted(r.slot for r in sess.prefilling) == sorted(ORDER[2 : 2 + n])

    def counter(name):
        return tel.registry.snapshot()[name]["samples"][0]["value"]

    base = counter("nxdi_prefill_chunk_dispatches_total")
    with StateWatch(app) as watch:
        sess.step()
        assert counter("nxdi_prefill_chunk_dispatches_total") - base == CASES[n]
        drain(sess)
    for i in range(n):
        assert list(sess.requests[f"r{i}"].generated) == alone[i], i
    if sess.slot_state:
        assert watch.dispatches >= CASES[n]

    R = app.token_generation_model.chunk_rows
    assert R == CHUNK_ROWS
    passes = [e for e in tel.events if e["type"] == "span" and e["name"] == "serving.prefill_chunk"]
    for e in passes:
        assert e["dispatches"] == -(-e["rows"] // R)
        assert e["real_tokens"] + e["padded_tokens"] == e["dispatches"] * R * e["q_bucket"]
    assert passes[1]["rows"] == n and passes[1]["dispatches"] == CASES[n]
    assert counter("nxdi_prefill_chunk_dispatches_total") == sum(e["dispatches"] for e in passes)
    assert counter("nxdi_prefill_real_tokens_total") == sum(e["real_tokens"] for e in passes)
    assert counter("nxdi_prefill_padded_tokens_total") == sum(e["padded_tokens"] for e in passes)
    steps = tel.registry.snapshot()["nxdi_steps_total"]["samples"]
    assert next(s["value"] for s in steps if s["labels"].get("kind") == "prefill") == len(passes)
    dispatch_spans = [e for e in tel.events if e["type"] == "span"
                      and e["name"] == "serving.prefill_chunk.dispatch"]
    assert len(dispatch_spans) == sum(e["dispatches"] for e in passes)
    tel.close()


@pytest.mark.parametrize("model_type", sorted(ATTRS))
def test_the_warmed_chunk_program_is_the_served_one(model_type, caplog):
    """``example_inputs(bucket, q_len=q)`` (what ``app.warmup()`` and the
    benchmark's warm-up run) compiles the program a real pass dispatches: one
    per (q bucket, kv bucket), 8 rows wide; serving then traces nothing and
    compiles no chunk program."""
    import dataclasses
    import logging

    import jax
    import jax.numpy as jnp

    app = make_app(model_type)
    tkg = app.token_generation_model
    ladder = generate_chunk_q_buckets(app.config.tpu_config)

    def chunk_compiles():
        return sum("Compiling jit(token_generation_model_chunk)" in r.getMessage()
                   for r in caplog.records)

    with jax.log_compiles(True), caplog.at_level(logging.WARNING, logger="jax"):
        for bucket in tkg.buckets:
            for q in ladder:
                inputs = tkg.example_inputs(bucket, q_len=q)
                assert inputs.input_ids.shape == (CHUNK_ROWS, q)
                assert inputs.block_table.shape[0] == inputs.slot_mapping.shape[0] == CHUNK_ROWS
                app.kv_cache = tkg(app.params, app.kv_cache, inputs, None).cache
            # the decode step twice, as the benchmark's warm-up runs it: ids
            # from the host, then ids chained on the device
            inputs = tkg.example_inputs(bucket)
            out = tkg(app.params, app.kv_cache, inputs, None)
            chained = jnp.where(jnp.ones(inputs.input_ids.shape, bool),
                                out.tokens[:, -1:].astype(jnp.int32), inputs.input_ids)
            inputs = dataclasses.replace(inputs, input_ids=chained)
            app.kv_cache = tkg(app.params, out.cache, inputs, None).cache
        assert chunk_compiles() == len(tkg.buckets) * len(ladder)
        sess = ScatteredSession(app)
        # 9 rows: two dispatches a pass; the 60-token prompt reaches kv 128
        ps = prompts(8) + [np.arange(1, 61, dtype=np.int32)]
        for i, p in enumerate(ps):
            assert sess.add_request(f"r{i}", p, max_new_tokens=2)
        with RetraceGuard() as guard:
            drain(sess)
        assert guard.traces == []
        assert chunk_compiles() == len(tkg.buckets) * len(ladder)
    assert all(len(sess.requests[f"r{i}"].generated) == 2 for i in range(9))


def test_external_forward_addresses_chunk_rows_by_seq_id(served):
    """``app.forward(phase="tkg")`` with a slot mapping and a block table:
    row i belongs to slot ``seq_ids[i]`` whatever i is, rows beyond the
    program's width run in groups, results come back in row order: one
    token a row, the chunk program's ``(B, 1)``."""
    app, _ = served
    tc = app.config.tpu_config
    bs, width, n = tc.pa_block_size, 64, 11
    per_row = width // bs
    slots = np.asarray(ORDER[:n], np.int32)
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 512, size=(n, CHUNK)).astype(np.int32)
    pos = np.tile(np.arange(CHUNK, dtype=np.int32), (n, 1))
    mask = np.zeros((n, width), np.int32)
    mask[:, :CHUNK] = 1

    def run(order):
        app.init_kv_cache()
        table = np.stack([1 + r * per_row + np.arange(per_row) for r in order]).astype(np.int32)
        sm = table[:, :1] * bs + np.arange(CHUNK)[None, :]
        tokens, _ = app.forward(ids[order], pos[order], slots[order], attention_mask=mask[order],
                                slot_mapping=sm.astype(np.int32), block_table=table, phase="tkg")
        return tokens

    straight = run(np.arange(n))
    perm = np.random.default_rng(4).permutation(n)
    shuffled = run(perm)
    assert straight.shape == (n, 1)  # the token after each row's last fed position
    np.testing.assert_array_equal(shuffled, straight[perm])
