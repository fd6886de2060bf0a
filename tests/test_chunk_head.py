"""The paged chunk program runs its head where a token can leave it: each
row's last fed position. ``tokens`` are ``(chunk_rows, 1)``; logits at every
position are an extra output of an ``output_logits`` application alone; the
decode step, a block step and a speculation verify (block table only) keep
every position.

10 slots (so the 8-row chunk program and the decode step differ in rows),
small widths, CPU, seeded random weights.
"""

import re
from functools import partial

import numpy as np
import pytest

import jax

from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig, TpuConfig
from neuronx_distributed_inference_tpu.models import base, get_model_builder
from neuronx_distributed_inference_tpu.ops.kernel_mode import CHUNK_ROWS
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import drain

SLOTS = 10
CHUNK = 16
BLOCK = 16
WIDTH = 64
VOCAB = 512
ATTRS = dict(
    model_type="qwen3", hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=VOCAB, rms_norm_eps=1e-5, hidden_act="silu", rope_theta=10000,
    tie_word_embeddings=True, intermediate_size=128, num_hidden_layers=2, head_dim=16,
    max_position_embeddings=256,
)


def make_app(dtype="float32", **tpu):
    tc = TpuConfig(
        dtype=dtype, batch_size=SLOTS, seq_len=128, enable_bucketing=True,
        context_encoding_buckets=[128], token_generation_buckets=[64, 128],
        is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
        pa_block_size=BLOCK, pa_num_blocks=64, is_chunked_prefill=True, fused_qkv=True,
        chunked_prefill_config=ChunkedPrefillConfig(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
        **tpu,
    )
    cfg = get_model_builder("qwen3").config_cls(
        tc, load_config=lambda c: [setattr(c, k, v) for k, v in ATTRS.items()]
    )
    return TpuModelForCausalLM(None, cfg).load(random_weights=True)


#: (slot, prompt length): two chunks, exactly one chunk, part of one
ROWS = [(7, 23), (2, 16), (4, 5)]
PER_ROW = WIDTH // BLOCK


def chunk_pass(start):
    """The inputs of the chunk pass over positions ``start`` .. of ``ROWS``
    and, per row, the tokens it feeds (0: the row sits the pass out, with no
    seq id and no slot)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for _, n in ROWS]
    B = len(ROWS)
    ids, sm = np.zeros((B, CHUNK), np.int32), np.full((B, CHUNK), -1, np.int32)
    pos = np.tile(start + np.arange(CHUNK, dtype=np.int32), (B, 1))
    mask = np.zeros((B, WIDTH), np.int32)
    seq = np.full(B, -1, np.int32)
    table = np.stack([1 + s * PER_ROW + np.arange(PER_ROW) for s, _ in ROWS]).astype(np.int32)
    fed = []
    for r, ((slot, _), p) in enumerate(zip(ROWS, prompts)):
        n = max(0, min(CHUNK, len(p) - start))
        fed.append(n)
        if not n:
            continue
        seq[r] = slot
        ids[r, :n] = p[start : start + n]
        at = start + np.arange(n)
        sm[r, :n] = table[r, at // BLOCK] * BLOCK + at % BLOCK
        mask[r, : start + n] = 1
    kw = dict(attention_mask=mask, slot_mapping=sm, block_table=table, phase="tkg")
    return (ids, pos, seq), kw, fed


@pytest.fixture(scope="module")
def apps():
    """(served, probe): one configuration in bf16 without and with
    ``output_logits``, the same seeded weights."""
    return make_app("bfloat16"), make_app("bfloat16", output_logits=True)


def test_a_chunk_pass_commits_the_token_of_each_rows_last_fed_position(apps):
    """Rows of different ``n`` (a full chunk, a partial one, a second chunk,
    a row that sits out): the served application returns ``tokens (B, 1)``
    and no logits, the probe the same tokens and logits at every position;
    the token is the argmax of the all-position head at ``n - 1``, and the
    gathered head's logits are that position's within bf16's rounding."""
    served, probe = apps
    tkg = probe.token_generation_model
    both = jax.jit(partial(
        base.model_logits, spec=probe.spec, phase=tkg.phase, mlp_fn=tkg.mlp_fn,
        layer_fn=tkg.layer_fn, return_aux=True,
    ))
    for app in apps:
        app.init_kv_cache()
    live = 0
    for start in (0, CHUNK):
        args, kw, fed = chunk_pass(start)
        inputs, _ = tkg.prepare(args[0], kw["attention_mask"], *args[1:],
                                slot_mapping=kw["slot_mapping"], block_table=kw["block_table"])
        with jax.set_mesh(tkg.mesh):
            gathered, _, _, wide = both(probe.params, probe.kv_cache, inputs)
        assert gathered.shape == (CHUNK_ROWS, 1, VOCAB) and wide.shape == (CHUNK_ROWS, CHUNK, VOCAB)
        tokens, none = served.forward(*args, **kw)
        p_tokens, logits = probe.forward(*args, **kw)
        assert none is None and tokens.shape == p_tokens.shape == (len(ROWS), 1)
        assert logits.shape == (len(ROWS), CHUNK, VOCAB)
        np.testing.assert_array_equal(logits, np.asarray(wide)[: len(ROWS)])
        for r, n in enumerate(fed):
            if not n:
                continue
            live += 1
            at_last = logits[r, n - 1].astype(np.float32)
            tol = 2.0**-8 * np.abs(at_last).max()
            np.testing.assert_allclose(np.asarray(gathered)[r, 0], at_last, rtol=0, atol=tol)
            best, second = np.sort(at_last)[[-1, -2]]
            assert best - second > 2 * tol  # no near-tie: the argmax is the token
            assert tokens[r, 0] == p_tokens[r, 0] == int(np.argmax(at_last))
    assert live == 4


def test_the_served_chunk_program_holds_no_logits_at_every_position(apps):
    """The lowered chunk program of an application that returns no logits:
    the head's product is ``(chunk_rows, 1, V)`` and no ``(rows, q, V)``
    array exists; the probe's holds both."""
    at_every_position = re.compile(rf"tensor<{CHUNK_ROWS}x{CHUNK}x{VOCAB}x")
    at_the_last = re.compile(rf"tensor<{CHUNK_ROWS}x1x{VOCAB}xf32>")
    for app, wide in zip(apps, (False, True)):
        tkg = app.token_generation_model
        inputs = tkg.example_inputs(128, q_len=CHUNK)
        assert inputs.slot_mapping is not None and inputs.block_table is not None
        traced, lowered, _ = tkg.trace_program(app.params, app.kv_cache, inputs, None)
        out = traced.out_info
        assert out.tokens.shape == (CHUNK_ROWS, 1)
        assert (out.logits.shape == (CHUNK_ROWS, CHUNK, VOCAB)) if wide else out.logits is None
        text = lowered.as_text()
        assert at_the_last.search(text)
        assert bool(at_every_position.search(text)) == wide


def plant_nan(monkeypatch, slot, position):
    """The final hidden state of a paged chunk pass holds NaN at ``position``
    of ``slot``: past the layers, so that no later position attends to it."""
    layers = base.run_decoder_layers

    def planted(params, hidden, cache, inputs, **kw):
        hidden, *rest = layers(params, hidden, cache, inputs, **kw)
        if base.is_paged_chunk(kw["phase"], inputs.slot_mapping, inputs.block_table):
            here = (inputs.seq_ids == slot)[:, None] & (inputs.position_ids == position)
            hidden = jax.numpy.where(here[..., None], jax.numpy.nan, hidden)
        return (hidden, *rest)

    monkeypatch.setattr(base, "run_decoder_layers", planted)


PROMPT = np.random.default_rng(5).integers(1, VOCAB, size=20).astype(np.int32)
OTHER = np.random.default_rng(6).integers(1, VOCAB, size=20).astype(np.int32)


def serve(app):
    """``PROMPT`` (slot 0) and ``OTHER`` through a session; (the session, its
    ``serving.prefill_chunk`` spans)."""
    with TelemetrySession() as tel:
        sess = ServingSession(app, telemetry=tel)
        assert sess.add_request("r", PROMPT, max_new_tokens=3)
        assert sess.add_request("other", OTHER, max_new_tokens=3)
        assert sess.requests["r"].slot == 0
        drain(sess)
        spans = [e for e in tel.events if e["type"] == "span" and e["name"] == "serving.prefill_chunk"]
    assert len(spans) == 2  # a prompt of 20 tokens: a chunk of 16 and a last chunk of 4
    return sess, spans


@pytest.fixture(scope="module")
def clean():
    """What the two requests give with nothing planted."""
    sess, spans = serve(make_app())
    assert all(e["head_positions"] == e["dispatches"] * CHUNK_ROWS for e in spans)
    return {name: list(req.generated) for name, req in sess.requests.items()}


@pytest.mark.parametrize("position, quarantined", [(19, True), (17, False), (15, False)])
def test_a_nan_at_a_rows_last_fed_position_is_the_sentinel_and_nowhere_else(
    clean, monkeypatch, position, quarantined
):
    """The non-finite mark runs on the gathered position: NaN there gives
    ``NON_FINITE_TOKEN`` and the session quarantines the row; NaN at an
    earlier position of the last chunk, or at the last position of a chunk
    that is not the prompt's last, leaves the request as it is served
    clean."""
    plant_nan(monkeypatch, 0, position)
    sess, _ = serve(make_app())  # a new application: its programs trace under the patch
    req = sess.requests["r"]
    if quarantined:
        assert req.status == "failed" and req.fail_reason == "non_finite" and not req.generated
    else:
        assert req.status == "finished" and list(req.generated) == clean["r"]
    assert list(sess.requests["other"].generated) == clean["other"]


def test_the_span_says_which_head_a_pass_ran(apps):
    """``head_positions`` of ``serving.prefill_chunk``: rows x 1 a dispatch
    where the gather alone runs, rows x q where ``output_logits`` keeps the
    head at every position."""
    for app, wide in zip(apps, (False, True)):
        app.init_kv_cache()
        _, spans = serve(app)
        for e in spans:
            assert e["head_positions"] == e["dispatches"] * CHUNK_ROWS * (e["q_bucket"] if wide else 1)


def test_the_dense_decode_step_and_a_verify_keep_every_position(apps):
    """The gather engages on a paged chunk pass alone: the decode step
    returns ``(B, n_active)`` and a multi-token pass with a block table and
    NO slot mapping (a speculation verify) a token at each of its
    positions."""
    served, _ = apps
    served.init_kv_cache()
    tkg = served.token_generation_model
    out = tkg(served.params, served.kv_cache, tkg.example_inputs(64), None)
    served.kv_cache = out.cache
    assert out.tokens.shape == (SLOTS, tkg.n_active_tokens) and out.logits is None
    ids = np.random.default_rng(2).integers(1, VOCAB, size=(SLOTS, 4)).astype(np.int32)
    pos = np.tile(np.arange(4, dtype=np.int32), (SLOTS, 1))
    table = (1 + np.arange(SLOTS * PER_ROW).reshape(SLOTS, PER_ROW)).astype(np.int32)
    tokens, _ = served.forward(ids, pos, np.arange(SLOTS, dtype=np.int32), block_table=table,
                               phase="tkg")
    assert tokens.shape == (SLOTS, 4)


def test_a_block_step_keeps_every_position_and_a_chunk_as_wide_is_no_block_step():
    """A block-step model: the decode program returns tokens, confidence and
    the next pass's ids at all ``block_length`` positions; a paged chunk pass
    that happens to be ``block_length`` wide (no session dispatches one: the
    q ladder starts above it) is told apart by its slot mapping, projects one
    position a row and reveals nothing."""
    from tests import test_sdar_reference as sdar

    app, _ = sdar.make_app()
    tkg = app.token_generation_model
    B = app.config.tpu_config.batch_size
    out = tkg(app.params, app.kv_cache, tkg.example_inputs(128), None)
    assert out.tokens.shape == out.next_ids.shape == out.confidence.shape == (B, 4)
    assert out.logits.shape == (B, 4, sdar.VOCAB)
    rows = tkg.chunk_rows
    out = tkg(app.params, out.cache, tkg.example_inputs(128, q_len=4), None)
    assert out.tokens.shape == (rows, 1) and out.next_ids is None and out.confidence is None
    assert out.logits.shape == (rows, 4, sdar.VOCAB)  # this application returns logits
