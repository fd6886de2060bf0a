"""The dense serving step against the benchmark's plain reference, in tier-1.

Four of the benchmark's five cells serve the one ``decoder_layer`` of
``models/base.py`` through the paged cache with chunked prefill. Here that
path — ``ServingSession``, the 8-row chunk program, 1-ahead decode, the
block pool — is held to ``benchmark/harness/references/dense.py`` (no cache,
no kernel, no line of the program's code) by logits, on three geometries:
Qwen3 with ``qk_norm`` and a tied head (the 1.7B's shape), Qwen3 untied with
more query heads than the width carries (the 14B's), and Llama without
``qk_norm``. Small size, CPU, weights from ``system.make_weights`` (norm
weights off 1, so that one applied wrongly shows). ``benchmark/selftest``
proves the RULE on a 256-wide bf16 model through the teacher-forced probe;
nothing here repeats its inputs.
"""

import numpy as np
import pytest

import jax

from benchmark.harness import correct, system
from benchmark.harness.references import dense
from neuronx_distributed_inference_tpu.ops.kernel_mode import CHUNK_ROWS
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import LogitSpy, drain

CHUNK = 32  # two blocks: a chunk boundary and a block boundary are different places
BLOCK = 16
SLOTS = 12
VOCAB = 512
SEED = 3200000017
TOL = 2e-5  # of the logits' scale, float32 served against float32 reference

COMMON = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=3, vocab_size=VOCAB,
              hidden_act="silu", max_position_embeddings=256)
GEOMETRIES = {
    "qwen3-tied": dict(COMMON, model_type="qwen3", num_attention_heads=8, num_key_value_heads=4,
                       head_dim=16, hidden_size=128, rms_norm_eps=1e-6, rope_theta=1000000,
                       tie_word_embeddings=True),
    "qwen3-untied": dict(COMMON, model_type="qwen3", num_attention_heads=8, num_key_value_heads=2,
                         head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000,
                         tie_word_embeddings=False),
    "llama": dict(COMMON, model_type="llama", num_attention_heads=4, num_key_value_heads=2,
                  rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False),
}


def make_app(name, dtype="float32", degree=1):
    """The application as a cell builds it (``system.build_app``: paged cache,
    chunked prefill, continuous batching, fused QKV layout), its weights made
    from SEED."""
    cfg = dict(
        GEOMETRIES[name],
        tpu_config=dict(
            dtype=dtype, tp_degree=degree, batch_size=SLOTS, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=64, is_chunked_prefill=True, fused_qkv=True,
            output_logits=True,
        ),
        chunked_prefill=dict(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
    )
    app = system.build_app(cfg, jax.devices()[:degree], SEED)
    system.give_weights(app, *system.make_weights(app, SEED))
    return app


@pytest.fixture(scope="module", params=sorted(GEOMETRIES))
def served(request):
    """(geometry's name, its float32 application)."""
    return request.param, make_app(request.param)


def reference_rows(app, name, prompt, generated, degree=1, twin=False):
    """(positions, logits): the reference's logits at the last prompt
    position and after every generated token but the last."""
    geo = dense.geometry(GEOMETRIES[name], degree)
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    fn = dense.twin_logits if twin else dense.reference_logits
    return positions, fn(app.params, geo, list(prompt) + list(generated[:-1]), positions)


def served_rows(spy, slots, positions, want):
    """The logits served at ``positions``, from whichever of ``slots`` served
    them nearest to ``want`` (a request re-admitted after a preemption may
    have changed slot)."""
    best = None
    for slot in slots:
        try:
            got = np.stack([spy.at(slot, p) for p in positions]).astype(np.float32)
        except AssertionError:
            continue
        if best is None or np.abs(got - want).max() < np.abs(best - want).max():
            best = got
    assert best is not None, "no slot served these positions"
    return best


def assert_is_the_reference(got, want):
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * max(1.0, np.abs(want).max()))


def test_chunked_prefill_then_decode_is_the_reference(served):
    """A prompt of 2.5 chunks (5 blocks), then 8 decode steps."""
    name, app = served
    app.init_kv_cache()
    prompt = np.random.default_rng(11).integers(0, VOCAB, size=int(2.5 * CHUNK))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        assert s.add_request("r", prompt, max_new_tokens=9)
        drain(s)
        generated = s.requests["r"].generated
        assert len(generated) == 9
        positions, want = reference_rows(app, name, prompt, generated)
        assert_is_the_reference(served_rows(spy, [0], positions, want), want)
    # greedy: every token served is the reference's best at its position
    assert [int(t) for t in generated] == [int(t) for t in want.argmax(-1)]


def test_bf16_serving_stays_within_the_twins_noise(served):
    """``correct.judge``'s rule (err <= K x the bf16 twin's error) on the
    session's own path: the same weights rounded to bf16, chunked prefill
    and decode through the paged cache."""
    name, _ = served
    app = make_app(name, dtype="bfloat16")
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(2.5 * CHUNK), 9)]
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=correct.PROBE_DECODE_STEPS + 1)
        slots = [s.requests[f"r{i}"].slot for i in range(2)]
        drain(s)
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            positions, want = reference_rows(app, name, p, generated)
            _, twin = reference_rows(app, name, p, generated, twin=True)
            got = served_rows(spy, [slots[i]], positions, want)
            err, floor = np.abs(got - want).max(), np.abs(twin - want).max()
            assert 0 < floor and err <= correct.K * floor, (name, i, err, floor)
            # every token the session chose is, by the reference, within the noise of the best
            regret = max(want[k].max() - want[k, generated[k]] for k in range(len(generated)))
            assert regret <= correct.K * floor, (name, i, regret, floor)


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(served):
    name, app = served
    app.init_kv_cache()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(1.5 * CHUNK) + 3, CHUNK - 5)]
    with LogitSpy(app) as spy:
        s = ServingSession(app, fault_injector=FaultInjector().exhaust_pool(4))
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=8)
        drain(s)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            assert len(generated) == 8
            positions, want = reference_rows(app, name, p, generated)
            assert_is_the_reference(served_rows(spy, range(s.num_slots), positions, want), want)


def test_ten_requests_prefilling_at_once_each_start_with_the_references_token(served):
    """Ten rows in one pass are two dispatches of the 8-row chunk program;
    rows 9 and 10 ride the second, addressed by slot."""
    name, app = served
    app.init_kv_cache()
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, VOCAB, size=3 + (11 * i) % (2 * CHUNK)) for i in range(10)]
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=2)
        slots = [s.requests[f"r{i}"].slot for i in range(10)]
        s.step()
        first_pass = [row for row in spy.rows if row[2] is not None]
        assert [row[0].shape[0] for row in first_pass] == [CHUNK_ROWS, CHUNK_ROWS]
        drain(s)
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            positions, want = reference_rows(app, name, p, generated)
            assert int(generated[0]) == int(want[0].argmax()), (name, i)
            assert_is_the_reference(served_rows(spy, [slots[i]], positions, want), want)


def test_tensor_parallel_over_four_devices_is_the_reference():
    """The tied Qwen3 geometry at tp_degree 4 on four virtual devices: the
    fused QKV matrix rank-interleaved, two query heads and one KV head a
    rank, the vocabulary-parallel head, all against ``Geometry(degree=4)``."""
    name = "qwen3-tied"
    app = make_app(name, degree=4)
    assert app.mesh.size == 4
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(1.5 * CHUNK) + 1, 7)]
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=5)
        slots = [s.requests[f"r{i}"].slot for i in range(2)]
        drain(s)
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            positions, want = reference_rows(app, name, p, generated, degree=4)
            assert_is_the_reference(served_rows(spy, [slots[i]], positions, want), want)
