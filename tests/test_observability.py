"""Observability (VERDICT r1 next #10): input snapshots, divergence
auto-capture with offline replay, profiler capture, debug IO logging."""

import glob
import logging
import os

import numpy as np
import pytest

from tests.conftest import make_random_hf_state_dict, make_tiny_config

from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.utils.snapshot import (
    enable_debug_logging,
    install_input_capture,
    load_inputs_snapshot,
    replay_snapshot,
    save_inputs_snapshot,
    uninstall_input_capture,
)

PROMPT = np.array([[5, 17, 92, 41, 33, 88, 2, 11], [64, 3, 27, 9, 14, 0, 0, 0]])
MASK = np.array([[1, 1, 1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 1, 0, 0, 0]])


@pytest.fixture(scope="module")
def app():
    cfg = make_tiny_config()
    a = TpuModelForCausalLM(None, cfg)
    a.load(state_dict=make_random_hf_state_dict(cfg))
    return a


def test_snapshot_round_trip(tmp_path, app):
    inputs, _ = app.context_encoding_model.prepare(
        PROMPT, MASK, np.tile(np.arange(8, dtype=np.int32), (2, 1)),
        np.arange(2, dtype=np.int32),
    )
    path = str(tmp_path / "snap.npz")
    save_inputs_snapshot(inputs, path, step=3, tag="context_encoding_model")
    loaded, meta = load_inputs_snapshot(path)
    assert meta["step"] == 3 and meta["tag"] == "context_encoding_model"
    np.testing.assert_array_equal(np.asarray(loaded.input_ids), np.asarray(inputs.input_ids))
    assert loaded.slot_mapping is None  # absent fields stay absent


def test_capture_and_replay(tmp_path, app):
    """Captured dispatches replay offline to the same tokens (the snapshot is
    a self-contained repro; reference re-feeding captured inputs)."""
    hook = install_input_capture(app, str(tmp_path / "caps"))
    try:
        out = app.generate(PROMPT, MASK, max_new_tokens=6)
    finally:
        uninstall_input_capture(app)
    assert hook.saved, "no dispatches captured"
    # replay the CTE snapshot: first token must match the original run
    cte = [p for p in hook.saved if "context_encoding" in p][0]
    replayed = replay_snapshot(app, cte)
    first = np.asarray(replayed.tokens)[:2, -1]
    np.testing.assert_array_equal(first, out.sequences[:, 8])
    # replay a decode-chunk snapshot end-to-end (runs without error and
    # produces the chunk's tokens)
    chunks = [p for p in hook.saved if p.endswith(".chunk.npz")]
    assert chunks, "decode chunks not captured"
    tokens, _, _ = replay_snapshot(app, chunks[0])
    assert np.asarray(tokens).shape[0] >= 2


def test_capture_indices_filter(tmp_path, app):
    hook = install_input_capture(app, str(tmp_path / "caps2"), capture_indices=[0])
    try:
        app.generate(PROMPT, MASK, max_new_tokens=6)
    finally:
        uninstall_input_capture(app)
    assert len(hook.saved) == 1 and "00000_" in hook.saved[0]


def test_divergence_auto_capture(tmp_path):
    """A failing logit check captures every dispatch plus the divergence
    artifacts (reference inference_demo.py:600-614 auto-capture)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from neuronx_distributed_inference_tpu.utils.accuracy import check_accuracy

    cfg = make_tiny_config(tpu=dict(output_logits=True))
    sd = make_random_hf_state_dict(cfg)
    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=sd)

    hf_config = transformers.LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        max_position_embeddings=256, tie_word_embeddings=False,
        eos_token_id=None, bos_token_id=None,
    )
    torch.manual_seed(99)  # DIFFERENT weights -> guaranteed divergence
    hf = transformers.LlamaForCausalLM(hf_config).eval().float()

    cap = str(tmp_path / "divergence")
    report = check_accuracy(
        app, PROMPT, MASK, hf, max_new_tokens=4, capture_dir=cap
    )
    assert not report.passed
    assert os.path.exists(os.path.join(cap, "divergence.npz"))
    with np.load(os.path.join(cap, "divergence.npz")) as z:
        assert z["divergence_index"] >= 0 or z["actual_sequences"].size
    assert glob.glob(os.path.join(cap, "*_context_encoding_model.npz"))
    assert "captured" in report.message


def test_debug_logging_smoke(app, caplog):
    enable_debug_logging()
    try:
        with caplog.at_level(logging.DEBUG, logger="nxdi_tpu.debug"):
            app.generate(PROMPT, MASK, max_new_tokens=2)
        assert any("context_encoding" in r.message for r in caplog.records)
    finally:
        logging.getLogger("nxdi_tpu.debug").setLevel(logging.WARNING)


def test_profiler_capture(tmp_path, app):
    """jax.profiler trace capture through the telemetry session's control +
    the per-op summary on ``ProfileData`` (reference
    utils/profiling.py:33-66). The capture turns the program's spans on with
    the profiler, so the trace holds them on the profiler's clock; the
    session is stopped again afterwards."""
    from jax.profiler import ProfileData

    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
    from neuronx_distributed_inference_tpu.telemetry import tracing as tel_tracing
    from neuronx_distributed_inference_tpu.utils.profiling import profile_fn

    tel = TelemetrySession(enabled=False)
    prev = tel_tracing.default_session()
    tel_tracing.set_default_session(tel)
    try:
        summary = profile_fn(
            lambda: app.generate(PROMPT, MASK, max_new_tokens=2).sequences,
            str(tmp_path / "prof"), n_warmup=1, n_profile=1,
        )
    finally:
        tel_tracing.set_default_session(prev)
    # a CPU trace has no device plane: the summary is there and empty
    assert summary == {"total_us": 0.0, "ops": []}
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"), recursive=True)
    host_events = {
        e.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    }
    assert "app.cte" in host_events  # the program's own span, in the profiler's trace
    assert not tel.enabled and any(e["name"] == "app.cte" for e in tel.events)


def test_newest_xplane_includes_gz_and_summary_reads_it(tmp_path):
    """The xplane glob must see gzipped traces (*.xplane.pb.gz) and pick the
    NEWEST artifact by mtime, not lexicographic order; ``summarize_trace``
    reads either through ``ProfileData``."""
    from neuronx_distributed_inference_tpu.telemetry.tracing import newest_xplane
    from neuronx_distributed_inference_tpu.utils.profiling import summarize_trace

    d1 = tmp_path / "plugins" / "profile" / "2024_01_01"
    d2 = tmp_path / "plugins" / "profile" / "2024_01_02"
    d1.mkdir(parents=True)
    d2.mkdir(parents=True)
    old = d1 / "host.xplane.pb"
    old.write_bytes(b"")
    new = d2 / "host.xplane.pb.gz"
    import gzip as _gzip

    new.write_bytes(_gzip.compress(b""))
    os.utime(old, (1_000_000, 1_000_000))
    os.utime(new, (2_000_000, 2_000_000))
    assert newest_xplane(str(tmp_path)) == str(new)
    assert summarize_trace(str(tmp_path)) == {"total_us": 0.0, "ops": []}

    # newest-by-mtime also holds within one suffix, against lexicographic
    os.utime(old, (3_000_000, 3_000_000))
    assert newest_xplane(str(tmp_path)) == str(old)
    assert summarize_trace(str(tmp_path)) == {"total_us": 0.0, "ops": []}
    assert newest_xplane(str(tmp_path / "empty-nowhere")) is None
    assert summarize_trace(str(tmp_path / "empty-nowhere"))["ops"] == []


def _decode_from_cache(a, history, pos, n_steps):
    """Decode directly off a (reconstructed) cache: re-feed the last history
    token at ITS position (idempotent write) and emit the successors."""
    from neuronx_distributed_inference_tpu.modules.autobucketing import (
        get_target_bucket,
    )
    from neuronx_distributed_inference_tpu.modules.sampling import (
        prepare_sampling_params,
    )

    B = history.shape[0]
    last = history[np.arange(B), pos - 1].astype(np.int32)
    bucket = get_target_bucket(
        a.token_generation_model.buckets, int(pos.max()) + n_steps
    )
    tokens, _, cache = a.token_generation_model.decode_chunk(
        a.params, a.kv_cache, last[:, None], (pos[:, None] - 1).astype(np.int32),
        np.arange(B, dtype=np.int32), prepare_sampling_params(B), None,
        num_steps=n_steps, bucket=bucket,
    )
    a.kv_cache = cache
    return np.asarray(tokens)[:, :n_steps]


def test_kv_cache_reconstruct(app):
    """A reconstructed cache continues generation exactly where an unbroken
    run would (reference kv_cache_reconstruct_utils.py)."""
    from neuronx_distributed_inference_tpu.utils.snapshot import reconstruct_kv_cache

    full = app.generate(PROMPT, MASK, max_new_tokens=10).sequences

    # simulate losing the cache after 4 generated tokens; the history must be
    # RIGHT-PACKED (each row's valid prompt tokens followed by its generated
    # tokens — generated tokens sit at positions ctx..ctx+3)
    ctx = MASK.sum(1)
    n_keep = 4
    width = int(ctx.max()) + n_keep
    history = np.zeros((2, width), full.dtype)
    hist_mask = np.zeros((2, width), MASK.dtype)
    for b in range(2):
        row = np.concatenate([PROMPT[b, : ctx[b]], full[b, 8 : 8 + n_keep]])
        history[b, : row.size] = row
        hist_mask[b, : row.size] = 1
    pos = reconstruct_kv_cache(app, history, hist_mask)
    np.testing.assert_array_equal(pos, hist_mask.sum(1))
    # decode DIRECTLY off the reconstructed cache (no re-prefill): the next
    # tokens must reproduce the unbroken run's suffix
    tokens = _decode_from_cache(app, history, pos, 6)
    np.testing.assert_array_equal(tokens, full[:, 8 + n_keep : 8 + n_keep + 6])


def test_kv_cache_reconstruct_long_history():
    """Histories longer than one CTE program reconstruct via the windowed
    path (r2 review finding)."""
    from neuronx_distributed_inference_tpu.utils.snapshot import reconstruct_kv_cache

    cfg = make_tiny_config(
        max_position_embeddings=512,
        tpu=dict(batch_size=1, seq_len=256, max_context_length=64),
    )
    a = TpuModelForCausalLM(None, cfg)
    a.load(state_dict=make_random_hf_state_dict(cfg))
    rng = np.random.RandomState(5)
    prompt = rng.randint(2, 120, size=(1, 100))
    full = a.generate(prompt, np.ones_like(prompt), max_new_tokens=10).sequences
    history = full[:, :105]
    pos = reconstruct_kv_cache(a, history)
    assert pos[0] == 105
    tokens = _decode_from_cache(a, history, pos, 5)
    np.testing.assert_array_equal(tokens, full[:, 105:110])
