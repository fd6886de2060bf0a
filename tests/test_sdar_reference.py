"""The block step against the benchmark's plain reference, in tier-1.

``model_type: "sdar_moe"`` (models/sdar.py) generates block by block from
mask tokens under a block-causal mask. Here its serving path —
``ServingSession``, the 8-row chunk program, the block step through the
paged cache, the reveal on the device, 1-ahead dispatch — is held to
``benchmark/harness/references/sdar_moe.py`` (no paging, no kernel, no line
of the program's code) by logits AT EVERY READ: every position of every
pass a session dispatched, replayed by the reference on the ids the session
fed and the experts the program chose. Small size, CPU, float32 (a bf16 case
holds the rule of ``correct.judge``), weights from ``system.make_weights``
with the attention rules that make a pass's masked positions differ.
"""

import json

import numpy as np
import pytest

import jax

from benchmark.harness import correct, system
from benchmark.harness.references import sdar_moe as ref
from neuronx_distributed_inference_tpu.config import BlockStepServingError
from neuronx_distributed_inference_tpu.modules import masks
from neuronx_distributed_inference_tpu.runtime.faults import FaultInjector
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from tests.conftest import drain

# held here in tier-1 since PR 39 (PR 37 could add nothing under tests/): a configuration that
# opts into nothing reads the parent's facts array for array, and draws the parent's prompts
from benchmark.selftest.test_traffic import (  # noqa: F401
    test_without_reserved_ids_every_prompt_is_the_parents_bit_for_bit,
)
from benchmark.selftest.test_unchanged_without_opt_in import (  # noqa: F401
    test_a_configuration_that_opts_into_nothing_reads_the_parents_facts,
)

CHUNK = 32
BLOCK = 16  # the pool's, not the model's
SLOTS = 6
VOCAB = 512
MASK = VOCAB - 1
SEED = 3900000017
#: of the logits' scale, float32 served against the float32 reference on the same ids and the same
#: experts: what differs is the order of float32 sums (the paged gather, the batched expert products).
#: The bf16 twin lies 100 times further off (asserted below), so bf16 in float32's place fails.
TOL = 2e-5

ATTRS = dict(
    model_type="sdar_moe", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
    vocab_size=VOCAB, rms_norm_eps=1e-6, rope_theta=1000000, max_position_embeddings=256,
    hidden_act="silu", tie_word_embeddings=False, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[], denoise_steps=4,
    mask_token_id=MASK,
)
RULES = [{"match": "self_attn/qkv_proj", "std": 0.1}, {"match": "self_attn/o_proj", "std": 0.06}]


def config(block=4, dtype="float32", tpu=None, chunked=None, **attrs) -> dict:
    return dict(
        ATTRS, block_length=block, **attrs,
        tpu_config=dict(dict(
            dtype=dtype, batch_size=SLOTS, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=64, is_chunked_prefill=True, fused_qkv=True,
            output_logits=True, output_choices=True), **(tpu or {})),
        chunked_prefill=dict(dict(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK), **(chunked or {})),
    )


def make_app(**kw):
    cfg = config(**kw)
    app = system.build_app(cfg, jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED, RULES))
    return app, ref.geometry(system.model_attrs(cfg), 1)


@pytest.fixture(scope="module")
def served():
    return make_app()


class PassSpy:
    """Every dispatch of the token-generation runner: (seq ids, positions,
    ids, slot mapping or None, logits, experts, confidence, next ids)."""

    def __init__(self, app):
        self.runner, self.calls = app.token_generation_model, []
        self.orig = self.runner._fn

    def __enter__(self):
        def spy(params, cache, inputs, rng=None):
            out = self.orig(params, cache, inputs, rng)
            get = lambda a: None if a is None else np.asarray(a)
            self.calls.append(dict(
                seq=get(inputs.seq_ids), pos=get(inputs.position_ids), ids=get(inputs.input_ids),
                sm=get(inputs.slot_mapping), logits=get(out.logits), experts=get(out.aux["experts"]),
                confidence=get(out.confidence), next_ids=get(out.next_ids)))
            return out

        self.runner._fn = spy
        return self

    def __exit__(self, *exc):
        self.runner._fn = self.orig

    def of_slot(self, slot: int):
        """(the slot's chunk passes, its block passes), each in dispatch order,
        a row's real positions only."""
        chunks, blocks = [], []
        for c in self.calls:
            for row in np.flatnonzero(c["seq"] == slot):
                if c["sm"] is not None:  # the chunk program: compact rows, the slot in seq_ids
                    n = int((c["sm"][row] >= 0).sum())
                    chunks.append({k: c[k][row, :n] for k in ("pos", "ids", "logits", "experts")})
                else:
                    blocks.append({k: c[k][row] for k in
                                   ("pos", "ids", "logits", "experts", "confidence", "next_ids")})
        return chunks, blocks


def replay_slot(app, geo, spy, slot, rounding=None):
    """The reference on what the session fed ``slot``: every chunk, then every
    block pass, on the program's ids and experts. Returns (served logits,
    the reference's) over every position of every pass, and per block pass
    the reference's logits (B, V)."""
    chunks, blocks = spy.of_slot(slot)
    row = ref.Row(app.params, geo, int(blocks[-1]["pos"][-1]) + 1, rounding)
    got, want, per_pass = [], [], []
    for c in chunks:
        logits, _, _ = row.run(c["ids"], c["pos"].tolist(), range(len(c["ids"])), c["experts"])
        got.append(c["logits"]), want.append(logits)
    for b in blocks:
        logits, _, _ = row.run(b["ids"], b["pos"].tolist(), range(len(b["ids"])), b["experts"])
        got.append(b["logits"]), want.append(logits), per_pass.append(logits)
    return np.concatenate(got).astype(np.float32), np.concatenate(want), per_pass


def assert_is_the_reference(got, want):
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * max(1.0, np.abs(want).max()))


def assert_reveals_by_confidence(geo, blocks, per_pass):
    """Every denoise pass revealed the ``per_pass`` most confident of its
    masked positions by the REFERENCE's confidences (ties by position), each
    with the reference's argmax; a commit pass changed nothing."""
    for b, logits in zip(blocks, per_pass):
        masked = np.flatnonzero(b["ids"] == geo.mask_id)
        revealed = np.flatnonzero((b["ids"] == geo.mask_id) & (b["next_ids"] != geo.mask_id))
        best, conf = ref.predict(logits, geo.mask_id)
        want = sorted(masked, key=lambda j: (-conf[j], j))[: geo.per_pass]
        assert sorted(revealed) == sorted(want), (b["ids"], conf, revealed)
        assert [int(b["next_ids"][j]) for j in revealed] == [int(best[j]) for j in revealed]
        kept = np.setdiff1d(np.arange(len(b["ids"])), revealed)
        assert (b["next_ids"][kept] == b["ids"][kept]).all()
        np.testing.assert_allclose(b["confidence"], conf, rtol=1e-3)


def test_chunked_prefill_and_block_steps_are_the_reference_at_every_read(served):
    """A prompt that ends inside a block (82 = 20 blocks and 2 tokens) and one
    that ends at a block's edge, a third row admitted three steps later (out
    of phase with both), budgets that are no multiple of the block."""
    app, geo = served
    app.init_kv_cache()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, VOCAB - 1, size=n) for n in (82, 64, 7)]
    budgets = [9, 6, 11]
    with PassSpy(app) as spy:
        s = ServingSession(app)
        for i in range(2):
            assert s.add_request(f"r{i}", prompts[i], max_new_tokens=budgets[i])
        for _ in range(3):
            s.step()
        assert s.add_request("r2", prompts[2], max_new_tokens=budgets[2])
        slots = [s.requests[f"r{i}"].slot for i in range(3)]
        drain(s)
    for i, slot in enumerate(slots):
        req = s.requests[f"r{i}"]
        assert req.status == "finished" and len(req.generated) == budgets[i] == len(req.revealed_at)
        assert MASK not in req.generated
        chunks, blocks = spy.of_slot(slot)
        assert sum(len(c["ids"]) for c in chunks) == len(prompts[i]) // 4 * 4
        got, want, per_pass = replay_slot(app, geo, spy, slot)
        assert_is_the_reference(got, want)
        assert_reveals_by_confidence(geo, blocks, per_pass)
        # bf16 in float32's place lies far outside the tolerance
        _, twin, _ = replay_slot(app, geo, spy, slot, rounding=jax.numpy.bfloat16)
        assert np.abs(twin - want).max() > 100 * TOL * np.abs(want).max()
        # the request's record: the tokens in position order, each with the pass that revealed it
        plan = ref.probe_passes(geo, prompts[i], req.generated, req.revealed_at)[1]
        whole = len(plan)  # the last, cut block is not planned (its tokens are not all known)
        assert [b["ids"].tolist() for b in blocks[:whole]] == [p["ids"] for p in plan]
    # rows in different phases shared dispatches: some pass held a commit row beside a denoise row
    mixed = [c for c in spy.calls if c["sm"] is None and (c["seq"] >= 0).sum() > 1]
    kinds = [{bool((c["ids"][r] == MASK).any()) for r in np.flatnonzero(c["seq"] >= 0)} for c in mixed]
    assert any(len(k) == 2 for k in kinds)


def test_the_session_generates_what_the_reference_generates(served):
    """The reference's OWN generation loop (its routes, its reveal) gives the
    session's tokens and ``revealed_at``."""
    app, geo = served
    app.init_kv_cache()
    prompt = np.random.default_rng(12).integers(0, VOCAB - 1, size=37)
    s = ServingSession(app)
    assert s.add_request("r", prompt, max_new_tokens=10)
    drain(s)
    tokens, revealed_at, seen = ref.generate(app.params, geo, prompt, 10)
    req = s.requests["r"]
    assert [int(t) for t in req.generated] == tokens and req.revealed_at == revealed_at
    # the weights rules: the masked positions of a pass differ in confidence by far more than
    # float32's noise (1e-6 of it), so the reveal has something to decide
    spread = [max(c.values()) / min(c.values()) for _, _, c in seen if len(c) > 1]
    assert min(spread) > 1.0005 and max(spread) > 1.05, spread


def test_a_row_preempted_mid_generation_resumes_with_the_same_tokens(served):
    app, geo = served
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB - 1, size=n) for n in (50, 29)]

    def run(injector=None):
        app.init_kv_cache()
        s = ServingSession(app, fault_injector=injector)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=14)
        drain(s)
        return s

    calm = run()
    shaken = run(FaultInjector().exhaust_pool(9))  # mid-generation: past both prompts' chunk passes
    assert sum(shaken.requests[f"r{i}"].preemptions for i in range(2)) >= 1
    for i in range(2):
        a, b = calm.requests[f"r{i}"], shaken.requests[f"r{i}"]
        assert b.status == "finished" and a.generated == b.generated and a.revealed_at == b.revealed_at
        assert MASK not in b.generated and len(b.generated) == 14


def test_bf16_block_steps_stay_within_the_twins_noise():
    """``correct.judge``'s rule on the session's own passes: err <= K x the
    bf16 twin's error over every position of every pass."""
    app, geo = make_app(dtype="bfloat16")
    prompt = np.random.default_rng(14).integers(0, VOCAB - 1, size=70)
    with PassSpy(app) as spy:
        s = ServingSession(app)
        assert s.add_request("r", prompt, max_new_tokens=8)
        slot = s.requests["r"].slot
        drain(s)
    got, want, _ = replay_slot(app, geo, spy, slot)
    _, twin, _ = replay_slot(app, geo, spy, slot, rounding=jax.numpy.bfloat16)
    err, floor = np.abs(got - want).max(), np.abs(twin - want).max()
    assert 0 < floor and err <= correct.K * floor, (err, floor)


def test_the_paged_kernels_serve_the_block_step_and_the_block_causal_chunk():
    """Both paged kernels forced (interpret mode here): the chunk program's
    flash kernel under the block frontier, the decode kernel at K = 4 with
    every query row seeing the whole block."""
    app, geo = make_app(head_dim=64, hidden_size=128, num_hidden_layers=2,
                        tpu=dict(attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
                                 token_generation_buckets=[128, 256], pa_block_size=32, pa_num_blocks=32))
    prompt = np.random.default_rng(15).integers(0, VOCAB - 1, size=38)
    with PassSpy(app) as spy:
        s = ServingSession(app)
        assert s.add_request("r", prompt, max_new_tokens=7)
        slot = s.requests["r"].slot
        drain(s)
    text = app.token_generation_model.trace_program(
        app.params, app.kv_cache, app.token_generation_model.example_inputs(128), None)[1].as_text()
    assert "paged_tkg_decode_attention" in text
    got, want, per_pass = replay_slot(app, geo, spy, slot)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-4 * np.abs(want).max())
    assert_reveals_by_confidence(geo, spy.of_slot(slot)[1], per_pass)


@pytest.mark.parametrize("block", [4, 8])
def test_the_masks_are_block_causal(block):
    """``j // B <= i // B``, clipped by the cache-valid mask: the chunk
    program's native mask, the frontier handed to the paged flash kernel, and
    the block step's (every query row sees the whole block)."""
    width, start, q = 64, 16, 24
    positions = np.stack([start + np.arange(q), 8 + np.arange(q)]).astype(np.int32)
    valid = (np.arange(width)[None, :] < (positions[:, -1:] + 1 - 3)).astype(np.int32)  # 3 padded tails
    got = np.asarray(masks.block_causal_token_gen_mask(valid, positions, block))[:, 0]
    i, j = positions[:, :, None], np.arange(width)[None, None, :]
    want = (j // block <= i // block) & valid.astype(bool)[:, None, :]
    assert (got == want).all()
    # the kernel's rule (kv <= the frontier, under kv_limit) is the same mask
    frontier = np.asarray(masks.block_frontier(positions, block))
    assert ((j <= frontier[:, :, None]) & valid.astype(bool)[:, None, :] == want).all()
    # the block step: arange(width) <= the block's last position, for all B query rows
    at = (start + np.arange(block))[None, :]
    step_valid = (np.arange(width)[None, :] <= at[:, -1:]).astype(np.int32)
    step = np.asarray(masks.token_gen_mask(step_valid, block))[0, 0]
    assert (step == ((np.arange(width)[None, :] // block) <= (at[0][:, None] // block))).all()


REFUSED = {
    "contiguous cache": dict(tpu=dict(is_block_kv_layout=False, is_chunked_prefill=False)),
    "whole-prompt prefill": dict(tpu=dict(is_chunked_prefill=False)),
    "speculation": dict(tpu=dict(speculation_length=4)),
    "serving_ragged": dict(tpu=dict(serving_ragged=True)),
    "is_prefix_caching": dict(tpu=dict(is_prefix_caching=True)),
    "kv_cache_dtype": dict(tpu=dict(kv_cache_dtype="int8")),
    "do_sample": dict(tpu=dict(on_device_sampling_config=dict(do_sample=True))),
    "degree > 1": dict(tpu=dict(tp_degree=2)),
    "sliding_window": dict(tpu=dict(sliding_window=64)),
    "block_length 8": dict(block=8),
    "block_length 3": dict(block=3),
    "no whole number of blocks": dict(block=4, chunked=dict(kernel_q_tile_size=30)),
    "denoise_steps": dict(denoise_steps=5),
    "mask_token_id": dict(mask_token_id=VOCAB),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_is_not_built_is_refused_by_type(what):
    kw = dict(REFUSED[what])
    tpu = kw.pop("tpu", {})
    if "on_device_sampling_config" in tpu:
        from neuronx_distributed_inference_tpu.config import OnDeviceSamplingConfig

        tpu = dict(tpu, on_device_sampling_config=OnDeviceSamplingConfig(**tpu["on_device_sampling_config"]))
    with pytest.raises(BlockStepServingError, match=what.split(" ")[0]):
        system.build_app(config(tpu=tpu, **kw), jax.devices()[:1], SEED)


def test_whole_prompt_programs_and_a_committed_mask_are_errors(served, monkeypatch):
    app, geo = served
    app.init_kv_cache()
    ids = np.zeros((1, 8), np.int32)
    with pytest.raises(BlockStepServingError, match="context encoding"):
        app.forward(ids, np.arange(8)[None], np.zeros(1, np.int32), slot_mapping=np.arange(8)[None] + BLOCK,
                    phase="cte")
    with pytest.raises(NotImplementedError):
        app.generate(ids)
    assert hasattr(app, "warm_serving")
    # a device that reveals nothing leaves a mask for the commit: a program error, not a token
    s = ServingSession(app)
    monkeypatch.setattr(s.blocks, "consume", lambda req, block, k, ids, keep=s.blocks.consume: keep(
        req, block, k, np.where(np.arange(len(ids)) == len(ids) - 1, MASK, ids)))
    assert s.add_request("r", np.arange(9), max_new_tokens=4)
    with pytest.raises(RuntimeError, match="committed with a mask token"):
        drain(s)


def test_run_to_completion_steps_pass_by_pass_and_the_counters_follow(served):
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    app, geo = served
    app.init_kv_cache()
    tel = TelemetrySession(enabled=True)
    s = ServingSession(app, telemetry=tel)
    rng = np.random.default_rng(16)
    assert s.add_request("a", rng.integers(0, VOCAB - 1, size=8), max_new_tokens=8)
    assert s.add_request("b", rng.integers(0, VOCAB - 1, size=10), max_new_tokens=6)
    out = s.run_to_completion()
    assert [len(out[k]) for k in "ab"] == [8, 6]
    snap = tel.registry.snapshot()
    total = lambda name, **labels: sum(
        x["value"] for x in snap[name]["samples"] if all(x["labels"].get(k) == v for k, v in labels.items()))
    # a: two blocks of 4 masks (4 denoise + commit each); b: [p, p, M, M] (2 + 1), then a block of 4
    assert total("nxdi_block_row_passes_total", kind="denoise") == 4 + 4 + 2 + 4
    assert total("nxdi_block_row_passes_total", kind="commit") == 4
    assert total("nxdi_block_blocks_committed_total") == 4
    assert total("nxdi_block_tokens_committed_total") == 8 + 6
    passes = total("nxdi_block_row_passes_total")
    assert total("nxdi_block_positions_total") == 4 * passes
    assert total("nxdi_moe_rows_routed_total", program="decode") == passes * 4 * 3 * 2
    assert json.dumps(s.requests["a"].revealed_at)  # host ints
