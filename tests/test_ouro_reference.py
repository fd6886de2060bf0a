"""Ouro (``model_type: "ouro"``): a layer stack that runs ``total_ut_steps``
times over one set of weights, held to the benchmark's plain reference
(``benchmark/harness/references/ouro.py``: float32, the two loops as Python
loops, no cache, no kernel, no line of the program's code). Small size (hidden
64, 4/4 heads of 16, MLP 160, 3 layers), CPU, weights from
``system.make_weights`` (norm weights off 1, so that one applied wrongly, or
not at all, shows).

What is new in this model and what holds it here: the outer scan over the
loops in ``models/base.py::run_decoder_layers`` with the cache index
``t * L + l``, the final norm after every loop, the two norms on a sub-block's
output, the exit gate, a cache (contiguous and paged) of T x L streams, and
the typed refusals of what a looped stack does not do yet.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import correct, system
from benchmark.harness.references import ouro as ref
from neuronx_distributed_inference_tpu.config import (
    LoopedStackError,
    LoraServingConfig,
    TensorCaptureConfig,
    TpuConfig,
)
from neuronx_distributed_inference_tpu.models import base, get_model_builder
from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
from tests.conftest import LogitSpy, drain

CHUNK = 32  # two blocks: a chunk boundary and a block boundary are different places
BLOCK = 16
SLOTS = 4
VOCAB = 512
LAYERS = 3
SEED = 5100000019
TOL = 2e-5  # of the logits' scale, float32 served against the float32 reference
LOOPS = (1, 2, 4)


def attrs(loops=4, **over):
    return dict(
        model_type="ouro", hidden_size=64, intermediate_size=160, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, num_hidden_layers=LAYERS, vocab_size=VOCAB,
        rms_norm_eps=1e-6, rope_theta=1000000, max_position_embeddings=256, hidden_act="silu",
        tie_word_embeddings=False, total_ut_steps=loops, early_exit_threshold=1, **over)


def paged_cfg(loops=4, dtype="float32", blocks=64, **tpu):
    return dict(
        attrs(loops),
        tpu_config=dict(
            dtype=dtype, tp_degree=1, batch_size=SLOTS, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=BLOCK, pa_num_blocks=blocks, is_chunked_prefill=True, fused_qkv=True,
            output_logits=True, **tpu),
        chunked_prefill=dict(max_num_seqs=SLOTS, kernel_q_tile_size=CHUNK),
    )


def paged_app(loops=4, dtype="float32", **kw):
    """The application as a cell builds it (``system.build_app``), its weights
    made from SEED."""
    app = system.build_app(paged_cfg(loops, dtype, **kw), jax.devices()[:1], SEED)
    system.give_weights(app, *system.make_weights(app, SEED))
    return app


def contiguous_app(loops, **tpu):
    opts = dict(batch_size=2, seq_len=128, dtype="float32", output_logits=True)
    opts.update(tpu)
    cls = get_model_builder("ouro").config_cls
    cfg = cls(TpuConfig(**opts),
              load_config=lambda c: [setattr(c, k, v) for k, v in attrs(loops).items()])
    app = TpuModelForCausalLM(None, cfg).load(random_weights=True)
    system.give_weights(app, *system.make_weights(app, SEED))
    return app


def geometry(loops=4):
    return ref.geometry(attrs(loops), 1)


def reference_rows(params, loops, prompt, generated, **kw):
    """(positions, logits): the reference's at the last prompt position and
    after every generated token but the last."""
    positions = [len(prompt) - 1 + k for k in range(len(generated))]
    tokens = [int(t) for t in prompt] + [int(t) for t in generated[:-1]]
    return positions, ref.reference_logits(params, geometry(loops), tokens, positions, **kw)


def assert_is_the_reference(got, want):
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * max(1.0, np.abs(want).max()))


def is_the_reference(got, want) -> bool:
    return bool(np.abs(got - want).max() <= TOL * max(1.0, np.abs(want).max()))


# ---- (a) context encoding, (b) generate() through the contiguous cache -------


@pytest.fixture(scope="module", params=LOOPS)
def contiguous(request):
    return request.param, contiguous_app(
        request.param, tensor_capture_config=TensorCaptureConfig(points=("exit_gate",)))


def test_context_encoding_gives_the_references_logits_and_gates(contiguous):
    loops, app = contiguous
    rng = np.random.default_rng(21)
    lens = (23, 9)
    ids = np.zeros((2, 24), np.int64)
    mask = np.zeros((2, 24), np.int64)
    for r, n in enumerate(lens):
        ids[r, :n], mask[r, :n] = rng.integers(0, VOCAB, size=n), 1
    assert app.kv_cache.k.shape[0] == loops * LAYERS  # one line a layer PASS
    out = app.generate(ids, mask, max_new_tokens=1)
    _, captured = app.capture_forward(ids, mask)
    # a stack that runs once is the plain decoder: no loop, no gate after one
    gates = captured["exit_gate"] if loops > 1 else None
    assert (loops == 1) == ("exit_gate" not in captured)
    for r, n in enumerate(lens):
        want = ref.reference_logits(app.params, geometry(loops), ids[r, :n], [n - 1])
        assert_is_the_reference(out.logits[r, 0], want[0])
        if gates is not None:
            want_gates = ref.reference_gates(app.params, geometry(loops), ids[r, :n])
            assert want_gates.shape == (loops, n) and want_gates.std() > 1e-4
            np.testing.assert_allclose(gates[:, r, :n], want_gates, rtol=0, atol=2e-6)


def test_generate_through_the_contiguous_cache_is_the_references_full_forward(contiguous):
    loops, app = contiguous
    rng = np.random.default_rng(22)
    lens = (17, 30)
    ids = np.zeros((2, 32), np.int64)
    mask = np.zeros((2, 32), np.int64)
    for r, n in enumerate(lens):
        ids[r, :n], mask[r, :n] = rng.integers(0, VOCAB, size=n), 1
    out = app.generate(ids, mask, max_new_tokens=6)
    assert out.logits.shape[1] == 6
    for r, n in enumerate(lens):
        generated = out.sequences[r, 32:38]
        _, want = reference_rows(app.params, loops, ids[r, :n], generated)
        assert_is_the_reference(out.logits[r], want)
        assert [int(t) for t in generated] == [int(t) for t in want.argmax(-1)]


# ---- (c) the serving session, (d) what its pool holds -------------------------


def serve_two_through_one_slot(app, budget=7):
    """A prompt of 2.5 chunks and then a shorter one through ONE reused slot
    (the second is admitted when the first has left), each followed by 1-ahead
    decode. Per request (prompt, generated, the pool blocks it held, the
    positions read, the logits served there)."""
    rng = np.random.default_rng(31)
    served = []
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for name, n in (("first", int(2.5 * CHUNK)), ("second", CHUNK + 5)):
            prompt = rng.integers(0, VOCAB, size=n)
            assert s.add_request(name, prompt, max_new_tokens=budget)
            assert s.requests[name].slot == 0
            blocks = []
            for _ in range(200):
                if not (s.active or s._readmit):
                    break
                blocks = list(s.allocator.seq_blocks.get(0) or blocks)
                s.step()
            generated = [int(t) for t in s.requests[name].generated]
            assert len(generated) == budget
            positions = [n - 1 + k for k in range(budget)]
            got = np.stack([spy.at(0, p) for p in positions]).astype(np.float32)
            served.append((prompt, generated, blocks, positions, got))
            spy.rows.clear()
    return served


@pytest.fixture(scope="module")
def served_four():
    """The float32 application at four loops and the two requests it served:
    what (c), (d) and the controls of (e) read."""
    app = paged_app(4)
    return app, serve_two_through_one_slot(app)


@pytest.mark.parametrize("loops", LOOPS)
def test_serving_session_float32_is_the_reference(loops, served_four):
    if loops == 4:
        app, served = served_four
    else:
        app = paged_app(loops)
        served = serve_two_through_one_slot(app)
    assert app.kv_cache.k.shape[0] == loops * LAYERS == app.paged_layers
    for prompt, generated, _, positions, got in served:
        _, want = reference_rows(app.params, loops, prompt, generated)
        assert_is_the_reference(got, want)
        assert generated == [int(t) for t in want.argmax(-1)]


@pytest.mark.parametrize("loops", LOOPS)
def test_serving_session_bf16_stays_within_the_twins_noise(loops):
    """``correct.judge``'s rule (err <= K x the bf16 twin's error) on the
    session's own path, the same weights rounded to bf16. At hidden 64 the
    ratio of two maxima over 7 x 512 logits swings by the prompt (0.65 - 1.53
    over five draws of the two prompts at the three depths, median 0.96: the
    twin is as noisy as the program); the draw used reads 0.72 - 1.02."""
    app = paged_app(loops, dtype="bfloat16")
    for prompt, generated, _, _, got in serve_two_through_one_slot(app):
        _, want = reference_rows(app.params, loops, prompt, generated)
        _, twin = reference_rows(app.params, loops, prompt, generated, rounding=jnp.bfloat16)
        err, floor = np.abs(got - want).max(), np.abs(twin - want).max()
        assert 0 < floor and err <= correct.K * floor, (loops, err, floor)
        regret = max(want[k].max() - want[k, generated[k]] for k in range(len(generated)))
        assert regret <= correct.K * floor, (loops, regret, floor)


def pool_stream(app, blocks, stream, n, which="k"):
    """(n, kv_heads, D): what the pool holds in ``stream`` for the first ``n``
    positions of a row that held ``blocks``."""
    pool = np.asarray(getattr(app.kv_cache, which))  # (T x L, NB + 1, H_kv, bs, D)
    at = np.arange(n)
    return pool[stream, np.asarray(blocks)[at // BLOCK], :, at % BLOCK, :]


def test_the_pool_holds_loop_t_of_layer_l_at_stream_t_times_L_plus_l(served_four):
    """The test that fails if two loops share a stream: after serving, stream
    ``t * L + l`` holds the K and V the reference computed in loop t of layer
    l, for every (t, l), at every position the request fed."""
    app, served = served_four
    prompt, generated, blocks, *_ = served[1]  # the slot's last request: nothing wrote after it
    tokens = [int(t) for t in prompt] + generated[:-1]
    streams = ref.reference_streams(app.params, geometry(4), tokens)
    assert len(streams) == 4 * LAYERS == app.kv_cache.k.shape[0]
    for t in range(4):
        for l in range(LAYERS):
            k, v = streams[t * LAYERS + l]
            got_k = pool_stream(app, blocks, t * LAYERS + l, len(tokens), "k")
            got_v = pool_stream(app, blocks, t * LAYERS + l, len(tokens), "v")
            np.testing.assert_allclose(got_k, k, rtol=0, atol=TOL * max(1.0, np.abs(k).max()))
            np.testing.assert_allclose(got_v, v, rtol=0, atol=TOL * max(1.0, np.abs(v).max()))
    # and the streams differ from loop to loop: a shared stream could not hold them all
    k0, k3 = streams[0][0], streams[3 * LAYERS][0]
    assert np.abs(k0 - k3).max() > 100 * TOL * np.abs(k0).max()


# ---- (e) three controls that must fail what (c) passes -------------------------


def _last_loops_stream(layers, loops):
    """``decoder_layer`` with every loop writing and attending the LAST
    loop's stream of its layer: one loop's K/V standing in for all."""
    sound = base.decoder_layer

    def faulty(layer_params, hidden, cos, sin, k_cache, v_cache, layer_idx, *args, **kw):
        return sound(layer_params, hidden, cos, sin, k_cache, v_cache,
                     (loops - 1) * layers + layer_idx % layers, *args, **kw)

    return faulty


@pytest.mark.parametrize("fault", ["three_loops_of_four", "the_last_loops_stream_for_every_loop",
                                   "no_norm_between_loops"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_fault_in_the_loop_fails_the_tolerance_a_sound_program_passes(fault, dtype, served_four,
                                                                       monkeypatch):
    """Read at the last position of the 2.5-chunk prompt (it depends on no
    token a faulty program chose): in float32 outside (c)'s tolerance, in
    bf16 over twice the limit ``K`` x the twin's error. A stream shared by the
    loops shows from a row's SECOND chunk pass on (inside one pass a loop
    attends what it has just written): hence 2.5 chunks."""
    sound_app, served = served_four
    prompt, generated, _, _, sound = served[0]
    _, want = reference_rows(sound_app.params, 4, prompt, generated[:1])
    _, twin = reference_rows(sound_app.params, 4, prompt, generated[:1], rounding=jnp.bfloat16)
    floor = np.abs(twin - want).max()
    assert is_the_reference(sound[:1], want)
    if fault == "no_norm_between_loops":
        # the reference with the fault, rounded as the program is, in the program's place
        _, got = reference_rows(sound_app.params, 4, prompt, generated[:1], between_loop_norm=False,
                                rounding=None if dtype == "float32" else jnp.bfloat16)
    else:
        if fault == "three_loops_of_four":
            app = paged_app(3, dtype)
        else:
            monkeypatch.setattr(base, "decoder_layer", _last_loops_stream(LAYERS, 4))
            app = paged_app(4, dtype)
        with LogitSpy(app) as spy:
            s = ServingSession(app)
            assert s.add_request("r", prompt, max_new_tokens=2)
            drain(s)
            got = spy.at(0, len(prompt) - 1)[None].astype(np.float32)
    if dtype == "float32":
        assert not is_the_reference(got, want)
    else:
        err = np.abs(got - want).max()
        assert err > 2 * correct.K * floor, (fault, err, floor)


# ---- (g) the checkpoint's names ----------------------------------------------------


def test_the_published_names_fill_the_tree():
    cls = get_model_builder("ouro").config_cls
    cfg = cls(TpuConfig(batch_size=1, seq_len=64, dtype="float32", fused_qkv=True),
              load_config=lambda c: [setattr(c, k, v) for k, v in attrs(4).items()])
    builder = get_model_builder("ouro")(cfg)
    rng = np.random.default_rng(24)
    H, I, D = 64, 160, 16
    sd = {"model.embed_tokens.weight": rng.normal(size=(VOCAB, H)),
          "model.norm.weight": rng.normal(size=(H,)),
          "lm_head.weight": rng.normal(size=(VOCAB, H)),
          "model.early_exit_gate.weight": rng.normal(size=(1, H)),
          "model.early_exit_gate.bias": rng.normal(size=(1,))}
    for i in range(LAYERS):
        p = f"model.layers.{i}."
        for name in ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            sd[p + name + ".weight"] = rng.normal(size=(H,))
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[p + f"self_attn.{name}.weight"] = rng.normal(size=(4 * D, H) if name != "o_proj" else (H, 4 * D))
        sd[p + "mlp.gate_proj.weight"] = rng.normal(size=(I, H))
        sd[p + "mlp.up_proj.weight"] = rng.normal(size=(I, H))
        sd[p + "mlp.down_proj.weight"] = rng.normal(size=(H, I))
    params = builder.convert_hf_state_dict(sd)
    shapes = builder.param_shapes()
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    assert got == jax.tree.map(lambda s: tuple(s), shapes, is_leaf=lambda x: isinstance(x, tuple))
    np.testing.assert_allclose(params["layers"]["input_layernorm_2"]["weight"][1],
                               sd["model.layers.1.input_layernorm_2.weight"], rtol=1e-6)
    np.testing.assert_allclose(params["layers"]["post_attention_layernorm_2"]["weight"][2],
                               sd["model.layers.2.post_attention_layernorm_2.weight"], rtol=1e-6)
    np.testing.assert_allclose(params["early_exit_gate"]["weight"][:, 0],
                               sd["model.early_exit_gate.weight"][0], rtol=1e-6)
    # and the tree computes: the reference reads the same leaves
    tokens = rng.integers(0, VOCAB, size=12)
    logits = ref.reference_logits(params, geometry(4), tokens, [11])
    assert np.isfinite(logits).all()
    del sd["model.layers.0.input_layernorm_2.weight"]
    with pytest.raises(KeyError, match="input_layernorm_2"):
        builder.convert_hf_state_dict(sd)


# ---- (h) what a looped stack does not do yet -----------------------------------------


def _config(model=None, **tpu):
    opts = dict(batch_size=2, seq_len=128, dtype="float32")
    opts.update(tpu)
    model = dict(attrs(4), **(model or {}))
    cls = get_model_builder("ouro").config_cls
    return cls(TpuConfig(**opts), load_config=lambda c: [setattr(c, k, v) for k, v in model.items()])


PAGED = dict(is_block_kv_layout=True, pa_block_size=16, pa_num_blocks=32, is_continuous_batching=True)


@pytest.mark.parametrize("tpu,what", [
    (dict(lora_config=LoraServingConfig(max_loras=1, max_lora_rank=4)), "lora_config"),
    (dict(speculation_length=3), "speculation"),
    (dict(enable_eagle_speculation=True, speculation_length=3, enable_fused_speculation=True),
     "speculation"),
    (dict(medusa_speculation_length=3, num_medusa_heads=2), "speculation"),
    (dict(serving_ragged=True, **PAGED), "serving_ragged"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(tp_degree=2), "degree > 1"),
    (dict(cp_degree=2, tp_degree=2), "degree > 1"),
    (dict(attention_dp_degree=2, tp_degree=2, is_continuous_batching=True), "degree > 1"),
])
def test_what_a_looped_stack_does_not_do_yet_is_refused_by_name(tpu, what):
    with pytest.raises(LoopedStackError, match=what):
        _config(**tpu)


def test_a_depth_that_differs_by_row_is_refused_and_a_missing_loop_count_too():
    with pytest.raises(LoopedStackError, match="early_exit_threshold 0.9 < 1"):
        _config(model=dict(early_exit_threshold=0.9))
    cls = get_model_builder("ouro").config_cls
    model = {k: v for k, v in attrs(4).items() if k != "total_ut_steps"}
    with pytest.raises(ValueError, match="total_ut_steps"):
        cls(TpuConfig(batch_size=2, seq_len=128),
            load_config=lambda c: [setattr(c, k, v) for k, v in model.items()])
    assert _config().total_ut_steps == 4  # and the sound config is taken


def test_the_ragged_step_and_a_per_layer_tap_refuse_a_looped_spec():
    app = contiguous_app(2, tensor_capture_config=TensorCaptureConfig(points=("layer_out",)))
    ids = np.arange(1, 9)[None].repeat(2, 0)
    with pytest.raises(NotImplementedError, match="looped stack"):
        app.capture_forward(ids)
    with pytest.raises(NotImplementedError, match="no looped stack"):
        base.mixed_forward(app.params, None, None, None, spec=app.spec)


def test_a_pass_counts_its_layer_passes_and_the_pool_its_streams(served_four):
    """``nxdi_loop_layer_passes_total{program}`` = dispatches x T x L and
    ``nxdi_kv_streams`` = T x L, from what the step knows; a stack that runs
    once counts none and its gauge stays 0."""
    total = lambda snap, name, **labels: sum(
        x["value"] for x in snap[name]["samples"]
        if all(x["labels"].get(k) == v for k, v in labels.items()))
    for app, streams in ((served_four[0], 4 * LAYERS), (paged_app(1), 0)):
        app.init_kv_cache()
        with TelemetrySession() as tel:
            s = ServingSession(app, telemetry=tel)
            assert s.block_bytes == max(streams, LAYERS) * BLOCK * 2 * 4 * 16 * 4  # float32
            s.add_request("a", np.arange(1, 41, dtype=np.int32), max_new_tokens=3)
            drain(s)
            snap = tel.registry.snapshot()
        decodes = total(snap, "nxdi_steps_total", kind="decode")
        assert decodes >= 2
        assert total(snap, "nxdi_loop_layer_passes_total", program="decode") == decodes * streams
        assert total(snap, "nxdi_loop_layer_passes_total", program="chunk") == 2 * streams  # 40 = 32 + 8
        assert [x["value"] for x in snap["nxdi_kv_streams"]["samples"]] == [streams]


# ---- (i) the KV manager at its edge ----------------------------------------------------


def test_preempt_then_resume_under_a_pool_of_one_request_and_a_fifth():
    """A pool of 1.2 x what one request needs and two requests: the second
    is admitted, the pool runs out while both decode, one is evicted and
    re-prefilled when the other has left; both end with the reference's
    logits at every position, through all four loops' streams."""
    rng = np.random.default_rng(25)
    budget = 12
    prompts = [rng.integers(0, VOCAB, size=n) for n in (int(1.5 * CHUNK) + 3, CHUNK + 1)]
    need = -(-(len(prompts[0]) + budget) // BLOCK)  # blocks of the longer request
    app = paged_app(4, blocks=int(np.ceil(1.2 * need)))
    with LogitSpy(app) as spy:
        s = ServingSession(app)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=budget)
        drain(s, limit=400)
        assert sum(s.requests[f"r{i}"].preemptions for i in range(2)) >= 1
        for i, p in enumerate(prompts):
            generated = s.requests[f"r{i}"].generated
            assert len(generated) == budget
            positions, want = reference_rows(app.params, 4, p, generated)
            best = None
            for slot in range(s.num_slots):
                try:
                    got = np.stack([spy.at(slot, q) for q in positions]).astype(np.float32)
                except AssertionError:
                    continue
                if best is None or np.abs(got - want).max() < np.abs(best - want).max():
                    best = got
            assert_is_the_reference(best, want)
