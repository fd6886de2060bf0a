"""The device side of the step programs under the program's own names
(``telemetry/device_scopes.py``; docs/OBSERVABILITY.md "Device scopes"):
``jax.named_scope`` around the parts of a layer in the shared code, the
dispatch spans that say which program they launch, the scope table a
recording session writes beside its trace, and ``summarize_trace``'s
``by_scope``. Small sizes, CPU, random weights.

A scope is a name at trace time: the compiled programs are what they were
but for ``op_name`` metadata, held here by lowering every program once more
with ``jax.named_scope`` patched to a null context (the one place that
switch exists)."""

import contextlib
import gzip
import json
import os
import re
import shutil

import numpy as np
import pytest

import jax

from benchmark.harness import system
from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
from neuronx_distributed_inference_tpu.telemetry import TelemetrySession, device_scopes
from neuronx_distributed_inference_tpu.telemetry.device_scopes import DEVICE_SCOPES
from neuronx_distributed_inference_tpu.utils.profiling import summarize_trace

SEED = 4200000017
CHUNK = 16
DENSE = dict(
    model_type="qwen3", hidden_size=64, intermediate_size=128, num_hidden_layers=3, vocab_size=512,
    hidden_act="silu", max_position_embeddings=256, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000, tie_word_embeddings=True,
)
FAMILIES = {
    "dense": DENSE,
    "mixtral": dict(DENSE, model_type="mixtral", num_local_experts=8, num_experts_per_tok=2,
                    tie_word_embeddings=False),
    "granite": dict(
        model_type="granitemoehybrid", hidden_size=64, shared_intermediate_size=128,
        intermediate_size=128, num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"], vocab_size=512, rms_norm_eps=1e-5,
        hidden_act="silu", rope_theta=10000, attention_multiplier=0.2, embedding_multiplier=3.0,
        residual_multiplier=0.5, logits_scaling=2.0, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=256, mamba_expand=2,
        mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
        position_embedding_type="nope", num_local_experts=0, num_experts_per_tok=0,
        tie_word_embeddings=True,
    ),
    "zaya": dict(
        model_type="zaya", hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, num_hidden_layers=3, layer_types=["hybrid"] * 3, vocab_size=512,
        rms_norm_eps=1e-5, hidden_act="silu", cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
        rope_parameters={"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                                    "rope_type": "default"}},
        num_experts=8, num_experts_per_tok=1, moe_intermediate_size=64, router_hidden_size=32,
        tie_word_embeddings=True, attention_bias=False, sliding_window=None,
    ),
    "sdar": dict(
        DENSE, model_type="sdar_moe", moe_intermediate_size=32, tie_word_embeddings=False,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, decoder_sparse_step=1,
        mlp_only_layers=[], denoise_steps=4, mask_token_id=511, block_length=4,
    ),
}
COMMON = {"embed", "layer.norm", "layer.qkv", "layer.kv_write", "layer.attn", "layer.o_proj",
          "layer.mlp", "head", "sample"}
EXPERTS = {"layer.moe.router", "layer.moe.experts"}
#: the scopes a family's step programs have (a block-step model's decode
#: step reveals, and marks non-finite tokens under ``sample`` as every step)
HAS = {
    ("dense", "decode"): COMMON, ("dense", "chunk"): COMMON,
    ("mixtral", "decode"): COMMON | EXPERTS, ("mixtral", "chunk"): COMMON | EXPERTS,
    ("granite", "decode"): COMMON | {"layer.ssm"}, ("granite", "chunk"): COMMON | {"layer.ssm"},
    ("zaya", "decode"): COMMON | EXPERTS, ("zaya", "chunk"): COMMON | EXPERTS,
    ("sdar", "decode"): COMMON | EXPERTS | {"reveal"}, ("sdar", "chunk"): COMMON | EXPERTS,
}
CASES = sorted(HAS)


def build(family: str):
    attrs = FAMILIES[family]
    cfg = dict(
        attrs,
        tpu_config=dict(
            dtype="float32", batch_size=4, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=16, pa_num_blocks=48, is_chunked_prefill=True,
            fused_qkv=family != "zaya",
        ),
        chunked_prefill=dict(max_num_seqs=4, kernel_q_tile_size=CHUNK),
    )
    return system.build_app(cfg, jax.devices()[:1], SEED).load(random_weights=True)


def program_texts(app) -> dict:
    tkg = app.token_generation_model
    out = {}
    for program, q in (("decode", None), ("chunk", CHUNK)):
        inputs = tkg.example_inputs(128, q_len=q)
        out[program] = tkg.trace_program(app.params, app.kv_cache, inputs, None)[2].as_text()
    return out


_APPS, _TEXTS, _PLAIN = {}, {}, {}


def app_of(family):
    if family not in _APPS:
        _APPS[family] = build(family)
    return _APPS[family]


def texts(family) -> dict:
    """{program: compiled text} of the family's decode and chunk programs."""
    if family not in _TEXTS:
        _TEXTS[family] = program_texts(app_of(family))
    return _TEXTS[family]


def plain_texts(family) -> dict:
    """The same programs of a second application, traced with
    ``jax.named_scope`` a null context."""
    if family not in _PLAIN:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            _PLAIN[family] = program_texts(build(family))
    return _PLAIN[family]


def components(text: str) -> set:
    return {c for name in re.findall(r'op_name="([^"]*)"', text) for c in name.split("/")}


def without_metadata(text: str) -> str:
    """The instructions alone: no ``metadata={...}``, no table of source
    positions at the module's head, no ``.<n>`` on a name. The number is
    the order in which the compiler first met an instruction, and the
    lowering emits a function that is called under two name stacks (a
    ``jnp.where`` under two scopes) once for each, to be inlined: same
    instructions, numbered in another order (zaya's chunk program)."""
    body = text[text.index("\n\n%") if "\n\n%" in text else 0:]
    return re.sub(r"\.\d+\b", "", re.sub(r",? ?metadata=\{[^}]*\}", "", body))


@pytest.mark.parametrize("family,program", CASES)
def test_every_scope_the_model_has_is_in_the_compiled_program(family, program):
    found = components(texts(family)[program]) & set(DEVICE_SCOPES)
    assert found == HAS[family, program]


@pytest.mark.parametrize("family,program", CASES)
def test_every_scope_found_is_in_the_vocabulary(family, program):
    """Nothing in the package names a scope outside ``DEVICE_SCOPES``, and
    ``layer.other`` is the one name no ``named_scope`` applies."""
    named = {c for c in components(texts(family)[program])
             if c.startswith("layer.") or c in DEVICE_SCOPES}
    assert named <= set(DEVICE_SCOPES) - {"layer.other"}


@pytest.mark.parametrize("family,program", CASES)
def test_the_program_is_what_it_was_but_for_metadata(family, program):
    scoped, plain = texts(family)[program], plain_texts(family)[program]
    assert not components(plain) & set(DEVICE_SCOPES)
    assert without_metadata(scoped) == without_metadata(plain)
    assert scoped.count(" = ") == plain.count(" = ")  # as many instructions


@pytest.mark.parametrize("family,program", CASES)
def test_the_table_names_every_instruction_that_runs_and_no_container(family, program):
    text = texts(family)[program]
    table = device_scopes.scope_table(text)
    assert table["module"] == f"jit_token_generation_model_{program}"
    assert set(table["ops"].values()) <= set(DEVICE_SCOPES) | {""}
    assert not any(device_scopes.CONTAINER.match(name) for name in table["ops"])
    # the layer loop's body is all named: nothing of it is left under ""
    assert {"layer.kv_write", "layer.attn", "layer.other", "head"} <= set(table["ops"].values())
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    in_body = re.search(r"^%?" + re.escape(body) + r" \(.*?\n\}", text, re.M | re.S).group(0)
    names = [m.group(1) for m in map(device_scopes._INSTRUCTION.match, in_body.splitlines()[1:]) if m]
    assert names and all(table["ops"][n] for n in names if n in table["ops"])


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/closed_call/layer.kv_write/scatter", "layer.kv_write"),
    ("jit(f)/while/body/closed_call/layer.mlp/layer.moe.experts/ragged_dot", "layer.moe.experts"),
    ("jit(f)/while/body/dynamic_slice", "layer.other"),
    ("jit(f)/while/cond/lt", "layer.other"),
    ("jit(f)/head/dot_general", "head"),
    ("jit(f)/reduce_sum", ""),
    ("", ""),
])
def test_scope_of_takes_the_innermost_scope(op_name, scope):
    assert device_scopes.scope_of(op_name) == scope


def test_the_table_reads_a_compiled_text():
    text = """HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner.1 = f32[4]{0} tanh(%p), metadata={op_name="jit(step)/while/body/layer.mlp/tanh"}
}

%body.3 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/layer.mlp/tanh"}
  %copy.2 = f32[4]{0} copy(%fusion.7)
  %fusion.8 = f32[4]{0} fusion(%u), kind=kCustom, calls=%fused_computation
  %bitcast.6 = f32[2,2]{1,0} bitcast(%fusion.8), metadata={op_name="jit(step)/while/body/layer.kv_write/scatter"}
  %ragged-dot-none.1 = f32[4]{0} custom-call(%slice.5), custom_call_target="x", metadata={op_name="ragged-dot-none"}
  %fusion.9 = f32[4]{0} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/layer.mlp/layer.moe.experts/mul"}
  %slice.5 = f32[4]{0} dynamic-slice(%w), metadata={op_name="jit(step)/while/body/dynamic_slice"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%i, %copy.2)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %gather.1 = f32[4]{0} gather(%x), metadata={op_name="jit(step)/embed/gather"}
  %while.4 = (s32[], f32[4]{0}) while(%t), condition=%cond.2, body=%body.3
  %copy.9 = f32[4]{0} copy(%g)
  %copy.10 = f32[4]{0} copy(%gather.1)
  ROOT %dot.3 = f32[4]{0} dot(%copy.9, %copy.9), metadata={op_name="jit(step)/head/dot_general"}
}
"""
    table = device_scopes.scope_table(text)
    assert table["module"] == "jit_step"
    ops = table["ops"]
    assert "inner.1" not in ops and "while.4" not in ops  # inside a fusion; a container
    assert ops["fusion.7"] == "layer.mlp" and ops["slice.5"] == "layer.other"
    # no metadata: named by what consumes it (a scatter the compiler rebuilt,
    # a copy it put in), else by where it stands
    assert ops["fusion.8"] == "layer.kv_write" and ops["copy.9"] == "head"
    assert ops["ragged-dot-none.1"] == "layer.moe.experts"  # the compiler's own name is no path
    assert ops["copy.2"] == "layer.other" and ops["copy.10"] == ""
    assert ops["gather.1"] == "embed" and ops["dot.3"] == "head"


def test_the_block_form_of_the_kv_write_is_under_its_scope():
    """At chunk widths on the 128 lanes the paged KV write gathers a row's
    pool blocks, merges and scatters them whole
    (modules/block_kvcache._write_blocks). Every instruction of the chunk
    program that holds one of those scatters or gathers is tabled under
    ``layer.kv_write``: ``chunk.kv_write_dev_ms`` keeps reading the write,
    and neither ``layer.other`` nor the unscoped share grows by it."""
    from tests.conftest import paged_write_instructions

    cfg = dict(
        dict(DENSE, head_dim=128),
        tpu_config=dict(
            dtype="float32", batch_size=4, seq_len=256, enable_bucketing=True,
            context_encoding_buckets=[256], token_generation_buckets=[128, 256],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_block_size=16, pa_num_blocks=48, is_chunked_prefill=True, fused_qkv=True,
        ),
        chunked_prefill=dict(max_num_seqs=4, kernel_q_tile_size=2 * CHUNK),
    )
    app = system.build_app(cfg, jax.devices()[:1], SEED).load(random_weights=True)
    tkg = app.token_generation_model
    inputs = tkg.example_inputs(128, q_len=2 * CHUNK)
    text = tkg.trace_program(app.params, app.kv_cache, inputs, None)[2].as_text()
    table = device_scopes.scope_table(text)["ops"]
    writes = [
        n for n in paged_write_instructions(text, app.kv_cache.k.shape, 4, 3) if n in table
    ]
    assert len(writes) >= 4  # K and V: a gather of the held blocks, a scatter
    assert {table[n] for n in writes} == {"layer.kv_write"}


class Annotations:
    """What ``jax.profiler.TraceAnnotation`` is handed at entry."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **fields):
        self.seen.append((name, fields))
        return contextlib.nullcontext()


def drive(app, tel, steps=12, start_at=None, profile_dir=None):
    """A 40-token and a 4-token request through ``step()``; telemetry
    started before step ``start_at``."""
    app.init_kv_cache()
    session = ServingSession(app, telemetry=tel)
    session.add_request("a", list(range(1, 41)), max_new_tokens=8)
    for k in range(steps):
        if k == start_at:
            tel.start(profile_dir=profile_dir)
        if k == 2:
            session.add_request("b", [5, 17, 92, 41], max_new_tokens=8)
        session.step()
    return session


def test_the_dispatch_spans_say_at_entry_which_program_they_launch(monkeypatch):
    app = app_of("dense")
    seen = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    with TelemetrySession() as tel:
        session = drive(app, tel)
    tkg = app.token_generation_model
    launches = [(n, f) for n, f in seen.seen if n.endswith(".dispatch")]
    assert {n for n, _ in launches} == {"serving.decode.dispatch", "serving.prefill_chunk.dispatch"}
    for name, fields in launches:
        assert fields["program"] == ("decode" if name == "serving.decode.dispatch" else "chunk")
        assert fields["kv"] in tkg.buckets and "step" in fields
        assert fields["q"] == 1 if fields["program"] == "decode" else fields["q"] in (8, CHUNK)
    # the decode pass's span no longer learns its bucket after entry
    events = [e for e in tel.events if e["type"] == "span" and e["name"] == "serving.decode"]
    assert events and all("kv_bucket" not in e for e in events)
    noted = {(f["program"], f["q"], f["kv"]) for _, f in launches}
    assert session._programs_noted == noted


def test_a_recording_session_writes_one_table_per_program_dispatched(tmp_path):
    app = app_of("dense")
    tel = TelemetrySession(enabled=False)
    session = drive(app, tel, start_at=0, profile_dir=str(tmp_path))
    noted = set(session._programs_noted)
    assert {p for p, _, _ in noted} == {"decode", "chunk"}
    trace = tel.stop()
    assert trace is not None and not session._programs_noted
    with open(tmp_path / device_scopes.TABLE_FILE) as f:
        tables = json.load(f)
    assert set(tables) == {device_scopes.table_key(*key) for key in noted}
    for key, table in tables.items():
        assert table["module"] == "jit_token_generation_model_" + key.split(":")[0]
        assert "layer.kv_write" in table["ops"].values()
    tel.close()


def test_a_stopped_session_notes_nothing_and_writes_nothing(tmp_path):
    app = app_of("dense")
    tel = TelemetrySession(enabled=False)
    session = drive(app, tel)
    assert not session._programs_noted
    # a profile with no dispatch in it, and a stop that stops no profile
    tel.start(profile_dir=str(tmp_path))
    assert tel.stop() is not None
    tel.start()
    session.add_request("c", [7, 8, 9], max_new_tokens=2)
    session.step()
    assert tel.stop() is None and session._programs_noted
    assert not os.path.exists(tmp_path / device_scopes.TABLE_FILE)
    tel.close()


DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "selftest", "data")


@pytest.fixture
def chip_trace(tmp_path):
    """The recorded chip trace of the benchmark's selftest, laid out as a
    profiler directory."""
    run = tmp_path / "plugins" / "profile" / "recorded"
    run.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "device_scope_small.xplane.pb.gz"), run / "small.xplane.pb.gz")
    return tmp_path


def test_summarize_trace_gives_the_old_keys_without_the_file(chip_trace):
    summary = summarize_trace(str(chip_trace))
    assert set(summary) == {"total_us", "ops"} and summary["ops"]


def test_summarize_trace_gives_device_time_by_scope_with_the_file(chip_trace):
    shutil.copy(os.path.join(DATA, "device_scope_small.device_scopes.json"),
                chip_trace / device_scopes.TABLE_FILE)
    summary = summarize_trace(str(chip_trace))
    assert {"total_us", "ops", "by_scope"} <= set(summary)
    by_scope = summary["by_scope"]
    assert set(by_scope) == {"jit_token_generation_model_decode", "jit_token_generation_model_chunk"}
    for module, scopes in by_scope.items():
        assert set(scopes) <= set(DEVICE_SCOPES) | {""}
        assert {"layer.attn", "layer.kv_write", "head"} <= set(scopes)
        assert sum(t["share"] for t in scopes.values()) == pytest.approx(1.0)
        assert all(t["seconds"] >= 0 for t in scopes.values())
    total = sum(t["seconds"] for scopes in by_scope.values() for t in scopes.values())
    assert 0 < total * 1e6 <= summary["total_us"]
