#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process, one TPU, Llama-3.2-1B at its full published width (16 layers,
vocab 128256, bf16) with weights made from ``--seed``, through the entry
points a user calls: ``TpuModelForCausalLM.load()`` -> ``generate()`` and
``ServingSession`` continuous batching on the paged cache. Phases, in order
— the first failure ends the run with a non-zero exit code:

1. device       JAX found a TPU whose ``device_kind`` the device registry knows
2. generate     bucketed CTE + chunked TKG, shapes / vocab / finite / same bytes twice
3. kernels      a ``tpu_custom_call`` is IN the compiled CTE/TKG programs wherever
                ``ops/kernel_mode`` says the kernel is on — read from the
                executable, not from the gate
4. kernel-vs-native  same weights and prompt with the attention kernels off;
                prefill and first decode-step logits within ``LOGIT_TOL``
5. serving      8 staggered requests of mixed length, split CTE/TKG path and
                ``serving_ragged=True``; all FINISHED with their full budget;
                first-step logits of the two paths within ``LOGIT_TOL``
6. no compilation after warm-up in phases 2 and 5 (counted at the compiler)

Every earlier stdout line is one JSON object of facts (no rates). The LAST
line is ``{"ok": true, "device": {...}}`` and is printed only if every phase
passed. Off the chip the script fails in phase 1 and prints no result.

``--multichip`` (four chips, run by hand) runs ONLY the tensor-parallel
path and what it is compared with: 1B at tp=4 against the same weights at
tp=1, Llama-3.1-8B bf16 at tp=4 with per-device memory, and a
``ServingRouter`` over four one-chip replicas.

The phase functions take the model shape as an argument, so
tests/test_chip_smoke.py runs them at a tiny shape on the CPU harness.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

#: kernel-vs-native, split-vs-ragged and tp4-vs-tp1 logits are compared as
#: max|a-b| <= LOGIT_TOL * max|b|. bf16 carries 8 mantissa bits (eps 2^-8 =
#: 0.4%); two reduction orders through 16 layers of bf16 matmuls and a
#: 2048-deep lm_head contraction differ by a few eps — 5% of the logit scale
#: is the bound the repo's hardware checks have always used for bf16. A wrong
#: kernel (a mis-masked tile, a dropped head) moves logits by their own scale.
LOGIT_TOL = 0.05

#: phase 2/3/4 shape: one bucket each
GENERATE_SHAPE = dict(
    batch=2, prompt_lens=(128, 97), new_tokens=64,
    seq_len=512, ce_buckets=(128,), tkg_buckets=(512,),
)

#: phase 5 shape: paged cache, chunked prefill, 8 slots, mixed prompt lengths
#: and budgets; bf16 so the serving apps share the generate app's weights
SERVING_SHAPE = dict(
    prompt_lens=(128, 37, 200, 64, 16, 150, 90, 255),
    budgets=(48, 32, 40, 48, 24, 32, 48, 40),
    seq_len=1024, blocks=512, block_size=32, max_seqs=8, q_tile=128,
)

#: --multichip shapes
TP_SHAPE = dict(
    batch=2, prompt_lens=(128, 97), seq_len=512,
    ce_buckets=(128,), tkg_buckets=(512,),
)
BIG_SHAPE = dict(
    batch=1, prompt_lens=(128,), new_tokens=8,
    seq_len=512, ce_buckets=(128,), tkg_buckets=(512,),
)
ROUTER_SHAPE = dict(
    prompt_lens=(64, 100, 30, 128, 80, 50, 120, 16),
    budgets=(16,) * 8,
    seq_len=512, blocks=128, block_size=32, max_seqs=4, q_tile=128,
)


class SmokeError(AssertionError):
    """A phase's check failed."""


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


def emit(**obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# counting compilations where they happen
# ---------------------------------------------------------------------------


class CompileLog:
    """Counts what reaches the compiler through ``jax.monitoring``: every
    jit-cache miss ends in one backend-compile event (a real compile or a
    persistent-cache retrieval), and the persistent cache reports its hits
    and misses. Unmarked helper jits are counted too, which the
    ``trace_marker``-based RetraceGuard cannot see."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1
        elif event == self.CACHE_MISS:
            self.cache_misses += 1


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def build(attrs, shape, seed, *, extra=None, weights_from=None, paged=False,
          devices=None, load=True):
    """One application, built the way a deployment builds it: bucketed
    CTE/TKG programs on the contiguous cache, or (``paged``) continuous
    batching with chunked prefill on the paged cache; the fused QKV layout
    either way. ``weights_from``: share that app's device weights instead of
    loading a second 3 GB copy — same model shape, so same param tree.
    ``load=False`` returns the app unloaded: the caller brings the weights.
    Weights come from ``seed`` alone."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig, TpuConfig
    from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig
    from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
    from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM
    from neuronx_distributed_inference_tpu.utils.compile_cache import configure_compile_cache

    # an app that shares weights never calls load(), which is where the
    # program points the persistent compile cache
    configure_compile_cache()
    if paged:
        kw = dict(
            batch_size=shape["max_seqs"],
            context_encoding_buckets=[shape["seq_len"]],
            token_generation_buckets=[shape["seq_len"]],
            is_continuous_batching=True, ctx_batch_size=1, is_block_kv_layout=True,
            pa_num_blocks=shape["blocks"], pa_block_size=shape["block_size"],
            is_chunked_prefill=True,
            chunked_prefill_config=ChunkedPrefillConfig(
                max_num_seqs=shape["max_seqs"], kernel_q_tile_size=shape["q_tile"]),
        )
    else:
        kw = dict(batch_size=shape["batch"],
                  context_encoding_buckets=list(shape["ce_buckets"]),
                  token_generation_buckets=list(shape["tkg_buckets"]))
    tc = TpuConfig(
        seq_len=shape["seq_len"], dtype="bfloat16", enable_bucketing=True, fused_qkv=True,
        seed=seed, output_logits=True, retrace_guard=True, **kw, **(extra or {}),
    )
    # a router replica's mesh spans its own partition of the devices
    mesh = mesh_from_config(tc, devices=devices) if devices is not None else None
    cfg = LlamaInferenceConfig(
        tc, load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])
    app = TpuModelForCausalLM(None, cfg, mesh=mesh)
    if weights_from is not None:
        app.params, app._pspecs = weights_from.params, weights_from._pspecs
        app.init_kv_cache()
    elif load:
        app.load(random_weights=True)
    return app


def make_prompts(vocab, prompt_lens, seed):
    """Right-padded (B, max_len) ids + mask from ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    width = max(prompt_lens)
    ids = np.zeros((len(prompt_lens), width), np.int64)
    mask = np.zeros_like(ids)
    for row, n in enumerate(prompt_lens):
        ids[row, :n] = rng.integers(0, vocab, size=n)
        mask[row, :n] = 1
    return ids, mask


def check_logits_close(what, got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), f"{what}: non-finite logits")
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    check(
        err <= LOGIT_TOL * scale,
        f"{what}: max logit error {err:.4g} > {LOGIT_TOL} * logit scale {scale:.4g}",
    )
    return {"max_abs_err": err, "logit_scale": scale}


def prefill_and_first_decode(app, ids, mask, forced=None):
    """Prefill + ONE decode step through the external-scheduler
    ``app.forward`` (the warmed CTE and one-step TKG programs). The decode
    step is teacher-forced with ``forced`` (default: this app's own prefill
    argmax) so two apps compare logits on identical inputs — random weights
    give near-flat logits and argmax flips on rounding."""
    import numpy as np

    B, S = ids.shape
    seq_ids = np.arange(B, dtype=np.int32)
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    tok, cte_logits = app.forward(
        ids, pos, seq_ids, attention_mask=mask, phase="cte"
    )
    if forced is None:
        forced = tok[:, -1:]
    ctx = mask.sum(axis=1).astype(np.int32)[:, None]
    _, tkg_logits = app.forward(forced, ctx, seq_ids, phase="tkg")
    return forced, cte_logits[:, -1], tkg_logits[:, -1]


def device_memory():
    """{device id: bytes_in_use / peak} where the backend reports it."""
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats()
        if stats:
            out[str(d.id)] = {
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            }
    return out


def release(*apps):
    """Drop apps' device arrays before the next phase loads: several 3 GB
    apps with their caches do not fit 16 GB by accident."""
    for app in apps:
        app.params = app.kv_cache = None
    gc.collect()


# ---------------------------------------------------------------------------
# phase 1 — device
# ---------------------------------------------------------------------------


def phase_device(expect_count=1):
    import jax

    from neuronx_distributed_inference_tpu.analysis.device_model import (
        resolve_device,
    )

    devices = jax.devices()
    check(devices, "jax.devices() is empty")
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found platform {dev.platform!r}, not a TPU")
    spec = resolve_device(dev.device_kind)
    check(spec is not None,
          f"device_kind {dev.device_kind!r} is not in DEVICE_REGISTRY")
    check(len(devices) == expect_count,
          f"expected {expect_count} chip(s), JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# phase 2 — generate()
# ---------------------------------------------------------------------------


def phase_generate(attrs, shape, seed, log, extra=None):
    """Returns (app, facts): the warmed app is kept for phases 3-5."""
    import numpy as np

    from neuronx_distributed_inference_tpu.analysis.retrace_guard import (
        RetraceGuard,
    )

    t0 = time.monotonic()
    app = build(attrs, shape, seed, extra=extra)
    load_s = time.monotonic() - t0
    ids, mask = make_prompts(attrs["vocab_size"], shape["prompt_lens"], seed)
    new = shape["new_tokens"]

    c0, s0 = log.compiles, log.compile_s
    t0 = time.monotonic()
    app.warmup()  # every CTE/TKG bucket; retrace_guard seals the runners
    out = app.generate(ids, mask, max_new_tokens=new)
    warm_wall_s = time.monotonic() - t0
    c1, s1 = log.compiles, log.compile_s
    with RetraceGuard(fail=False) as guard:
        again = app.generate(ids, mask, max_new_tokens=new)
    after = log.compiles - c1

    B, S = ids.shape
    V = attrs["vocab_size"]
    check(out.sequences.shape == (B, S + new),
          f"sequences {out.sequences.shape} != {(B, S + new)}")
    check(out.num_generated == new, f"generated {out.num_generated} != {new}")
    gen = out.sequences[:, S:]
    check(((gen >= 0) & (gen < V)).all(), "generated token outside the vocabulary")
    check(out.logits is not None and out.logits.shape == (B, new, V),
          f"logits shape {None if out.logits is None else out.logits.shape}")
    check(np.isfinite(out.logits).all(), "non-finite logits from generate()")
    check(out.sequences.tobytes() == again.sequences.tobytes()
          and out.logits.tobytes() == again.logits.tobytes(),
          "second generate() of the same program gave different bytes")
    check(after == 0 and not guard.traces,
          f"{after} compilation(s) after warm-up in generate(): {guard.traces}")
    facts = dict(
        layers=attrs["num_hidden_layers"], hidden=attrs["hidden_size"],
        vocab=V, dtype="bfloat16", batch=B, prompt_lens=list(shape["prompt_lens"]),
        new_tokens=new, load_s=round(load_s, 2),
        warmup_wall_s=round(warm_wall_s, 2), warmup_compiles=c1 - c0,
        warmup_compile_s=round(s1 - s0, 2), compiles_after_warmup=after,
        logit_scale=float(np.abs(out.logits).max()),
    )
    return app, facts


# ---------------------------------------------------------------------------
# phase 3 — the kernels are really in the program
# ---------------------------------------------------------------------------


def _program_row(traced, compiled, gate):
    from neuronx_distributed_inference_tpu.analysis.kernel_registry import (
        find_pallas_eqns,
    )

    return {
        "gate": bool(gate),
        "pallas_calls": len(find_pallas_eqns(traced.jaxpr.jaxpr)),
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
    }


def _runner_row(runner, app, inputs, gate):
    with runner.seal_suspended():
        traced, _, compiled = runner.trace_program(
            app.params, app.kv_cache, inputs, None
        )
    return _program_row(traced, compiled, gate)


def kernel_census(app, *, chunk_q=None, mixed_buckets=()):
    """{program: {gate, pallas_calls, tpu_custom_calls}} for the programs
    this app serves: every CTE and one-step TKG bucket, the multi-step
    decode programs generate()/the drain built, the paged chunk-prefill
    program at ``chunk_q`` and the mixed step at ``mixed_buckets``. ``gate``
    is what ops/kernel_mode decides for that shape; the counts are read from
    the traced jaxpr and the compiled executable."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.ops import kernel_mode as km

    aspec = app.spec.attn
    tc = app.config.tpu_config
    cte, tkg = app.context_encoding_model, app.token_generation_model
    rows = {}
    if not tc.is_block_kv_layout:
        for b in cte.buckets:
            rows[f"cte[{b}]"] = _runner_row(
                cte, app, cte.example_inputs(b), km.use_flash(aspec, b)
            )
    # a ragged app serves every step through the mixed program alone
    for b in tkg.buckets if app.mixed_step_model is None else ():
        rows[f"tkg[{b}]"] = _runner_row(
            tkg, app, tkg.example_inputs(b), km.use_tkg(aspec, 1, b)
        )
        if chunk_q:
            rows[f"tkg_chunk_prefill[q{chunk_q},{b}]"] = _runner_row(
                tkg, app, tkg.example_inputs(b, q_len=chunk_q),
                km.use_paged_flash(aspec, chunk_q) or km.use_tkg(aspec, chunk_q, b),
            )
    B = tkg.batch_size
    sds = jax.ShapeDtypeStruct
    for (steps, b, has_adapter), fn in tkg._decode_fns.items():
        if has_adapter:
            continue
        with jax.set_mesh(tkg.mesh), tkg.seal_suspended():
            traced = fn.trace(
                app.params, app.kv_cache, sds((B, 1), jnp.int32),
                sds((B, 1), jnp.int32), sds((B,), jnp.int32),
                sds((B, 3), jnp.float32), None,  # greedy: no rng
            )
            compiled = traced.lower().compile()
        rows[f"tkg_decode[{steps}x,{b}]"] = _program_row(
            traced, compiled, km.use_tkg(aspec, 1, b)
        )
    mixed = app.mixed_step_model
    for b in mixed_buckets:
        rows[f"mixed[{b}]"] = _runner_row(
            mixed, app, mixed.example_inputs(b), km.use_ragged(aspec, b)
        )
    return rows


def check_census(rows, *, interpret, require=()):
    """The gate and the program must agree, and off interpret mode a
    dispatched kernel must be a ``tpu_custom_call`` in the executable.
    ``require``: program-name prefixes that MUST carry a compiled kernel
    (the chip run names them, so a gate that flips off in silence fails)."""
    for prefix in require:
        named = [r for n, r in rows.items() if n.startswith(prefix)]
        check(not interpret, "kernel_interpret() is True on the chip path")
        check(named and all(r["tpu_custom_calls"] for r in named),
              f"no compiled kernel in the {prefix} programs: {rows}")
    for name, r in rows.items():
        check(bool(r["pallas_calls"]) == r["gate"],
              f"{name}: kernel_mode gate={r['gate']} but the traced program "
              f"has {r['pallas_calls']} pallas_call(s)")
        if r["gate"] and not interpret:
            check(r["tpu_custom_calls"] > 0,
                  f"{name}: kernel gated on but no tpu_custom_call in the "
                  f"compiled executable")


def phase_kernels(app, *, require=()):
    from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret

    interpret = kernel_interpret()
    rows = kernel_census(app)
    check_census(rows, interpret=interpret, require=require)
    return {"kernel_interpret": interpret, "programs": rows}


# ---------------------------------------------------------------------------
# phase 4 — kernel path against native path
# ---------------------------------------------------------------------------

NATIVE = dict(attn_kernel_enabled=False, attn_block_tkg_kernel_enabled=False)


def phase_kernel_vs_native(attrs, shape, seed, app):
    ids, mask = make_prompts(attrs["vocab_size"], shape["prompt_lens"], seed)
    forced, k_cte, k_tkg = prefill_and_first_decode(app, ids, mask)
    native = build(attrs, shape, seed, extra=NATIVE, weights_from=app)
    try:
        _, n_cte, n_tkg = prefill_and_first_decode(native, ids, mask, forced)
    finally:
        release(native)
    return {
        "tolerance": LOGIT_TOL,
        "prefill": check_logits_close("kernel vs native prefill", k_cte, n_cte),
        "first_decode": check_logits_close(
            "kernel vs native first decode step", k_tkg, n_tkg
        ),
    }


# ---------------------------------------------------------------------------
# phase 5 — serving on the paged cache, split and ragged
# ---------------------------------------------------------------------------


class FirstStepTap:
    """Keeps the logits of a runner's FIRST dispatch (the device array; the
    caller fetches the rows it wants)."""

    def __init__(self, runner):
        self.runner = runner
        self.logits = None

    def __enter__(self):
        self._orig = self.runner._fn

        def tapped(*args, **kwargs):
            out = self._orig(*args, **kwargs)
            if self.logits is None:
                self.logits = out.logits
            return out

        # instance attribute: the runner calls self._fn(...)
        self.runner._fn = tapped
        return self

    def __exit__(self, *exc):
        self.runner._fn = self._orig


def drain(session, prompts, budgets):
    """Staggered arrivals: two requests up front, one more per scheduler
    step, then drain."""
    n = len(prompts)
    nxt = 0
    while nxt < 2:
        check(session.add_request(str(nxt), prompts[nxt], max_new_tokens=budgets[nxt]),
              f"request {nxt} refused")
        nxt += 1
    while nxt < n:
        session.step()
        if session.free_slots:
            check(session.add_request(str(nxt), prompts[nxt],
                                      max_new_tokens=budgets[nxt]),
                  f"request {nxt} refused")
            nxt += 1
    session.run_to_completion()
    return {rid: list(r.generated) for rid, r in session.requests.items()}


def serve_twice(app, prompts, budgets, log, tap_runner, vocab):
    """Warm-up drain, then the same mix again with compilations counted and
    the first dispatch tapped. Returns (tokens, first-step logits of
    requests 0 and 1, compile facts)."""
    import numpy as np

    from neuronx_distributed_inference_tpu.runtime.serving import (
        STATUS_FINISHED,
        ServingSession,
    )

    c0, s0 = log.compiles, log.compile_s
    warm = drain(ServingSession(app), prompts, budgets)
    app.init_kv_cache()  # fresh block pool
    c1, s1 = log.compiles, log.compile_s
    session = ServingSession(app)
    with FirstStepTap(tap_runner) as tap:
        tokens = drain(session, prompts, budgets)
    after = log.compiles - c1
    check(len(session.requests) == len(prompts) and not session.rejected,
          f"dropped requests: have {sorted(session.requests)}, "
          f"rejected {sorted(session.rejected)}")
    for rid, req in session.requests.items():
        check(req.status == STATUS_FINISHED,
              f"request {rid} ended {req.status}({req.fail_reason})")
        check(len(req.generated) == budgets[int(rid)],
              f"request {rid}: {len(req.generated)} tokens of {budgets[int(rid)]}")
        check(all(0 <= t < vocab for t in req.generated),
              f"request {rid}: token outside the vocabulary")
    check(tokens == warm, "the second drain of the same mix gave different tokens")
    check(after == 0, f"{after} compilation(s) after warm-up in serving")
    # the tapped dispatch prefilled requests 0 and 1 whole (both fit one
    # chunk): split path logits are (B, q, V) — take each row's last prompt
    # position; the mixed step gathers that position itself, (R, 1, V)
    first = tap.logits
    rows = []
    for i in (0, 1):  # admission takes the lowest free slot: request i -> slot i
        idx = len(prompts[i]) - 1 if first.shape[1] > 1 else 0
        rows.append(np.asarray(first[i, idx], np.float32))
    facts = dict(finished=len(tokens), requests=len(prompts),
                 tokens=sum(len(t) for t in tokens.values()),
                 warmup_compiles=c1 - c0, warmup_compile_s=round(s1 - s0, 2),
                 compiles_after_warmup=after)
    return tokens, np.stack(rows), facts


def phase_serving(attrs, shape, seed, log, weights_from, *, compiled=False,
                  extra=None):
    """``compiled``: the chip run — the TKG and mixed programs must carry a
    compiled kernel (see check_census ``require``)."""
    import numpy as np

    from neuronx_distributed_inference_tpu.modules.autobucketing import (
        get_target_bucket,
    )
    from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret

    check(max(shape["prompt_lens"][:2]) <= shape["q_tile"],
          "requests 0 and 1 must prefill in one chunk (first-step comparison)")
    rng = np.random.default_rng(seed + 1)
    vocab = attrs["vocab_size"]
    prompts = [rng.integers(0, vocab, size=n).tolist() for n in shape["prompt_lens"]]
    budgets = list(shape["budgets"])
    interpret = kernel_interpret()
    facts = {"tolerance": LOGIT_TOL}

    split = build(attrs, shape, seed, extra=extra, weights_from=weights_from,
                  paged=True)
    try:
        split_tokens, split_first, facts["split"] = serve_twice(
            split, prompts, budgets, log, split.token_generation_model, vocab
        )
        rows = kernel_census(split, chunk_q=shape["q_tile"])
        check_census(rows, interpret=interpret, require=("tkg",) if compiled else ())
        facts["split"]["programs"] = rows
    finally:
        release(split)

    ragged = build(attrs, shape, seed, weights_from=weights_from, paged=True,
                   extra=dict(serving_ragged=True, **(extra or {})))
    try:
        ragged_tokens, ragged_first, facts["ragged"] = serve_twice(
            ragged, prompts, budgets, log, ragged.mixed_step_model, vocab
        )
        mixed = ragged.mixed_step_model
        buckets = sorted({mixed.buckets[0],
                          get_target_bucket(mixed.buckets, 2 * shape["q_tile"])})
        rows = kernel_census(ragged, mixed_buckets=buckets)
        check_census(rows, interpret=interpret, require=("mixed",) if compiled else ())
        facts["ragged"]["programs"] = rows
    finally:
        release(ragged)

    facts["first_step"] = check_logits_close(
        "ragged vs split first-step logits", ragged_first, split_first
    )
    facts["tokens_agree"] = sum(
        split_tokens[r] == ragged_tokens[r] for r in split_tokens
    )
    return facts


# ---------------------------------------------------------------------------
# --multichip: the tensor-parallel path on four chips
# ---------------------------------------------------------------------------


def random_hf_state_dict(attrs, seed):
    """Random weights in the HF llama layout — the degree-independent
    source both tp degrees convert from (the fused-QKV layout is
    rank-interleaved, so two random PARAM trees at different degrees are
    different models). One PCG64 stream per tensor, in threads."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    H, I = attrs["hidden_size"], attrs["intermediate_size"]
    D = attrs.get("head_dim") or H // attrs["num_attention_heads"]
    Hq, Hkv = attrs["num_attention_heads"], attrs["num_key_value_heads"]
    V = attrs["vocab_size"]
    shapes = {"model.embed_tokens.weight": (V, H)}
    if not attrs.get("tie_word_embeddings"):
        shapes["lm_head.weight"] = (V, H)
    for i in range(attrs["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes.update({
            p + "self_attn.q_proj.weight": (Hq * D, H),
            p + "self_attn.k_proj.weight": (Hkv * D, H),
            p + "self_attn.v_proj.weight": (Hkv * D, H),
            p + "self_attn.o_proj.weight": (H, Hq * D),
            p + "mlp.gate_proj.weight": (I, H),
            p + "mlp.up_proj.weight": (I, H),
            p + "mlp.down_proj.weight": (H, I),
        })

    def gen(item):
        i, (name, shp) = item
        g = np.random.Generator(np.random.PCG64([seed, i]))
        a = g.standard_normal(shp, dtype=np.float32)
        a *= 0.02
        return name, a

    with ThreadPoolExecutor(max_workers=8) as ex:
        sd = dict(ex.map(gen, enumerate(shapes.items())))
    sd["model.norm.weight"] = np.ones(H, np.float32)
    for i in range(attrs["num_hidden_layers"]):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = np.ones(H, np.float32)
        sd[p + "post_attention_layernorm.weight"] = np.ones(H, np.float32)
    return sd


def phase_tp_vs_single(attrs, shape, seed, serving_shape, *, tp, require=()):
    """1B at ``tp_degree=tp`` against the SAME weights at tp=1 on one of the
    chips: prefill + first decode logits, and the ragged mixed step still
    carries its kernel through ``jax.shard_map``."""
    import jax

    sd = random_hf_state_dict(attrs, seed)
    ids, mask = make_prompts(attrs["vocab_size"], shape["prompt_lens"], seed)

    ref = build(attrs, shape, seed, devices=jax.devices()[:1], load=False)
    ref.load(state_dict=sd)
    ref.warmup()
    forced, r_cte, r_tkg = prefill_and_first_decode(ref, ids, mask)
    release(ref)

    sharded = build(attrs, shape, seed, extra=dict(tp_degree=tp), load=False)
    sharded.load(state_dict=sd)
    sharded.warmup()
    _, s_cte, s_tkg = prefill_and_first_decode(sharded, ids, mask, forced)
    facts = {
        "tp_degree": tp, "tolerance": LOGIT_TOL,
        "prefill": check_logits_close(f"tp{tp} vs tp1 prefill", s_cte, r_cte),
        "first_decode": check_logits_close(
            f"tp{tp} vs tp1 first decode step", s_tkg, r_tkg
        ),
    }
    del sd

    # the ragged mixed step at tp (same mesh, so the sharded weights are
    # shared): the kernel rides shard_map, so the partitioned executable
    # must still hold the custom call
    from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret

    ragged = build(attrs, serving_shape, seed, paged=True, weights_from=sharded,
                   extra=dict(tp_degree=tp, serving_ragged=True))
    mixed = ragged.mixed_step_model
    rows = kernel_census(ragged, mixed_buckets=[mixed.buckets[0]])
    check_census(rows, interpret=kernel_interpret(), require=require)
    facts["ragged_programs"] = rows
    release(ragged, sharded)
    return facts


def phase_big_model(attrs, shape, seed, *, tp):
    """Llama-3.1-8B bf16 at tp: load + a few decode steps; every device
    within 1.5x of its share of weights + cache."""
    import jax
    import numpy as np

    from neuronx_distributed_inference_tpu.modules.kvcache import cache_nbytes

    app = build(attrs, shape, seed, extra=dict(tp_degree=tp))
    ids, mask = make_prompts(attrs["vocab_size"], shape["prompt_lens"], seed)
    out = app.generate(ids, mask, max_new_tokens=shape["new_tokens"])
    gen = out.sequences[:, ids.shape[1]:]
    check(gen.shape[1] == shape["new_tokens"], f"generated {gen.shape}")
    check(((gen >= 0) & (gen < attrs["vocab_size"])).all(),
          "generated token outside the vocabulary")
    check(np.isfinite(out.logits).all(), "non-finite logits from the 8B model")
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(app.params))
    cache_bytes = cache_nbytes(app.kv_cache)
    share = (weight_bytes + cache_bytes) / tp
    del out
    gc.collect()
    mem = device_memory()
    for dev, m in mem.items():
        check(m["bytes_in_use"] <= 1.5 * share,
              f"device {dev} holds {m['bytes_in_use']} bytes > 1.5 x its "
              f"share {share:.0f} of weights + cache")
        check(m["bytes_in_use"] >= 0.5 * share,
              f"device {dev} holds {m['bytes_in_use']} bytes < half its "
              f"share {share:.0f}: the model is not spread over the mesh")
    facts = dict(tp_degree=tp, layers=attrs["num_hidden_layers"],
                 weight_bytes=int(weight_bytes), cache_bytes=int(cache_bytes),
                 share_bytes=int(share), bound=1.5, per_device=mem)
    release(app)
    return facts


def phase_router(attrs, shape, seed, *, replicas):
    """ServingRouter over ``partition_devices``: one single-chip replica per
    chip, all in this process, each replica's arrays on its own device."""
    import jax
    import numpy as np

    from neuronx_distributed_inference_tpu.runtime.router import (
        RSTATUS_FINISHED,
        ServingRouter,
        partition_devices,
    )
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    parts = partition_devices(replicas)
    apps = [
        build(attrs, shape, seed, paged=True, devices=parts[i],
              extra=dict(serving_ragged=True))
        for i in range(replicas)
    ]
    for i, app in enumerate(apps):
        own = set(parts[i])
        for leaf in jax.tree.leaves((app.params, app.kv_cache)):
            check(leaf.devices() == own,
                  f"replica {i}: an array lives on {leaf.devices()}, not {own}")
    rng = np.random.default_rng(seed + 2)
    vocab = attrs["vocab_size"]
    prompts = [rng.integers(0, vocab, size=n).tolist() for n in shape["prompt_lens"]]
    with ServingRouter([ServingSession(a) for a in apps],
                       policy="least_loaded") as router:
        for i, p in enumerate(prompts):
            check(router.add_request(str(i), p, max_new_tokens=shape["budgets"][i]),
                  f"router refused request {i}")
        while router.has_live_work:
            router.step()
        for rid, r in router.requests.items():
            check(r.status == RSTATUS_FINISHED,
                  f"request {rid} ended {r.status}({r.fail_reason})")
            check(len(r.tokens) == shape["budgets"][int(rid)],
                  f"request {rid}: {len(r.tokens)} tokens")
        served = [h.tokens_served for h in router.replicas]
    check(all(served), f"a replica served nothing: {served}")
    facts = dict(replicas=replicas, tokens_per_replica=served,
                 devices=[[d.id for d in p] for p in parts])
    release(*apps)
    return facts


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def run_single_chip(seed):
    from neuronx_distributed_inference_tpu.analysis.device_model import LLAMA_1B
    from neuronx_distributed_inference_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    device = phase_device(expect_count=1)
    emit(phase="device", **device)
    log = CompileLog()
    cache_dir = configure_compile_cache()

    app, facts = phase_generate(LLAMA_1B, GENERATE_SHAPE, seed, log)
    emit(phase="generate", **facts)
    emit(phase="kernels", **phase_kernels(app, require=("cte", "tkg")))
    emit(phase="kernel_vs_native",
         **phase_kernel_vs_native(LLAMA_1B, GENERATE_SHAPE, seed, app))
    emit(phase="serving",
         **phase_serving(LLAMA_1B, SERVING_SHAPE, seed, log, app,
                         compiled=True))
    emit(phase="compile_cache", dir=cache_dir, hits=log.cache_hits,
         misses=log.cache_misses, compiles=log.compiles,
         compile_s=round(log.compile_s, 2))
    emit(phase="memory", per_device=device_memory())
    return device


def run_multichip(seed):
    from neuronx_distributed_inference_tpu.analysis.device_model import (
        LLAMA_1B,
        LLAMA_8B,
    )
    from neuronx_distributed_inference_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    device = phase_device(expect_count=4)
    emit(phase="device", **device)
    configure_compile_cache()
    emit(phase="tp_vs_single",
         **phase_tp_vs_single(LLAMA_1B, TP_SHAPE, seed, SERVING_SHAPE, tp=4,
                              require=("mixed",)))
    emit(phase="big_model", **phase_big_model(LLAMA_8B, BIG_SHAPE, seed, tp=4))
    emit(phase="router", **phase_router(LLAMA_1B, ROUTER_SHAPE, seed, replicas=4))
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the tp path on four chips")
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    try:
        run = run_multichip if args.multichip else run_single_chip
        device = run(args.seed)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit(phase="done", wall_s=round(time.monotonic() - t0, 1))
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
