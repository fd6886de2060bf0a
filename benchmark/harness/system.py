"""Build the system under test from a configuration file: the application,
its weights on the device(s) from the seed, and the warm-up of the shapes a
cell can reach. The only file of the benchmark that imports the program.

The benchmark keeps its own construction (``build_app``), weight hand-over
(``give_weights``) and compile counter (``CompileLog``) so that a PR can
change the program's tools and not the yardstick.

Keys of a configuration file that are the benchmark's and never reach the
model's attributes (``META_KEYS``), three of them for a model that needs more
than the default:

``weights``
    an ordered list of rules for ``make_weights``; the first whose ``match``
    (a regular expression searched in ``"/".join(path)`` of a leaf, e.g.
    ``layers/mlp/router/weight``) finds the leaf decides it:
        {"match": ..., "std": s}               N(0, s)
        {"match": ..., "mean": m, "std": s}    m + N(0, s)
        {"match": ..., "value": v}             the constant v
    A leaf that no rule matches gets the default (``rope`` = the inverse
    frequencies, a path with "norm" in it 1 + N(0, 0.05), anything else
    N(0, 0.02)). So a configuration can say "this balancing bias is 0",
    "this gate is about 1", "this router has std s" in its own file. A rule
    that matches no leaf is an error that names it. A configuration that
    declares no rules gets, bit for bit, the weights it got before the key
    existed (``selftest/test_weights_rules.py`` holds that against a frozen
    copy).
``probe_tpu_config``
    a dict of ``TpuConfig`` options that ``correct.probe_overrides`` lays
    over the PROBE application only (as it lays ``output_logits``): where a
    model makes discrete choices, the option of the program that makes the
    step return them (``correct.py``'s docstring, "A model that chooses"),
    or the session record at which pass it revealed a token ("A model whose
    step is a block").
``reserved_token_ids``
    ids of the vocabulary that no prompt may hold and no request may
    generate (a block model's mask token): ``traffic.draw_ids`` draws every
    prompt without them, and ``correct.check_window`` makes a generated one
    a fault of the window.
"""

from __future__ import annotations

import copy
import os
from typing import Dict, Iterable, List, Optional, Tuple

#: keys of a configuration file that describe the benchmark's use of the
#: model; every other top-level key is an attribute of the model's config
META_KEYS = frozenset(
    {"name", "source", "deployment", "assumed", "reduced", "tpu_config",
     "chunked_prefill", "why", "rehearsal", "notes", "memory", "reference",
     "weights", "probe_tpu_config", "reserved_token_ids"}
)

WEIGHT_STD = 0.02


class CompileLog:
    """Counts what reaches the compiler, through ``jax.monitoring``: every
    jit-cache miss ends in one backend-compile event (a real compile or a
    retrieval from the persistent cache)."""

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

    def facts(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits, "cache_misses": self.cache_misses}


def configure_cache() -> str:
    """The persistent compilation cache, at the place the program's own
    helper decides (``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    ``<checkout>/.bench_cache/xla``), holding EVERY program: the default
    thresholds keep the many programs that compile in under a second out of
    it, and a run pays for them again each time."""
    import jax

    from neuronx_distributed_inference_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def resolve_config(config: dict, rehearsal: bool) -> dict:
    """The configuration as run: the file itself, or, for the CPU rehearsal,
    the file with its ``rehearsal`` overrides laid over it."""
    cfg = copy.deepcopy(config)
    if rehearsal:
        over = cfg.get("rehearsal") or {}
        for key, val in over.get("model", {}).items():
            cfg[key] = val
        cfg["tpu_config"] = {**cfg["tpu_config"], **over.get("tpu_config", {})}
        cfg["chunked_prefill"] = {**cfg.get("chunked_prefill", {}), **over.get("chunked_prefill", {})}
    return cfg


def model_attrs(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items() if k not in META_KEYS}


def build_app(cfg: dict, devices, seed: int, *, tpu_overrides: Optional[dict] = None,
              chunked_overrides: Optional[dict] = None):
    """The application exactly as a deployment builds it — paged cache,
    chunked prefill, continuous batching — with NO weights and no cache yet.
    Every TpuConfig option the file does not set stays at the program's
    default."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig, TpuConfig
    from neuronx_distributed_inference_tpu.models import get_model_builder
    from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
    from neuronx_distributed_inference_tpu.runtime.application import TpuModelForCausalLM

    opts = {**cfg["tpu_config"], **(tpu_overrides or {})}
    cp = {**cfg.get("chunked_prefill", {}), **(chunked_overrides or {})}
    tc = TpuConfig(seed=int(seed) % (2**31), chunked_prefill_config=ChunkedPrefillConfig(**cp), **opts)
    attrs = model_attrs(cfg)
    config_cls = get_model_builder(attrs["model_type"]).config_cls
    icfg = config_cls(tc, load_config=lambda c: [setattr(c, k, v) for k, v in attrs.items()])
    return TpuModelForCausalLM(None, icfg, mesh=mesh_from_config(tc, devices=list(devices)))


def _is_norm(path: Tuple[str, ...]) -> bool:
    return any("norm" in p for p in path)


class WeightRuleError(ValueError):
    """A ``weights`` rule of a configuration is malformed or matches no leaf."""


def _leaf_rules(rules, paths) -> List[Optional[Tuple[float, float]]]:
    """Per leaf the (mean, std) of the first rule that matches its path
    (std None: the constant ``mean``), or None where no rule does."""
    import re

    parsed = []
    for rule in rules or ():
        keys = set(rule)
        if keys == {"match", "value"}:
            dist = (float(rule["value"]), None)
        elif keys in ({"match", "std"}, {"match", "mean", "std"}):
            dist = (float(rule.get("mean", 0.0)), float(rule["std"]))
        else:
            raise WeightRuleError(
                f"weights rule {rule!r}: give 'match' with 'std', 'mean' and 'std', or 'value'")
        parsed.append((re.compile(rule["match"]), dist, rule))
    joined = ["/".join(p) for p in paths]
    out = [next((dist for rx, dist, _ in parsed if rx.search(name)), None) for name in joined]
    for rx, _, rule in parsed:
        if not any(rx.search(name) for name in joined):
            raise WeightRuleError(f"weights rule {rule!r} matches no leaf of {sorted(joined)}")
    return out


def weights_program(app, rules=None):
    """(generate, out_shardings, pspecs): ``generate(key)`` is the function
    ``make_weights`` jits, apart so that a test can read its jaxpr."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_inference_tpu.config import to_dtype
    from neuronx_distributed_inference_tpu.modules.rope import compute_inv_freq

    b = app.builder
    dtype = to_dtype(app.config.tpu_config.dtype)
    shapes = b.param_shapes()
    pspecs = b.param_pspecs()
    tied = "lm_head" not in shapes
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    paths = [tuple(getattr(k, "key", str(k)) for k in kp) for kp, _ in flat]
    declared = _leaf_rules(rules, paths)
    inv_freq = compute_inv_freq(app.config)

    def draw(key, shape, mean, std):
        """mean + N(0, std) in the served dtype; a leaf of rank >= 3 one
        leading index at a time, so that the float32 temporary is one matrix
        (one expert's, of a stack over layers and experts)."""
        if len(shape) >= 3:
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(lambda k: draw(k, shape[1:], mean, std), keys)
        noise = std * jax.random.normal(key, shape, jnp.float32)
        return (noise if mean == 0.0 else mean + noise).astype(dtype)

    def one(key, path, shape, rule):
        if rule is not None:
            mean, std = rule
            return jnp.full(shape, mean, dtype) if std is None else draw(key, shape, mean, std)
        if path[0] == "rope":
            return inv_freq
        if _is_norm(path):
            return draw(key, shape, 1.0, 0.05)
        return draw(key, shape, 0.0, WEIGHT_STD)

    def generate(key):
        keys = jax.random.split(key, len(flat))
        leaves = [one(k, p, s, r) for k, p, (_, s), r in zip(keys, paths, flat, declared)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        if tied:
            params["lm_head"] = {"weight": params["embed_tokens"]["weight"].T}
        return params

    def sharding(spec):
        return NamedSharding(app.mesh, spec if spec is not None else P())

    out_shardings = jax.tree.map(sharding, pspecs, is_leaf=lambda x: isinstance(x, P) or x is None)
    return generate, out_shardings, pspecs


def make_weights(app, seed: int, rules=None):
    """(params, pspecs): the whole parameter tree made on the device(s) in
    ONE jitted call from ``seed``, in the dtype it is served in, each leaf
    born with the sharding the builder declares — nothing passes through
    the host or through one chip. ``rules`` is the configuration's
    ``weights`` list (module docstring); a leaf it does not name is
    N(0, 0.02), or 1 + N(0, 0.05) for a norm weight, so that a norm weight
    applied wrongly shows against the reference. Stacks are drawn one matrix
    at a time (a float32 temporary of one layer's or one expert's matrix,
    not of the stack); a tied model gets the transposed head the program
    keeps."""
    import jax

    generate, out_shardings, pspecs = weights_program(app, rules)
    # a large seed folds into the key's two 32-bit words
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31)), int(seed) >> 31)
    with jax.set_mesh(app.mesh):
        params = jax.jit(generate, out_shardings=out_shardings)(key)
    jax.block_until_ready(params)
    return params, pspecs


def give_weights(app, params, pspecs):
    """Hand a parameter tree to an application and give it a fresh cache."""
    app.params, app._pspecs = params, pspecs
    app.init_kv_cache()


def reachable_shapes(app, max_prompt: int, max_context: int) -> List[Tuple[int, int]]:
    """(q length, kv bucket) of every program the split serving step can
    dispatch for prompts up to ``max_prompt`` and contexts up to
    ``max_context``: the decode step, written ``(1, bucket)`` whatever its
    width in positions, at each kv bucket a context can fall in, and a
    prefill chunk at each rung of the program's q ladder for each kv bucket
    a prompt position can fall in."""
    from neuronx_distributed_inference_tpu.modules import autobucketing

    tc = app.config.tpu_config
    buckets = app.token_generation_model.buckets
    top_ctx = autobucketing.get_target_bucket(buckets, min(max_context, tc.seq_len))
    top_prompt = autobucketing.get_target_bucket(buckets, max_prompt)
    shapes = [(1, b) for b in buckets if b <= top_ctx]
    for q in autobucketing.generate_chunk_q_buckets(tc):
        shapes += [(q, b) for b in buckets if b <= top_prompt]
    return shapes


def warm_up(app, shapes: Iterable[Tuple[int, int]]):
    """Every program a session can dispatch for ``shapes`` compiles here.
    An application that has ``warm_serving(shapes)`` is asked: which programs
    its session dispatches, and every way it feeds them, is its own to say
    (``(1, bucket)`` means "your decode step at this kv bucket"); rule (c)
    of ``correct.py``, no compilation inside the window, holds it to
    completeness. An application that serves through the ragged mixed step
    has one family of programs and its own warm-up for it. Any other is
    warmed by what is known of the split step: each program run once on
    inputs that write to the garbage block, a decode step twice, because the
    serving loop feeds it token ids from the host on a row's first step and
    ids still on the device (the previous step's output, chained) after
    that, and jit keeps a program for each."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    if hasattr(app, "warm_serving"):
        app.warm_serving(list(shapes))
        return
    if app.mixed_step_model is not None:
        app.warmup()
        return
    tkg = app.token_generation_model
    for q, bucket in shapes:
        inputs = tkg.example_inputs(bucket, q_len=q if q > 1 else None)
        out = tkg(app.params, app.kv_cache, inputs, None)
        if q == 1:
            chained = jnp.where(
                jnp.ones(inputs.input_ids.shape, bool),
                out.tokens[:, -1:].astype(jnp.int32), inputs.input_ids,
            )
            out = tkg(app.params, out.cache, dataclasses.replace(inputs, input_ids=chained), None)
        app.kv_cache = out.cache
    jax.block_until_ready(app.kv_cache)


def kernel_census(app, shapes: Iterable[Tuple[int, int]]) -> Dict[str, int]:
    """{"decode": n, "prefill": n}: the number of ``tpu_custom_call`` in the
    compiled decode step and in the compiled full prefill chunk
    (largest q), each at the widest kv bucket the cell reaches. Whether a
    Pallas kernel is IN a program is read from the executable, not from a
    gate (as ``chip_smoke.kernel_census``)."""
    shapes = list(shapes)
    tkg = app.token_generation_model
    out = {}
    for name, pick in (("decode", [s for s in shapes if s[0] == 1]),
                       ("prefill", [s for s in shapes if s[0] > 1])):
        if not pick:
            continue
        q, bucket = max(pick)
        inputs = tkg.example_inputs(bucket, q_len=q if q > 1 else None)
        _, _, compiled = tkg.trace_program(app.params, app.kv_cache, inputs, None)
        out[name] = compiled.as_text().count("tpu_custom_call")
    return out


def cache_dir_listing(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0
