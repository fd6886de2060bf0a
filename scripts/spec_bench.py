#!/usr/bin/env python
"""Speculation machinery benchmark: measured tok/s for the speculative
decoding modes on real hardware (VERDICT r4 next #6 — "measured, not just
bounded"; reference fused-spec decode step model_base.py:2562-3021).

No trained draft weights exist in this environment, so the harness builds
drafts whose acceptance is a PROPERTY OF CONSTRUCTION:

- ``assisted_self``: vanilla assisted decoding with the draft = a second
  app holding the SAME weights as the target (self-draft). Greedy
  verification then accepts every proposal, so the measured tok/s isolates
  the machinery (draft chain + multi-token verify + host accept loop) at
  acceptance = 100% — directly comparable to the r4 verify-ceiling
  microbench (PERF.md: k=4 => 720 tok/s ceiling with a FREE draft; here the
  draft costs k-1 full target steps, so the self-draft ideal is ~= plain
  decode; the gap to that ideal is the machinery overhead).
- ``eagle_chain`` / ``eagle_tree``: fused EAGLE speculation with a
  CORRELATED 1-layer draft (shared embed/lm-head/final-norm, target layer 0,
  pass-through fusion) — a real feature-chained draft with nontrivial
  acceptance on a random-weight target; tok/s is reported TOGETHER with the
  measured acceptance (tokens/round) so the machinery cost per round is
  separable from draft quality.
- ``plain``: the no-speculation baseline on the same weights.

Every mode is size-parameterized and smoke-run by the CPU suite
(tests/test_spec_bench_smoke.py) — bench-only crash classes must stay
impossible (VERDICT r3 weak #2).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def _sizes(tiny):
    if tiny:
        return dict(
            hf=dict(
                model_type="llama", hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=128, rms_norm_eps=1e-5,
                rope_theta=1e4, max_position_embeddings=256,
                hidden_act="silu", tie_word_embeddings=False,
            ),
            seq=128, prompt=8, gen=16, k=4,
        )
    import bench

    return dict(hf=dict(bench.LLAMA_1B), seq=1024, prompt=128, gen=256, k=4)


def _mk_config(hf, seq, tpu_kwargs):
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig

    def load_cfg(c):
        for k, v in hf.items():
            setattr(c, k, v)

    tc = TpuConfig(batch_size=1, seq_len=seq, dtype="bfloat16", **tpu_kwargs)
    return LlamaInferenceConfig(tc, load_config=load_cfg)


def _plain_app(hf, seq, **tpu_kwargs):
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    cfg = _mk_config(hf, seq, tpu_kwargs)
    return TpuModelForCausalLM(None, cfg).load(random_weights=True)


def _eagle_app(hf, seq, k, tree=None):
    """Fused EAGLE app with a correlated 1-layer draft: the draft shares the
    target's embedding/lm-head/final-norm, copies target layer 0, and uses a
    pass-through fusion layer — feature-chained speculation with measurable
    acceptance on a random-weight target (the construction
    tests/test_token_tree.py's acceptance test pins)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.config import FusedSpecConfig
    from neuronx_distributed_inference_tpu.parallel.sharding import shard_pytree
    from neuronx_distributed_inference_tpu.runtime.fused_spec import (
        TpuEagleSpecModelForCausalLM,
    )

    cfg = _mk_config(
        hf, seq,
        dict(
            speculation_length=k,
            enable_fused_speculation=True,
            enable_eagle_speculation=True,
            token_tree_config=tree,
        ),
    )
    draft_hf = dict(hf, num_hidden_layers=1, model_type="llama-eagle")
    draft_cfg = _mk_config(draft_hf, seq, {})
    cfg.fused_spec_config = FusedSpecConfig(
        draft_model_name="self-1l", draft_config=draft_cfg
    )
    app = TpuEagleSpecModelForCausalLM(None, cfg)
    app.load(random_weights=True)

    t = jax.device_get(app.target_params)
    d = app.draft_builder.random_params(on_host=False)
    H = cfg.hidden_size
    fc = np.zeros((2 * H, H), np.float32)
    fc[H:, :] = np.eye(H)
    d["fc"]["weight"] = jnp.asarray(fc, jnp.bfloat16)
    for name in ("embed_tokens", "lm_head", "norm"):
        if name in t:
            d[name] = t[name]
    d["layers"] = jax.tree.map(lambda x: x[:1], t["layers"])
    app.draft_params = shard_pytree(
        d, app.draft_builder.param_pspecs(), app.mesh
    )
    return app


def burst_round_ms(app, R=24):
    """Pure DEVICE cost of one fused speculation round: dispatch R rounds
    back-to-back on fixed inputs (caches donate-thread through _call_tkg)
    and block once at the end, so host scheduling between rounds is not in
    the number."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.models.base import StepInputs
    from neuronx_distributed_inference_tpu.modules.sampling import (
        prepare_sampling_params,
    )

    ids = np.array([[5, 7, 11, 13]])
    app.generate(ids, np.ones_like(ids), max_new_tokens=8)  # compile + seed state
    B = 1
    bucket = app.tkg_buckets[-1]
    inputs = StepInputs(
        input_ids=jnp.asarray([[17]], jnp.int32),
        attention_mask=jnp.zeros((B, bucket), jnp.int32),
        position_ids=jnp.asarray([[bucket // 2]], jnp.int32),
        seq_ids=jnp.asarray(np.arange(B, dtype=np.int32)),
        sampling_params=jnp.asarray(prepare_sampling_params(B), jnp.float32),
    )
    out = app._call_tkg(inputs, None)
    jax.block_until_ready(out.tokens)
    t0 = time.time()
    for _ in range(R):
        out = app._call_tkg(inputs, None)
    jax.block_until_ready(out.tokens)
    return (time.time() - t0) / R * 1e3


def _measure_generate(app, prompt, gen, count_rounds=False):
    ids = np.asarray(prompt)[None, :]
    mask = np.ones_like(ids)
    app.generate(ids, mask, max_new_tokens=gen)  # compile/warm
    rounds = [0]
    if count_rounds:
        orig = app._call_tkg

        def counting(inputs, key):
            rounds[0] += 1
            return orig(inputs, key)

        app._call_tkg = counting
    # no cache reset needed: prefill rewrites from position 0 and the masks
    # bound every read to the live positions
    t0 = time.time()
    out = app.generate(ids, mask, max_new_tokens=gen)
    dt = time.time() - t0
    if count_rounds:
        app._call_tkg = orig
    return out.num_generated / dt, out.num_generated, rounds[0]


def run(tiny=False):
    s = _sizes(tiny)
    hf, seq, prompt_len, gen, k = s["hf"], s["seq"], s["prompt"], s["gen"], s["k"]
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, hf["vocab_size"] - 10, size=prompt_len).tolist()
    res = {}

    # plain decode baseline
    app = _plain_app(hf, seq)
    tok_s, _, _ = _measure_generate(app, prompt, gen)
    res["plain_tok_s"] = round(tok_s, 2)
    del app

    # vanilla assisted, self-draft (acceptance == 1 by construction)
    from neuronx_distributed_inference_tpu.runtime.assisted import assisted_generate

    target = _plain_app(hf, seq)
    draft = _plain_app(hf, seq)  # same seed -> identical weights
    ids = np.asarray(prompt)[None, :]
    mask = np.ones_like(ids)
    assisted_generate(target, draft, ids, mask, max_new_tokens=gen,
                      speculation_length=k)  # compile/warm
    target.init_kv_cache()
    draft.init_kv_cache()
    t0 = time.time()
    out = assisted_generate(target, draft, ids, mask, max_new_tokens=gen,
                            speculation_length=k)
    dt = time.time() - t0
    res["assisted_self_tok_s"] = round(out.num_generated / dt, 2)
    res["assisted_k"] = k
    del target, draft

    # fused EAGLE chain + static tree with the correlated draft
    for name, tree in (
        ("eagle_chain", None),
        ("eagle_tree", {0: [1, 2], 1: [3, 4]}),
    ):
        app = _eagle_app(hf, seq, k, tree=tree)
        tok_s, n_gen, rounds = _measure_generate(
            app, prompt, gen, count_rounds=True
        )
        res[f"{name}_tok_s"] = round(tok_s, 2)
        res[f"{name}_tokens_per_round"] = round(n_gen / max(rounds, 1), 2)
        res[f"{name}_round_ms_device"] = round(burst_round_ms(app), 2)
        del app

    return res


def main():
    if "--cpu" in sys.argv:
        import jax

        jax.config.update("jax_platforms", "cpu")
    tiny = "--tiny" in sys.argv
    res = run(tiny=tiny)
    import jax

    res["device"] = str(jax.devices()[0])
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
