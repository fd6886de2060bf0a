"""``system.make_weights`` and the ``weights`` rules a configuration declares
(``harness/system.py``'s docstring), at rehearsal size on the CPU:

- the configurations that declare no rules get bit for bit the
  weights a frozen copy of the generator as it stood before the rules
  (``frozen_make_weights``, PR 33's) gives for the same seed;
- a rule list sets a bias leaf to 0, a gate leaf to 1 +- 0.05 and a router
  leaf to the std asked, first match winning, the other leaves untouched;
- an expert stack (rank 4: layers x experts x in x out) is drawn with no
  float32 temporary larger than one expert's matrix;
- a rule whose regex matches no leaf, or with keys that say nothing, is an
  error that names it.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, system

SEED = 4000000555


def config_names():
    with open(os.path.join(catalog.REPO_DIR, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["configs"]]


def rehearsal_app(name: str, **model):
    import jax

    with open(os.path.join(catalog.BENCH_DIR, "configs", name + ".json")) as f:
        cfg = system.resolve_config(json.load(f), rehearsal=True)
    cfg.update(model)
    cfg["tpu_config"]["tp_degree"] = 1  # sharding places the same values
    return cfg, system.build_app(cfg, jax.devices()[:1], SEED)


def expert_app(layers=3, experts=4):
    """A tiny ``mixtral`` through the qwen3-1p7b file's serving options: the
    builder whose tree holds rank-4 expert stacks and a router."""
    return rehearsal_app("qwen3-1p7b", model_type="mixtral", num_local_experts=experts,
                         num_experts_per_tok=1, num_hidden_layers=layers, max_window_layers=layers)


def frozen_make_weights(app, seed: int):
    """``system.make_weights`` as PR 33 left it, kept here word for word."""
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
    inv_freq = compute_inv_freq(app.config)

    def one(key, path, shape):
        if path[0] == "rope":
            return inv_freq
        if any("norm" in p for p in path):
            return (1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
        if len(shape) == 3:  # stacked over layers
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(
                lambda k: (0.02 * jax.random.normal(k, shape[1:], jnp.float32)).astype(dtype),
                keys,
            )
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def generate(key):
        keys = jax.random.split(key, len(flat))
        leaves = [one(k, p, s) for k, p, (_, s) in zip(keys, paths, flat)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        if tied:
            params["lm_head"] = {"weight": params["embed_tokens"]["weight"].T}
        return params

    def sharding(spec):
        return NamedSharding(app.mesh, spec if spec is not None else P())

    out_shardings = jax.tree.map(sharding, pspecs, is_leaf=lambda x: isinstance(x, P) or x is None)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31)), int(seed) >> 31)
    with jax.set_mesh(app.mesh):
        params = jax.jit(generate, out_shardings=out_shardings)(key)
    return params


def leaves_by_path(params) -> dict:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(getattr(k, "key", str(k)) for k in kp): np.asarray(v.astype("float32"))
            for kp, v in flat}


@pytest.mark.parametrize("name", config_names())
def test_a_configuration_without_rules_gets_the_weights_it_got(name):
    cfg, app = rehearsal_app(name)
    if "weights" in cfg:
        pytest.skip(f"{name} declares weights rules: its weights are the rules', by its own file")
    now = leaves_by_path(system.make_weights(app, SEED, cfg.get("weights"))[0])
    then = leaves_by_path(frozen_make_weights(app, SEED))
    assert list(now) == list(then) and len(now) >= 10
    for path in now:
        assert now[path].dtype == then[path].dtype and now[path].tobytes() == then[path].tobytes(), path


RULES = [
    {"match": r"router/weight$", "std": 0.5},
    {"match": r"experts/down_proj", "value": 0},
    {"match": r"post_attention_layernorm", "mean": 1.0, "std": 0.05},
    {"match": r"layers/.*norm", "value": 7},  # never reached by post_attention_layernorm: first match wins
]


def test_rules_set_a_leaf_to_zero_to_about_one_and_to_the_std_asked():
    _, app = expert_app(layers=3, experts=4)
    ruled = leaves_by_path(system.make_weights(app, SEED, RULES)[0])
    plain = leaves_by_path(system.make_weights(app, SEED)[0])
    router = ruled["layers/mlp/router/weight"]
    assert router.shape == (3, 64, 4) and abs(router.std() - 0.5) < 0.05 and abs(router.mean()) < 0.05
    assert abs(plain["layers/mlp/router/weight"].std() - 0.02) < 0.002
    assert not ruled["layers/mlp/experts/down_proj/weight"].any()
    gate = ruled["layers/post_attention_layernorm/weight"]
    assert abs(gate.mean() - 1.0) < 0.02 and 0.03 < gate.std() < 0.07
    assert (ruled["layers/input_layernorm/weight"] == 7).all()
    untouched = [p for p in ruled if not any(k in p for k in ("router", "down_proj", "layernorm"))]
    assert len(untouched) >= 5
    for path in untouched:  # a leaf's key does not depend on what the others are given
        assert ruled[path].tobytes() == plain[path].tobytes(), path


def largest_float32(jaxpr) -> int:
    """Elements of the largest float32 value anywhere in a jaxpr, loops' bodies included."""
    import jax

    biggest = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if getattr(aval, "dtype", None) == np.float32:
                biggest = max(biggest, int(np.prod(aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            biggest = max(biggest, largest_float32(sub))
    return biggest


def test_an_expert_stack_is_drawn_one_matrix_at_a_time():
    import jax

    _, app = expert_app(layers=3, experts=4)
    shapes = app.builder.param_shapes()
    stack = shapes["layers"]["mlp"]["experts"]["gate_proj"]["weight"]
    vocab, hidden = shapes["embed_tokens"]["weight"]
    assert len(stack) == 4 and stack[:2] == (3, 4)
    generate, _, _ = system.weights_program(app, [{"match": "router", "std": 0.5}])
    biggest = largest_float32(jax.make_jaxpr(generate)(jax.random.PRNGKey(0)).jaxpr)
    # the embedding (rank 2) is the largest whole leaf; no float32 value is a stack
    assert biggest == vocab * hidden < int(np.prod(stack))
    params, _ = system.make_weights(app, SEED)
    got = np.asarray(params["layers"]["mlp"]["experts"]["gate_proj"]["weight"].astype("float32"))
    assert got.shape == stack and abs(got.std() - 0.02) < 0.002
    # every expert of every layer has a draw of its own
    flat = got.reshape(12, -1)
    assert len({row.tobytes() for row in flat}) == 12


@pytest.mark.parametrize("rules,named", [
    ([{"match": "no_such_leaf", "value": 0}], "no_such_leaf"),
    ([{"match": "router", "std": 0.1}, {"match": "e_score_correction_bias$", "value": 0}], "e_score"),
    ([{"match": "router", "mean": 1.0}], "'mean'"),
    ([{"match": "router", "value": 0, "std": 1.0}], "'value'"),
])
def test_a_rule_that_matches_nothing_or_says_nothing_is_an_error_that_names_it(rules, named):
    _, app = expert_app(layers=2, experts=2)
    with pytest.raises(system.WeightRuleError) as e:
        system.make_weights(app, SEED, rules)
    assert named in str(e.value)
