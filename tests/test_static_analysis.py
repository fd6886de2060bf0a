"""The static-analysis subsystem analyzing itself and the tree.

Layers:
1. fixture snippets with KNOWN violations — every tpulint rule must fire
   (host-sync under jit, print/time under trace, pallas without interpret,
   mutable defaults, np.asarray under trace, large unsharded constants) and
   pragmas must suppress;
2. the REAL package must be clean: zero non-baselined tpulint findings,
   zero flag-audit findings, zero graph-audit findings (collective census,
   dtype discipline, KV donation, bucket skeleton invariance across
   context-encoding / token-generation / fused-speculation × 2 buckets),
   zero shard-audit findings (realized-vs-declared PartitionSpec per leaf,
   no replicated cache, no in-loop weight gathers, pinned sharding census)
   and zero memory-audit findings (donation-alias proof across all three
   cache variants, pinned per-bucket HBM accounting);
3. every GRAPH30x/MEM40x rule has a PROVEN detector: a deliberately broken
   synthetic program (replicated weight, replicated cache, in-loop gather,
   undonated cache, doctored baseline) the rule must flag — green never
   means "didn't look";
4. the retrace guard must prove steady-state decode performs ZERO recompiles
   after warmup — and must catch an induced retrace.
"""

import pathlib
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tests.conftest import make_random_hf_state_dict, make_tiny_config

from neuronx_distributed_inference_tpu.analysis import (
    Baseline,
    Finding,
    RetraceError,
    RetraceGuard,
)
from neuronx_distributed_inference_tpu.analysis import tpulint
from neuronx_distributed_inference_tpu.analysis.tpulint import lint_paths

pytestmark = pytest.mark.static_analysis


# ---------------------------------------------------------------------------
# 1. fixture snippets: every rule fires
# ---------------------------------------------------------------------------


def _lint_snippet(tmp_path, source: str):
    pkg = tmp_path / "neuronx_distributed_inference_tpu"
    pkg.mkdir(exist_ok=True)
    f = pkg / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return lint_paths([f], tmp_path)


def _rules(findings):
    return {f.rule for f in findings}


def test_rule_host_sync_under_jit(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import jax

        def step(params, x):
            y = params["w"] @ x
            host = jax.device_get(y)      # BUG: sync under trace
            return y + host.shape[0]

        fn = jax.jit(step)
        """,
    )
    assert "TPU101" in _rules(findings)
    assert any("device_get" in f.message for f in findings if f.rule == "TPU101")


def test_rule_bare_imported_device_get(tmp_path):
    """`from jax import device_get` must not slip past TPU101 or the
    TPU102 census."""
    findings = _lint_snippet(
        tmp_path,
        """
        import jax
        from jax import device_get

        @jax.jit
        def step(x):
            return device_get(x)          # BUG: bare-name host sync
        """,
    )
    assert "TPU101" in _rules(findings)
    assert "TPU102" in _rules(findings)


def test_rule_item_and_block_until_ready_under_jit(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            x.block_until_ready()         # BUG
            return x.sum().item()         # BUG
        """,
    )
    assert sum(1 for f in findings if f.rule == "TPU101") == 2


def test_rule_traced_through_partial_and_call_graph(tmp_path):
    """jax.jit(partial(outer)) -> outer -> helper: the violation in the
    helper two hops away must still be found."""
    findings = _lint_snippet(
        tmp_path,
        """
        import jax
        from functools import partial

        def helper(y):
            return jax.device_get(y)      # BUG: traced transitively

        def outer(x, flag):
            return helper(x) + 1

        fn = jax.jit(partial(outer, flag=True))
        """,
    )
    assert "TPU101" in _rules(findings)


def test_rule_traced_through_assigned_step_variable(tmp_path):
    """The runtime's own idiom — `step = partial(forward, ...);
    jax.jit(step)` — must seed `forward` as a traced root."""
    findings = _lint_snippet(
        tmp_path,
        """
        import jax
        from functools import partial

        def forward(params, x):
            return jax.device_get(x)      # BUG: traced via the step variable

        step = partial(forward, spec=1)
        fn = jax.jit(step, donate_argnums=(1,))
        """,
    )
    assert "TPU101" in _rules(findings)


def test_rule_time_and_print_under_trace(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import time
        import jax

        @jax.jit
        def step(x):
            t0 = time.time()              # BUG: trace-time constant
            print("step", x)              # BUG: prints once, at trace
            return x * t0
        """,
    )
    assert sum(1 for f in findings if f.rule == "TPU103") == 2


def test_rule_pallas_missing_interpret(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        from jax.experimental import pallas as pl

        def kernel_call(x):
            return pl.pallas_call(lambda r: r, out_shape=x)(x)  # BUG: no interpret=

        def good_call(x, interp):
            return pl.pallas_call(lambda r: r, out_shape=x, interpret=interp)(x)
        """,
    )
    assert sum(1 for f in findings if f.rule == "TPU104") == 1


def test_rule_mutable_default(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        class Module:
            def __init__(self, layers=[]):   # BUG
                self.layers = layers

        def fn(cfg={}):                      # BUG
            return cfg
        """,
    )
    assert sum(1 for f in findings if f.rule == "TPU105") == 2


def test_rule_np_asarray_under_trace_and_pragma(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            bad = np.asarray(x)                              # BUG (warning)
            ok = np.asarray([1, 2, 3])  # tpulint: ignore[TPU106]
            return x + bad.shape[0] + ok[0]
        """,
    )
    assert sum(1 for f in findings if f.rule == "TPU106") == 1


def test_rule_telemetry_under_trace(tmp_path):
    """TPU107: metric recording under a jit trace — both the import-based
    detector (telemetry symbols) and the mutator heuristic (.inc/.observe)
    must fire; host-side recording stays clean."""
    pkg = tmp_path / "neuronx_distributed_inference_tpu"
    (pkg / "telemetry").mkdir(parents=True)
    tel_init = pkg / "telemetry" / "__init__.py"
    tel_init.write_text("def default_session():\n    return None\n")
    snippet = pkg / "snippet.py"
    snippet.write_text(
        textwrap.dedent(
            """
            import jax
            from neuronx_distributed_inference_tpu.telemetry import (
                default_session,
            )

            @jax.jit
            def step(x, m):
                m.inc(1)                 # BUG: metric mutator under trace
                tel = default_session()  # BUG: telemetry symbol under trace
                return x

            def host_loop(x, m):
                m.inc(1)                 # fine: host side
                m.observe(2.0)           # fine: host side
                return default_session()
            """
        )
    )
    findings = lint_paths([snippet, tel_init], tmp_path)
    t107 = [f for f in findings if f.rule == "TPU107"]
    assert len(t107) == 2
    assert all(f.severity == "error" for f in t107)
    msgs = " ".join(f.message for f in t107)
    assert ".inc(...)" in msgs and "default_session" in msgs


def test_rule_large_unsharded_constant(tmp_path):
    """TPU108: a statically-large jnp creation under trace fires; wrapping
    it in a sharding constraint (or being small / dynamically shaped)
    silences it."""
    findings = _lint_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, n):
            big = jnp.zeros((2048, 1024))                  # BUG: 2M elems, replicated
            tab = jnp.arange(3000000)                      # BUG: 3M elems
            kw = jnp.ones(shape=(4096, 1024))              # BUG: kw-form is just as static
            ok_small = jnp.ones((8, 8))                    # fine: tiny
            ok_dyn = jnp.zeros((n, 1024))                  # fine: not static
            ok_wrapped = jax.lax.with_sharding_constraint(
                jnp.zeros((2048, 1024)), None              # fine: constrained
            )
            return x + big[0, 0] + tab[0] + kw[0, 0] + ok_small[0, 0] + ok_dyn[0, 0]

        def host(x):
            return jnp.zeros((4096, 4096)) + x             # fine: not traced
        """,
    )
    t108 = [f for f in findings if f.rule == "TPU108"]
    assert len(t108) == 3
    assert all(f.severity == "warning" for f in t108)
    assert any("jnp.zeros" in f.message for f in t108)
    assert any("jnp.arange" in f.message for f in t108)
    assert any("jnp.ones" in f.message for f in t108)


def test_pragma_suppresses_on_def_line(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):  # tpulint: ignore
            return jax.device_get(x)
        """,
    )
    assert "TPU101" not in _rules(findings)


def test_host_sync_census_counts_per_file(tmp_path):
    findings = _lint_snippet(
        tmp_path,
        """
        import jax

        def host_loop(out):
            a = jax.device_get(out.tokens)
            b = jax.device_get(out.logits)
            out.cache.block_until_ready()
            return a, b
        """,
    )
    census = [f for f in findings if f.rule == "TPU102"]
    assert len(census) == 3
    # the baseline pins the count: 3 allowed, a 4th is new
    base = Baseline.from_findings(census)
    assert base.filter_new(census) == []
    extra = census + [
        Finding(rule="TPU102", severity="warning", key=census[0].key,
                location=census[0].key + ":999", message="one more")
    ]
    assert len(base.filter_new(extra)) == 1


# ---------------------------------------------------------------------------
# 2. the real tree is clean
# ---------------------------------------------------------------------------


def test_package_tpulint_clean_vs_baseline():
    findings = tpulint.run()
    baseline = Baseline.load(
        pathlib.Path(tpulint.__file__).parent / "tpulint_baseline.json"
    )
    new = baseline.filter_new(findings)
    assert new == [], "non-baselined tpulint findings:\n" + "\n".join(
        f.render() for f in new
    )
    # no hard errors may exist at all, baselined or not
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], "\n".join(f.render() for f in errors)


def test_flag_audit_clean():
    from neuronx_distributed_inference_tpu.analysis import flag_audit

    findings = flag_audit.run()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_graph_audit_clean_and_covers_tags():
    """The jaxpr/HLO auditor over the real programs: context-encoding,
    token-generation, and fused-speculation tags, ≥2 buckets each, zero
    findings (census matches baseline, donation present, no stray f32
    upcasts, one skeleton per tag)."""
    from neuronx_distributed_inference_tpu.analysis import graph_audit

    findings = graph_audit.run()
    assert findings == [], "\n".join(f.render() for f in findings)
    # coverage floor: the audited tag set is the acceptance-criteria set
    # (+ the quantized-cache program set, ISSUE 3; + the ragged mixed-step
    # serving family, ISSUE 6; + the fused-speculation int8 variant,
    # ISSUE 11 — the spec-decode path the cost model covers; + the int4
    # weight-streaming decode/mixed programs, ISSUE 17)
    assert set(graph_audit.AUDIT_TAGS) == {
        "context_encoding",
        "token_generation",
        "fused_speculation",
        "context_encoding_kvq8",
        "token_generation_kvq8",
        "fused_speculation_kvq8",
        "mixed_step",
        "token_generation_w4",
        "mixed_step_w4",
    }
    baseline = graph_audit.load_census_baseline()
    assert set(baseline) == set(graph_audit.AUDIT_TAGS)
    # a tp=2 decode graph must actually communicate: vacuous censuses (all
    # zeros) would mean the auditor is looking at the wrong HLO
    assert baseline["token_generation"]["all-reduce"] > 0
    # kv-quant must not change the communication pattern: the int8-cache
    # decode census matches the bf16 one (the scale math is shard-local),
    # for the plain AND the fused-speculation decode programs
    assert baseline["token_generation_kvq8"] == baseline["token_generation"]
    assert baseline["fused_speculation_kvq8"] == baseline["fused_speculation"]


def test_graph_audit_flags_census_drift(tmp_path):
    """A doctored baseline must produce GRAPH201 findings."""
    from neuronx_distributed_inference_tpu.analysis import graph_audit

    good = graph_audit.load_census_baseline()
    doctored = {t: dict(c) for t, c in good.items()}
    doctored["token_generation"]["all-reduce"] += 1
    p = tmp_path / "graph_baseline.json"
    graph_audit.save_census_baseline(doctored, p)
    findings = graph_audit.run(baseline_path=p, tags=("token_generation",))
    assert any(f.rule == "GRAPH201" for f in findings)


# ---------------------------------------------------------------------------
# 3. retrace guard
# ---------------------------------------------------------------------------


def test_retrace_guard_records_and_raises():
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.retrace_guard import (
        trace_marker,
    )

    fn = jax.jit(trace_marker("toy", lambda x: x * 2))
    fn(jnp.ones((2,)))  # first compile
    with RetraceGuard(fail=False) as g:
        fn(jnp.ones((2,)))  # cache hit: no trace
    assert g.traces == []
    with pytest.raises(RetraceError):
        with RetraceGuard():
            fn(jnp.ones((3,)))  # new shape: retrace inside the guard
    with RetraceGuard(allowed=1):
        fn(jnp.ones((4,)))  # tolerated when explicitly allowed


def test_steady_state_decode_zero_recompiles_after_warmup():
    """The acceptance contract: after warmup() + one generate() (which
    compiles the decode-chunk programs), further steady-state decode performs
    ZERO recompiles."""
    cfg = make_tiny_config(tpu=dict(skip_warmup=False))
    sd = make_random_hf_state_dict(cfg)
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=sd)
    app.warmup()
    prompt = np.array([[5, 17, 92, 41], [64, 3, 27, 9]])
    mask = np.ones_like(prompt)
    app.generate(prompt, mask, max_new_tokens=8)  # decode-chunk compile
    with RetraceGuard() as g:  # raises on ANY trace in scope
        out = app.generate(prompt, mask, max_new_tokens=8)
    assert g.traces == []
    assert out.num_generated == 8


def test_sealed_runner_raises_on_post_warmup_retrace():
    """TpuConfig.retrace_guard: after warmup the step programs are sealed —
    a new shape reaching them raises instead of silently recompiling."""
    cfg = make_tiny_config(tpu=dict(retrace_guard=True))
    sd = make_random_hf_state_dict(cfg)
    from neuronx_distributed_inference_tpu.runtime.application import (
        TpuModelForCausalLM,
    )

    app = TpuModelForCausalLM(None, cfg)
    app.load(state_dict=sd)
    app.warmup()
    assert app.token_generation_model._sealed
    # every warmed bucket still serves fine
    prompt = np.array([[5, 17, 92, 41], [64, 3, 27, 9]])
    app.generate(prompt, np.ones_like(prompt), max_new_tokens=4)
    # an unwarmed multi-token TKG shape (q_len=3 was never compiled) must
    # refuse to silently recompile
    runner = app.token_generation_model
    bad_inputs = runner.example_inputs(runner.buckets[-1], q_len=3)
    with pytest.raises(RetraceError):
        runner(app.params, app.kv_cache, bad_inputs, None)
    # decode programs: a NEW (num_steps, bucket) key may still lazily build
    # its first program while sealed...
    last = np.array([[3], [4]], np.int32)
    pos = np.array([[4], [4]], np.int32)
    seq_ids = np.arange(2, dtype=np.int32)
    sp = np.tile(np.array([1, 1.0, 1.0], np.float32), (2, 1))
    _, _, cache2 = runner.decode_chunk(
        app.params, app.kv_cache, last, pos, seq_ids, sp, None,
        num_steps=2, bucket=runner.buckets[-1],
    )
    # ...but RE-tracing that same keyed program (here: rng None -> PRNGKey
    # changes the arg pytree) is the steady-state recompile the seal forbids
    import jax

    with pytest.raises(RetraceError):
        runner.decode_chunk(
            app.params, cache2, last, pos, seq_ids, sp,
            jax.random.PRNGKey(0), num_steps=2, bucket=runner.buckets[-1],
        )


def test_fused_spec_steady_state_zero_recompiles():
    """The fused-speculation decode loop must reuse ONE compiled program
    across rounds (each round: same bucket, same shapes)."""
    from neuronx_distributed_inference_tpu.config import FusedSpecConfig
    from neuronx_distributed_inference_tpu.runtime.fused_spec import (
        TpuFusedSpecModelForCausalLM,
    )

    target_cfg = make_tiny_config()
    target_sd = make_random_hf_state_dict(target_cfg, seed=0)
    draft_cfg = make_tiny_config()
    draft_sd = make_random_hf_state_dict(draft_cfg, seed=7)
    spec_cfg = make_tiny_config()
    spec_cfg.tpu_config.speculation_length = 4
    spec_cfg.tpu_config.enable_fused_speculation = True
    spec_cfg.fused_spec_config = FusedSpecConfig(
        draft_model_name="tiny-draft", draft_config=draft_cfg
    )
    app = TpuFusedSpecModelForCausalLM(None, spec_cfg)
    app.load(target_state_dict=target_sd, draft_state_dict=draft_sd)

    prompt = np.array([[5, 17, 92, 41, 33, 88, 2, 11], [64, 3, 27, 9, 14, 1, 7, 2]])
    # first call compiles CTE + the TKG program(s) for the visited buckets
    app.generate(prompt, np.ones_like(prompt), max_new_tokens=8)
    app.seal()
    with RetraceGuard() as g:
        app.generate(prompt, np.ones_like(prompt), max_new_tokens=8)
    assert g.traces == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_main_clean_tree_exits_zero(capsys):
    """The in-process CLI path over the fast suites (lint + flags): a clean
    tree exits 0 and reports zero new findings."""
    from neuronx_distributed_inference_tpu.analysis.__main__ import main

    rc = main(["--suites", "lint,flags", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    import json

    report = json.loads(out)
    assert report["new"] == 0
    assert report["total"] >= 1  # the pinned host-sync census is visible


def test_cli_unknown_suite_errors_nonzero(capsys):
    """An unknown --suites name must ERROR with the known list — a typo
    must never select nothing and exit 0 (vacuous green)."""
    from neuronx_distributed_inference_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["--suites", "shardz"])
    assert exc.value.code not in (0, None)
    err = capsys.readouterr().err
    assert "unknown suite" in err
    for known in ("lint", "flags", "graph", "shard", "memory"):
        assert known in err
    # an all-whitespace selection is equally vacuous
    with pytest.raises(SystemExit) as exc:
        main(["--suites", " , "])
    assert exc.value.code not in (0, None)


def test_cli_entry_points_share_one_parser():
    """scripts/run_static_analysis.py and the module CLI must expose the
    SAME flag surface (the drift this satellite existed to fix)."""
    import importlib.util

    from neuronx_distributed_inference_tpu.analysis import cli
    from neuronx_distributed_inference_tpu.analysis.__main__ import (
        main as module_main,
    )

    spec = importlib.util.spec_from_file_location(
        "run_static_analysis",
        pathlib.Path(__file__).resolve().parents[1]
        / "scripts" / "run_static_analysis.py",
    )
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.main is cli.main
    assert module_main is cli.main
    flags = {a.option_strings[0] for a in cli.build_parser()._actions if a.option_strings}
    assert {"--json", "--suites", "--write-baseline"} <= flags


def test_write_baseline_diff_rendering():
    """--write-baseline prints a reviewable unified diff of every baseline
    file it rewrote."""
    from neuronx_distributed_inference_tpu.analysis import cli

    before = {"graph_baseline.json": '{"census": {"a": 1}}\n'}
    after = {"graph_baseline.json": '{"census": {"a": 2}}\n'}
    diff = cli.baseline_diffs(before, after)
    assert "a/analysis/graph_baseline.json" in diff
    assert '-{"census": {"a": 1}}' in diff
    assert '+{"census": {"a": 2}}' in diff
    assert cli.baseline_diffs(before, dict(before)) == ""


def test_cli_full_json_schema(capsys):
    """--json over ALL suites: machine-readable report with suite list,
    finding records (rule/severity/location with file:line or tag/bucket),
    the memory suite's per-bucket HBM breakdown, and the cost suite's
    per-bucket FLOPs/bytes/projection section."""
    from neuronx_distributed_inference_tpu.analysis.__main__ import main

    rc = main(["--json"])
    out = capsys.readouterr().out
    assert rc == 0
    import json

    report = json.loads(out)
    assert report["suites"] == [
        "lint", "flags", "graph", "shard", "memory", "cost", "conc",
        "kernel", "life"
    ]
    assert report["new"] == 0
    assert {"total", "findings", "new_findings", "memory", "cost",
            "concurrency", "kernel", "lifecycle"} <= set(report)
    for f in report["findings"]:
        assert {"rule", "severity", "location", "message", "key"} <= set(f)
        assert f["rule"][:3] in ("TPU", "GRA", "MEM", "FLA", "COS", "CON",
                                 "KER", "LIF")
        # file:line for source rules, tag/bucket for graph rules
        assert (":" in f["location"]) or ("/" in f["location"])
    mem = report["memory"]
    for tag in ("token_generation", "token_generation_ring", "token_generation_paged"):
        assert tag in mem
        for bucket, row in mem[tag].items():
            assert int(bucket) > 0
            assert {"weights_bytes", "cache_bytes", "temp_bytes", "total_bytes"} <= set(row)
            assert row["total_bytes"] == (
                row["weights_bytes"] + row["cache_bytes"] + row["temp_bytes"]
            )
    # the cost section: every audited program carries the full census and a
    # device projection; the mixed packing contract rides beside it
    cost = report["cost"]
    assert {"programs", "mixed_packing"} <= set(cost)
    for tag in ("token_generation", "fused_speculation_kvq8", "mixed_step"):
        assert tag in cost["programs"], tag
        for bucket, row in cost["programs"][tag].items():
            assert int(bucket) > 0
            assert row["flops"] > 0
            assert row["hbm_bytes"] == (
                row["weights_bytes"] + row["cache_read_bytes"]
                + row["cache_write_bytes"] + row["act_bytes"]
            )
            assert row["classification"] in ("compute", "bandwidth")
            proj = row["projection"]
            assert proj["t_step_lb_us"] > 0 and proj["tok_s_ub"] > 0
            assert proj["t_step_lb_us"] >= max(
                proj["t_flops_us"], proj["t_hbm_us"], proj["t_ici_us"]
            )
    assert cost["mixed_packing"]["q_tile"] > 0
    # the concurrency section (ISSUE 13): full classification breakdown of
    # the write-site census plus the router->session touch allowlist
    conc = report["concurrency"]
    assert {"write_sites", "classifications", "census",
            "session_touches", "worker_entries"} <= set(conc)
    assert conc["write_sites"] == sum(conc["classifications"].values()) > 0
    assert set(conc["classifications"]) <= {
        "init-confined", "lock-protected", "replica-step-confined",
        "router-thread",
    }
    assert conc["errors"] == 0
    assert "ReplicaHandle.step" in conc["worker_entries"]
    # the kernel section (ISSUE 16): per-instance census over every
    # registered pallas_call instantiation
    kern = report["kernel"]
    assert {"device", "vmem_budget", "instances", "n_sites",
            "n_registered"} <= set(kern)
    assert kern["n_sites"] > 0 and kern["n_registered"] >= kern["n_sites"]
    for key, row in kern["instances"].items():
        assert key.count("/") == 2, key  # kernel/shape_class/dtype
        # the default scoped budget, or the limit the call itself asks for
        assert 0 < row["vmem_bytes"] <= max(kern["vmem_budget"], row["vmem_limit"] or 0)
        assert row["flops_per_step"] > 0
        assert row["bound"] in ("compute", "memory")


# ---------------------------------------------------------------------------
# shard audit (GRAPH30x)
# ---------------------------------------------------------------------------


def _toy_sharded_program(weight_spec, cache_spec_p, declared_weight, declared_cache):
    """Compile a toy (params, cache, x) step on the 8-device CPU mesh with
    the given REALIZED placements, returning what the shard-audit leaf walk
    consumes. The declared specs may deliberately disagree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(mesh_utils.create_device_mesh((8,)), ("tp",))
    params = {
        "w": jax.device_put(
            np.ones((64, 128), np.float32), NamedSharding(mesh, weight_spec)
        )
    }
    cache = {
        "k": jax.device_put(
            np.zeros((2, 64, 64), np.float32), NamedSharding(mesh, cache_spec_p)
        ),
        "v": jax.device_put(
            np.zeros((2, 64, 64), np.float32), NamedSharding(mesh, cache_spec_p)
        ),
    }
    x = jax.device_put(np.ones((4, 64), np.float32), NamedSharding(mesh, P()))

    def step(params, cache, x):
        y = x @ params["w"]
        new_cache = {k: v + 1.0 for k, v in cache.items()}
        return y, new_cache

    import neuronx_distributed_inference_tpu  # noqa: F401  (jax.set_mesh shim)

    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(step, donate_argnums=(1,)).lower(params, cache, x).compile()
        )
    ish = compiled.input_shardings[0]
    declared_p = {"w": declared_weight}
    declared_c = {"k": declared_cache, "v": declared_cache}
    return mesh, params, cache, compiled, ish, declared_p, declared_c


def test_shard_audit_clean_and_covers_committed_tags():
    """The shard auditor over the real programs: zero findings, the
    committed tag set, ≥2 buckets per causal/fused family, and a
    census whose tp-sharded weights are actually pinned sharded."""
    from neuronx_distributed_inference_tpu.analysis import programs, shard_audit

    findings = shard_audit.run()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert set(shard_audit.SHARD_AUDIT_TAGS) == {
        "context_encoding",
        "token_generation",
        "fused_speculation",
        "context_encoding_kvq8",
        "token_generation_kvq8",
        "fused_speculation_kvq8",
        "mixed_step",
        "token_generation_w4",
        "mixed_step_w4",
    }
    records = programs.collect_programs(shard_audit.SHARD_AUDIT_TAGS)
    for tag, per_bucket in records.items():
        assert len(per_bucket) >= 2, f"{tag}: need ≥2 buckets"
    baseline = shard_audit.load_shard_baseline()
    assert set(baseline) == set(shard_audit.SHARD_AUDIT_TAGS)
    tg = baseline["token_generation"]
    # a vacuous census (everything replicated) would mean the auditor reads
    # the wrong executable: the MLP projections must pin as tp-sharded
    assert "tp" in tg["params"]["layers/mlp/gate_proj/weight"]
    assert "tp" in tg["cache"]["k"]
    # the quantized pair pins the scale leaves head-sharded
    assert "tp" in baseline["token_generation_kvq8"]["cache"]["k/scale"]
    assert baseline["token_generation"]["mesh"]["tp"] == 2


def test_graph301_detects_silently_replicated_weight():
    """Proven detector: a weight DECLARED tp-sharded but realized fully
    replicated must produce GRAPH301 with the replication cost spelled
    out; the matching placement stays clean."""
    from neuronx_distributed_inference_tpu.analysis import shard_audit

    mesh, params, cache, compiled, ish, declared_p, _ = _toy_sharded_program(
        weight_spec=P(),  # BUG: loads replicated
        cache_spec_p=P(None, None, "tp"),
        declared_weight=P(None, "tp"),  # contract says column-sharded
        declared_cache=P(None, None, "tp"),
    )
    findings = []
    shard_audit._audit_leaves(
        "toy", 64, "GRAPH301", "weight", declared_p, ish[0], params, mesh, findings
    )
    assert [f.rule for f in findings] == ["GRAPH301"]
    assert "FULLY REPLICATED" in findings[0].message
    assert "8x" in findings[0].message
    # the honest placement is clean
    mesh, params, cache, compiled, ish, declared_p, _ = _toy_sharded_program(
        P(None, "tp"), P(None, None, "tp"), P(None, "tp"), P(None, None, "tp")
    )
    findings = []
    shard_audit._audit_leaves(
        "toy", 64, "GRAPH301", "weight", declared_p, ish[0], params, mesh, findings
    )
    assert findings == []


def test_graph301_detects_unexpectedly_sharded_replicated_leaf():
    """The inverse direction: a leaf DECLARED replicated (a norm, an MLA
    scale) that realizes sharded is equally a contract break."""
    from neuronx_distributed_inference_tpu.analysis import shard_audit

    mesh, params, cache, compiled, ish, declared_p, _ = _toy_sharded_program(
        weight_spec=P(None, "tp"),  # realized sharded
        cache_spec_p=P(None, None, "tp"),
        declared_weight=P(),  # contract says replicated
        declared_cache=P(None, None, "tp"),
    )
    findings = []
    shard_audit._audit_leaves(
        "toy", 64, "GRAPH301", "weight", declared_p, ish[0], params, mesh, findings
    )
    assert [f.rule for f in findings] == ["GRAPH301"]
    assert "declared replicated but realized sharded" in findings[0].message


def test_graph302_detects_replicated_cache():
    """Proven detector: a fully replicated cache-sized leaf on a >1 model
    group must produce GRAPH302 (the double-HBM catastrophic case), via
    both the declared-spec walk and the replication check."""
    from neuronx_distributed_inference_tpu.analysis import shard_audit

    mesh, params, cache, compiled, ish, _, declared_c = _toy_sharded_program(
        weight_spec=P(None, "tp"),
        cache_spec_p=P(),  # BUG: cache replicated
        declared_weight=P(None, "tp"),
        declared_cache=P(None, None, "tp"),
    )
    findings = shard_audit.cache_replication_findings(
        declared_c, ish[1], cache, mesh, "toy/64", "toy"
    )
    assert len(findings) == 2  # k and v
    assert all(f.rule == "GRAPH302" for f in findings)
    assert "FULLY REPLICATED" in findings[0].message
    # sharded cache is clean
    mesh, params, cache, compiled, ish, _, declared_c = _toy_sharded_program(
        P(None, "tp"), P(None, None, "tp"), P(None, "tp"), P(None, None, "tp")
    )
    assert (
        shard_audit.cache_replication_findings(
            declared_c, ish[1], cache, mesh, "toy/64", "toy"
        )
        == []
    )
    # a DECLARED-replicated cache (the deepseek MLA latent streams) is the
    # builder's explicit contract, not a silent bug: no finding
    mesh, params, cache, compiled, ish, _, declared_c = _toy_sharded_program(
        P(None, "tp"), P(), P(None, "tp"), P()
    )
    assert (
        shard_audit.cache_replication_findings(
            declared_c, ish[1], cache, mesh, "toy/64", "toy"
        )
        == []
    )


def test_graph303_detects_in_loop_weight_gather():
    """Proven detector: a sharded stacked weight forced replicated INSIDE a
    scan body compiles to an all-gather in the while loop — GRAPH303 must
    flag it; the same gather hoisted out of the loop stays clean."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh, NamedSharding

    from neuronx_distributed_inference_tpu.analysis import shard_audit

    mesh = Mesh(mesh_utils.create_device_mesh((8,)), ("tp",))
    W = jax.device_put(
        np.ones((4, 256, 256), np.float32), NamedSharding(mesh, P(None, None, "tp"))
    )
    x = jax.device_put(np.ones((4, 256), np.float32), NamedSharding(mesh, P()))

    def bad_body(carry, w):
        wr = jax.lax.with_sharding_constraint(w, NamedSharding(mesh, P()))
        return jnp.tanh(carry @ wr), None

    def bad_step(x, W):
        y, _ = jax.lax.scan(bad_body, x, W)
        return y

    def good_body(carry, w):
        return jnp.tanh(carry @ w), None

    def good_step(x, W):
        y, _ = jax.lax.scan(good_body, x, W)
        return y

    with jax.set_mesh(mesh):
        bad = jax.jit(bad_step).lower(x, W).compile().as_text()
        good = jax.jit(good_step).lower(x, W).compile().as_text()
    threshold = 256 * 256 * 4  # one layer's full weight
    findings = shard_audit.in_loop_gather_findings(bad, threshold, "toy/64", "toy")
    assert len(findings) >= 1
    assert all(f.rule == "GRAPH303" for f in findings)
    assert "INSIDE the step's loop body" in findings[0].message
    assert shard_audit.in_loop_gather_findings(good, threshold, "toy/64", "toy") == []
    # weight-signature discrimination: the gathered buffer matches the
    # per-layer weight shape, so a sig set containing it still flags; a
    # sig set that doesn't (the gather is then activation-shaped by
    # elimination) suppresses — output-only int4 sharding legitimately
    # re-gathers decode activations every step and must not trip GRAPH303
    sig = ("f32", (256, 256))
    flagged = shard_audit.in_loop_gather_findings(
        bad, threshold, "toy/64", "toy", weight_sigs={sig}
    )
    assert len(flagged) >= 1
    assert shard_audit.in_loop_gather_findings(
        bad, threshold, "toy/64", "toy", weight_sigs={("f32", (31, 17))}
    ) == []


def test_graph304_detects_census_drift(tmp_path):
    """A doctored sharding baseline must produce GRAPH304; a missing tag
    must demand a reviewed regeneration instead of passing vacuously."""
    from neuronx_distributed_inference_tpu.analysis import shard_audit

    good = shard_audit.load_shard_baseline()
    doctored = {t: {k: dict(v) if isinstance(v, dict) else v for k, v in c.items()}
                for t, c in good.items()}
    doctored["token_generation"]["params"]["layers/mlp/gate_proj/weight"] = "P()"
    p = tmp_path / "shard_baseline.json"
    shard_audit.save_shard_baseline(doctored, p)
    findings = shard_audit.run(baseline_path=p, tags=("token_generation",))
    assert any(f.rule == "GRAPH304" and "drifted" in f.message for f in findings)
    # an absent tag is a finding, not silence
    findings = shard_audit.run(
        baseline_path=tmp_path / "empty.json", tags=("token_generation",)
    )
    assert any(f.rule == "GRAPH304" and "no committed" in f.message for f in findings)


# ---------------------------------------------------------------------------
# memory audit (MEM40x)
# ---------------------------------------------------------------------------


def test_memory_audit_clean_and_covers_cache_variants():
    """The memory auditor over the real programs: zero findings, and the
    audited tag set covers all three cache variants (contiguous incl. the
    quantized pair, ring-bounded, paged) — the MEM401 donation-alias proof
    therefore holds for QuantizedKV code+scale leaves in every variant."""
    from neuronx_distributed_inference_tpu.analysis import memory_audit, programs

    findings = memory_audit.run()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert set(memory_audit.MEMORY_AUDIT_TAGS) == {
        "context_encoding",
        "token_generation",
        "fused_speculation",
        "context_encoding_kvq8",
        "token_generation_kvq8",
        "fused_speculation_kvq8",
        "mixed_step",
        "token_generation_ring",
        "token_generation_paged",
        "token_generation_w4",
        "mixed_step_w4",
    }
    records = programs.collect_programs(memory_audit.MEMORY_AUDIT_TAGS)
    # the quantized contiguous/ring/paged programs all donate code AND scale
    # leaves: 4 cache leaves each (k/v × data/scale)
    for tag in ("token_generation_kvq8", "token_generation_ring",
                "token_generation_paged", "mixed_step"):
        rec = next(iter(records[tag].values()))
        assert rec.n_cache_leaves == 4, tag
        paths = memory_audit.cache_leaf_paths(rec)
        assert {"k/data", "k/scale", "v/data", "v/scale"} == set(paths)
        # and the alias table really contains them (the proof MEM401 ran)
        aliased = memory_audit.aliased_param_numbers(rec.compiled_text)
        lo, hi = rec.cache_param_range
        assert set(range(lo, hi)) <= aliased, tag
    # the fused-speculation int8 variant donates BOTH quantized caches:
    # draft + target × k/v × data/scale = 8 aliased leaves
    rec = next(iter(records["fused_speculation_kvq8"].values()))
    assert rec.n_cache_leaves == 8
    paths = set(memory_audit.cache_leaf_paths(rec))
    assert {"draft/k/data", "draft/k/scale", "target/v/data",
            "target/v/scale"} <= paths
    aliased = memory_audit.aliased_param_numbers(rec.compiled_text)
    lo, hi = rec.cache_param_range
    assert set(range(lo, hi)) <= aliased
    report = memory_audit.last_report()
    # the quantized cache halves the bf16 cache bytes (plus small scales)
    bf16 = report["token_generation"]["64"]["cache_bytes"]
    q8 = report["token_generation_kvq8"]["64"]["cache_bytes"]
    assert q8 < 0.6 * bf16


def test_mem401_detects_undonated_cache():
    """Proven detector: the SAME step compiled without donate_argnums has no
    alias-table entries for the cache leaves — MEM401 must fail loudly on
    the double-buffer case, and pass on the donated compile."""
    import jax
    import numpy as np

    from neuronx_distributed_inference_tpu.analysis import memory_audit

    params = {"w": np.ones((128, 128), np.float32)}
    cache = {"k": np.zeros((2, 64, 128), np.float32),
             "v": np.zeros((2, 64, 128), np.float32)}
    x = np.ones((4, 128), np.float32)

    def step(params, cache, x):
        y = x @ params["w"]
        return y, {k: v + 1.0 for k, v in cache.items()}

    donated = jax.jit(step, donate_argnums=(1,)).lower(params, cache, x).compile()
    undonated = jax.jit(step).lower(params, cache, x).compile()
    cache_range = (1, 3)  # flat args: w, k, v, x
    paths = ["k", "v"]
    assert (
        memory_audit.donation_findings(
            donated.as_text(), cache_range, paths, "toy/64", "toy"
        )
        == []
    )
    findings = memory_audit.donation_findings(
        undonated.as_text(), cache_range, paths, "toy/64", "toy"
    )
    assert len(findings) == 1
    assert findings[0].rule == "MEM401"
    assert "double-buffers" in findings[0].message
    assert "k" in findings[0].message and "v" in findings[0].message


def test_mem402_hlo_temp_fallback_reads_result_buffers():
    """The memory_analysis fallback must size RESULT buffers (between ' = '
    and the op call), not operands or parameters — the LHS carries no type
    at all."""
    from neuronx_distributed_inference_tpu.analysis import memory_audit

    hlo = "\n".join(
        [
            "ENTRY %main (p.0: f32[512,512]) -> f32[64,64] {",
            "  %p.0 = f32[512,512]{1,0} parameter(0)",  # param: excluded
            "  %big = f32[128,128]{1,0} add(f32[512,512] %p.0, f32[512,512] %p.0)",
            "  %small = bf16[8,8]{1,0} multiply(bf16[8,8] %x, bf16[8,8] %x)",
            "  ROOT %out = f32[64,64]{1,0} tuple(f32[64,64] %y)",  # ROOT: excluded
            "}",
        ]
    )
    # 128*128*4 from %big's RESULT — not 512*512*4 from its operands
    assert memory_audit._largest_temp_from_hlo(hlo) == 128 * 128 * 4


def test_mem402_detects_footprint_regression(tmp_path):
    """Proven detector: a doctored baseline (committed footprint 25% below
    what the tree builds) must produce MEM402 with the component and
    percentage; within-tolerance drift stays green; a missing bucket is a
    finding, not silence."""
    import json

    from neuronx_distributed_inference_tpu.analysis import memory_audit

    good = memory_audit.load_memory_baseline()
    doctored = json.loads(json.dumps(good))  # deep copy
    row = doctored["programs"]["token_generation"]["64"]
    shrunk = dict(row)
    shrunk["cache_bytes"] = int(row["cache_bytes"] * 0.75)
    shrunk["total_bytes"] = (
        shrunk["weights_bytes"] + shrunk["cache_bytes"] + shrunk["temp_bytes"]
    )
    doctored["programs"]["token_generation"]["64"] = shrunk
    p = tmp_path / "memory_baseline.json"
    memory_audit.save_memory_baseline(doctored, p)
    findings = memory_audit.run(baseline_path=p, tags=("token_generation",))
    mem402 = [f for f in findings if f.rule == "MEM402"]
    assert mem402, "25% cache growth over baseline must trip the gate"
    assert any("cache_bytes" in f.message and "grew" in f.message for f in mem402)
    # within tolerance: a 1% nudge passes with the default 2% gate
    nudged = json.loads(json.dumps(good))
    row = nudged["programs"]["token_generation"]["64"]
    row["temp_bytes"] = int(row["temp_bytes"] * 1.01)
    memory_audit.save_memory_baseline(nudged, p)
    findings = memory_audit.run(baseline_path=p, tags=("token_generation",))
    assert [f for f in findings if "temp_bytes" in f.message] == []
    # missing bucket: loud
    findings = memory_audit.run(
        baseline_path=tmp_path / "missing.json", tags=("token_generation",)
    )
    assert any(f.rule == "MEM402" and "no committed" in f.message for f in findings)


# ---------------------------------------------------------------------------
# cost audit (COST50x) + device model
# ---------------------------------------------------------------------------


def test_cost_audit_clean_and_census_sane():
    """The roofline cost auditor over the real programs: zero findings on
    the committed baseline, every family covered (incl. the fused-spec int8
    variant), and the census behaves: FLOPs grow with the bucket, decode
    FLOPs grow SUBlinearly (constant weight term + linear attention), and
    the quantized cache halves the decode read traffic."""
    from neuronx_distributed_inference_tpu.analysis import cost_audit, programs

    findings = cost_audit.run()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert set(cost_audit.COST_AUDIT_TAGS) == set(programs.ALL_TAGS)
    report = cost_audit.last_report()
    progs = report["programs"]
    assert set(progs) == set(programs.ALL_TAGS)
    tg = progs["token_generation"]
    f64, f128 = tg["64"]["flops"], tg["128"]["flops"]
    assert f64 > 0 and f128 > f64
    assert f128 < 2 * f64  # sublinear: weights dominate the tiny decode
    # int8 cache: decode read traffic ~halves vs bf16 (+ tiny scales)
    q8 = progs["token_generation_kvq8"]
    assert q8["128"]["cache_read_bytes"] < 0.6 * tg["128"]["cache_read_bytes"]
    # weights stream identically (cache dtype doesn't touch weights)
    assert q8["128"]["weights_bytes"] == tg["128"]["weights_bytes"]
    # CTE flops scale superlinearly in S (causal attention) — and that is
    # fine: COST502 gates only decode-phase families
    cte = progs["context_encoding"]
    assert cte["128"]["flops"] > 2 * cte["64"]["flops"]
    # the fused-spec int8 variant is costed (ROADMAP item 2's path)
    assert progs["fused_speculation_kvq8"]["128"]["flops"] > 0
    # collective bytes ride the census: the tp=2 decode program moves bytes
    assert tg["128"]["collective_bytes"] > 0


def test_jaxpr_flops_counts_scan_multiplied_dots():
    """The FLOPs walk: a dot inside a scan body counts once per iteration;
    the closed-form count is exact."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.analysis.cost_audit import jaxpr_flops

    W = jnp.ones((4, 16, 16))
    x = jnp.ones((8, 16))

    def step(x, W):
        def body(carry, w):
            return carry @ w, None

        y, _ = jax.lax.scan(body, x, W)
        return y

    jaxpr = jax.make_jaxpr(step)(x, W)
    # 4 scan iterations × (8×16 output × 16 contraction × 2)
    assert jaxpr_flops(jaxpr) == 4 * 2 * 8 * 16 * 16

    def plain(x):
        return x @ x.T

    assert jaxpr_flops(jax.make_jaxpr(plain)(x)) == 2 * 8 * 8 * 16


def test_cost501_detects_census_drift(tmp_path):
    """Proven detector: a doctored baseline (committed FLOPs 50% below what
    the tree compiles) must produce COST501 with the component and
    percentage; a 1% nudge inside the 5% tolerance stays green; a missing
    bucket is a finding, not silence."""
    import json

    from neuronx_distributed_inference_tpu.analysis import cost_audit

    good = cost_audit.load_cost_baseline()
    doctored = json.loads(json.dumps(good))
    row = doctored["programs"]["token_generation"]["64"]
    row["flops"] = int(row["flops"] * 0.5)
    p = tmp_path / "cost_baseline.json"
    cost_audit.save_cost_baseline(doctored, p)
    findings = cost_audit.run(baseline_path=p, tags=("token_generation",))
    c501 = [f for f in findings if f.rule == "COST501"]
    assert c501, "2x FLOPs over baseline must trip the gate"
    assert any("flops" in f.message and "grew" in f.message for f in c501)
    # within tolerance: 1% drift passes with the default 5% gate
    nudged = json.loads(json.dumps(good))
    row = nudged["programs"]["token_generation"]["64"]
    row["act_bytes"] = int(row["act_bytes"] * 1.01)
    cost_audit.save_cost_baseline(nudged, p)
    findings = cost_audit.run(baseline_path=p, tags=("token_generation",))
    assert [f for f in findings if f.rule == "COST501"] == []
    # missing bucket: loud
    findings = cost_audit.run(
        baseline_path=tmp_path / "missing.json", tags=("token_generation",)
    )
    assert any(
        f.rule == "COST501" and "no committed" in f.message for f in findings
    )


def test_cost502_detects_superlinear_scaling():
    """Proven detector: synthetic per-bucket censuses — an O(T²) FLOPs term
    trips, linear-plus-constant (real decode) passes."""
    from neuronx_distributed_inference_tpu.analysis.cost_audit import (
        scaling_findings,
    )

    # real decode shape: constant weights + linear attention
    linear = {
        64: dict(flops=1000 + 64 * 10, cache_read_bytes=64 * 8, act_bytes=50),
        128: dict(flops=1000 + 128 * 10, cache_read_bytes=128 * 8, act_bytes=50),
    }
    assert scaling_findings("toy", linear) == []
    # quadratic attention: decode attending (W, W) instead of (1, W)
    quad = {
        64: dict(flops=1000 + 64 * 64, cache_read_bytes=64 * 8, act_bytes=50),
        128: dict(flops=1000 + 128 * 128, cache_read_bytes=128 * 8, act_bytes=50),
    }
    findings = scaling_findings("toy", quad)
    assert len(findings) == 1
    assert findings[0].rule == "COST502"
    assert "SUPERLINEARLY" in findings[0].message
    assert "flops" in findings[0].message


def test_cost503_detects_packing_drift(tmp_path):
    """Proven detector: a doctored packing contract (committed q_tile
    smaller than the tree's — i.e. the tree regressed to a coarser granule)
    must produce COST503; a doctored efficiency above the observed one
    reports the regression; an absent contract is loud."""
    import json

    from neuronx_distributed_inference_tpu.analysis import cost_audit

    good = cost_audit.load_cost_baseline()
    doctored = json.loads(json.dumps(good))
    doctored["mixed_packing"]["q_tile"] = 8
    p = tmp_path / "cost_baseline.json"
    cost_audit.save_cost_baseline(doctored, p)
    findings = cost_audit.run(baseline_path=p, tags=("mixed_step",))
    c503 = [f for f in findings if f.rule == "COST503"]
    assert any("q_tile" in f.message for f in c503)
    # efficiency regression direction (pure comparator)
    observed = dict(q_tile=16, num_rows=2, efficiency={"32": 0.03125})
    expected = dict(q_tile=16, num_rows=2, efficiency={"32": 0.0625})
    findings = cost_audit.packing_findings(observed, expected)
    assert any("REGRESSED" in f.message for f in findings)
    # observed == expected: clean
    assert cost_audit.packing_findings(expected, expected) == []
    # absent contract: loud
    assert any(
        "no committed" in f.message
        for f in cost_audit.packing_findings(expected, None)
    )


def test_cost504_detects_regime_flip(tmp_path):
    """Proven detector: a baseline that pins a program compute-bound while
    the tree compiles it bandwidth-bound must produce COST504 (the
    dequant/layout-flip gate)."""
    import json

    from neuronx_distributed_inference_tpu.analysis import cost_audit

    good = cost_audit.load_cost_baseline()
    doctored = json.loads(json.dumps(good))
    doctored["programs"]["token_generation"]["64"]["classification"] = "compute"
    p = tmp_path / "cost_baseline.json"
    cost_audit.save_cost_baseline(doctored, p)
    findings = cost_audit.run(baseline_path=p, tags=("token_generation",))
    c504 = [f for f in findings if f.rule == "COST504"]
    assert len(c504) == 1
    assert "FLIPPED" in c504[0].message
    assert "compute -> bandwidth" in c504[0].message


def test_device_model_projections():
    """The analytic roofline: registry resolution, the committed 1B/8B
    numbers, and the dtype/width monotonicities."""
    from neuronx_distributed_inference_tpu.analysis import device_model as dm

    # device_kind resolution (jax's device strings)
    assert dm.resolve_device("TPU v5 lite0").name == "v5e"
    assert dm.resolve_device("TPU v4").name == "v4"
    assert dm.resolve_device("cpu") is None
    assert dm.resolve_device("") is None

    # the committed v5e numbers: 1B bf16 ≈ 330 tok/s, 8B int8 ≈ 110
    p1 = dm.decode_projection(dm.LLAMA_1B, batch=1, kv_width=512)
    assert 320 < p1["tok_s"] < 340 and p1["bound"] == "hbm"
    assert abs(p1["weight_bytes"] - 2.47e9) < 0.05e9
    p8 = dm.decode_projection(dm.LLAMA_8B, batch=1, kv_width=512,
                              weight_dtype="int8")
    assert 100 < p8["tok_s"] < 120
    # int8 weights project faster than bf16; 16k kv slower than 8k
    assert dm.decode_projection(dm.LLAMA_1B, batch=1, kv_width=512,
                                weight_dtype="int8")["tok_s"] > p1["tok_s"]
    t8k = dm.decode_projection(dm.LLAMA_1B, batch=1, kv_width=8704)["tok_s"]
    t16k = dm.decode_projection(dm.LLAMA_1B, batch=1, kv_width=16896)["tok_s"]
    assert t16k < t8k < p1["tok_s"]
    # quantizing the cache recovers throughput at long context
    assert dm.decode_projection(dm.LLAMA_1B, batch=1, kv_width=16896,
                                kv_dtype="int8")["tok_s"] > t16k
    # prefill: compute-bound at real sequence lengths
    pf = dm.prefill_projection(dm.LLAMA_1B, batch=1, seq=8192)
    assert pf["bound"] == "flops" and pf["t_pass_s"] > 0


def _hot_path_snippet(omit=()):
    """A fixture serving.py defining every SERVING_STEP_HOT_PATH function
    (minus ``omit``), with a hot-path fetch in _ragged_step and an
    admission-path fetch in _windowed_admit."""
    from neuronx_distributed_inference_tpu.analysis.tpulint import (
        SERVING_STEP_HOT_PATH,
    )

    stubs = "\n".join(
        f"    def {name}(self):\n        pass"
        for name in sorted(SERVING_STEP_HOT_PATH - {"_ragged_step"} - set(omit))
    )
    return textwrap.dedent(
        """
        import jax

        class ServingSession:
            def _ragged_step(self, pend):
                return jax.device_get(pend)  # BUG: fetch in the step hot path

            def _windowed_admit(self, out):
                return jax.device_get(out)   # admission path: file bucket only
        """
    ) + "\n" + stubs + "\n"


def _lint_serving_snippet(tmp_path, source):
    pkg = tmp_path / "neuronx_distributed_inference_tpu" / "runtime"
    pkg.mkdir(parents=True, exist_ok=True)
    f = pkg / "serving.py"
    f.write_text(source)
    return lint_paths([f], tmp_path)


def test_rule_step_hot_path_census(tmp_path):
    """ISSUE 8: a blocking `jax.device_get` inside a ServingSession
    step()-hot-path function earns a SECOND TPU102 finding in the
    separately-pinned `<file>::step-hot-path` bucket — so a future
    blocking fetch added to the per-step serving loop trips the gate on
    its own; the same call on an admission-path function stays in the
    file-level census only."""
    findings = _lint_serving_snippet(tmp_path, _hot_path_snippet())
    census = [x for x in findings if x.rule == "TPU102"]
    hot = [x for x in census if x.key.endswith("::step-hot-path")]
    assert len(hot) == 1
    assert "_ragged_step" not in hot[0].key  # bucket is per-file, not per-fn
    assert len([x for x in census if not x.key.endswith("::step-hot-path")]) == 2


def test_rule_step_hot_path_stale_name_is_loud(tmp_path):
    """A renamed/removed hot-path function must not silently disarm the
    gate: a SERVING_STEP_HOT_PATH name with no matching function is a
    non-baselined ERROR, not a quietly-empty census bucket."""
    findings = _lint_serving_snippet(
        tmp_path, _hot_path_snippet(omit=("_consume_ragged",))
    )
    stale = [
        x for x in findings
        if x.rule == "TPU102" and x.key.endswith("::step-hot-path-stale")
    ]
    assert len(stale) == 1
    assert stale[0].severity == "error"
    assert "_consume_ragged" in stale[0].message


def _router_hot_snippet(omit=(), handoff_fetch=False):
    """A fixture router.py defining every ROUTER_HOT_PATH and
    ROUTER_HANDOFF_HOT_PATH function (minus ``omit``), with a hot-path
    fetch in _place_pending and an admission-path fetch in add_request;
    ``handoff_fetch`` adds a fetch in _handoff (the handoff-hot-path
    bucket's detector)."""
    from neuronx_distributed_inference_tpu.analysis.tpulint import (
        ROUTER_HANDOFF_HOT_PATH,
        ROUTER_HOT_PATH,
    )

    defined = {"_place_pending"} | ({"_handoff"} if handoff_fetch else set())
    stubs = "\n".join(
        f"    def {name}(self):\n        pass"
        for name in sorted(
            (ROUTER_HOT_PATH | ROUTER_HANDOFF_HOT_PATH) - defined - set(omit)
        )
    )
    handoff = (
        "\n    def _handoff(self, payload):\n"
        "        return jax.device_get(payload)  # BUG: fetch in hand-off\n"
        if handoff_fetch else ""
    )
    return textwrap.dedent(
        """
        import jax

        class ServingRouter:
            def _place_pending(self, scores):
                return jax.device_get(scores)  # BUG: fetch in placement loop

            def add_request(self, ids):
                return jax.device_get(ids)     # admission: file bucket only
        """
    ) + handoff + "\n" + stubs + "\n"


def _lint_router_snippet(tmp_path, source):
    pkg = tmp_path / "neuronx_distributed_inference_tpu" / "runtime"
    pkg.mkdir(parents=True, exist_ok=True)
    f = pkg / "router.py"
    f.write_text(source)
    return lint_paths([f], tmp_path)


def test_rule_route_hot_path_census(tmp_path):
    """ISSUE 10: a blocking `jax.device_get` inside a ServingRouter
    placement/failover function earns a SECOND TPU102 finding in the
    separately-pinned `runtime/router.py::route-hot-path` bucket (pinned
    at ZERO entries — ANY blocking fetch in the router loop fails lint);
    the same call on the admission path stays in the file-level census."""
    findings = _lint_router_snippet(tmp_path, _router_hot_snippet())
    census = [x for x in findings if x.rule == "TPU102"]
    hot = [x for x in census if x.key.endswith("::route-hot-path")]
    assert len(hot) == 1
    assert "router.py" in hot[0].key
    assert len([x for x in census if not x.key.endswith("::route-hot-path")]) == 2


def test_rule_route_hot_path_stale_name_is_loud(tmp_path):
    """A renamed router hot-path function is a loud non-baselined error —
    the route-hot-path bucket must not silently disarm."""
    findings = _lint_router_snippet(
        tmp_path, _router_hot_snippet(omit=("_sync_terminals",))
    )
    stale = [
        x for x in findings
        if x.rule == "TPU102" and x.key.endswith("::route-hot-path-stale")
    ]
    assert len(stale) == 1
    assert stale[0].severity == "error"
    assert "_sync_terminals" in stale[0].message


def test_rule_handoff_hot_path_census(tmp_path):
    """ISSUE 15: a blocking `jax.device_get` inside a ServingRouter
    hand-off function earns a SECOND TPU102 finding in the separately-
    pinned `runtime/router.py::handoff-hot-path` bucket (pinned at ZERO
    entries — the designated hand-off sync lives in
    disaggregated.validate_handoff_payload, not in router.py). The
    placement-loop fetch lands in the route-hot-path bucket, not this one:
    the two buckets pin independently."""
    findings = _lint_router_snippet(
        tmp_path, _router_hot_snippet(handoff_fetch=True)
    )
    census = [x for x in findings if x.rule == "TPU102"]
    handoff = [x for x in census if x.key.endswith("::handoff-hot-path")]
    assert len(handoff) == 1
    route = [x for x in census if x.key.endswith("::route-hot-path")]
    assert len(route) == 1  # the placement fetch did NOT leak into handoff


def test_rule_handoff_hot_path_stale_name_is_loud(tmp_path):
    findings = _lint_router_snippet(
        tmp_path, _router_hot_snippet(omit=("_pick_prefill",))
    )
    stale = [
        x for x in findings
        if x.rule == "TPU102" and x.key.endswith("::handoff-hot-path-stale")
    ]
    assert len(stale) == 1
    assert stale[0].severity == "error"
    assert "_pick_prefill" in stale[0].message


def test_router_tree_route_hot_path_is_clean():
    """The REAL runtime/router.py carries ZERO route-hot-path AND zero
    handoff-hot-path census entries (and zero file-level host syncs): the
    router is host bookkeeping only, by contract — the one designated
    hand-off sync lives in disaggregated.validate_handoff_payload."""
    findings = tpulint.run()
    router = [
        f for f in findings
        if f.rule == "TPU102" and "runtime/router.py" in f.key
    ]
    assert router == [], router


# ---------------------------------------------------------------------------
# TPU109: module-level mutable state in runtime/ written from functions
# (ISSUE 13 satellite; 0 baseline entries — the tree must stay clean)
# ---------------------------------------------------------------------------


def _lint_runtime_snippet(tmp_path, source: str):
    pkg = tmp_path / "neuronx_distributed_inference_tpu" / "runtime"
    pkg.mkdir(parents=True, exist_ok=True)
    f = pkg / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return lint_paths([f], tmp_path)


def test_tpu109_module_mutable_written_from_function_fires(tmp_path):
    findings = _lint_runtime_snippet(
        tmp_path,
        """
        _CACHE = {}
        _SEEN = []
        _IDS = set()

        def remember(key, value):
            _CACHE[key] = value          # BUG: hidden shared state

        def note(item):
            _SEEN.append(item)           # BUG: mutator call

        def tag(i):
            _IDS.add(i)                  # BUG: mutator call
        """,
    )
    hits = [f for f in findings if f.rule == "TPU109"]
    assert {f.key.rsplit("::", 1)[-1] for f in hits} == {
        "_CACHE", "_SEEN", "_IDS"
    }
    assert all(f.severity == "warning" for f in hits)


def test_tpu109_global_rebind_and_constructor_calls_fire(tmp_path):
    findings = _lint_runtime_snippet(
        tmp_path,
        """
        from collections import deque

        _QUEUE = deque()
        _TABLE = dict()

        def push(x):
            _QUEUE.append(x)             # BUG

        def reset():
            global _TABLE
            _TABLE = dict()              # BUG: global rebind
        """,
    )
    hits = {f.key.rsplit("::", 1)[-1] for f in findings if f.rule == "TPU109"}
    assert hits == {"_QUEUE", "_TABLE"}


def test_tpu109_clean_forms_pass(tmp_path):
    """The fixed forms: read-only module constants, state on an owning
    class, locals shadowing a module name, and a pragma'd registry."""
    findings = _lint_runtime_snippet(
        tmp_path,
        """
        _LIMITS = {"max": 8}           # read-only: never written
        _KINDS = ("a", "b")            # immutable anyway
        _REGISTRY = {}

        class Owner:
            def __init__(self):
                self.cache = {}

            def remember(self, k, v):
                self.cache[k] = v      # owned state, not module state

        def local_shadow():
            _CACHE = {}
            _CACHE["k"] = 1            # a LOCAL, not the module global
            return _CACHE

        def annotated_local_shadow():
            _REGISTRY: dict = {}
            _REGISTRY["k"] = 1         # AnnAssign-bound LOCAL shadows too
            return _REGISTRY

        def register(name, fn):
            _REGISTRY[name] = fn  # tpulint: ignore[TPU109]
        """,
    )
    assert [f for f in findings if f.rule == "TPU109"] == []


def test_tpu109_outside_runtime_not_in_scope(tmp_path):
    """The rule audits runtime/ only (the serving layers the threaded
    router makes concurrent) — a telemetry/ops module does not fire."""
    pkg = tmp_path / "neuronx_distributed_inference_tpu" / "ops"
    pkg.mkdir(parents=True, exist_ok=True)
    f = pkg / "snippet.py"
    f.write_text(
        textwrap.dedent(
            """
            _TUNE = {}

            def put(k, v):
                _TUNE[k] = v
            """
        )
    )
    findings = lint_paths([f], tmp_path)
    assert [x for x in findings if x.rule == "TPU109"] == []


def test_tpu109_tree_is_clean():
    """Zero TPU109 baseline entries: the real runtime/ tree carries no
    unsuppressed module-level mutable state written from functions."""
    from neuronx_distributed_inference_tpu.analysis import tpulint

    hits = [f for f in tpulint.run() if f.rule == "TPU109"]
    assert hits == [], [f.render() for f in hits]


# ---------------------------------------------------------------------------
# TPU110: silent-swallow except handlers in runtime/ + telemetry/
# ---------------------------------------------------------------------------


def _lint_scoped(tmp_path, subdir, source):
    pkg = tmp_path / "neuronx_distributed_inference_tpu" / subdir
    pkg.mkdir(parents=True, exist_ok=True)
    f = pkg / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return lint_paths([f], tmp_path)


@pytest.mark.parametrize("subdir", ["runtime", "telemetry"])
def test_tpu110_silent_swallow_fires(tmp_path, subdir):
    findings = _lint_scoped(
        tmp_path, subdir,
        """
        def probe(server):
            try:
                server.poke()
            except Exception:
                pass
        """,
    )
    hits = [f for f in findings if f.rule == "TPU110"]
    assert len(hits) == 1
    assert hits[0].severity == "warning"
    assert "swallow" in hits[0].message
    assert hits[0].key.endswith("::silent-swallow")


def test_tpu110_typed_or_handled_does_not_fire(tmp_path):
    """A narrow class, a handler that DOES something, or a docstring-only
    body followed by real statements are all out of scope — only broad AND
    silent fires."""
    findings = _lint_scoped(
        tmp_path, "runtime",
        """
        import logging

        def probe(server):
            try:
                server.poke()
            except OSError:
                pass          # typed: the author named the failure
            try:
                server.poke()
            except Exception:
                logging.exception("poke failed")   # broad but LOUD
        """,
    )
    assert [f for f in findings if f.rule == "TPU110"] == []


def test_tpu110_outside_scope_not_audited(tmp_path):
    """modules/ (pure jitted math, no lifecycle state) is out of scope."""
    findings = _lint_scoped(
        tmp_path, "modules",
        """
        def probe(server):
            try:
                server.poke()
            except Exception:
                pass
        """,
    )
    assert [f for f in findings if f.rule == "TPU110"] == []


def test_tpu110_tree_is_clean():
    """ZERO baseline entries: the real runtime/ + telemetry/ trees carry no
    silent-swallow handlers (the application.py cache-dir handler now names
    its classes)."""
    hits = [f for f in tpulint.run() if f.rule == "TPU110"]
    assert hits == [], [f.render() for f in hits]
