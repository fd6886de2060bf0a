#!/usr/bin/env python3
"""One run of one cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

A new process finds the cell's chips or fails, builds the model from the
cell's configuration file, makes its bf16 weights on the device(s) from
``--seed``, checks the served logits against the plain reference, warms the
shapes the cell's traffic can reach, offers the traffic for ``--seconds`` on
the wall clock, and prints ONE last line of JSON: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, ``host`` (what
the host's cores did while the window ran: facts for the reader of a run
that reads off its set, ``harness/hostfacts.py``; the driver ignores the
key) and last ``compared``: every number ``correct`` was decided by, beside its limit
(the same, a line each, are the run's last lines on standard error). With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a slice at the end of the window is profiled and the metrics
are the cell's per-layer metrics. ``--trace 2`` is a ``--trace 0`` run up to
the closing of the window — its end-to-end numbers, ``correct``,
``attempted`` and ``failed`` are taken there and held — followed, in the
same process, by a short traced phase of the same traffic (``traced_phase``
below); its last line carries both kinds of metric side by side. Earlier
lines are JSON facts of the run.

This file knows no cell, mix, configuration or metric by name: it finds them
through ``harness/catalog.py`` by the names in ``BENCHMARK.json``.
``--rehearsal 1`` (the selftest's) lays the configuration's tiny
``rehearsal`` preset over it and lets a CPU through; its line names the
device ``cpu`` and carries counts only.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: the program and `benchmark`

#: seconds profiled at the end of a traced window: some ten steps of the
#: system as it is; stopping the profiler takes seconds, and at the end of the
#: window it disturbs nothing that is measured
TRACE_SLICE_S = 6.0


def emit(**facts):
    print(json.dumps(facts), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--catalog-root", default=None,
                    help="selftest and knee sweep: a directory with another BENCHMARK.json "
                         "and benchmark/ data files (cells at other rates are data)")
    ap.add_argument("--out", default=None,
                    help="also write the run's facts (and a traced run's trace summary) here")
    return ap.parse_args(argv)


class Profiler:
    """Starts and stops the profiler from the driver's loop: the last
    ``TRACE_SLICE_S`` seconds of the window. The device is drained (a tiny
    program queued behind everything dispatched so far, and waited for)
    before the profiler starts and, inside a driver span, before it stops:
    the trace then holds the device work of exactly the steps the driver
    ran in between, whole, and the readers count the work of those steps
    and no other."""

    def __init__(self, trace_dir: str, driver, devices, telemetry=None):
        import jax
        import numpy as np

        self.profiler = jax.profiler
        self.dir = trace_dir
        self.driver = driver
        #: ``--trace 2``: the profiler is started and stopped through the
        #: program's own control (``TelemetrySession.start/stop``), the
        #: slice is armed by the driver's traced phase and not by the clock
        self.telemetry = telemetry
        self.stop_at = driver.seconds
        self.start_at = max(0.0, self.stop_at - TRACE_SLICE_S)
        if telemetry is not None:
            self.start_at = self.stop_at = float("inf")
        self.started = self.stopped = None
        self._bump = jax.jit(lambda x: x + 1)
        self._marks = [jax.device_put(np.int32(0), d) for d in devices]
        self.drain()  # compiles here, in set-up

    def drain(self):
        import jax

        jax.block_until_ready([self._bump(m) for m in self._marks])

    def arm(self, now: float):
        """The slice starts at the next tick and lasts TRACE_SLICE_S."""
        self.start_at, self.stop_at = now, now + TRACE_SLICE_S

    def tick(self, now: float):
        if self.started is None and now >= self.start_at:
            self.drain()
            if self.telemetry is not None:
                self.telemetry.start(profile_dir=self.dir)
            else:
                opts = self.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # the driver's TraceMe spans only
                opts.host_tracer_level = 2
                self.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = now
        elif self.started is not None and self.stopped is None and now >= self.stop_at:
            with self.driver.span("trace_drain"):
                self.drain()
            if self.telemetry is not None:
                self.telemetry.stop()
            else:
                self.profiler.stop_trace()
            self.stopped = now


def warm_drive(app, vocab: int, seed: int, reserved=()):
    """Two short requests through a throw-away session at the served batch:
    the small host-side programs of the serving loop (token chaining, pads)
    compile here and not in the window."""
    import numpy as np

    from benchmark.harness.traffic import draw_ids
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    rng = np.random.default_rng([int(seed), 9])
    session = ServingSession(app)
    for i, n in enumerate((24, 150)):
        session.add_request(f"warm-{i}", draw_ids(rng, vocab, n, reserved), max_new_tokens=4)
    for _ in range(32):
        if not session.active:
            break
        session.step()


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.harness import catalog, correct, device, hostfacts, stats, system
    from benchmark.harness.driver import LoadDriver
    from benchmark.harness.traffic import Traffic, scale_mix

    rehearsal = bool(args.rehearsal)
    cell = catalog.load_cell(args.workload, root=args.catalog_root or catalog.REPO_DIR)
    try:
        devices, peaks, device_info = device.find_chips(cell.chips, rehearsal=rehearsal)
    except device.DeviceError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    log = system.CompileLog()
    cache_dir = system.configure_cache()
    cfg = system.resolve_config(cell.config, rehearsal)
    attrs = system.model_attrs(cfg)
    degree = cfg["tpu_config"].get("tp_degree", 1)
    reserved = cfg.get("reserved_token_ids", ())  # ids no prompt holds and no request may generate
    emit(phase="start", cell=cell.name, seed=args.seed, seconds=args.seconds,
         trace=args.trace, device=device_info, compile_cache=cache_dir,
         cache_entries=system.cache_dir_listing(cache_dir))

    # ---- set-up: model, weights, correctness, warm-up -----------------------
    t = time.perf_counter()
    app = system.build_app(cfg, devices, args.seed)
    params, pspecs = system.make_weights(app, args.seed, cfg.get("weights"))
    emit(phase="weights", seconds=time.perf_counter() - t, **log.facts())
    spec, mix = cell.spec, cell.traffic
    if rehearsal:
        spec = {**spec, **spec.get("rehearsal", {})}
        mix = scale_mix(mix, cfg["tpu_config"]["seq_len"] - 2)
    traffic = Traffic(
        mix, seed=args.seed, vocab_size=attrs["vocab_size"], loop=spec["loop"],
        seconds=args.seconds, rate_rps=spec.get("rate_rps"),
        max_prompt_len=cfg["tpu_config"]["seq_len"] - 2, reserved_ids=reserved,
    )
    t = time.perf_counter()
    try:
        model_facts = correct.check_model(cfg, devices, args.seed, params, pspecs, degree,
                                          traffic.bounds()["max_prompt"])
        model_ok = True
    except correct.CorrectnessError as e:
        model_facts, model_ok = {"error": str(e), **e.facts}, False
    emit(phase="reference", ok=model_ok, seconds=time.perf_counter() - t, **model_facts)

    t = time.perf_counter()
    system.give_weights(app, params, pspecs)
    shapes = system.reachable_shapes(app, **traffic.bounds())
    system.warm_up(app, shapes)
    kernels = system.kernel_census(app, shapes) if args.trace == 1 and not rehearsal else {}
    warm_drive(app, attrs["vocab_size"], args.seed, reserved)
    emit(phase="warm_up", seconds=time.perf_counter() - t, programs=len(shapes),
         kernels=kernels, traffic=traffic.summary(), digest=traffic.digest(),
         memory=device.memory_by_chip(devices), **log.facts())

    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession

    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession

    # a session of the run's own in every mode, STOPPED unless --trace 1 (a
    # stopped one is what ServingSession takes by default): up to the closing
    # of the window a --trace 0 and a --trace 2 run execute the same
    # statements, and only --trace 2 starts it, afterwards
    telemetry = TelemetrySession(enabled=args.trace == 1)
    session = ServingSession(app, telemetry=telemetry)
    driver = LoadDriver(
        session, traffic, loop=spec["loop"], seconds=args.seconds,
        clients=spec.get("clients", 0), prestart=int(spec.get("prestart", 0)),
        traced=args.trace == 1,
    )
    if driver.prestart:
        driver.fill()
    profiler = None
    trace_dir = os.path.join(os.path.dirname(HERE), ".bench_cache", "trace")
    if args.trace == 1:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler = Profiler(trace_dir, driver, devices)
    counters_before = telemetry.registry.snapshot() if args.trace == 1 else None
    compiles_before = log.compiles
    setup_s = time.perf_counter() - T_PROCESS

    # ---- the window ---------------------------------------------------------
    watch = hostfacts.HostWatch()
    watch.start()
    wall = driver.run(on_tick=profiler.tick if profiler else None)
    host = watch.stop()
    if profiler is not None and profiler.started is not None:
        profiler.tick(float("inf"))  # a window that ended between two ticks
    compiled_in_window = log.compiles - compiles_before
    counters = None
    if args.trace == 1:
        counters = {"before": counters_before, "after": telemetry.registry.snapshot()}

    # ---- reduction ------------------------------------------------------------
    records = list(driver.records.values())
    summary = stats.summarize(records, driver.window_s)
    spans = stats.span_stats(driver.spans, driver.window_s)
    host["steps"] = hostfacts.step_facts(driver.spans, driver.window_s)
    faults = correct.check_window(records, session, attrs["vocab_size"], reserved)
    counted = sum(len(session.requests[r.req_id].generated) for r in records
                  if r.req_id in session.requests)
    stamped = sum(r.tokens for r in records)
    emit(phase="window", wall_s=wall, window_s=driver.window_s, summary=summary, spans=spans,
         host=host,
         compiled_in_window=compiled_in_window, faults=faults[:10],
         tokens_counted=counted, tokens_stamped=stamped,
         preemptions=sum(getattr(session.requests[r.req_id], "preemptions", 0)
                         for r in records if r.req_id in session.requests),
         backlog_mid=_at(driver.samples["backlog"], args.seconds * 0.5),
         backlog_end=_at(driver.samples["backlog"], args.seconds),
         in_flight_mid=_in_flight(records, args.seconds * 0.5),
         in_flight_end=_in_flight(records, args.seconds))
    correct_all = bool(model_ok and not faults and compiled_in_window == 0 and counted == stamped)
    compared = correct.compared(model_facts, faults, compiled_in_window, counted, stamped)

    device_out = dict(device_info, memory_peak_bytes=device.memory_peak_bytes(devices))
    metrics, breakdown = {}, None
    if rehearsal:
        for key in ("finished", "out_tokens"):
            metrics[key] = {"value": summary[key], "unit": "count"}
    elif args.trace != 1:
        values = dict(summary, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                print(f"benchmark: no sample for end-to-end metric {m['name']}", file=sys.stderr)
                return 3
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    if args.trace == 2:
        # everything above is taken and held; from here on nothing reads the
        # window's records again
        phase = traced_phase(args, spec, mix, cfg, attrs, app, shapes, driver,
                             telemetry, devices, trace_dir, log, rehearsal)
        profiler, kernels, counters = phase["profiler"], phase["kernels"], phase["counters"]
        emit(phase="traced_phase", **phase["facts"])
        device_out["memory_peak_bytes"] = device.memory_peak_bytes(devices)  # of the whole run
    # a CPU rehearsal of --trace 2 reduces its (host-only) trace and calls
    # every reader, so that the path is exercised end to end, but prints
    # only what a CPU run may say: the metrics that are counts
    if args.trace and (not rehearsal or args.trace == 2):
        from benchmark.harness import trace_reduce

        path = trace_reduce.find_xplane(profiler.dir)
        reduced = trace_reduce.reduce_trace(path)
        if args.trace == 1:
            # the driver-side layer metrics stop where the slice starts:
            # starting the profiler stalls the loop for about a second, which
            # is not the load generator's lateness nor the scheduler's step time
            before = profiler.started
            summary_ctx = stats.summarize(records, before, due_before=before)
            spans_ctx = stats.span_stats(driver.spans, before)
        else:
            # the measured window, whole and untraced
            summary_ctx, spans_ctx = summary, spans
        ctx = dict(summary=summary_ctx, spans=spans_ctx,
                   samples=driver.samples, counters=counters,
                   trace=reduced, slice=(profiler.started, profiler.stopped), peaks=peaks,
                   attrs=attrs, chips=degree, kernels=kernels)
        read_by_name = []
        for m in cell.per_layer:
            reader = importlib.import_module("benchmark.harness.readers." + m["reader"]["reader"])
            value = reader.read(m["reader"], ctx)
            if value is not None:
                read_by_name.append(m["name"])
                if not rehearsal or m["source"] == "program_counter":
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if not rehearsal:
            device_out.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = reduced["breakdown"]
        emit(phase="trace", file_bytes=os.path.getsize(path), chips=reduced["chips"],
             collectives=reduced["collectives"], idle_by_span=reduced["idle_by_span"],
             span_counts=reduced["span_counts"], read=read_by_name,
             modules=sorted(reduced["module_sums"].items(), key=lambda kv: -kv[1][1])[:10])
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{cell.name}.trace_describe.json"), "w") as f:
                json.dump(trace_reduce.describe(path), f, indent=1)
        if args.trace == 2:
            if args.out:
                table = importlib.import_module(
                    "benchmark.harness.readers.program_span").idle_by_program_span(path)
                with open(os.path.join(args.out, f"{cell.name}.idle_by_program_span.json"), "w") as f:
                    json.dump(table, f, indent=1)
            shutil.rmtree(trace_dir, ignore_errors=True)  # reduced: the trace can go
        if reduced["busy_s"] <= 0 and not rehearsal:
            print("benchmark: the trace holds no device operation", file=sys.stderr)
            return 4
    result = {"correct": correct_all, "attempted": int(summary["attempted"]),
              "failed": int(summary["failed"]), "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["host"] = host  # what the host did meanwhile (harness/hostfacts.py); the driver ignores it
    result["compared"] = compared  # every number beside its limit; the last key of the line
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{cell.name}.seed{args.seed}.trace{args.trace}.json"), "w") as f:
            json.dump({"result": result, "summary": summary, "spans": spans}, f, indent=1)
    print(json.dumps(result), flush=True)
    for name, (number, limit) in compared.items():
        print(f"compared {name}: {number!r} limit {limit!r}", file=sys.stderr)
    if not model_ok:
        print(f"compared: {model_facts['error']}", file=sys.stderr)
    sys.stderr.flush()
    return 0


def traced_phase(args, spec, mix, cfg, attrs, app, shapes, driver, telemetry,
                 devices, trace_dir, log, rehearsal) -> dict:
    """``--trace 2``, after the window has closed and its numbers are held:
    what only a traced run needs is built now (the kernel census, the
    profiler's drain program), the profiler is started and stopped once and
    that trace thrown away (the cost of its first start falls into no
    number), then the program's telemetry is started, the driver re-opens
    the traffic and settles, and TRACE_SLICE_S of steps are profiled between
    two drains of the device. Returns the profiler (its slice and
    directory), the kernel census, the registry's snapshots at both ends of
    the phase, and the phase's facts."""
    from benchmark.harness import system
    from benchmark.harness.driver import SETTLE_LIMIT_S
    from benchmark.harness.traffic import Traffic

    t0 = time.perf_counter()
    kernels = system.kernel_census(app, shapes) if not rehearsal else {}
    shutil.rmtree(trace_dir, ignore_errors=True)
    profiler = Profiler(trace_dir, driver, devices, telemetry=telemetry)
    throwaway = trace_dir + ".first"
    telemetry.start(profile_dir=throwaway)
    telemetry.stop()
    shutil.rmtree(throwaway, ignore_errors=True)
    built_s = time.perf_counter() - t0
    arrivals = None
    if spec["loop"] == "open":
        # an open loop goes on at the cell's rate, from the same mix: a
        # Traffic of its own, long enough for the settling and the slice
        arrivals = Traffic(
            mix, seed=args.seed + 1, vocab_size=attrs["vocab_size"], loop="open",
            seconds=SETTLE_LIMIT_S + 2 * TRACE_SLICE_S, rate_rps=spec.get("rate_rps"),
            max_prompt_len=cfg["tpu_config"]["seq_len"] - 2,
            reserved_ids=cfg.get("reserved_token_ids", ()),
        )
    compiles_before = log.compiles
    telemetry.start()
    before = telemetry.registry.snapshot()
    facts = driver.trace_phase(profiler, arrivals)
    counters = {"before": before, "after": telemetry.registry.snapshot()}
    telemetry.stop()  # a slice that never started leaves the session recording
    facts.update(built_s=built_s, compiled_in_phase=log.compiles - compiles_before,
                 slice=[profiler.started, profiler.stopped])
    return {"profiler": profiler, "kernels": kernels, "counters": counters, "facts": facts}


def _at(samples, t):
    """The last per-step sample at or before time ``t`` (None if none)."""
    value = None
    for when, v in samples:
        if when > t:
            break
        value = v
    return value


def _in_flight(records, t) -> int:
    """Requests admitted at or before time ``t`` that had not ended by then,
    from the records' own stamps (nothing is sampled in the loop for it)."""
    ended = lambda r: (r.finished or r.failed) and r.commits and r.commits[-1][0] <= t
    return sum(1 for r in records
               if r.admitted_s is not None and r.admitted_s <= t and not ended(r))


if __name__ == "__main__":
    sys.exit(main())
