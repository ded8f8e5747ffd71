#!/usr/bin/env python3
"""One process, one cell, one run of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up from ``--seed`` (weights, batches, the compiled step and
its first checked steps), measures for ``--seconds``, and prints as the
last line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and last
``compared``, every number that decided ``correct`` beside its limit.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read by ``layer_metrics/<name>.py``.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: ``configs/<config>.json`` -> ``families/<family>.py``,
``traffic/<traffic>.json`` -> ``traffic_kinds/<kind>.py``,
``limits/<workload>.json``.  Adding a cell adds files and edits none.

Without a TPU whose ``device_kind`` is in ``peaks.json``, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
``--rehearsal`` is the one exception and has to be asked for: the same code
at the configuration's ``rehearsal_size`` on whatever backend JAX has, to
debug the harness; its line says ``"rehearsal": true`` and is no
measurement.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()          # set-up is counted from here

import argparse                 # noqa: E402
import gc                       # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import statistics               # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "_out")

CHECKED_STEPS = 3       # the first steps of the timed object, compared
WARM_STEPS = 2          # further steps before the window opens
P95_SPAN_STEPS = 4      # a host-clock reading spans this many steps
TRACED_STEPS = 40       # the traced sub-window of a --trace 1 run


def mark(what):
    """Say on stderr how far into the run ``what`` was reached: the split
    of ``setup_s`` (and of what follows the window) for PERF.md."""
    print("benchmark: %8.3f s  %s" % (time.monotonic() - _T0, what),
          file=sys.stderr)


class Refused(SystemExit):
    """The run cannot be made here; nothing is printed on stdout."""

    def __init__(self, message):
        print("benchmark: " + message, file=sys.stderr)
        super().__init__(3)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(folder, name):
    """``benchmark/<folder>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        raise Refused("no %s/%s.py" % (folder, name))
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (folder, name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(manifest, workload):
    """The cell's entry, configuration, traffic parameters and the names of
    the metrics it reports, from the manifest and the files it names."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused("BENCHMARK.json has no workload %r" % workload)
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(REPO, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def reported(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return (cell, config, traffic, reported(manifest["end_to_end"]),
            reported(manifest["per_layer"]))


def cell_size(config, rehearsal):
    """The sizes the cell runs at: the configuration's own, or with
    ``--rehearsal`` its ``rehearsal_size`` (the same keys, made small)."""
    if rehearsal:
        return dict(config["rehearsal_size"])
    return {k: config[k] for k in config["rehearsal_size"]}


def p95_ms(returns, ring):
    """(value, samples): the 95th percentile of the time per step over
    every run of ``P95_SPAN_STEPS`` consecutive returns of ``step`` once
    the run-ahead ring is full.  One reading spans several steps because a
    host-clock reading is off by some half a millisecond."""
    steady = returns[ring:]
    spans = [(b - a) / P95_SPAN_STEPS * 1e3
             for a, b in zip(steady, steady[P95_SPAN_STEPS:])]
    return statistics.quantiles(spans, n=20, method="inclusive")[18], \
        len(spans)


def measure(program, feed, seconds, ring):
    """The window: steps dispatched back to back for ``seconds``, from a
    drained ring to the return of the flush that ends it."""
    program.flush()
    returns, losses = [], []
    fewest = ring + P95_SPAN_STEPS + 2
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        x, y = feed[len(returns) % len(feed)]
        losses.append(program.step(x, y))
        now = time.perf_counter()
        returns.append(now)
        if now >= deadline and len(returns) >= fewest:
            break
    program.flush()
    end = time.perf_counter()
    return {"start": start, "end": end, "returns": returns, "losses": losses}


def peak_bytes(device):
    """The most of ``device``'s memory that was taken at one time.  The
    TPU's allocator counts the live arrays (``peak_bytes_in_use``) apart
    from what it set aside for the running programs' own scratch memory
    (``peak_bytes_reserved``, the compiled step's temporaries); the peak is
    both.  0 where the backend keeps no count (a CPU)."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def traced_steps(program, feed, directory):
    """A short steady sub-window under the profiler; returns the trace."""
    import jax

    import trace_reduce

    shutil.rmtree(directory, ignore_errors=True)    # the last run's trace
    os.makedirs(directory)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    program.flush()
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        for i in range(TRACED_STEPS):
            x, y = feed[i % len(feed)]
            with jax.profiler.StepTraceAnnotation(trace_reduce.STEP_SPAN,
                                                  step_num=i):
                program.step(x, y)
        program.flush()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load(directory)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    manifest = load_json(REPO, "BENCHMARK.json")
    cell, config, traffic, end_to_end, per_layer = resolve(
        manifest, args.workload)
    chips = int(cell["chips"])
    sys.path.insert(0, REPO)

    import jax

    import mxnet_tpu as mx

    mark("imported jax and mxnet_tpu")
    devices = jax.devices()
    mark("devices found")
    peaks = load_json(HERE, "peaks.json")["devices"].get(
        devices[0].device_kind)
    if not args.rehearsal:
        if devices[0].platform != "tpu":
            raise Refused("JAX found platform %r, not a TPU"
                          % devices[0].platform)
        if peaks is None:
            raise Refused("device_kind %r is not in peaks.json"
                          % devices[0].device_kind)
    if len(devices) < chips:
        raise Refused("the cell asks for %d chips, JAX found %d"
                      % (chips, len(devices)))
    mx.base.use_compilation_cache()
    # every program of a warm start comes from the cache, the many
    # sub-second ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from mxnet_tpu.parallel import make_mesh

    import correctness

    family = load_module("families", config["family"])
    kind = load_module("traffic_kinds", traffic["kind"])
    limits = correctness.load_limits(args.workload)
    size = cell_size(config, args.rehearsal)
    mesh = make_mesh((chips,), ("data",), devices[:chips])

    # -- set-up: the one object the window will drive, through its first
    # steps on the window's own feed
    feed = kind.batches(config, size, mesh, args.seed, traffic)
    mark("batches made")
    program = family.build(config, size, mesh, args.seed)
    mark("weights made, network and trainer built")
    first_losses = [program.step(*feed[0])]
    after_first = program.snapshot()
    mark("first step (compiled or loaded) and its snapshot")
    for i in range(1, CHECKED_STEPS):
        first_losses.append(program.step(*feed[i % len(feed)]))
    after_last = program.snapshot()
    first_losses = [float(v) for v in first_losses]
    for i in range(WARM_STEPS):
        program.step(*feed[(CHECKED_STEPS + i) % len(feed)])
    program.flush()
    ring = mx.engine.bulk_size()
    if args.trace:
        mx.telemetry.enable()
        before = mx.telemetry.attribution().snapshot()
    setup_s = time.monotonic() - _T0
    mark("set-up done")

    # -- the measured window
    window = measure(program, feed, args.seconds, ring)
    seconds = window["end"] - window["start"]
    steps = len(window["returns"])
    items = steps * int(size["batch_per_chip"]) * chips
    failed = sum(1 for v in window["losses"] if not math.isfinite(float(v)))
    p95, p95_samples = p95_ms(window["returns"], ring)
    print("benchmark: %d steps in %.3f s; step_ms_p95 over %d readings of "
          "%d steps" % (steps, seconds, p95_samples, P95_SPAN_STEPS),
          file=sys.stderr)
    if args.trace:
        after = mx.telemetry.attribution().snapshot()
        attribution = {
            "steps": after["steps"] - before["steps"],
            "phases_s": {k: v - before["phases_s"].get(k, 0.0)
                         for k, v in after["phases_s"].items()}}
        trace = traced_steps(program, feed,
                             os.path.join(OUT_DIR, "trace", args.workload))
    memory_peak = max(peak_bytes(d) for d in devices[:chips])

    # -- the comparison, with the program's state gone from the chip
    program_readings = program.readings(first_losses, after_first, after_last)
    program.close()
    del program, after_first, after_last, window["losses"]
    checked = feed[:CHECKED_STEPS]
    del feed
    gc.collect()
    mark("window, trace and memory read; program freed")
    reference = family.reference_readings(config, size, args.seed, checked)
    mark("reference done")
    correct, compared = correctness.verdict(
        correctness.compare(program_readings, reference, limits), limits)
    correct = correct and failed == 0

    values = {
        "train_throughput": items / seconds / chips,
        "step_ms_p95": p95,
        "setup_s": setup_s,
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if args.trace:
        run = {"trace": trace, "traced_steps": TRACED_STEPS,
               "attribution": attribution, "memory_peak_bytes": memory_peak,
               "window": {"steps": steps, "items": items, "seconds": seconds,
                          "chips": chips},
               "config": config, "size": size, "peaks": peaks,
               "family": family}
        metrics = {}
        for m in per_layer:
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        import scope_reduce
        import trace_reduce
        planes = list(trace["devices"].values())
        if planes:
            device["busy_s"] = sum(trace_reduce.busy_ns(p)
                                   for p in planes) / len(planes) / 1e9
            device["window_s"] = max(trace_reduce.window_ns(p)
                                     for p in planes) / 1e9
            # the first chip's operations, with their op_names where the
            # trace's file is found: the breakdown names them by scope
            first = next(iter(trace["devices"]))
            with_scopes = scope_reduce.of_run(run) or trace
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(
                    with_scopes["devices"].get(first, planes[0])),
                "idle_gaps": trace_reduce.idle_gaps(planes[0],
                                                    trace["steps"])}
    else:
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in end_to_end}
    result["device"] = device
    if args.rehearsal:
        result["rehearsal"] = True
    result["compared"] = compared
    for row in compared:
        print("benchmark: compared %(name)s = %(value).6g (limit %(limit)g, "
              "worst at %(at)s)" % row, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
