"""The benchmark's harness (``benchmark/``), on the CPU at rehearsal size.

Nothing here touches a TPU or describes a topology at import; nothing here
is a measurement.  What it proves: the manifest finds every file by name;
the FLOP count; the trace reduction; that the plain reference is the zoo
network's mathematics (both block types); that the lower-precision control
and each fault a one-chip training cell can have come out not correct; and
the command's contract (the result's keys, no result without a TPU).
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# limits for the rehearsal size only: 16 images of 64x64 through BatchNorm
# are a chaotic system, so sound bfloat16 runs read far above what they
# read at the cell's size on the chip (limits/<workload>.json)
REHEARSAL_LIMITS = {"loss_gap": 0.1, "grad_norm_gap": 0.3,
                    "grad_norm_gap_median": 0.05, "update_norm_gap": 0.4,
                    "update_norm_gap_median": 0.05, "stats_norm_gap": 0.3}


def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def harness():
    return _load(os.path.join(BENCH, "run.py"), "benchmark_run")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["repo", "scratch"])
def tree(request, harness, manifest, tmp_path_factory):
    """(root, manifest, harness) of the benchmark as the repo has it, and of
    a scratch copy with a fourth configuration, a fourth cell and one more
    per-layer metric appended (``manifest_rule.scratch_tree``): what does
    not depend on which cells exist holds on both, with no test edited."""
    if request.param == "repo":
        yield REPO, manifest, harness
        return
    rule = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "manifest_rule.py"), "manifest_rule")
    root = str(tmp_path_factory.mktemp("scratch_benchmark"))
    grown = rule.scratch_tree(root)
    yield root, grown, _load(os.path.join(root, "benchmark", "run.py"),
                             "benchmark_run_scratch")
    rule.forget(root)


@pytest.fixture()
def keep_jax_config():
    """``run.main`` turns the persistent compilation cache on for its
    process; put the settings back for the tests that follow."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    import mxnet_tpu as mx
    mx.telemetry.disable()


# -- the manifest finds everything by name ---------------------------------
def test_manifest_names_and_units(tree):
    root, manifest, _ = tree
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    assert 1 <= manifest["run_seconds"] <= 51
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(root, path))
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")


def test_manifest_finds_every_file(tree):
    root, manifest, harness = tree
    bench = os.path.join(root, "benchmark")
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for cell in manifest["workloads"]:
        assert cell["chips"] in (1, 4)
        entry = configs[cell["config"]]
        used.add(cell["config"])
        assert any(entry["file"].startswith(p + "/")
                   for p in manifest["paths"])
        _, config, traffic, end_to_end, per_layer = harness.resolve(
            manifest, cell["name"])
        assert config["reduced"] == entry["reduced"]
        assert len(entry["source"]) <= 200
        for folder, name in (("families", config["family"]),
                             ("traffic_kinds", traffic["kind"])):
            assert os.path.isfile(os.path.join(bench, folder, name + ".py"))
        family = harness.load_module("families", config["family"])
        for needed in ("build", "flops_per_item", "reference_readings"):
            assert callable(getattr(family, needed))
        assert callable(harness.load_module(
            "traffic_kinds", traffic["kind"]).batches)
        limits = _load(os.path.join(bench, "correctness.py"),
                       "correctness").load_limits(cell["name"])
        assert set(limits) <= set(REHEARSAL_LIMITS) and len(limits) >= 4
        assert {"setup_s", "train_throughput"} <= {m["name"]
                                                   for m in end_to_end}
        assert per_layer
    assert used == set(configs)


def test_every_per_layer_metric_has_a_reader(tree):
    root, manifest, harness = tree
    cells = {w["name"] for w in manifest["workloads"]}
    layers = set()
    for m in manifest["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
        assert m["workloads"] and set(m["workloads"]) <= cells
        moved = next(e for e in manifest["end_to_end"]
                     if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)
        layers.add(m["layer"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, "PERF.md's list of layers lacks %r" % layer


def test_readers_say_nothing_when_there_is_nothing_to_read(tree):
    _, manifest, harness = tree
    family = harness.load_module("families", "gluon_resnet_v1")
    run = {"trace": None, "traced_steps": 0, "attribution": None,
           "memory_peak_bytes": 0, "peaks": None, "family": family,
           "window": {"steps": 0, "items": 0, "seconds": 0.0, "chips": 1},
           "config": {}, "size": {}}
    for m in manifest["per_layer"]:
        assert harness.load_module("layer_metrics",
                                   m["name"]).read(run) is None


def test_peaks_table_has_no_default():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"] and "error" in peaks["rule"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    assert "cpu" not in peaks["devices"]


# -- the benchmark's own count of the arithmetic ---------------------------
@pytest.mark.parametrize("name,low,high", [
    # the zoo's v1 bottleneck strides in its first 1x1, which makes it a
    # little cheaper than the 4.1 G of the stride-in-3x3 variant
    ("resnet50_v1", 3.8e9, 4.15e9),
    ("resnet18_v1", 1.80e9, 1.83e9),
])
def test_flops_per_item(harness, name, low, high):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    family = harness.load_module("families", config["family"])
    size = {k: config[k] for k in config["rehearsal_size"]}
    macs = family.forward_macs_per_item(config, size)
    assert low <= macs <= high
    assert family.flops_per_item(config, size) == 6 * macs
    # an independent count: XLA's own, of the reference's forward pass
    # (it leaves out the taps that fall on padding, and adds the BatchNorms)
    import jax
    import jax.numpy as jnp
    small = dict(size, batch_per_chip=1)
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, _, s in family.leaves(config, small)}
    x = jax.ShapeDtypeStruct((1, small["side"], small["side"], 3),
                             jnp.float32)
    arch = family.architecture(config)
    cost = jax.jit(lambda p, x: family.forward(arch, p, x, family.HOLD["float32"])[0]
                   ).lower(shapes, x).compile().cost_analysis()
    assert 0.9 * 2 * macs <= cost["flops"] <= 1.25 * 2 * macs


# -- the reduction from trace to numbers -----------------------------------
def test_trace_reduce_on_hand_made_intervals(harness):
    tr = _load(os.path.join(BENCH, "trace_reduce.py"), "trace_reduce")
    ops = [("fusion.1", 0, 100), ("fusion.2", 50, 150),      # overlap
           ("_fused_sgd_mom_kernel", 150, 160), ("inner", 155, 158),  # nested
           ("fusion.1", 300, 400)]
    assert tr.merged(ops) == [[0, 160], [300, 400]]
    assert tr.busy_ns(ops) == 260
    assert tr.window_ns(ops) == 400
    assert tr.idle_share(ops) == pytest.approx(1 - 260 / 400)
    assert tr.idle_share([]) is None
    assert [e[1] for e in tr.named(ops, "_fused_sgd_mom_kernel")] == [150]
    assert tr.top_ops(ops, 2) == [["fusion.1", 200 / 1e9],
                                  ["fusion.2", 100 / 1e9]]
    steps = [(tr.STEP_SPAN, 100, 200)]
    assert tr.idle_gaps(ops, steps) == [[tr.STEP_SPAN, 140 / 1e9]]
    assert tr.idle_gaps(ops, []) == [["between_steps", 140 / 1e9]]

    family = harness.load_module("families", "gluon_resnet_v1")
    with open(os.path.join(BENCH, "configs", "resnet50_v1.json")) as f:
        config = json.load(f)
    run = {"trace": {"devices": {"/device:TPU:0": ops}, "steps": steps},
           "traced_steps": 2, "family": family, "config": config,
           "size": {"classes": 1000, "side": 224, "batch_per_chip": 256},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    read = {m: harness.load_module("layer_metrics", m).read(run)
            for m in ("step_device_ms", "device_idle_share",
                      "fused_update_us")}
    assert read["step_device_ms"] == pytest.approx(260 / 2 / 1e6)
    assert read["device_idle_share"] == pytest.approx(35.0)
    assert read["fused_update_us"] == pytest.approx(10 / 2 / 1e3)
    run["trace"]["devices"]["/device:TPU:0"] = ops[:2]     # no such kernel
    assert harness.load_module("layer_metrics",
                               "fused_update_us").read(run) is None


def test_trace_reduce_loads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    tr = _load(os.path.join(BENCH, "trace_reduce.py"), "trace_reduce")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(3):
            with jax.profiler.StepTraceAnnotation(tr.STEP_SPAN, step_num=i):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = tr.load(str(tmp_path))
    assert len(trace["steps"]) == 3
    assert trace["devices"] == {}          # a CPU has no device plane


# -- the plain reference is the zoo network's mathematics ------------------
def _one_cell(harness, name, dtype, steps):
    """(program readings, family, config, size, feed) of ``steps`` steps of
    the zoo network under the trainer at rehearsal size."""
    import jax
    from mxnet_tpu.parallel import make_mesh
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    config["dtype"] = dtype
    size = dict(config["rehearsal_size"])
    family = harness.load_module("families", config["family"])
    kind = harness.load_module("traffic_kinds", "device_resident")
    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    seed = 2 ** 31 + 12345
    feed = kind.batches(config, size, mesh, seed,
                        {"distinct_batches": steps})
    program = family.build(config, size, mesh, seed)
    losses = [program.step(*feed[0])]
    after_first = program.snapshot()
    losses += [program.step(*feed[i]) for i in range(1, steps)]
    after_last = program.snapshot()
    readings = program.readings([float(v) for v in losses], after_first,
                                after_last)
    program.close()
    return readings, family, config, size, feed, seed


@pytest.mark.parametrize("name", ["resnet50_v1", "resnet18_v1"])
def test_reference_against_the_zoo_network_and_its_control(harness, name):
    """In float32 the program and the reference are the same arithmetic:
    loss, every leaf's gradient, one update (both groups, momentum and
    weight decay in it) and the BatchNorm statistics agree to rounding.
    The same readings with the reference computed in float8, or with a
    fault planted, do not — under the cell's own limits."""
    correctness = _load(os.path.join(BENCH, "correctness.py"), "correctness")
    readings, family, config, size, feed, seed = _one_cell(
        harness, name, "float32", 1)
    assert len({float(feed[0][0][i].sum()) for i in range(4)}) == 4
    reference = family.reference_readings(config, size, seed, feed)
    numbers = correctness.compare(readings, reference)
    assert numbers["loss_gap"][0] < 1e-4
    assert numbers["stats_norm_gap"][0] < 1e-4
    # one step through sixteen 64x64 images amplifies rounding a thousand
    # times (near-constant channels under BatchNorm); 1e-2 still tells a
    # wrong formula (a missing weight decay reads 1.0) from rounding
    assert numbers["grad_norm_gap"][0] < 1e-2
    assert numbers["update_norm_gap"][0] < 1e-2
    nought = [k for k, v in reference["grad_norms"].items()
              if v < correctness.NOUGHT_GRADIENT
              * sorted(reference["grad_norms"].values())[
                  len(reference["grad_norms"]) // 2]]
    assert all(k.endswith(".bias") for k in nought)
    assert bool(nought) == (config["block"] == "bottleneck")

    limits = correctness.load_limits(name + ".synthetic")
    for variant, fault in (("fp8", None), ("float32", "half_batch"),
                           ("float32", "state_unchanged")):
        control = family.reference_readings(config, size, seed, feed,
                                            variant=variant, fault=fault)
        correct, rows = correctness.verdict(
            correctness.compare(control, reference), limits)
        assert not correct, (variant, fault, rows)


# -- a run with the timed path broken underneath ---------------------------
def _rehearse(harness, monkeypatch, capsys, seed=77):
    import correctness
    monkeypatch.setattr(correctness, "load_limits",
                        lambda workload: dict(REHEARSAL_LIMITS))
    capsys.readouterr()
    assert harness.main(["--workload", "resnet18_v1.synthetic", "--seed",
                         str(seed), "--seconds", "0.2", "--trace", "0",
                         "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_rehearsal_is_correct(harness, monkeypatch, capsys,
                                      keep_jax_config):
    line = _rehearse(harness, monkeypatch, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert [r["name"] for r in line["compared"]] == list(REHEARSAL_LIMITS)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(harness, monkeypatch, capsys,
                                      keep_jax_config, fault):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import DataParallelTrainer
    real_step = DataParallelTrainer.step

    def state_unchanged(self, data, label):
        if not self._ready:
            return real_step(self, data, label)     # the first builds it
        kept = {n: jnp.copy(p.data()._data)
                for n, p in self._params_by_name.items()}
        states = jax.tree_util.tree_map(jnp.copy, self._states_raw)
        loss = real_step(self, data, label)
        self.flush()
        for n, p in self._params_by_name.items():
            p._data._set_data(kept[n])
        self._states_raw = states
        return loss

    def half_batch(self, data, label):
        half = data.shape[0] // 2
        return real_step(self, data[:half], label[:half])

    monkeypatch.setattr(DataParallelTrainer, "step",
                        {"state_unchanged": state_unchanged,
                         "half_batch": half_batch}[fault])
    line = _rehearse(harness, monkeypatch, capsys)
    assert line["correct"] is False
    assert any(r["value"] > r["limit"] for r in line["compared"])


# -- the command's contract ------------------------------------------------
def test_run_end_to_end_prints_the_contracts_keys():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    for trace, wanted in (("0", {"train_throughput", "step_ms_p95",
                                 "setup_s"}),
                          ("1", {"dispatch_ms"})):
        done = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             "resnet18_v1.synthetic", "--seed", "3000000019", "--seconds",
             "0.2", "--trace", trace, "--rehearsal"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-2000:]
        line = json.loads(done.stdout.strip().splitlines()[-1])
        assert set(line) == {"correct", "attempted", "failed", "metrics",
                             "device", "rehearsal", "compared"}
        assert set(line["metrics"]) == wanted
        assert all(set(v) == {"value", "unit"}
                   for v in line["metrics"].values())
        assert line["device"]["platform"] == "cpu"
        names = [row["name"] for row in line["compared"]]
        last = done.stderr.strip().splitlines()[-len(names):]
        assert names and [row.split()[2] for row in last] == names
        assert all("(limit " in row for row in last)


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet18_v1.synthetic", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr
