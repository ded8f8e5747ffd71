"""The rule ``BENCHMARK.json`` is held to, and the proof that the harness
takes an addition with no edit (ISSUE 34).  Nothing here is a measurement.

*The accepted entries keep their names, their fields and their relative
order; what is new comes after them* (``accepted_manifest.json``,
``manifest_rule.py``).  A PR that appends a configuration, a cell or a
metric edits neither: it adds its own test file beside this one.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
GRANITE_CELL = "granite-4.0-h-micro.tokens"
RESNET_CELLS = ["resnet50_v1.synthetic", "resnet18_v1.synthetic"]
# what ISSUE 34 appended after the accepted sixteen, and where each reads
APPENDED = {"ssm_scan_device_ms": [GRANITE_CELL],
            "mamba_device_ms": [GRANITE_CELL],
            "attention_device_ms": [GRANITE_CELL],
            "mlp_device_ms": [GRANITE_CELL],
            "head_loss_device_ms": [GRANITE_CELL],
            "unscoped_device_ms": RESNET_CELLS + [GRANITE_CELL],
            "ssd_scan_kernel_us": [GRANITE_CELL],
            "ssd_scan_roofline": [GRANITE_CELL]}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def rule():
    return _load(os.path.join(HERE, "manifest_rule.py"), "manifest_rule")


@pytest.fixture(scope="module")
def scratch(rule, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("scratch_benchmark"))
    return root, rule.scratch_tree(root)


def test_the_manifest_starts_with_what_was_accepted(rule):
    accepted = rule.load_accepted()
    assert rule.departures(rule.load_manifest(), accepted) == []
    assert rule.departures(accepted, accepted) == []
    assert set(accepted) >= set(rule.LISTS)


def test_the_table_holds_what_issue_34_appended(rule):
    """Eight metrics after the sixteen, named for mixers and feed-forwards
    and not for a model, each in the cells where its reader finds
    something; the layers are ones the manifest already named."""
    accepted = rule.load_accepted()
    tail = accepted["per_layer"][16:16 + len(APPENDED)]
    assert {m["name"]: m["workloads"] for m in tail} == APPENDED
    assert [m["name"] for m in tail] == list(APPENDED)
    layers = {m["layer"] for m in accepted["per_layer"][:16]}
    for m in tail:
        assert m["layer"] in layers and m["moves"] == "train_throughput"
        assert m["source"] == "device_trace"
    by_name = {m["name"]: m for m in tail}
    assert (by_name["ssd_scan_roofline"]["unit"],
            by_name["ssd_scan_roofline"]["better"]) == ("%", "higher")
    # the whole step's share of the peak stands beside the kernel's, in
    # the same cell, moving the same metric
    mfu = next(m for m in accepted["per_layer"] if m["name"] == "step_mfu")
    assert GRANITE_CELL in mfu["workloads"]
    assert mfu["moves"] == by_name["ssd_scan_roofline"]["moves"]


def test_an_appended_configuration_cell_and_metric_leave_the_rule_whole(
        rule, scratch):
    root, grown = scratch
    assert rule.departures(grown, rule.load_accepted()) == []
    assert grown["workloads"][-1]["name"] == rule.ADDED_CELL
    assert grown["per_layer"][-1]["name"] == rule.ADDED_METRIC
    harness = _load(os.path.join(root, "benchmark", "run.py"),
                    "benchmark_run_scratch_rule")
    try:
        cell, config, traffic, end_to_end, per_layer = harness.resolve(
            grown, rule.ADDED_CELL)
        assert cell["config"] == rule.ADDED_CONFIG
        assert config["family"] == "granite_hybrid"
        assert traffic["kind"] == "device_resident_tokens"
        assert [m["name"] for m in end_to_end] == [
            "train_throughput", "step_ms_p95", "setup_s"]
        names = [m["name"] for m in per_layer]
        assert names[-1] == rule.ADDED_METRIC and "step_mfu" in names
        # the Granite cell's own list did not change by the addition
        assert [m["name"] for m in harness.resolve(grown, GRANITE_CELL)[4]] \
            == names[:-1]
    finally:
        rule.forget(root)


def test_the_appended_cell_runs_through_the_unedited_harness(rule, scratch):
    """One traced rehearsal of the scratch benchmark's fourth cell, by the
    scratch copy of ``run.py``: configuration, family, traffic, limits and
    the one more reader are all found by name."""
    root, _ = scratch
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", rule.ADDED_CELL, "--seed", "3400000019", "--seconds",
         "0.2", "--trace", "1", "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    assert [r["name"] for r in line["compared"]] == [
        "loss_gap", "grad_norm_gap", "grad_norm_gap_median",
        "update_norm_gap", "update_norm_gap_median", "stats_norm_gap"]
    # a CPU has no device plane: the trace's readers say nothing, the
    # host's and the counters' speak
    assert "dispatch_ms" in line["metrics"]
    assert rule.ADDED_METRIC not in line["metrics"]
    assert not set(APPENDED) & set(line["metrics"])


def _moved(m):
    m["per_layer"].insert(6, m["per_layer"].pop())


def _renamed(m):
    m["per_layer"][9]["name"] = "conv_ms"


def _dropped(m):
    del m["per_layer"][3]


def _new_entry_first(m):
    m["workloads"].insert(0, dict(m["workloads"][0], name="first.cell"))


def _cell_taken_from_a_metric(m):
    m["per_layer"][0]["workloads"].remove("resnet18_v1.synthetic")


def _cell_entered_before_the_accepted(m):
    m["per_layer"][3]["workloads"].insert(0, GRANITE_CELL)


def _bound_loosened(m):
    m["end_to_end"][0]["bound"] = 0.05


def _configuration_dropped(m):
    del m["configs"][1]


@pytest.mark.parametrize("change", [
    _moved, _renamed, _dropped, _new_entry_first, _cell_taken_from_a_metric,
    _cell_entered_before_the_accepted, _bound_loosened,
    _configuration_dropped], ids=lambda f: f.__name__.lstrip("_"))
def test_an_accepted_entry_moved_renamed_or_dropped_is_caught(rule, change):
    manifest = copy.deepcopy(rule.load_manifest())
    change(manifest)
    assert rule.departures(manifest, rule.load_accepted())


def test_what_comes_after_the_accepted_is_free(rule):
    manifest = copy.deepcopy(rule.load_manifest())
    manifest["per_layer"].append(dict(manifest["per_layer"][0],
                                      name="later_ms", workloads=["x.y"]))
    manifest["per_layer"][3]["workloads"].append("x.y")
    manifest["workloads"].append(dict(manifest["workloads"][0], name="x.y"))
    assert rule.departures(manifest, rule.load_accepted()) == []
