"""``flash_attn_kernel_us`` (ISSUE 36), on the CPU: the reader finds the
three flash kernels by the instructions' own names on a hand-made step,
takes no operation that only reads a kernel's result, says nothing where a
step has no such kernel (the parent's, the ResNets'), and the entry a
``benchmark`` PR appends for it fits the manifest.  Nothing here is a
measurement.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = "flash_attn_kernel_us"
CELLS = ["granite-4.0-h-micro.tokens", "JoyAI-LLM-Flash.tokens"]
ENTRY = {"name": NAME, "unit": "us", "better": "lower",
         "source": "device_trace", "layer": "Pallas kernels",
         "moves": "train_throughput", "workloads": CELLS}
CALL = "%%%s = (bf16[2,32,8192,128], f32[2,32,1,8192]) custom-call(bf16[2," \
    "32,8192,192] %%maximum_bitcast_fusion), custom_call_target=" \
    "\"tpu_custom_call\""


@pytest.fixture(scope="module")
def reader():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ops():
    """(name, start, end) in ns: two layers' kernels of one step, and one
    fusion that reads the forward kernel's result."""
    named = [("_fa_kernel.6", 15_000), ("_fa_kernel.7", 15_000),
             ("_fa_dq_kernel.6", 21_000), ("_fa_dkv_kernel.6", 23_000),
             ("_fa_dq_kernel.7", 21_000), ("_fa_dkv_kernel.7", 23_000)]
    ops, at = [], 0
    for name, took in named:
        ops.append((CALL % name, at, at + took))
        at += took + 100
    ops.append(("%fusion.650 = bf16[2,8192,2048] fusion(bf16[2,32,8192,128] "
                "%_fa_kernel.6)", at, at + 5_000))
    return ops


def _run(ops, steps=2, chips=1):
    return {"trace": {"devices": {"/device:TPU:%d" % i: ops
                                  for i in range(chips)}},
            "traced_steps": steps}


@pytest.mark.parametrize("steps,chips", [(1, 1), (2, 1), (2, 4)])
def test_the_three_kernels_runs_a_step_and_chip(reader, steps, chips):
    per_step = 2 * (15_000 + 21_000 + 23_000) / steps / 1e3
    assert reader.read(_run(_ops(), steps, chips)) == pytest.approx(per_step)


@pytest.mark.parametrize("run", [
    {"trace": None},
    {"trace": {"devices": {}}, "traced_steps": 2},
    _run([("%fusion.1 = f32[8] fusion()", 0, 10),
          ("%_ssd_scan_fwd_kernel.3 = bf16[8] custom-call()", 20, 30)]),
    _run([("%fusion.650 = bf16[8] fusion(bf16[8] %_fa_kernel.6)", 0, 10)]),
], ids=["no trace", "no device", "a step without the kernels",
        "only a reader of a kernel's result"])
def test_says_nothing_where_no_flash_kernel_ran(reader, run):
    assert reader.read(run) is None


def test_the_entry_a_benchmark_pr_appends_fits_the_manifest():
    """``BENCHMARK.json`` does not list the metric yet: the accepted
    ``test_joyai_cell.py`` holds what follows the accepted metrics to PR 35's
    five, and that file is a ``benchmark`` PR's to edit (``PERF.md`` section
    7).  ENTRY is what such a PR appends; here it is held to the manifest as
    it stands, and to the manifest's own entry once it is there."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    if NAME in names:
        assert manifest["per_layer"][names.index(NAME)] == ENTRY
    assert set(ENTRY) == set(manifest["per_layer"][-1])
    assert ENTRY["layer"] in {m["layer"] for m in manifest["per_layer"]}
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == ENTRY["moves"])
    assert set(CELLS) <= set(moved.get("workloads") or [
        w["name"] for w in manifest["workloads"]])
    # no roofline share of it: operations and bytes are a benchmark PR's
    assert not [n for n in names if n.startswith("flash_attn")
                and "roofline" in n]


def test_the_metric_has_its_row_in_the_docs():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    assert "| `%s` |" % NAME in text
    assert "| `attention_layers`, `flash_attention_layers` |" in text
