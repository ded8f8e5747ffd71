"""``compute_cast_device_ms`` (ISSUE 40), on the CPU: the reader finds the
casts at the forward boundary by the program's scope ``compute_cast``
(``precision._to_compute``), forward and backward; what it reads leaves
``unscoped_device_ms`` by as much; it says nothing where no operation has
the scope (a parent before it, a float32 step); and the manifest holds its
entry.  Nothing here is a measurement.
"""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = "compute_cast_device_ms"
CELLS = ["granite-4.0-h-micro.tokens", "JoyAI-LLM-Flash.tokens",
         "Ling-3.0-flash.tokens"]
ENTRY = {"name": NAME, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "compiled step",
         "moves": "train_throughput", "workloads": CELLS}
STEP = "jit(replica_step)/"


def _load(folder, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, folder, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ops(cast_scopes=("jvp(compute_cast)", "transpose(jvp(compute_cast))")):
    """(name, start, end, op_name) over two steps: a layer's product, the
    masters' cast and its transpose, the head's loss mean, a copy XLA
    made."""
    rows = [("%fusion.1 = bf16[8] fusion()", 700,
             STEP + "jvp(l0)/l0/checkpoint/gated_mlp/dot_general"),
            ("%fusion.2 = bf16[8] fusion()", 300,
             STEP + cast_scopes[0] + "/convert_element_type"),
            ("%fusion.3 = f32[8] fusion()", 100,
             STEP + cast_scopes[1] + "/convert_element_type"),
            ("%fusion.4 = f32[] fusion()", 20,
             STEP + "jvp(lm_head_loss)/div"),
            ("%copy.5 = f32[8] copy()", 80, "")]
    ops, at = [], 0
    for name, ns, scope in rows:
        ops.append((name, at, at + ns, scope))
        at += ns
    return ops


def _run(monkeypatch, ops, steps=2, chips=1):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import scope_reduce
    planes = ["/device:TPU:%d" % i for i in range(chips)]
    reduced = {"devices": {p: ops for p in planes},
               "modules": {p: [] for p in planes}, "spans": []}
    monkeypatch.setattr(scope_reduce, "of_run", lambda run: reduced)
    return {"trace": {"devices": {p: [op[:3] for op in ops] for p in planes},
                      "steps": []},
            "traced_steps": steps}


@pytest.fixture(scope="module")
def reader():
    return _load("layer_metrics", NAME)


@pytest.mark.parametrize("steps,chips", [(1, 1), (2, 1), (2, 4)])
def test_the_casts_forward_and_backward_a_step_and_chip(reader, monkeypatch,
                                                        steps, chips):
    run = _run(monkeypatch, _ops(), steps, chips)
    assert reader.read(run) == pytest.approx((300 + 100) / steps / 1e6)


def test_what_it_reads_leaves_the_unscoped_time(reader, monkeypatch):
    """The parent's casts sat under no scope (``jvp()``): there they are
    ``unscoped_device_ms`` and this reader says nothing; scoped, they move
    from the one to the other, and the loss mean is the head's."""
    before = _run(monkeypatch, _ops(("jvp()", "transpose(jvp())")))
    unscoped = _load("layer_metrics", "unscoped_device_ms")
    head = _load("layer_metrics", "head_loss_device_ms")
    assert reader.read(before) is None
    was = unscoped.read(before)
    assert was == pytest.approx((300 + 100 + 80) / 2 / 1e6)
    after = _run(monkeypatch, _ops())
    assert unscoped.read(after) == pytest.approx(80 / 2 / 1e6)
    assert was - unscoped.read(after) == pytest.approx(reader.read(after))
    assert head.read(after) == pytest.approx(20 / 2 / 1e6)


@pytest.mark.parametrize("ops", [None, []], ids=["no trace", "no device"])
def test_says_nothing_without_a_trace(reader, monkeypatch, ops):
    if ops is None:
        assert reader.read({"trace": None}) is None
        return
    assert reader.read(_run(monkeypatch, ops, chips=0)) is None


def test_the_manifest_holds_the_entry(manifest):
    """By name, wherever it stands; its ``workloads`` start with the three
    language-model cells (the ResNets' step casts nothing at its forward
    boundary: their compute dtype is float32); it comes after the metrics
    it takes time from."""
    held = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert dict(held, workloads=held["workloads"][:3]) == ENTRY
    names = [m["name"] for m in manifest["per_layer"]]
    for before in ("unscoped_device_ms", "head_loss_device_ms"):
        assert names.index(NAME) > names.index(before)
        m = next(m for m in manifest["per_layer"] if m["name"] == before)
        assert set(CELLS) <= set(m["workloads"])
        assert m["moves"] == ENTRY["moves"]
