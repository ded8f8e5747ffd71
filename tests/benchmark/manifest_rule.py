"""The rule the manifest's tests hold ``BENCHMARK.json`` to, and a scratch
benchmark to prove it on (no test itself; the test files load it by path).

The rule (``accepted_manifest.json``, the frozen table): *the accepted
entries keep their names, their fields and their relative order; what is
new comes after them.*  ``departures`` lists where a manifest leaves it.
A PR that appends a configuration, a cell or a metric edits nothing here:
its own test file proves its own entries.

``scratch_tree`` is the proof that the harness takes such an addition with
no edit: a copy of the benchmark in a directory of the test's, with a fourth
configuration, a fourth cell and one more per-layer metric appended, each
found by its name through files that are copies of Granite's under other
names.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
COPIED = "granite-4.0-h-micro"
ADDED_CONFIG = "granite-copy"
ADDED_CELL = ADDED_CONFIG + ".tokens"
ADDED_METRIC = "embed_device_ms"


def load_accepted():
    with open(os.path.join(HERE, "accepted_manifest.json")) as f:
        return json.load(f)


def load_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def departures(manifest, accepted):
    """Where ``manifest`` leaves the rule, one line each; [] where every
    list starts with its accepted entries, field for field, an accepted
    metric's ``workloads`` with its accepted cells."""
    out = []
    for key in LISTS:
        for i, old in enumerate(accepted[key]):
            where = "%s[%d] %s" % (key, i, old["name"])
            if i >= len(manifest[key]):
                out.append(where + ": gone")
                continue
            new = manifest[key][i]
            if new["name"] != old["name"]:
                out.append("%s: %s stands there" % (where, new["name"]))
                continue
            for field, value in old.items():
                held = new.get(field)
                if field == "workloads":
                    held = (held or [])[:len(value)]
                if held != value:
                    out.append("%s: %s is %r, accepted as %r"
                               % (where, field, new.get(field), value))
    return out


def scratch_tree(root):
    """A benchmark under ``root`` that is the repo's plus one configuration,
    one cell and one per-layer metric, appended; no file it had is edited.
    Returns its manifest."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "PERF.md"), root)
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    manifest = load_manifest()
    for folder, old, new in (("configs", COPIED, ADDED_CONFIG),
                             ("limits", COPIED + ".tokens", ADDED_CELL)):
        shutil.copy(os.path.join(bench, folder, old + ".json"),
                    os.path.join(bench, folder, new + ".json"))
    with open(os.path.join(bench, "layer_metrics",
                           ADDED_METRIC + ".py"), "w") as f:
        f.write('"""``%s`` — compiled step: the scope ``embed``."""\n'
                "import scope_reduce\n\n\n"
                "def read(run):\n"
                '    return scope_reduce.scope_ms(run, ("embed",))\n'
                % ADDED_METRIC)
    config = dict(next(c for c in manifest["configs"]
                       if c["name"] == COPIED),
                  name=ADDED_CONFIG,
                  file="benchmark/configs/%s.json" % ADDED_CONFIG)
    cell = dict(next(w for w in manifest["workloads"]
                     if w["name"] == COPIED + ".tokens"),
                name=ADDED_CELL, config=ADDED_CONFIG)
    manifest["configs"].append(config)
    manifest["workloads"].append(cell)
    for m in manifest["per_layer"]:
        if COPIED + ".tokens" in m["workloads"]:
            m["workloads"].append(ADDED_CELL)
    manifest["per_layer"].append({
        "name": ADDED_METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "compiled step",
        "moves": "train_throughput", "workloads": [ADDED_CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def forget(root):
    """Take a scratch tree off ``sys.path`` and out of ``sys.modules``: its
    ``run.py`` put its own directory first on the path."""
    sys.path[:] = [p for p in sys.path if not p.startswith(root)]
    for name, module in list(sys.modules.items()):
        if (getattr(module, "__file__", None) or "").startswith(root):
            del sys.modules[name]
