"""``benchmark/scope_reduce.py`` and the layer metrics that read it, on the
CPU (ISSUE 26).  Nothing here is a measurement.  What it proves: the rules
from an ``op_name`` to a phase; self time over nested events; the split,
the coverage and the programs per step on hand-made operations; the reader
of the ``XSpace`` wire format on a hand-made device plane and on a recorded
trace, whose host spans come back with their parents; and that every new
reader says nothing where there is nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
import struct
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NEW_METRICS = ("fwd_device_ms", "bwd_device_ms", "update_device_ms",
               "conv_device_ms", "scoped_device_share", "host_wait_ms",
               "programs_per_step", "window_compiles", "setup_trace_s",
               "setup_load_s")
STEP = "jit(pure_step)/"
FWD_CONV = STEP + "jvp(net)/stage1/conv2d0/conv_general_dilated"
BWD_CONV = STEP + "transpose(jvp(net))/stage1/conv2d0/conv_general_dilated"
BWD_NORM = STEP + "transpose(jvp(net))/stage1/batchnorm0/reduce_sum"
UPDATE = STEP + "optimizer_update/sub"
KERNEL = STEP + "optimizer_update/_fused_sgd_mom_kernel/pallas_call"
PLAIN = "jit(convert_element_type)/convert_element_type"


def _load(name, folder=""):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def sr():
    """``scope_reduce`` under the name the readers import it by, so that
    a test can put a hand-made trace in its place."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import scope_reduce
    return scope_reduce


def _ops():
    """One device's operations over two steps, in ns.  ``while.1`` holds
    two operations of its own (nested events)."""
    return [
        ("fusion.1", 0, 100, FWD_CONV),
        ("fusion.2", 100, 150, BWD_NORM),
        ("while.1", 150, 350, BWD_CONV),            # self time 200 - 120
        ("fusion.3", 160, 240, BWD_CONV),           # nested
        ("fusion.4", 250, 290, BWD_NORM),           # nested
        ("fusion.5", 350, 380, UPDATE),
        ("_fused_sgd_mom_kernel", 380, 390, KERNEL),
        ("copy.1", 390, 400, ""),                   # no metadata at all
        ("convert", 500, 510, PLAIN),               # 100 ns idle before it
    ]


# -- the rules --------------------------------------------------------------
@pytest.mark.parametrize("scope,parts,names,phase,conv", [
    (FWD_CONV, 5, ["net", "stage1", "conv2d0"], "forward", True),
    (BWD_CONV, 5, ["net", "stage1", "conv2d0"], "backward", True),
    (BWD_NORM, 5, ["net", "stage1", "batchnorm0"], "backward", False),
    (UPDATE, 3, ["optimizer_update"], "update", False),
    (KERNEL, 4, ["optimizer_update", "_fused_sgd_mom_kernel"], "update",
     False),
    (PLAIN, 2, [], "other", False),
    ("", 0, [], "other", False),
    # a program without named scopes (a commit before them): the
    # transforms are there, the names are not
    (STEP + "transpose(jvp())/conv_general_dilated", 3, [], "backward",
     True),
    # a / inside parentheses does not separate
    (STEP + "jvp(a/b)/jit(relu)/max", 4, ["a/b"], "forward", False),
    # a rematerialised forward pass runs in the backward pass
    (STEP + "transpose(jvp(net))/checkpoint/stage1/conv2d0/"
     "conv_general_dilated", 6, ["net", "checkpoint", "stage1", "conv2d0"],
     "backward", True),
])
def test_from_op_name_to_phase(sr, scope, parts, names, phase, conv):
    assert len(sr.path_parts(scope)) == parts
    assert sr.named_scopes(scope) == names
    assert sr.phase_of(scope) == phase
    assert sr.is_convolution(scope) is conv


def test_scope_of_reads_the_tpu_spelling(sr):
    assert sr.scope_of({"tf_op": FWD_CONV + ":"}) == FWD_CONV
    assert sr.scope_of({"hlo_category": "convolution"}) == ""


# -- the reductions ---------------------------------------------------------
def test_self_times_add_up_to_the_union(sr):
    tr = _load("trace_reduce")
    ops = _ops()
    selves = dict((op[0], ns) for op, ns in sr.self_times(ops))
    assert selves["while.1"] == 200 - 80 - 40
    assert selves["fusion.3"] == 80 and selves["fusion.4"] == 40
    assert sum(selves.values()) == tr.busy_ns(
        [(name, start, end) for name, start, end, _ in ops]) == 410


def test_device_time_by_phase_coverage_and_convolutions(sr):
    total = sr.device_time(_ops())
    assert total == {"forward": 100, "backward": 50 + 80 + 80 + 40,
                     "update": 30 + 10, "other": 10 + 10,
                     "convolution": 100 + 80 + 80, "scoped": 390,
                     "busy": 410}
    assert sum(total[p] for p in sr.PHASES) == total["busy"]
    table = sr.by_prefix(_ops(), 2)
    assert table[("net/stage1", "backward")] == 250
    assert table[("(unscoped)", "other")] == 20
    assert table[("optimizer_update/_fused_sgd_mom_kernel", "update")] == 10


def test_gaps_are_named_by_the_innermost_span(sr):
    spans = [("train.step", 300, 520, "a", None, 7),
             ("step.prepare", 380, 505, "b", "a", 7),
             ("step.enqueue", 505, 515, "c", "a", 7)]
    assert sr.gaps(_ops(), spans, least_ns=50) == [(400, 100,
                                                    "step.prepare")]
    assert sr.gaps(_ops(), [], least_ns=50) == [(400, 100, None)]
    assert sr.gaps(_ops(), spans, least_ns=101) == []


def test_programs_per_step(sr):
    modules = [("jit_convert_element_type(1)", 0, 1)] * 12 \
        + [("jit_pure_step(2)", 1, 2)] * 2
    reduced = {"devices": {"/device:TPU:0": _ops()},
               "modules": {"/device:TPU:0": modules}, "spans": []}
    assert sr.programs_per_step(reduced, 2) == 7.0
    assert sr.programs_per_step(reduced, 0) is None
    assert sr.programs_per_step({"devices": {}, "modules": {}}, 2) is None


# -- the wire format --------------------------------------------------------
def _varint(value):
    value &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, float):
        return _varint(number << 3 | 1) + struct.pack("<d", payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, metadata, lines):
    """An ``XPlane``: ``metadata`` is {id: (name, {stat id: value})},
    ``lines`` {name: (timestamp_ns, [(metadata id, offset_ps, duration_ps,
    {stat id: value})])}."""
    def stat(key, value):
        return _field(1, key) + _field(
            {int: 4, float: 2, str: 5}[type(value)], value)

    body = _field(2, name)
    for key, text in stat_names.items():
        body += _field(5, _field(1, key) + _field(
            2, _field(1, key) + _field(2, text)))
    for key, (text, stats) in metadata.items():
        entry = _field(1, key) + _field(2, text)
        for k, v in stats.items():
            entry += _field(5, stat(k, v))
        body += _field(4, _field(1, key) + _field(2, entry))
    for text, (origin, events) in lines.items():
        line = _field(2, text) + _field(3, origin)
        for meta, offset, duration, stats in events:
            event = _field(1, meta) + _field(2, offset) + _field(3, duration)
            for k, v in stats.items():
                event += _field(4, stat(k, v))
            line += _field(4, event)
        body += _field(3, line)
    return _field(1, body)


def test_load_reads_scopes_modules_and_spans_from_one_file(sr, tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "step", 4: "span_id",
             5: "parent_id", 6: "clock_ns"}
    device = _plane("/device:TPU:0", stats, {
        1: ("%fusion.9 = bf16[8] fusion(...)", {1: FWD_CONV + ":", 2: 64}),
        2: ("%copy.1 = bf16[8] copy(...)", {2: 0}),
        3: ("jit_pure_step(77)", {}),
    }, {
        "XLA Ops": (1000, [(2, 150_000, 10_000, {}),
                           (1, 0, 100_000, {})]),
        "XLA Modules": (1000, [(3, 0, 160_000, {})]),
        "Steps": (1000, [(3, 0, 160_000, {})]),
    })
    host = _plane("/host:CPU", stats, {
        1: ("train.step", {}), 2: ("step.prepare", {}),
        3: ("PjitFunction(pure_step)", {}),
    }, {
        "python3": (2000, [(1, 0, 90_000, {3: 12, 4: "aa", 6: 123456789}),
                           (2, 10_000, 50_000, {3: 12, 4: "bb", 5: "aa"}),
                           (3, 20_000, 30_000, {})]),
    })
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host + _plane("/host:metadata", {}, {}, {}))
    reduced = sr.load(str(tmp_path))
    assert reduced["devices"] == {"/device:TPU:0": [
        ("%fusion.9 = bf16[8] fusion(...)", 1000.0, 1100.0, FWD_CONV),
        ("%copy.1 = bf16[8] copy(...)", 1150.0, 1160.0, "")]}
    assert reduced["modules"] == {"/device:TPU:0": [
        ("jit_pure_step(77)", 1000.0, 1160.0)]}
    assert reduced["spans"] == [
        ("train.step", 2000.0, 2090.0, "aa", None, 12),
        ("step.prepare", 2010.0, 2060.0, "bb", "aa", 12)]
    assert sr.load(str(path)) is reduced          # parsed once
    times = sr.mean_device_time(reduced)
    assert (times["forward"], times["other"], times["scoped"]) == (
        100.0, 10.0, 100.0)


def test_profile_data_hides_the_metadata_statistics(sr, tmp_path):
    """Why ``scope_reduce`` reads the wire format itself: ``ProfileData``
    hands out an event's own statistics and not those of its metadata,
    where the scope lives.  When this fails JAX exposes them, and the
    reader can go."""
    from jax.profiler import ProfileData
    stats = {1: sr.SCOPE_STAT, 2: "flops"}
    device = _plane("/device:TPU:0", stats, {
        1: ("%fusion.9 = bf16[8] fusion(...)", {1: FWD_CONV + ":"}),
    }, {"XLA Ops": (1000, [(1, 0, 100_000, {2: 7})])})
    (plane,) = ProfileData.from_serialized_xspace(device).planes
    (event,) = [e for line in plane.lines for e in line.events]
    assert list(event.stats) == [("flops", 7)]
    (tmp_path / "t.xplane.pb").write_bytes(device)
    (op,) = sr.load(str(tmp_path))["devices"]["/device:TPU:0"]
    assert op[3] == FWD_CONV


@pytest.mark.parametrize("ids", [
    None,                   # as the program draws them: sixteen hex digits
    # once in some thousand ids all sixteen are decimal, and the profile
    # hands such an id back as an integer, without the zeros it began with
    ["1234567890123456", "0000000000000042", "0123456789012345",
     "9999999999999999", "00000000000000a1", "0000000000000001"],
])
def test_a_recorded_trace_yields_the_programs_spans_with_parents(
        sr, tmp_path, monkeypatch, ids):
    """The spans the program opens land in the same ``.xplane.pb`` as the
    device's operations would, with their ids, as text: no second file, no
    second clock."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import trace
    if ids:
        assert len(trace._new_span_id()) == sr.SPAN_ID_WIDTH
        monkeypatch.setattr(trace, "_new_span_id", iter(ids).__next__)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    mx.telemetry.enable()
    trace.reset_spans()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for number in (41, 42):
            with trace.span("train.step", step=number):
                with trace.span("step.prepare", step=number):
                    pass
                with trace.span("step.enqueue", step=number):
                    jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
        mx.telemetry.disable()
    buffered = trace.spans()
    trace.reset_spans()
    reduced = sr.load(str(tmp_path))
    assert reduced["devices"] == {}        # a CPU has no device plane
    spans = reduced["spans"]
    assert [s[0] for s in spans] == ["train.step", "step.prepare",
                                     "step.enqueue"] * 2
    assert [s[5] for s in spans] == [41] * 3 + [42] * 3
    for parent, first, second in (spans[:3], spans[3:]):
        assert parent[4] is None
        assert first[4] == second[4] == parent[3]
        assert parent[1] <= first[1] <= first[2] <= second[1] \
            <= second[2] <= parent[2]
    # the trace's spans are the buffer's spans: same ids, same lengths to
    # within the annotation's own cost
    by_id = {s[3]: s for s in buffered}
    assert set(by_id) == {s[3] for s in spans} and (
        not ids or set(by_id) == set(ids))
    for s in spans:
        assert abs((s[2] - s[1]) - (by_id[s[3]][2] - by_id[s[3]][1])) < 2e5


# -- the readers ------------------------------------------------------------
def test_the_host_control_keeps_at_most_two_steps_in_flight():
    """``host_control.stepped``: the wait for step ``n - 1`` comes after
    ``step`` has returned for step ``n`` and before step ``n + 1``, and is
    no part of the host's time."""
    control = _load("host_control")
    log = []

    class Loss:
        def __init__(self, n):
            self.n = n

        def block_until_ready(self):
            log.append(("wait", self.n))

    class Program:
        steps = 0

        def step(self, x, y):
            self.steps += 1
            log.append(("step", self.steps))
            return Loss(self.steps)

        def flush(self):
            log.append(("flush",))

    host, wait = control.stepped(Program(), [(0, 0)], 3)
    assert log == [("flush",), ("step", 1), ("step", 2), ("wait", 1),
                   ("step", 3), ("wait", 2), ("flush",)]
    assert host >= 0 and wait >= 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_say_nothing_on_the_empty_run(name):
    run = {"trace": None, "traced_steps": 0, "attribution": None,
           "memory_peak_bytes": 0, "peaks": None, "family": None,
           "window": {"steps": 0, "items": 0, "seconds": 0.0, "chips": 1},
           "config": {}, "size": {}}
    assert _load(name, "layer_metrics").read(run) is None
    # a rehearsal on the CPU: a trace, but no device plane in it
    run.update(trace={"devices": {}, "steps": [("bench_step", 0, 1)]},
               traced_steps=40,
               attribution={"steps": 9, "phases_s": {"runahead_stall": 1.0,
                                                     "dispatch": 1.0}})
    assert _load(name, "layer_metrics").read(run) is None


def test_new_readers_on_a_hand_made_run(sr, monkeypatch):
    import mxnet_tpu as mx
    modules = [("m", 0, 1)] * 14
    reduced = {"devices": {"/device:TPU:0": _ops()},
               "modules": {"/device:TPU:0": modules}, "spans": []}
    monkeypatch.setattr(sr, "of_run", lambda run: reduced)
    mx.telemetry.enable()
    try:
        run = {"trace": {"devices": {"/device:TPU:0": [("fusion.1", 0, 1)]},
                         "steps": []},
               "traced_steps": 2,
               "attribution": {"steps": 4, "phases_s": {
                   "dispatch": 0.02, "runahead_stall": 0.2}}}
        read = {name: _load(name, "layer_metrics").read(run)
                for name in NEW_METRICS}
    finally:
        mx.telemetry.disable()
    assert read["fwd_device_ms"] == pytest.approx(100 / 2 / 1e6)
    assert read["bwd_device_ms"] == pytest.approx(250 / 2 / 1e6)
    assert read["update_device_ms"] == pytest.approx(40 / 2 / 1e6)
    assert read["conv_device_ms"] == pytest.approx(260 / 2 / 1e6)
    assert read["scoped_device_share"] == pytest.approx(100 * 390 / 410)
    assert read["programs_per_step"] == 7.0
    assert read["host_wait_ms"] == pytest.approx(50.0)
    assert read["window_compiles"] == 0.0
    assert read["setup_trace_s"] >= 0.0 and read["setup_load_s"] >= 0.0
    # a program without the scopes (the parent of this change): the
    # readers of what it lacks say nothing, the others still read
    bare = [(n, s, e, "" if "optimizer_update" in scope else
             scope.replace("jvp(net)/stage1/conv2d0", "jvp()")
             .replace("jvp(net))/stage1/conv2d0", "jvp())")
             .replace("jvp(net))/stage1/batchnorm0", "jvp())"))
            for n, s, e, scope in _ops()]
    reduced["devices"]["/device:TPU:0"] = bare
    assert _load("update_device_ms", "layer_metrics").read(run) is None
    assert _load("scoped_device_share", "layer_metrics").read(run) is None
    assert _load("bwd_device_ms", "layer_metrics").read(run) == \
        pytest.approx(250 / 2 / 1e6)


def test_manifest_lists_the_new_metrics_last():
    """The ten metrics of ISSUE 26 follow the first six, in the order they
    were accepted in, and the manifest still starts with all sixteen; what
    later PRs append comes after them (``manifest_rule.py``)."""
    rule = _load("manifest_rule", os.path.dirname(os.path.abspath(__file__)))
    first = ["dispatch_ms", "step_mfu", "step_device_ms", "fused_update_us",
             "device_idle_share", "peak_hbm_gib"]
    for manifest in (rule.load_manifest(), rule.load_accepted()):
        names = [m["name"] for m in manifest["per_layer"]]
        assert names[:len(first) + len(NEW_METRICS)] == first + list(
            NEW_METRICS)
