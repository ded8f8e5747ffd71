"""Step spans, program scopes, the ``dispatch`` phase and the compile
counters (tier-1, ISSUE 26; docs/observability.md "Spans", "Program
scopes", "Compile counters").

Contract points:
(a) armed, ``DataParallelTrainer.step`` is a ``train.step`` span whose
    children are disjoint, lie inside it and carry the step's number;
    disarmed it builds no span, no annotation and no record (counted,
    not timed);
(b) a call that the runtime holds back lands in ``runahead_stall``, not
    in ``dispatch``, and the phases still reconcile with the wall;
(c) the lowered step carries the blocks' names, ``loss``,
    ``optimizer_update`` and ``transpose(jvp(``; the split-program tiers
    carry ``grad_reduce``;
(d) the compile counters rise by one program on a new shape and by none
    on a repeat, and the traces' seconds are a union;
(e) the span buffer is bounded, is written out by ``dump_metrics``, and a
    span still feeds ``mx.profiler`` when that runs.
"""
import json
import time
from collections import deque

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.parallel import DataParallelTrainer
from mxnet_tpu.telemetry import compiles, trace
from mxnet_tpu.telemetry.attribution import EnqueueSplit

CHILDREN = {"step.h2d", "step.prepare", "step.enqueue", "step.commit",
            "step.backpressure"}


@pytest.fixture(autouse=True)
def _isolation():
    trace.reset_spans()
    yield
    telemetry.disable()
    telemetry.reset_attribution()
    trace.reset_spans()


def _trainer(**kwargs):
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=10,
                                           thumbnail=True)
    net.initialize()
    net.hybridize()
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9}, **kwargs)
    x = np.random.RandomState(0).rand(8, 3, 32, 32).astype("float32")
    y = (np.arange(8) % 10).astype("float32")
    return trainer, x, y


@pytest.fixture(scope="module")
def warm():
    """One trainer whose step is compiled, shared by the tests that only
    drive it."""
    trainer, x, y = _trainer()
    trainer.step(x, y)
    trainer.flush()
    return trainer, x, y


# -- (a) spans ---------------------------------------------------------------
def test_step_spans_are_disjoint_children_of_their_step(warm):
    trainer, x, y = warm
    telemetry.enable()
    first = trainer._step_count + 1
    with mx.engine.bulk(2):             # the ring fills: backpressure too
        for _ in range(5):
            trainer.step(x, y)
    spans = trace.spans()
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[5] for s in steps] == list(range(first, first + 5))
    assert all(s[4] is None for s in steps)          # roots
    seen = set()
    for name, start, end, span_id, _, number in steps:
        kids = sorted((s for s in spans if s[4] == span_id),
                      key=lambda s: s[1])
        assert {k[0] for k in kids} <= CHILDREN
        assert {"step.h2d", "step.prepare", "step.enqueue",
                "step.commit"} <= {k[0] for k in kids}
        assert all(k[5] == number for k in kids)
        assert all(start <= k[1] <= k[2] <= end for k in kids)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        seen |= {k[0] for k in kids}
    assert "step.backpressure" in seen
    assert [s[0] for s in spans if s[4] is None and s[0] != "train.step"] \
        == ["train.flush"]
    assert trace.dropped_spans() == 0


def test_a_disarmed_step_builds_no_span_annotation_or_record(
        warm, monkeypatch):
    trainer, x, y = warm
    built = {"span": 0, "annotation": 0}
    real_init = trace.span.__init__

    def counting_init(self, *args, **kwargs):
        built["span"] += 1
        real_init(self, *args, **kwargs)

    class CountingAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, *args, **kwargs):
            built["annotation"] += 1
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(trace.span, "__init__", counting_init)
    monkeypatch.setattr(trace, "_ANNOTATION", CountingAnnotation)
    for _ in range(3):
        trainer.step(x, y)
    trainer.flush()
    assert built == {"span": 0, "annotation": 0}
    assert trace.spans() == []
    telemetry.enable()               # one annotation: telemetry.armed
    trainer.step(x, y)
    trainer.flush()
    assert built["span"] >= 5 and built["annotation"] == built["span"] + 1
    assert len(trace.spans()) == built["span"]


def test_a_span_without_telemetry_still_feeds_the_profiler():
    mx.profiler.set_state("run")
    try:
        with trace.span("ps.push", category="ps", step=7, cmd="push"):
            time.sleep(0.002)
    finally:
        mx.profiler.set_state("stop")
    events = [e for e in json.loads(mx.profiler.dumps(reset=True))[
        "traceEvents"] if e["name"] == "ps.push"]
    assert len(events) == 1 and events[0]["dur"] >= 2000
    assert events[0]["args"]["step"] == 7 and events[0]["args"]["cmd"] == \
        "push"
    assert trace.spans() == []           # disarmed: nothing buffered


def test_the_span_buffer_is_bounded(monkeypatch):
    telemetry.enable()
    monkeypatch.setattr(trace, "_SPANS", deque(maxlen=3))
    for i in range(5):
        with trace.span("step.h2d", step=i):
            pass
    assert [s[5] for s in trace.spans()] == [2, 3, 4]    # the newest
    assert trace.dropped_spans() == 2


def test_dump_metrics_writes_the_spans_and_the_compile_counters(tmp_path):
    telemetry.enable()
    with trace.span("train.step", step=3):
        with trace.span("step.h2d", step=3):
            pass
    path = str(tmp_path / "metrics.json")
    telemetry.dump_metrics(path, extra={"step_count": 3})
    with open(path) as f:
        doc = json.load(f)
    assert doc["step_count"] == 3
    assert doc["spans"]["fields"] == list(trace.SPAN_FIELDS)
    child, parent = doc["spans"]["events"]
    assert (child[0], parent[0]) == ("step.h2d", "train.step")
    assert child[4] == parent[3] and parent[4] is None
    assert doc["spans"]["dropped"] == 0
    assert set(doc["compiles"]) == {"trace_s", "lower_s", "backend_s",
                                    "in_span_programs", "blocked_bias_grads",
                                    "ssm_layers", "recomputed_layers",
                                    "ssm_chunks_per_seq",
                                    "kept_product_layers",
                                    "kept_product_bytes",
                                    "ssm_kernel_layers",
                                    "moe_grouped_rows", "attention_layers",
                                    "flash_attention_layers",
                                    "linear_attention_layers",
                                    "kda_chunks_per_seq",
                                    "kda_kernel_layers"}


def test_the_doctor_reads_the_spans_and_the_compile_counters(
        warm, tmp_path):
    """The dump's span buffer and compile counters have a reader: the
    doctor says which spans ``dispatch`` is made of, from the same
    timestamps the phase was fed from, and whether steps compiled."""
    trainer, x, y = warm
    telemetry.enable(directory=str(tmp_path), rank=0)
    for _ in range(3):
        trainer.step(x, y)
        trainer.flush()                  # nothing in flight: none held
    # a new shape: the step compiles
    trainer.step(np.concatenate([x, x]), np.concatenate([y, y]))
    trainer.flush()
    attr = telemetry.attribution()
    attr.flush_window()
    telemetry.dump_metrics(str(tmp_path / "metrics-worker0-1.json"),
                           extra={"attribution": attr.snapshot()})
    report = telemetry.doctor_report(str(tmp_path))
    rank = report["ranks"]["worker0"]
    assert rank["spans"]["steps"] == 4 and rank["spans"]["dropped"] == 0
    seconds = rank["spans"]["seconds"]
    assert sum(seconds[k] for k in ("step.prepare", "step.enqueue",
                                    "step.commit")) == pytest.approx(
        rank["phases_s"]["dispatch"], rel=1e-6)
    assert rank["compiles"]["in_span_programs"] >= 1
    text = telemetry.render_doctor(report)
    assert "spans, ms per step over the last 4 steps: " in text
    assert "program(s) compiled inside training steps" in text


# -- (b) the dispatch phase holds host work only -----------------------------
@pytest.mark.parametrize("seconds,in_flight,samples,expected", [
    # nothing in flight: nothing can hold the call back (a compile)
    (5.0, 0, [0.002] * 8, (5.0, 0.0)),
    # steps in flight and far slower than the samples: held back
    (0.060, 4, [0.002] * 8, (0.002, 0.058)),
    # so is one below a depth seen before: the limit is in programs
    (0.050, 2, [0.002] * 8, (0.002, 0.048)),
    # steps in flight, no slower than the samples: not held back
    (0.0041, 4, [0.002] * 8, (0.0041, 0.0)),
    # no sample yet: the host is billed everything, and it is no sample
    (0.060, 4, [], (0.060, 0.0)),
])
def test_enqueue_split(seconds, in_flight, samples, expected):
    split = EnqueueSplit()
    split._samples.extend(samples)
    own, held = split.split(seconds, in_flight)
    assert (own, held) == pytest.approx(expected)
    # only a call that cannot have been held back, or was not, is a
    # sample of the own cost
    sampled = in_flight == 0 or (bool(samples) and not expected[1])
    assert len(split._samples) == len(samples) + sampled


def test_enqueue_split_when_the_depth_swings_below_the_deepest():
    """After a flush the ring refills past the depth it then settles at;
    calls at that settled depth are held back a whole step and must
    neither be billed to the host nor move the own cost."""
    split = EnqueueSplit()
    own_s, step_s = 0.001, 0.090
    billed = []
    for in_flight in (0, 1, 2, 3, 4, 5):         # the refill: room
        billed.append(split.split(own_s, in_flight))
    for i in range(40):                          # settled one below
        in_flight = 4 if i % 2 else 3
        billed.append(split.split(own_s + step_s, in_flight))
    billed.append(split.split(1.5 * own_s, 4))   # a slow call, not held
    assert split.own_cost_s() == pytest.approx(own_s)
    assert sum(own for own, _ in billed) == pytest.approx(
        46 * own_s + 1.5 * own_s)
    assert sum(held for _, held in billed) == pytest.approx(40 * step_s)


@pytest.mark.parametrize("where", ["step.enqueue", "step.prepare"])
def test_a_held_back_call_lands_in_runahead_stall(warm, monkeypatch, where):
    """The runtime's own limit of programs in flight makes a call that
    enqueues a program wait for a whole step *inside* the call: in the
    jitted step, or in one of the small programs ``step.prepare``
    enqueues (where the TPU's runtime lands it, PERF.md PR 26)."""
    trainer, x, y = warm
    telemetry.enable()
    started = time.perf_counter()
    # every step is drained before the next, so that this backend's own
    # runtime never holds a call back: the only wait is the injected one
    for _ in range(5):                   # samples of the calls' own cost
        trainer.step(x, y)
        trainer.flush()
    sound = telemetry.attribution().snapshot()
    stall, held_steps = 0.2, 5
    monkeypatch.setattr(trainer, "_unfinished", lambda: 3)
    name = "_step_fn" if where == "step.enqueue" else "_step_args"
    real = getattr(trainer, name)

    def held_back(*args):
        time.sleep(stall)
        return real(*args)

    monkeypatch.setattr(trainer, name, held_back)
    for _ in range(held_steps):
        trainer.step(x, y)
        trainer.flush()
    trainer.step(x, y)                   # closes the last held window
    wall = time.perf_counter() - started
    snapshot = telemetry.attribution().snapshot()
    phases = snapshot["phases_s"]
    assert (sound["steps"], snapshot["steps"]) == (4, 5 + held_steps)
    assert phases["runahead_stall"] >= held_steps * stall * 0.95
    # host work per step reads the same with the wait as without it
    per_step = sound["phases_s"]["dispatch"] / sound["steps"]
    with_wait = (phases["dispatch"] - sound["phases_s"]["dispatch"]) \
        / (snapshot["steps"] - sound["steps"])
    assert with_wait < 1.5 * per_step + 0.1 * stall
    held = [s for s in trace.spans() if s[0] == where][-3:]
    assert all(s[2] - s[1] >= stall * 1e9 for s in held)
    # the phases still reconcile with the wall: nothing is billed twice
    assert sum(phases.values()) <= wall * 1.01
    assert snapshot["overshoot_s"] < 0.01 * wall


# -- (c) program scopes ------------------------------------------------------
def test_block_scope_names_are_relative_and_transparent():
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=10,
                                           thumbnail=True)
    assert net._scope_name == net.name
    stage = next(b for b in net.features if b.name.endswith("_stage4"))
    assert stage._scope_name == "stage4"
    block = stage[0]
    assert block._scope_name == ""       # empty prefix: no part of its own
    inner = block.body[0]
    assert inner.name == stage.name + "_" + inner._scope_name
    assert net.features._scope_name == ""


def test_the_lowered_step_carries_the_program_scopes(warm):
    trainer, x, y = warm
    text = trainer.lower_step(x, y).as_text(debug_info=True)
    net = trainer._block.name
    for part in ("jvp(%s)/stage1/conv2d0/conv_general_dilated" % net,
                 "transpose(jvp(%s))/stage4/" % net,
                 "transpose(jvp(loss))", "/optimizer_update/",
                 "/batchnorm0/", "/dense0/"):
        assert part in text, part


def test_the_split_program_tier_names_its_collective():
    trainer, x, y = _trainer(zero=1)
    telemetry.enable()
    trainer.step(x, y)
    trainer.step(x, y)
    trainer.flush()
    grads = trainer._zero_grad_fn.lower(
        *trainer._live_vals(), trainer._put_batch(x, trainer.batch_sharding),
        trainer._put_batch(y, trainer.batch_sharding),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "grad_reduce/reduce_scatter" in grads.replace(
        "psum_scatter", "reduce_scatter")
    # two programs a step, each its own step.enqueue; the update program
    # is billed to the collective phase
    spans = trace.spans()
    step = [s for s in spans if s[0] == "train.step"][-1]
    kids = [s[0] for s in spans if s[4] == step[3]]
    assert kids.count("step.enqueue") == 2
    phases = telemetry.attribution().snapshot()["phases_s"]
    assert phases["collective_or_ps"] > 0 and phases["dispatch"] > 0


# -- (d) compile counters ----------------------------------------------------
def test_compile_counters_count_programs_not_calls():
    @jax.jit
    def double(v):
        return v * 2

    def in_a_step(n):
        with trace.span("train.step", step=n):
            double(np.ones(n, np.float32)).block_until_ready()
        return compiles.counters()["in_span_programs"]

    start = compiles.counters()["in_span_programs"]
    assert in_a_step(5) == start + 1
    assert in_a_step(5) == start + 1                         # a repeat
    telemetry.enable()
    assert compiles.since_armed()["in_span_programs"] == 0
    assert in_a_step(6) == start + 2                         # a new shape
    double(np.ones(7, np.float32)).block_until_ready()       # outside one
    since = compiles.since_armed()
    assert since["in_span_programs"] == 1
    assert since["trace_s"] > 0 and since["lower_s"] > 0 \
        and since["backend_s"] > 0
    assert compiles.at_armed()["in_span_programs"] == start + 1


def test_compile_counters_take_nested_traces_once(monkeypatch):
    monkeypatch.setattr(compiles, "_open_traces", [])
    before = compiles.counters()["trace_s"]
    compiles._on_duration(compiles.TRACE_EVENT, 0.25, fun_name="inner")
    compiles._on_duration(compiles.TRACE_EVENT, 0.125, fun_name="inner")
    # the outer function's report ends last and spans both inner ones
    compiles._on_duration(compiles.TRACE_EVENT, 3600.0, fun_name="outer")
    assert compiles.counters()["trace_s"] - before == pytest.approx(3600.0)
