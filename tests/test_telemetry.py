"""mxnet_tpu.telemetry: unified fleet observability (tier-1, ISSUE 9).

Contract points:
(a) the metrics registry: instruments + weakly-held collectors, valid
    Prometheus text exposition, versioned JSON round-tripped through
    tools/parse_log.py (newer schema refused, not misparsed);
(b) the flight recorder: mmap ring ordering/truncation/CRC, the
    per-step progress cursor, and — the point of the thing — events
    surviving a SIGKILL, read back by the postmortem CLI;
(c) chrome-trace hygiene: dumps() schema (ph/ts/pid/tid), the bounded
    event buffer with a dropped-event count, Counter/Marker thread
    safety under concurrent emitters;
(d) trace correlation: a trace context round-trips over a REAL PS
    push/pull (worker span id == server-side flight record id), chaos
    faults stamp instant events + ring records at their probe sites,
    and tools/trace_merge.py aligns per-rank traces + rings into one
    timeline;
(e) the serving /metrics route returns parseable Prometheus text;
    DataParallelTrainer.fit dumps the versioned metrics JSON;
(f) the headline: a 2-worker + 1-server fleet with a chaos SIGKILL of
    the server mid-training yields a merged fleet chrome trace where
    the killed push's worker span links to the server-side fault event
    (same trace_id), and a postmortem recovered from the dead server's
    mmap ring showing its last applied (rank, push_step).
"""
import ast
import gc
import glob
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, kvstore_ps, profiler, telemetry
from mxnet_tpu.resilience import Fault, chaos
from mxnet_tpu.telemetry import flight, trace
from mxnet_tpu.telemetry.metrics import MetricsRegistry

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    yield
    telemetry.disable()
    chaos.uninstall()
    if profiler.state() == "run":
        profiler.set_state("stop")


def _cpu_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("MXTPU_CHAOS", None)
    env.pop("MXTPU_TELEMETRY_DIR", None)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in extra.items()})
    return env


# ---------------------------------------------------------------------------
# (a) metrics registry
# ---------------------------------------------------------------------------
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+(nan|inf)?$")


def _assert_prometheus_text(text):
    """Every non-comment, non-blank line must be a valid sample line."""
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), "bad exposition line: %r" % line


def test_registry_instruments_and_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests seen")
    c.inc(3, model="a", tier="gold")
    c.inc(model="b")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("t_depth")
    g.set(7)
    g.inc(2)
    h = reg.histogram("t_lat_ms", "latency")
    for i in range(200):
        h.observe(float(i))
    # re-registration is idempotent; a kind conflict is an error
    assert reg.counter("t_requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("t_requests_total")
    text = reg.prometheus_text()
    _assert_prometheus_text(text)
    assert '# TYPE t_requests_total counter' in text
    assert 't_requests_total{model="a",tier="gold"} 3' in text
    assert "t_depth 9" in text
    assert '# TYPE t_lat_ms summary' in text
    assert 't_lat_ms{quantile="0.5"}' in text
    assert "t_lat_ms_count 200" in text
    p50, p99 = h.quantiles()
    assert p50 == pytest.approx(99.0, abs=2)
    assert p99 == pytest.approx(197.0, abs=3)


def test_histogram_reservoir_bounds_window():
    reg = MetricsRegistry()
    h = reg.histogram("t_win", reservoir=64)
    for i in range(1000):
        h.observe(float(i))
    p50, _ = h.quantiles()
    # old samples aged out: the window covers [936, 999], not [0, 999]
    assert p50 > 900
    (_, cell), = h.samples()
    assert cell["count"] == 1000 and cell["sum"] == sum(range(1000))


def test_histogram_percentile_accuracy_after_wrap():
    """After the reservoir wraps, p50/p99 must track the NEWEST
    ``reservoir`` observations accurately — not a mixture with aged-out
    samples (ISSUE-10 satellite: the PR-9 hammer covered Counter, not
    Histogram)."""
    reg = MetricsRegistry()
    h = reg.histogram("t_acc", reservoir=256)
    # first era: uniform 0..999 — fully aged out by the second era
    for i in range(1000):
        h.observe(float(i))
    # second era: exactly 256 samples of a known uniform grid 0..255
    for i in range(256):
        h.observe(float(i))
    p50, p99 = h.quantiles()
    # nearest-rank over 0..255: p50 = 128, p99 = 252 (+-1 for rounding)
    assert abs(p50 - 127.5) <= 1.0
    assert abs(p99 - 252.45) <= 1.0
    (_, cell), = h.samples()
    assert cell["count"] == 1256                       # exact lifetime
    assert cell["sum"] == sum(range(1000)) + sum(range(256))
    assert cell["p50"] == p50 and cell["p99"] == p99
    # per-label-set reservoirs are independent
    h.observe(1e6, shard="other")
    assert h.quantiles() == (p50, p99)


def test_histogram_concurrent_observe_four_threads():
    """4 threads observing concurrently (the serving-handler pattern):
    no update lost, no exception, percentiles land inside the observed
    range — under a concurrent scrape loop too."""
    reg = MetricsRegistry()
    h = reg.histogram("t_conc", reservoir=512)
    n_per, errs = 5000, []

    def worker(tid):
        try:
            for i in range(n_per):
                h.observe(float(tid * n_per + i), thread=str(tid % 2))
        except Exception as e:   # pragma: no cover - the failure mode
            errs.append(e)

    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            reg.prometheus_text()
            reg.to_json()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(4)]
    s = threading.Thread(target=scraper)
    s.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    s.join(timeout=60)
    assert not errs
    total = {}
    for labels, cell in h.samples():
        total[labels["thread"]] = cell
        lo, hi = 0.0, 4.0 * n_per
        assert lo <= cell["p50"] <= hi
        assert lo <= cell["p99"] <= hi
        assert cell["p50"] <= cell["p99"]
    # exactly-once accounting across the 4 threads (2 per label set)
    assert total["0"]["count"] == total["1"]["count"] == 2 * n_per
    assert total["0"]["sum"] + total["1"]["sum"] == \
        sum(range(4 * n_per))


def test_collector_weakref_drops_dead_source():
    reg = MetricsRegistry()

    class Src:
        def samples(self):
            return [("t_coll_gauge", {"who": "x"}, 1.0)]

    src = Src()
    reg.register_collector(src.samples, name="src")
    assert "t_coll_gauge" in reg.prometheus_text()
    del src
    gc.collect()
    assert "t_coll_gauge" not in reg.prometheus_text()
    # dict-returning and raising collectors are both handled
    reg.register_collector(lambda: {"t_flat": 2})
    reg.register_collector(lambda: 1 / 0)
    text = reg.prometheus_text()
    assert "t_flat 2" in text


def test_metrics_json_roundtrip_and_parse_log(tmp_path):
    reg = MetricsRegistry()
    reg.counter("t_total").inc(5, rank="0")
    reg.histogram("t_ms").observe(4.0)
    path = str(tmp_path / "metrics.json")
    payload = reg.dump_json(path, source="test")
    assert payload["schema_version"] == telemetry.SCHEMA_VERSION
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "parse_log.py"),
         path], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert 't_total{rank="0"}\t5' in out.stdout
    assert "t_ms_p50\t4" in out.stdout
    # a NEWER schema version is refused, never misparsed
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import parse_log
        with pytest.raises(ValueError):
            parse_log.parse_metrics_json({"schema_version": 999,
                                          "metrics": {}})
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# (b) flight recorder
# ---------------------------------------------------------------------------
def test_flight_ring_order_wrap_truncation_cursor(tmp_path):
    path = str(tmp_path / "r.mxring")
    ring = flight.FlightRecorder(path, slots=8, slot_bytes=128,
                                 meta={"rank": 3, "role": "worker"})
    for i in range(20):            # wraps: only the last 8 survive
        ring.record("ev", i=i)
    ring.record("big", blob="x" * 500)   # oversized -> truncated marker
    ring.set_cursor(41)
    ring.close()
    meta, events = flight.read_ring(path)
    assert meta["rank"] == 3 and meta["role"] == "worker"
    assert meta["cursor_step"] == 41 and meta["cursor_ts_ns"] > 0
    assert [e["i"] for e in events[:-1]] == list(range(13, 20))
    assert events[-1]["kind"] == "big" and events[-1]["truncated"] == 1
    assert "blob" not in events[-1]
    assert all("ts_ns" in e and "wall_ns" in e for e in events[:-1])


def test_flight_ring_survives_sigkill(tmp_path):
    d = str(tmp_path)
    src = (
        "import os, signal\n"
        "from mxnet_tpu import telemetry\n"
        "telemetry.enable(%r, rank=5, role='worker')\n"
        "for i in range(30):\n"
        "    telemetry.record('ps.apply', rank=1, step=i, key='w0')\n"
        "telemetry.cursor(29)\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n" % d)
    proc = subprocess.run([sys.executable, "-c", src], env=_cpu_env(),
                          timeout=120)
    assert proc.returncode == -signal.SIGKILL
    report = telemetry.postmortem(d)
    (ring,) = report["rings"]
    assert ring["meta"]["rank"] == 5
    assert ring["meta"]["cursor_step"] == 29
    assert ring["last_apply"]["step"] == 29
    assert len(ring["events"]) > 0


def test_postmortem_cli(tmp_path):
    d = str(tmp_path)
    telemetry.enable(d, rank=0, role="server")
    telemetry.record("ps.apply", rank=2, step=7, key="w1")
    chaos.install([Fault("kvstore.snapshot", 1, "raise")])
    with pytest.raises(chaos.ChaosError):
        chaos.maybe_inject("kvstore.snapshot")
    telemetry.disable()
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "postmortem", d,
         "--json"], capture_output=True, text=True, timeout=120,
        env=_cpu_env(), cwd=_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout)
    (ring,) = report["rings"]
    assert ring["last_apply"]["rank"] == 2
    assert ring["last_apply"]["step"] == 7
    assert ring["faults"][0]["site"] == "kvstore.snapshot"
    # human rendering names the essentials too
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "postmortem", d],
        capture_output=True, text=True, timeout=120, env=_cpu_env(),
        cwd=_ROOT)
    assert "last applied push: rank=2 push_step=7" in out.stdout
    assert "FAULT kvstore.snapshot@1" in out.stdout
    # empty dir -> rc 1
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "postmortem",
         str(tmp_path / "nothing")], capture_output=True, text=True,
        timeout=120, env=_cpu_env(), cwd=_ROOT)
    assert out.returncode == 1


# ---------------------------------------------------------------------------
# (c) chrome-trace hygiene
# ---------------------------------------------------------------------------
def test_chrome_trace_schema_and_metadata():
    profiler.set_state("run")
    with profiler.Task("work"):
        time.sleep(0.001)
    domain = profiler.Domain("t")
    domain.new_counter("c", 1).increment()
    domain.new_marker("m").mark()
    profiler.record_instant("inst", "cat", args={"k": 1})
    profiler.set_metadata(rank=4)
    doc = json.loads(profiler.dumps())
    profiler.set_state("stop")
    events = doc["traceEvents"]
    assert events and doc["displayTimeUnit"] == "ms"
    for ev in events:
        assert ev["ph"] in ("X", "i", "C", "M")
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "X":
            assert "dur" in ev and "tid" in ev
        if ev["ph"] == "i":
            assert "tid" in ev and ev["s"] == "p"
    meta = doc["metadata"]
    assert meta["rank"] == 4
    assert meta["pid"] == os.getpid()
    assert meta["perf_origin_ns"] > 0
    assert meta["dropped_events"] == 0


def test_profiler_event_buffer_bounded(monkeypatch):
    monkeypatch.setattr(profiler, "_MAX_EVENTS", 10)
    profiler.set_state("run")
    for i in range(50):
        profiler.record_instant("e%d" % i, "cat")
    assert profiler.dropped_events() == 40
    doc = json.loads(profiler.dumps())
    profiler.set_state("stop")
    assert len(doc["traceEvents"]) == 10
    assert doc["metadata"]["dropped_events"] == 40
    assert doc["metadata"]["event_cap"] == 10


def test_counter_marker_thread_safety_under_dumps():
    profiler.set_state("run")
    domain = profiler.Domain("t")
    counter = domain.new_counter("n", 0)
    marker = domain.new_marker("m")
    stop = threading.Event()
    errors = []

    def emit():
        try:
            for _ in range(2000):
                counter.increment()
                marker.mark()
        except Exception as e:   # pragma: no cover - the failure mode
            errors.append(e)

    def drain():
        while not stop.is_set():
            json.loads(profiler.dumps(reset=True))

    drainer = threading.Thread(target=drain)
    drainer.start()
    threads = [threading.Thread(target=emit) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    drainer.join()
    profiler.set_state("stop")
    assert not errors
    # no lost increments: 4 threads x 2000 atomic +1s
    assert counter._value == 8000


# ---------------------------------------------------------------------------
# (d) trace correlation
# ---------------------------------------------------------------------------
def test_trace_wire_roundtrip():
    ctx = trace.SpanContext(rank=3, incarnation="abc")
    back = trace.from_wire(trace.to_wire(ctx))
    assert (back.trace_id, back.span_id, back.parent_id, back.rank,
            back.incarnation) == (ctx.trace_id, ctx.span_id, None, 3,
                                  "abc")
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    with pytest.raises(ValueError):
        trace.from_wire((99, "x"))


def test_trace_context_roundtrip_over_real_ps(tmp_path):
    telemetry.enable(str(tmp_path), rank=0, role="worker")
    profiler.set_state("run")
    srv = kvstore_ps.PSServer(port=0)
    cli = kvstore_ps.PSClient("127.0.0.1", srv.port, rank=0)
    try:
        assert cli.clock_offset_ns is not None   # sync_clock ran
        cli.init_array("k", np.zeros(8, np.float32))
        cli.push_array("k", np.ones(8, np.float32), step=1)
        cli.pull_array("k")
    finally:
        cli.close()
        srv.stop()
    doc = json.loads(profiler.dumps())
    profiler.set_state("stop")
    telemetry.disable()
    push_spans = [e for e in doc["traceEvents"] if e["name"] == "ps.push"
                  and "cmd" in e.get("args", {}) is not None]
    client_push = [e for e in push_spans if "rank" in e["args"]
                   and e["args"].get("incarnation")]
    assert client_push, "client push span missing"
    tid = client_push[0]["args"]["trace_id"]
    # the server's handling span carries the SAME trace id (in-process
    # server: both sides land in one trace buffer)
    server_side = [e for e in push_spans
                   if e["args"]["trace_id"] == tid and e is not
                   client_push[0]]
    assert server_side, "server-side span not linked to the client push"
    # ... and so does the flight-ring apply record
    (ring_file,) = glob.glob(str(tmp_path / "*.mxring"))
    _, events = flight.read_ring(ring_file)
    applies = [e for e in events if e["kind"] == "ps.apply"]
    assert applies and applies[-1]["trace_id"] == tid
    assert applies[-1]["rank"] == 0 and applies[-1]["step"] == 1
    # clock metadata landed for trace_merge
    assert "ps_clock_offset_ns" in doc["metadata"]


def test_chaos_fault_stamps_instant_event_and_ring(tmp_path):
    telemetry.enable(str(tmp_path), rank=1, role="worker")
    profiler.set_state("run")
    chaos.install([Fault("trainer.step", 3, "raise")])
    for step in (1, 2):
        chaos.maybe_inject("trainer.step", step)
    with pytest.raises(chaos.ChaosError):
        chaos.maybe_inject("trainer.step", 3, ctx="ctx-object")
    doc = json.loads(profiler.dumps())
    profiler.set_state("stop")
    instants = [e for e in doc["traceEvents"]
                if e["name"] == "chaos.trainer.step"]
    assert len(instants) == 1 and instants[0]["ph"] == "i"
    assert instants[0]["args"]["at"] == 3
    assert instants[0]["args"]["action"] == "raise"
    (ring_file,) = glob.glob(str(tmp_path / "*.mxring"))
    _, events = flight.read_ring(ring_file)
    faults = [e for e in events if e["kind"] == "chaos.fault"]
    assert faults and faults[0]["site"] == "trainer.step"
    assert telemetry.registry().counter(
        "mxtpu_chaos_faults_total").value(site="trainer.step",
                                          action="raise") >= 1
    telemetry.disable()


def test_trace_merge_aligns_ranks_and_rings(tmp_path):
    # two synthetic rank traces 1s apart in perf-origin, the worker
    # knowing its offset to the server's clock; one server ring event
    worker = {"traceEvents": [
        {"name": "ps.push", "cat": "ps", "ph": "X", "ts": 1000.0,
         "dur": 50.0, "pid": 1, "tid": 1, "args": {"trace_id": "t1"}}],
        "displayTimeUnit": "ms",
        "metadata": {"rank": 0, "perf_origin_ns": 5_000_000_000,
                     "ps_clock_offset_ns": 2_000_000_000}}
    server = {"traceEvents": [
        {"name": "apply", "cat": "ps", "ph": "X", "ts": 500.0,
         "dur": 10.0, "pid": 9, "tid": 2, "args": {}}],
        "displayTimeUnit": "ms",
        "metadata": {"rank": None, "role": "server",
                     "perf_origin_ns": 7_000_000_000}}
    wpath, spath = str(tmp_path / "w.json"), str(tmp_path / "s.json")
    json.dump(worker, open(wpath, "w"))
    json.dump(server, open(spath, "w"))
    ring = flight.FlightRecorder(str(tmp_path / "flight-server-1.mxring"),
                                 meta={"role": "server", "rank": None})
    ring.record("chaos.fault", site="kvstore.server_apply",
                trace_id="t1")
    ring.close()
    merged_path = str(tmp_path / "fleet.json")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "trace_merge.py"),
         "-o", merged_path, wpath, spath, "--rings", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.load(open(merged_path))
    by_name = {}
    for ev in doc["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    # worker event at abs 5e9 + 1e6 + 2e9 = 7.001e9; server at 7.0005e9:
    # after re-basing to the min the server apply precedes the push
    push, apply = by_name["ps.push"][0], by_name["apply"][0]
    assert apply["ts"] < push["ts"]
    assert push["ts"] - apply["ts"] == pytest.approx(500.0, abs=1.0)
    # distinct pids with process_name metadata, ring folded as instant
    assert push["pid"] != apply["pid"]
    assert "process_name" in by_name
    fault = by_name["chaos.fault"][0]
    assert fault["ph"] == "i" and fault["args"]["trace_id"] == "t1"
    merged_meta = doc["metadata"]["merged_from"]
    assert merged_meta["worker0"]["aligned"] is True
    assert doc["metadata"]["skipped_count"] == 0


def test_trace_merge_skips_torn_inputs_with_recorded_warning(tmp_path):
    """ISSUE-10 satellite regression test: a missing or torn per-rank
    trace/ring must be skipped with a recorded warning (surfaced in the
    merged metadata), not abort the whole merge — exactly the files a
    SIGKILLed rank leaves behind."""
    good = {"traceEvents": [
        {"name": "step", "cat": "t", "ph": "X", "ts": 10.0, "dur": 5.0,
         "pid": 1, "tid": 1}],
        "metadata": {"rank": 0, "perf_origin_ns": 1_000_000}}
    gpath = str(tmp_path / "good.json")
    json.dump(good, open(gpath, "w"))
    torn = str(tmp_path / "torn.json")
    with open(torn, "w") as f:
        f.write(json.dumps(good)[:40])          # mid-write crash
    wrong_shape = str(tmp_path / "list.json")
    json.dump([1, 2, 3], open(wrong_shape, "w"))
    missing = str(tmp_path / "never_written.json")
    # one good ring + one garbage ring
    ring = flight.FlightRecorder(str(tmp_path / "flight-worker0-1.mxring"),
                                 meta={"role": "worker", "rank": 0})
    ring.record("trainer.step", step=3)
    ring.close()
    bad_ring = str(tmp_path / "flight-worker1-2.mxring")
    with open(bad_ring, "wb") as f:
        f.write(b"NOTARING" + b"\x00" * 64)
    merged_path = str(tmp_path / "fleet.json")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "trace_merge.py"),
         "-o", merged_path, gpath, torn, wrong_shape, missing,
         "--rings", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "4 unreadable input(s) skipped" in out.stdout
    for name in ("torn.json", "list.json", "never_written.json"):
        assert name in out.stderr
    doc = json.load(open(merged_path))
    # the survivors merged: the good trace's event + the good ring's
    names = {e["name"] for e in doc["traceEvents"]}
    assert "step" in names and "trainer.step" in names
    # the skip count and per-file reasons are IN the merged output — a
    # partial merge can never pass as a complete one
    meta = doc["metadata"]
    assert meta["skipped_count"] == 4
    skipped_files = {s["file"] for s in meta["skipped"]}
    assert skipped_files == {"torn.json", "list.json",
                             "never_written.json",
                             os.path.basename(bad_ring)}
    assert all(s["error"] for s in meta["skipped"])
    # importable API agrees (tests call merge() directly)
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import trace_merge
        doc2 = trace_merge.merge([gpath, missing])
        assert doc2["metadata"]["skipped_count"] == 1
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# (e) serving /metrics + trainer fit dump
# ---------------------------------------------------------------------------
def _hybrid_runner(seed=0):
    from mxnet_tpu.serving import ModelRunner
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return ModelRunner(net, buckets=(1, 4), example_shape=(8,))


def test_serving_metrics_route_parses_as_prometheus():
    from mxnet_tpu.serving import ModelFleet, Server
    fleet = ModelFleet(batch_timeout_ms=1.0)
    fleet.register("m", _hybrid_runner())
    server = Server(fleet, port=0)
    host, port = server.start()
    try:
        fleet.infer(np.zeros(8, np.float32), model="m")
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        _assert_prometheus_text(body)
        assert 'mxtpu_serving_requests_total{model="m"} 1' in body
        assert 'mxtpu_serving_breaker_state{model="m"} 0' in body
        assert "mxtpu_serving_modeled_hbm_total_bytes" in body
        conn.close()
    finally:
        server.drain(timeout=10)


def test_trainer_fit_dumps_versioned_metrics_json(tmp_path):
    from mxnet_tpu.parallel import DataParallelTrainer
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05})
    x = np.random.rand(32, 10).astype(np.float32)
    y = np.random.randint(0, 4, 32).astype(np.int64)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    path = str(tmp_path / "metrics.json")
    trainer.fit(it, num_epoch=1, metrics_path=path)
    doc = json.load(open(path))
    assert doc["schema_version"] == telemetry.SCHEMA_VERSION
    assert doc["source"] == "trainer.fit"
    assert doc["step_count"] == 4
    assert doc["dispatch_stats"]["dispatched_steps"] == 4
    # the trainer's dispatch PipelineStats registered as a collector
    names = {s["labels"].get("name")
             for m in doc["metrics"].values() for s in m["samples"]}
    assert "engine.dispatch" in names
    # and the same document is parse_log-readable
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "parse_log.py"),
         path], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "mxtpu_pipeline" in out.stdout


# ---------------------------------------------------------------------------
# (f) the headline: 2 workers + 1 server, chaos SIGKILL of the server
# ---------------------------------------------------------------------------
_SERVER_SRC = (
    "from mxnet_tpu.kvstore_server import _init_kvstore_server_module\n"
    "_init_kvstore_server_module()\n")

_WORKER_SRC = """\
import os, pickle, sys
import numpy as np
from mxnet_tpu import kvstore_ps, profiler, telemetry
from mxnet_tpu import optimizer as opt
port, outdir, steps, rank = (int(sys.argv[1]), sys.argv[2],
                             int(sys.argv[3]), int(sys.argv[4]))
telemetry.maybe_enable_from_env()
profiler.set_state('run')
profiler.set_metadata(role='worker', rank=rank)
cli = kvstore_ps.PSClient('127.0.0.1', port, rank=rank,
                          connect_retry_s=120)
if rank == 0:
    cli.request('set_optimizer', pickle.dumps(
        opt.create('sgd', learning_rate=0.1, momentum=0.9)))
keys = ['w0', 'w1']
rng = np.random.RandomState(11 + rank)
for k in keys:
    cli.init_array(k, rng.rand(32).astype(np.float32))
step = 0
for s in range(steps):
    for k in keys:
        step += 1
        g = rng.rand(32).astype(np.float32) - 0.5
        cli.push_array(k, g, step=step)
        telemetry.cursor(step)
cli.pull_array('w0')
with open(os.path.join(outdir, 'trace-rank%d.json' % rank), 'w') as f:
    f.write(profiler.dumps())
print('DONE', step, flush=True)
cli.close()
"""


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_fleet_sigkill_server_trace_and_postmortem(tmp_path):
    """The ISSUE-9 acceptance test.  A 2-worker + 1-server fleet is run
    with telemetry armed; the chaos harness SIGKILLs the server at
    applied push #13; the server rank is respawned over the same state
    dir (what launch.py --restart-failed does) and both workers finish
    through the failover.  Then:

    (a) the merged fleet chrome trace (trace_merge over both worker
        traces + every flight ring) contains the server-side fault
        instant event, sharing its trace_id with the killed push's
        worker-side span — the worker→server link;
    (b) the postmortem recovered from the DEAD server's mmap ring shows
        its last applied (rank, push_step) and the fault.
    """
    tele_dir = str(tmp_path / "tele")
    os.makedirs(tele_dir)
    state = str(tmp_path / "state")
    port = _free_port()
    senv = _cpu_env(DMLC_ROLE="server", MXTPU_PS_PORT=port,
                    MXTPU_PS_STATE_DIR=state, MXTPU_PS_SNAPSHOT_EVERY=5,
                    MXTPU_HEARTBEAT_INTERVAL_S=0,
                    MXTPU_TELEMETRY_DIR=tele_dir,
                    MXTPU_CHAOS="kvstore.server_apply:13:kill")
    server = subprocess.Popen([sys.executable, "-c", _SERVER_SRC],
                              env=senv, stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
    workers = [subprocess.Popen(
        [sys.executable, "-c", _WORKER_SRC, str(port), tele_dir, "10",
         str(rank)],
        env=_cpu_env(MXTPU_PS_RETRIES=12, MXTPU_TELEMETRY_DIR=tele_dir,
                     DMLC_WORKER_ID=rank),   # what launch.py exports
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank in (0, 1)]
    try:
        # the chaos kill fires mid-run; respawn over the SAME state dir
        assert server.wait(timeout=300) == -signal.SIGKILL
        senv.pop("MXTPU_CHAOS")
        server = subprocess.Popen([sys.executable, "-c", _SERVER_SRC],
                                  env=senv, stdout=subprocess.DEVNULL,
                                  stderr=subprocess.DEVNULL)
        for rank, w in enumerate(workers):
            wout, werr = w.communicate(timeout=300)
            assert w.returncode == 0, werr[-2000:]
            assert "DONE 20" in wout
    finally:
        for w in workers:
            w.kill()
        server.terminate()
        try:
            server.wait(timeout=30)
        except subprocess.TimeoutExpired:
            server.kill()

    # -- (b) postmortem from the dead server's ring -----------------------
    rings = sorted(glob.glob(os.path.join(tele_dir, "flight-server*")))
    assert len(rings) == 2, "expected the dead and respawned server rings"
    dead = None
    for path in rings:
        _, events = flight.read_ring(path)
        if any(e["kind"] == "chaos.fault" for e in events):
            dead = (path, events)
    assert dead is not None, "no ring captured the chaos fault"
    dead_path, dead_events = dead
    (fault,) = [e for e in dead_events if e["kind"] == "chaos.fault"]
    assert fault["site"] == "kvstore.server_apply"
    killed_rank, killed_step, killed_key = ast.literal_eval(fault["ctx"])
    applies = [e for e in dead_events if e["kind"] == "ps.apply"]
    assert len(applies) == 12          # 13th was the killed one
    last = applies[-1]
    assert last["step"] is not None and last["rank"] in (0, 1)
    # the CLI tells the same story
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "postmortem",
         tele_dir], capture_output=True, text=True, timeout=120,
        env=_cpu_env(), cwd=_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "last applied push: rank=%s push_step=%s" \
        % (last["rank"], last["step"]) in out.stdout
    assert "FAULT kvstore.server_apply@13 action=kill" in out.stdout
    # worker rings carry the progress cursor
    wrings = glob.glob(os.path.join(tele_dir, "flight-worker*"))
    assert len(wrings) == 2
    for path in wrings:
        meta, _ = flight.read_ring(path)
        assert meta["cursor_step"] == 20

    # -- (a) merged fleet trace: worker span <-> server fault link --------
    traces = [os.path.join(tele_dir, "trace-rank%d.json" % r)
              for r in (0, 1)]
    merged_path = os.path.join(tele_dir, "fleet.json")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "trace_merge.py"),
         "-o", merged_path] + traces + ["--rings", tele_dir],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.load(open(merged_path))
    faults = [e for e in doc["traceEvents"]
              if e["name"] == "chaos.fault" and e["ph"] == "i"]
    assert faults, "fault instant event missing from the merged trace"
    fault_tid = faults[0]["args"]["trace_id"]
    # the killed push's span in the WORKER trace shares the trace id the
    # dead server recorded for the fault: worker -> server, linked
    killed_worker_spans = [
        e for e in doc["traceEvents"]
        if e["name"] == "ps.push" and e.get("args", {})
        .get("trace_id") == fault_tid and e["ph"] == "X"]
    assert killed_worker_spans, \
        "killed push's worker span not linked to the server fault"
    assert killed_worker_spans[0]["args"]["rank"] == killed_rank
    # every merged member is clock-aligned (workers synced against the
    # server; server rings are the base timebase)
    merged_from = doc["metadata"]["merged_from"]
    assert all(m.get("aligned") for m in merged_from.values()), merged_from
    # applies recovered from the dead ring appear on the fleet timeline
    assert any(e["name"] == "ps.apply" for e in doc["traceEvents"])
