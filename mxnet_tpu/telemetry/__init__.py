"""mxnet_tpu.telemetry — one observability layer for the whole fleet.

Three pillars (docs/observability.md):

- **metrics** (:mod:`.metrics`): a process-wide registry (counters,
  gauges, bounded-reservoir histograms with p50/p99) that every existing
  stat surface registers into — ``profiler.PipelineStats``, serving
  per-model/per-tier stats and circuit-breaker states, heartbeat lag,
  PS WAL seq/replay counters.  Exported as Prometheus text via the
  serving ``/metrics`` route and as versioned JSON by
  ``DataParallelTrainer.fit`` / ``tools/launch.py``.
- **traces** (:mod:`.trace`): spans with ``(trace_id, span_id,
  parent_id, rank, incarnation)`` contexts that PS RPCs carry on the
  wire, so a server-side apply links to the worker push that caused it;
  ``tools/trace_merge.py`` aligns per-rank chrome traces into one fleet
  timeline using clock offsets estimated from request round trips.
- **flight recorder** (:mod:`.flight`): an mmap-backed bounded ring of
  recent structured events per process that survives SIGKILL;
  ``python -m mxnet_tpu.telemetry postmortem <dir>`` reconstructs the
  last-N-events-per-rank story of a dead fleet.

On top of the pillars sits the **performance doctor**
(:mod:`.attribution`): per-step wall-clock decomposition into named
phases, EWMA step-time/queue-growth anomaly flags, a server-side
straggler detector over heartbeat step clocks, and the
``python -m mxnet_tpu.telemetry doctor <dir>`` CLI that names each
rank's bottleneck phase with the knob that moves it.

Off by default.  The hot-path contract matches the profiler's: every
instrumented site guards on the module-global ``_ENABLED`` bool — one
attribute load + bool check when telemetry is off; what the armed path
costs per step is measured on the chip (PERF.md).  The one always-on part
is :mod:`.compiles`, whose listener fires only when something compiles.

Arming:

- ``telemetry.enable(directory, rank=..., role=...)`` in-process;
- ``MXTPU_TELEMETRY_DIR=<dir>`` (+ optional ``MXTPU_TELEMETRY=0`` to
  veto) via :func:`maybe_enable_from_env` — how launched subprocesses
  (the standalone PS server, workers under ``tools/launch.py``) arm
  themselves; rank/role are inferred from the ``DMLC_*`` handshake.
"""
from __future__ import annotations

import os

from . import flight as _flight
from . import trace
from . import compiles
from .flight import (FlightRecorder, postmortem, read_ring,
                     render_postmortem)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      SCHEMA_VERSION, flatten_samples, registry)
from . import attribution as attribution_mod
from .attribution import (PHASES, HINTS, StepAttribution, EnqueueSplit,
                          StragglerDetector, attribution,
                          reset_attribution, dominant_phase_or_none,
                          step_p50_or_none, doctor_report,
                          render_doctor)

__all__ = ["enable", "disable", "enabled", "maybe_enable_from_env",
           "compiles",
           "record", "cursor", "recorder", "telemetry_dir", "dump_metrics",
           "registry", "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "SCHEMA_VERSION", "flatten_samples",
           "FlightRecorder", "read_ring", "postmortem",
           "render_postmortem", "trace", "fault_event",
           "PHASES", "HINTS", "StepAttribution", "EnqueueSplit",
           "StragglerDetector",
           "attribution", "reset_attribution", "dominant_phase_or_none",
           "step_p50_or_none", "doctor_report", "render_doctor"]

# the one-bool-check hot-path flag (profiler._PROFILING discipline):
# instrumented sites read this module global and bail before touching
# anything else
_ENABLED = False
_RECORDER = None
_DIR = None
_RANK = None
_ROLE = None
_INSTALL_PID = None


def enabled():
    return _ENABLED


def telemetry_dir():
    """The armed output directory (rings + metrics dumps), or None."""
    return _DIR


def rank():
    return _RANK


def enable(directory=None, rank=None, role=None, slots=None,
           slot_bytes=None):
    """Arm telemetry for this process.  With ``directory`` set, a flight
    ring ``flight-<role><rank>-<pid>.mxring`` is opened there (and
    ``fit``'s metrics JSON lands there too); without it, only the
    in-memory pillars (trace contexts, metrics registry) arm.  Idempotent
    re-arming replaces the previous ring."""
    global _ENABLED, _RECORDER, _DIR, _RANK, _ROLE, _INSTALL_PID
    if rank is None:
        rank = os.environ.get("DMLC_WORKER_ID")
        rank = int(rank) if rank is not None else None
    if role is None:
        role = os.environ.get("DMLC_ROLE", "worker")
    old = _RECORDER
    _RANK, _ROLE = rank, role
    _DIR = str(directory) if directory else None
    _INSTALL_PID = os.getpid()
    if _DIR:
        name = "flight-%s%s-%d%s" % (role, "" if rank is None else rank,
                                     os.getpid(), _flight.RING_SUFFIX)
        _RECORDER = FlightRecorder(
            os.path.join(_DIR, name),
            slots=slots or int(os.environ.get("MXTPU_TELEMETRY_RING_SLOTS",
                                              _flight.DEFAULT_SLOTS)),
            slot_bytes=slot_bytes or int(os.environ.get(
                "MXTPU_TELEMETRY_SLOT_BYTES", _flight.DEFAULT_SLOT_BYTES)),
            meta={"rank": rank, "role": role})
    else:
        _RECORDER = None
    _ENABLED = True
    # the attribution layer's on_step fuses the progress-cursor store;
    # hand it the armed ring so the trainer hot path stays one call
    attribution_mod.set_ring(_RECORDER)
    trace.arm()
    compiles.mark_armed()
    if old is not None:
        old.close()
    return _RECORDER


def disable():
    """Disarm; the ring file (if any) is closed but left on disk — a
    postmortem over a cleanly-exited fleet still reads it."""
    global _ENABLED, _RECORDER
    _ENABLED = False
    rec, _RECORDER = _RECORDER, None
    attribution_mod.set_ring(None)
    trace.disarm()
    if rec is not None:
        rec.close()


def maybe_enable_from_env():
    """Arm from ``MXTPU_TELEMETRY_DIR`` (subprocess hook — the analogue
    of ``chaos.install_from_env``).  ``MXTPU_TELEMETRY=0`` vetoes.
    Returns the recorder or None; a process already armed by a parent's
    env is NOT re-armed (fork/spawn calls this freely)."""
    if os.environ.get("MXTPU_TELEMETRY", "1") == "0":
        return None
    d = os.environ.get("MXTPU_TELEMETRY_DIR")
    if not d:
        return None
    if _ENABLED and _INSTALL_PID == os.getpid() and _DIR == d:
        return _RECORDER
    return enable(d)


def recorder():
    return _RECORDER


def cursor(step):
    """The per-step hot path: store the training-progress cursor into
    the ring header (fixed-size struct store — no JSON, no slot; see
    ``FlightRecorder.set_cursor``).  No-op without an armed ring."""
    rec = _RECORDER
    if rec is not None:
        rec.set_cursor(step)


def record(kind, **fields):
    """Flight-record one structured event (no-op unless enabled with a
    directory).  The current trace context, if any, is attached — this
    is what links a ring event recovered from a dead process back to the
    worker-side span that caused it."""
    rec = _RECORDER
    if rec is None:
        return -1
    ctx = trace.current()
    if ctx is not None:
        fields.setdefault("trace_id", ctx.trace_id)
        fields.setdefault("span_id", ctx.span_id)
    if _RANK is not None:
        fields.setdefault("src_rank", _RANK)
    return rec.record(kind, **fields)


def fault_event(site, at, action, ctx=None):
    """Stamp a fired chaos fault: an instant event on the profiler
    timeline AND a flight-ring record (written *before* the fault's
    action runs, so even a ``kill`` leaves the evidence behind).  Called
    by ``chaos.maybe_inject`` — the single emission point the TEL001
    lint pins."""
    args = {"site": site, "at": at, "action": action}
    span_ctx = trace.current()
    if span_ctx is not None:
        args.update(span_ctx.args())
    if ctx is not None:
        args["ctx"] = repr(ctx)
    from .. import profiler as _prof
    _prof.record_instant("chaos.%s" % site, "chaos", args=args)
    record("chaos.fault", site=site, at=at, action=action,
           ctx=None if ctx is None else repr(ctx))
    reg = registry()
    reg.counter("mxtpu_chaos_faults_total",
                "chaos faults fired by site").inc(site=site, action=action)


def dump_metrics(path, source="mxnet_tpu", extra=None):
    """Write the registry's versioned JSON to ``path`` (see
    ``metrics.SCHEMA_VERSION`` / docs/observability.md), with the compile
    counters (``compiles``) and the buffered spans (``spans``: the field
    names, the events, how many were dropped) beside it: the end of a run
    is where the span buffer is written out, never the hot path.  The
    doctor reads both back (``attribution.doctor_report``)."""
    payload = {"compiles": compiles.counters(),
               "spans": {"fields": list(trace.SPAN_FIELDS),
                         "clock": "perf_counter_ns",
                         "dropped": trace.dropped_spans(),
                         "events": trace.spans()}}
    payload.update(extra or {})
    return registry().dump_json(path, source=source, extra=payload)


# always on: the listener fires only when something compiles
compiles.install()
