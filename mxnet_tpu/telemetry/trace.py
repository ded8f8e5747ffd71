"""Fleet-wide trace correlation: trace contexts, spans, wire format and
clock-offset estimation.

The reference profiler (``src/profiler/profiler.h:256``) and our
``profiler.py`` both stop at the process boundary: a PS push on rank 2
and the server-side apply it caused are two unrelated events in two
files.  This module makes them one story:

- a :class:`SpanContext` is ``(trace_id, span_id, parent_id, rank,
  incarnation)``; the current context rides a thread-local so nested
  spans chain parent→child;
- PS RPCs carry the context on the wire (``to_wire``/``from_wire`` — a
  plain tuple, pickle-friendly and version-tolerant), so the server's
  apply span and the flight-recorder record of a chaos fault both name
  the worker push that caused them;
- :func:`estimate_clock_offset` turns a few request round-trips into a
  ``server_clock - local_clock`` offset (midpoint method, best-of-N by
  RTT — the NTP discipline), which is what lets ``tools/trace_merge.py``
  align per-rank ``perf_counter`` timelines into one fleet timeline.

Timestamps everywhere in the telemetry layer are
``time.perf_counter_ns()`` — monotonic, the same clock ``profiler.py``
derives its trace ``ts`` from, so one offset aligns both surfaces.

While telemetry is armed (``telemetry.enable()``) a span has two more
readers besides ``mx.profiler`` (docs/observability.md "Spans"):

- it enters a ``jax.profiler.TraceAnnotation`` carrying ``step``,
  ``span_id`` and ``parent_id``, so it lands on the host plane of whatever
  ``jax.profiler`` trace is running — the file that holds the device's
  timeline — on that trace's clock.  A span without a parent also carries
  ``clock_ns``, its start on this module's clock, and arming emits one
  instant ``telemetry.armed`` with the same argument: either maps the
  buffer's clock onto the trace's;
- on exit it appends ``(name, start_ns, end_ns, span_id, parent_id,
  step)`` to one bounded in-memory buffer (:func:`spans`: the newest
  :data:`MAX_SPANS`; an older one it pushes out is counted in
  :func:`dropped_spans`).  ``telemetry.dump_metrics`` writes the buffer
  out, nothing on the hot path does, and the performance doctor reads it
  from there: which spans a step's host time is made of.

Disarmed, a span is what it always was: context propagation and, under a
running ``mx.profiler``, one chrome-trace event.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

__all__ = ["SpanContext", "new_trace_id", "current", "set_current",
           "span", "spans", "dropped_spans", "reset_spans", "MAX_SPANS",
           "arm", "disarm",
           "to_wire", "from_wire", "estimate_clock_offset"]

_tls = threading.local()

# set by telemetry.enable()/disable() (the attribution.set_ring pattern:
# this module cannot import its own package's flag)
_ARMED = False
# jax.profiler.TraceAnnotation, resolved when armed; None where this
# process has no jax (a postmortem host)
_ANNOTATION = None
_lock = threading.Lock()
MAX_SPANS = 65536       # some ten thousand training steps
_SPANS = deque(maxlen=MAX_SPANS)
_dropped = 0
SPAN_FIELDS = ("name", "start_ns", "end_ns", "span_id", "parent_id", "step")


def arm():
    """Called by ``telemetry.enable()``: spans start to feed the profiler
    trace and the buffer.  Emits the instant ``telemetry.armed`` whose
    ``clock_ns`` is this module's clock at that moment."""
    global _ARMED, _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            pass
    _ARMED = True
    if _ANNOTATION is not None:
        with _ANNOTATION("telemetry.armed",
                         clock_ns=time.perf_counter_ns()):
            pass


def disarm():
    global _ARMED
    _ARMED = False


def spans():
    """The buffered spans, oldest first: tuples of :data:`SPAN_FIELDS`
    on the ``time.perf_counter_ns()`` clock."""
    with _lock:
        return list(_SPANS)


def dropped_spans():
    """Spans no longer kept: pushed out of the full buffer by newer
    ones."""
    with _lock:
        return _dropped


def reset_spans():
    global _dropped
    with _lock:
        _SPANS.clear()
        _dropped = 0


def _keep(record):
    global _dropped
    with _lock:
        _dropped += len(_SPANS) == _SPANS.maxlen
        _SPANS.append(record)


def new_trace_id():
    """128-bit hex trace id (collision-safe across a fleet; uniqueness,
    not reproducibility, is the contract)."""
    return os.urandom(16).hex()


def _new_span_id():
    return os.urandom(8).hex()


class SpanContext:
    """One span's identity plus the process coordinates that make a
    fleet trace navigable (rank, client incarnation)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "rank", "incarnation")

    def __init__(self, trace_id=None, span_id=None, parent_id=None,
                 rank=None, incarnation=None):
        self.trace_id = trace_id or new_trace_id()
        self.span_id = span_id or _new_span_id()
        self.parent_id = parent_id
        self.rank = rank
        self.incarnation = incarnation

    def child(self):
        """A new span under this trace, parented here."""
        return SpanContext(trace_id=self.trace_id, parent_id=self.span_id,
                           rank=self.rank, incarnation=self.incarnation)

    def args(self):
        """The chrome-trace ``args`` payload linking events to spans."""
        out = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        if self.rank is not None:
            out["rank"] = self.rank
        if self.incarnation is not None:
            out["incarnation"] = self.incarnation
        return out

    def __repr__(self):
        return "SpanContext(%s/%s<-%s rank=%s)" % (
            self.trace_id[:8], self.span_id, self.parent_id, self.rank)


def current():
    """The thread's active SpanContext, or None."""
    return getattr(_tls, "ctx", None)


def set_current(ctx):
    """Install ``ctx`` as the thread's active context; returns the
    previous one (caller restores it — the server serve-loop pattern)."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


class span:
    """Scoped span: child of the current context (or a fresh trace root),
    installed as current for the duration.  On exit it is emitted as a
    profiler complete event (``ph: X``) carrying the trace args when
    ``mx.profiler`` runs, and, when telemetry is armed, kept in the span
    buffer; armed, it also sits in a ``jax.profiler.TraceAnnotation`` for
    its duration (module docstring).  The context propagation works
    either way.  ``step`` is the training step the span belongs to;
    ``seconds`` is its duration once it has exited."""

    def __init__(self, name, category="telemetry", rank=None,
                 incarnation=None, step=None, **extra_args):
        self.name = name
        self.category = category
        self.step = step
        self.seconds = 0.0
        self._extra = extra_args
        parent = current()
        self.ctx = parent.child() if parent is not None else SpanContext(
            rank=rank, incarnation=incarnation)
        if rank is not None:
            self.ctx.rank = rank
        if incarnation is not None:
            self.ctx.incarnation = incarnation
        self._prev = None
        self._t0_ns = None
        self._note = None

    def __enter__(self):
        self._prev = set_current(self.ctx)
        self._t0_ns = time.perf_counter_ns()
        if _ARMED and _ANNOTATION is not None:
            ctx = self.ctx
            args = {"span_id": ctx.span_id}
            if self.step is not None:
                args["step"] = self.step
            if ctx.parent_id is not None:
                args["parent_id"] = ctx.parent_id
            else:
                args["clock_ns"] = self._t0_ns
            self._note = _ANNOTATION(self.name, **args)
            self._note.__enter__()
        return self.ctx

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None
        set_current(self._prev)
        self.seconds = (end_ns - self._t0_ns) / 1e9
        if _ARMED:
            _keep((self.name, self._t0_ns, end_ns, self.ctx.span_id,
                   self.ctx.parent_id, self.step))
        from .. import profiler as _prof
        if _prof._PROFILING:
            args = self.ctx.args()
            args.update(self._extra)
            if self.step is not None:
                args["step"] = self.step
            origin = _prof._start_time or 0
            _prof.record_event(self.name, self.category,
                               (self._t0_ns - origin) / 1000.0,
                               (end_ns - self._t0_ns) / 1000.0, args=args)


# -- wire format -------------------------------------------------------------
_WIRE_VERSION = 1


def to_wire(ctx):
    """SpanContext -> tuple for an RPC payload.  Leading version lets a
    newer peer extend the tuple without breaking an older one."""
    return (_WIRE_VERSION, ctx.trace_id, ctx.span_id, ctx.parent_id,
            ctx.rank, ctx.incarnation)


def from_wire(wire):
    """Tuple -> SpanContext; tolerant of longer (newer) tuples."""
    if not wire or wire[0] != _WIRE_VERSION:
        raise ValueError("unknown trace-context wire version %r"
                         % (wire[:1],))
    _, trace_id, span_id, parent_id, rank, incarnation = wire[:6]
    return SpanContext(trace_id=trace_id, span_id=span_id,
                       parent_id=parent_id, rank=rank,
                       incarnation=incarnation)


# -- clock alignment ---------------------------------------------------------
def estimate_clock_offset(probe_fn, n=5):
    """Estimate ``remote_perf_ns - local_perf_ns``.

    ``probe_fn()`` must return the remote process's
    ``time.perf_counter_ns()`` (one RPC round trip).  For each probe the
    midpoint method assumes symmetric network delay: the remote stamp was
    taken near ``(t0 + t1) / 2`` locally.  The sample with the smallest
    RTT bounds the error tightest (classic NTP selection), so that
    sample's offset wins.  Returns ``(offset_ns, rtt_ns)``."""
    best = None
    for _ in range(max(1, int(n))):
        t0 = time.perf_counter_ns()
        remote = int(probe_fn())
        t1 = time.perf_counter_ns()
        rtt = t1 - t0
        offset = remote - (t0 + t1) // 2
        if best is None or rtt < best[1]:
            best = (offset, rtt)
    return best
