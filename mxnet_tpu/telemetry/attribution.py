"""Per-step time attribution, straggler detection and rolling-baseline
anomaly flags — the "performance doctor" (docs/observability.md).

PR 9 built the telemetry substrate (registry, traces, flight rings) but
nothing *interpreted* it: a slow step could be input wait, an H2D
transfer, dispatch overhead, backpressure against the device, the PS
round, metric drains or a checkpoint — and no component could say which.
TensorFlow (arxiv 1605.08695) and MXNet (arxiv 1512.01274) both treat
per-phase time attribution as the tool that makes distributed
performance debuggable; this module is that tool for this stack:

- :class:`StepAttribution` decomposes every training step's wall clock
  into the named :data:`PHASES` — instrumented sites
  (``DataParallelTrainer.step``/``fit``, the engine backpressure path,
  the kvstore push/pull, ``save_checkpoint``) call
  ``add_phase(name, seconds)`` between two ``on_step`` marks, each
  guarded by the telemetry ``_ENABLED`` bool so the disabled cost stays
  one check.  Window *k* is the wall interval between the step-*k* and
  step-*k+1* dispatch marks; its phase sums never exceed its wall by
  construction (all phases are disjoint host intervals on the training
  thread), so ``wall == sum(phases) + unattributed`` reconciles exactly
  up to timer overhead (tracked as ``overshoot_s``).
- phase durations land in the metrics registry two ways: cheap per-step
  accumulators exported by a collector (totals, true per-step
  p50/p99 over a bounded window) and per-phase registry *histograms*
  observed once per flight window (per-step means) — the hot path never
  touches a registry instrument.
- every ``ring_every`` steps the aggregated window is flight-recorded
  (``perf.phases``) so attribution survives a SIGKILL: a dead rank's
  ring still says where its time went.
- a rolling EWMA baseline flags step-time regressions (``perf.anomaly``)
  and queue growth (``perf.queue_growth``) as flight-ring events *while
  the run is still alive* — a run dying slow leaves the same evidence a
  run dying fast does.
- :class:`StragglerDetector` (server-side, fed by the heartbeat RPCs'
  step clocks stamped onto the server timebase via the PR-9 clock-offset
  estimation) computes per-rank step-time p50s and emits a
  ``perf.straggler`` event (rank, lag, dominant phase) when one rank's
  p50 exceeds the fleet median by a configurable factor.
- :func:`doctor_report` / the ``python -m mxnet_tpu.telemetry doctor``
  CLI read the merged metrics dumps + flight rings of a (possibly dead)
  fleet and name each rank's bottleneck phase with an actionable hint
  (:data:`HINTS` — phase -> existing knob), plus the fleet straggler
  verdict.

Stdlib-only (no jax/numpy): the doctor must run on a postmortem host,
and the accumulators must be importable from pipeline workers and the
PS server alike.  Phase names are pinned three ways — :data:`PHASES`,
:data:`HINTS` and the ``docs/observability.md`` phase table — by the
TEL002 lint (``--self-check``).
"""
from __future__ import annotations

import glob as _glob
import json as _json
import os
import re as _re
import threading
import time
from collections import deque

__all__ = ["PHASES", "HINTS", "CONTEXT_HINTS", "StepAttribution",
           "EnqueueSplit",
           "StragglerDetector", "attribution", "reset_attribution",
           "dominant_phase_or_none", "step_p50_or_none",
           "doctor_report", "render_doctor"]

# The step wall-clock decomposition.  Every name here must (a) be used
# by an ``add_phase`` call somewhere in the shipped sources, (b) have a
# row in the docs/observability.md phase table and (c) have a HINTS
# entry — TEL002 checks all three both ways.
PHASES = (
    "input_wait",        # training loop blocked waiting for the next batch
    "h2d_transfer",      # device_put of the batch inside step()
    "dispatch",          # host work only: prepare + own enqueue cost + commit
    "runahead_stall",    # blocked on the device: ring wait + held-back enqueue
    "collective_or_ps",  # cross-worker kvstore push/pull round
    "metric_drain",      # lazy-metric updates + batch-end callback fetches
    "checkpoint",        # snapshot encode + atomic write (post-flush)
)

# phase -> actionable hint naming the EXISTING knob that moves it; the
# doctor prints these verbatim.  TEL002 pins the key set to PHASES.
HINTS = {
    "input_wait": "host input pipeline is the bottleneck: raise "
                  "preprocess_threads (decode pool) and/or "
                  "prefetch_buffer (pipeline ring depth)",
    "h2d_transfer": "batch transfers are not overlapped: raise "
                    "prefetch_buffer / feed through PrefetchToDeviceIter "
                    "so the put rides the prefetch thread",
    "dispatch": "host-side per-step work dominates (preparing the "
                "arguments, enqueuing the step, committing its outputs; "
                "waits on the device are NOT in this phase): cut the "
                "per-step host work — fewer small device programs per "
                "step, fewer parameters to commit — and check SRC004 for "
                "per-step syncs; widening bulk_size only helps while "
                "runahead_stall is zero",
    "runahead_stall": "the device is the bottleneck (in-flight ring full "
                      "at bulk_size): widening bulk_size will NOT help — "
                      "make the step itself cheaper (batch/precision) or "
                      "accept device-bound",
    "collective_or_ps": "the cross-worker round dominates: raise "
                        "max_staleness (bounded-staleness async push) or "
                        "check the PS network path",
    "metric_drain": "metric fetches flush the run-ahead window too "
                    "often: keep update_lazy and fetch at bulk_size "
                    "flush boundaries (wider callback intervals)",
    "checkpoint": "snapshot cost dominates: raise checkpoint_every "
                  "(fewer snapshots) or lower checkpoint_keep",
}

# context-specialized hints: when a rank's attribution context tags a
# phase with a mode, the doctor prints the mode's hint instead of the
# generic one.  Keyed (phase, context-tag); the phase key set is a
# subset of PHASES (TEL002 pins PHASES/HINTS; this map only refines).
CONTEXT_HINTS = {
    ("collective_or_ps", "zero1"):
        "the zero1 collective dominates: the ZeRO-1 reduce-scatter/"
        "all-gather program is the bottleneck — grow the per-replica "
        "batch so compute amortizes the gather, or drop zero=1 if the "
        "optimizer state fits replicated (docs/elastic.md)",
    ("dispatch", "grad_accum"):
        "the step runs grad_accum microbatches back-to-back before "
        "its one optimizer update: lower grad_accum if HBM allows the "
        "full batch in one pass, or grow the microbatch so compute "
        "amortizes the per-microbatch dispatch (docs/distributed.md)",
    # tagged by trainer.fusion_report() when the top fusable chain
    # covers > FUSION_HINT_MIN_PCT of step bytes (docs/fusion.md)
    ("dispatch", "fusable"):
        "dispatch dominates and the fusion report ranks a chain "
        "covering a large share of step bytes: enable the fused "
        "optimizer update (MXTPU_FUSED_OPTIMIZER=1 off-TPU; on by "
        "default on TPU) and check `--fusion` for further chains "
        "(docs/fusion.md)",
    ("collective_or_ps", "fusable"):
        "the collective/update program dominates and the fusion "
        "report ranks a chain covering a large share of step bytes: "
        "the fused reduce-scatter→update→all-gather spelling "
        "(MXTPU_FUSED_OPTIMIZER=1 off-TPU) collapses the shard-local "
        "update to one HBM pass (docs/fusion.md)",
}


# the armed flight ring, pushed here by telemetry.enable()/disable():
# on_step fuses the per-step progress-cursor store into its mark, so the
# trainer's armed hot path makes ONE telemetry call per step
_RING = None


def set_ring(recorder):
    global _RING
    _RING = recorder


def _percentile(samples, q):
    data = sorted(samples)
    if not data:
        return 0.0
    rank = max(0, min(len(data) - 1,
                      int(round(q / 100.0 * (len(data) - 1)))))
    return data[rank]


class StepAttribution:
    """Per-step phase accumulator with EWMA anomaly detection.

    Hot-path contract: ``on_step``/``add_phase`` are a few dict float
    adds + bounded-deque appends under one lock (no registry instrument,
    no JSON); the flight-ring record and registry-histogram observes
    amortize over ``ring_every`` steps.  ``now`` is injectable for
    deterministic tests.
    """

    def __init__(self, ring_every=None, anomaly_factor=None, warmup=20,
                 window=512, now=None):
        self._lock = threading.Lock()
        self._now = now or time.perf_counter
        self.ring_every = int(ring_every or os.environ.get(
            "MXTPU_ATTRIB_RING_EVERY", "50"))
        self.anomaly_factor = float(anomaly_factor or os.environ.get(
            "MXTPU_ANOMALY_FACTOR", "4.0"))
        self.warmup = int(warmup)
        # open window: SPARSE phase dict — only touched phases have keys.
        # The per-step hot path is deliberately tiny: on_step appends one
        # (step, wall, phases) tuple to a pending list and add_phase is
        # a GIL-atomic dict add (single writer: the training thread);
        # ALL aggregation — totals, EWMA, flight windows, histograms —
        # batches up in _drain_locked every _defer steps or at any
        # reader (snapshot/dominant_phase/flush_window).
        self._open_t = None
        self._open_step = None
        self._cur = {}
        self._phase_set = frozenset(PHASES)
        self._pending = []
        self._defer = max(1, min(16, self.ring_every))
        # lifetime accumulators
        self._totals = dict.fromkeys(PHASES, 0.0)
        self._steps = 0
        self._wall_total = 0.0
        self._unattributed_total = 0.0
        self._overshoot_total = 0.0      # sum(phases) past wall (timer skew)
        self._recent_wall = deque(maxlen=int(window))
        # flight window (aggregated between ring records); the previous
        # window is kept so dominant_phase always sees >= ring_every
        # recent steps without any per-step per-phase bookkeeping
        self._win_first = None
        self._win_steps = 0
        self._win_wall = 0.0
        self._win_phases = {}
        self._last_win_phases = {}
        # EWMA baseline
        self._ewma = None
        self._anomalies = 0
        self._last_anomaly_step = None
        self._last_anomaly_t = None
        # queue-growth baselines: name -> [fast, slow, n, last_emit_n]
        self._queues = {}
        self.queue_growth_factor = float(os.environ.get(
            "MXTPU_QUEUE_GROWTH_FACTOR", "2.0"))
        self._queue_growth = 0
        # free-form phase context: instrumented sites tag WHAT a phase
        # is measuring in their mode (e.g. the zero=1 trainer tags
        # collective_or_ps as "zero1" so the doctor can name the ZeRO
        # collective as the knob instead of the PS round).  Snapshot-
        # carried; never touched on the hot path.
        self._context = {}
        # registry export: one weakly-held collector (the PipelineStats
        # discipline) — a reset drops the old instance out of the scrape
        from .metrics import registry as _registry
        _registry().register_collector(self._metrics_samples,
                                       name="attribution")

    def set_context(self, phase, tag):
        """Tag ``phase`` with a mode string (off the hot path — called
        once at setup).  Lands in :meth:`snapshot` as ``context`` and in
        the metrics dump, where the doctor reads it to specialize the
        phase's hint (docs/observability.md "zero1 collective")."""
        if phase not in self._phase_set:
            raise ValueError("unknown attribution phase %r (PHASES=%r)"
                             % (phase, PHASES))
        with self._lock:
            self._context[str(phase)] = str(tag)

    # -- hot path ----------------------------------------------------------
    def add_phase(self, name, seconds):
        """Accumulate ``seconds`` into phase ``name`` of the open window.
        Lock-free: a GIL-atomic dict add — the training thread is the
        single writer (cross-thread adds like the engine's flush path
        land in whatever window is open, which is the semantics)."""
        if name not in self._phase_set:
            raise ValueError("unknown attribution phase %r (PHASES=%r)"
                             % (name, PHASES))
        if seconds <= 0.0:
            return
        # deliberately lock-free (this is the per-phase hot path): the
        # ref load and dict add are each GIL-atomic, and an add racing
        # a window close lands in whichever dict it loaded — a window
        # boundary is the documented semantics, not corruption
        cur = self._cur  # mxlint: disable=RACE001
        cur[name] = cur.get(name, 0.0) + seconds  # mxlint: disable=RACE001

    def on_step(self, step):
        """Mark the step-``step`` dispatch: closes the previous window
        (attributing everything added since the last mark to it), opens
        a new one, and stores the flight-ring progress cursor (the
        PR-9 "how far did it train" field — fused here so the armed
        trainer makes one telemetry call per step).  The close is an
        append; aggregation amortizes over ``_defer`` steps."""
        now = self._now()
        ring = _RING
        if ring is not None:
            ring.set_cursor(step, int(now * 1e9))
        # the window bookkeeping shares _lock with flush_window: a
        # metrics dump on the scrape thread closing the open window
        # mid-append here would double-count or drop it
        with self._lock:
            prev_t = self._open_t
            self._open_t = now
            if prev_t is None:
                self._open_step = int(step)
                self._cur = {}
                return
            self._pending.append((self._open_step, now - prev_t,
                                  self._cur))
            self._open_step = int(step)
            self._cur = {}
            if len(self._pending) >= self._defer:
                self._drain_locked()

    def flush_window(self):
        """Close the open window and flight-record the partial flight
        window (end of ``fit`` / metrics dump — a run's tail steps must
        not evaporate)."""
        now = self._now()
        with self._lock:
            if self._open_t is not None:
                self._pending.append((self._open_step, now - self._open_t,
                                      self._cur))
                self._open_t = None
                self._open_step = None
                self._cur = {}
            self._drain_locked()
            if self._win_steps:
                self._record_window_locked()

    def _drain_locked(self):
        pending, self._pending = self._pending, []
        if not pending:
            return
        ewma = self._ewma
        # the EWMA baseline and its regression bound are per-BATCH: the
        # bound is fixed while the batch drains and the average updates
        # once — same signal, a fraction of the per-item arithmetic.
        # Accumulators ride locals through the loop (attribute access is
        # the cost floor here; this loop IS the armed per-step price).
        bound = self.anomaly_factor * ewma if ewma is not None else None
        batch_wall = 0.0
        steps = self._steps
        wall_total = self._wall_total
        un_total = self._unattributed_total
        overshoot = self._overshoot_total
        recent_append = self._recent_wall.append
        win = self._win_phases
        win_steps = self._win_steps
        win_wall = self._win_wall
        ring_every = self.ring_every
        warmup = self.warmup
        for step, wall, phases in pending:
            if phases:
                phase_sum = 0.0
                for p, v in phases.items():  # sparse: touched phases only
                    win[p] = win.get(p, 0.0) + v
                    phase_sum += v
                unattributed = wall - phase_sum
                if unattributed < 0.0:
                    overshoot += -unattributed
                    unattributed = 0.0
            else:
                unattributed = wall
            steps += 1
            wall_total += wall
            un_total += unattributed
            recent_append(wall)
            batch_wall += wall
            if self._win_first is None:
                self._win_first = step
            win_steps += 1
            win_wall += wall
            if win_steps >= ring_every:
                self._win_steps, self._win_wall = win_steps, win_wall
                self._record_window_locked(last_step=step)
                win = self._win_phases
                win_steps, win_wall = 0, 0.0
            # flag a step-time regression while the run is still alive —
            # a run dying slow leaves the same ring evidence a run dying
            # fast does
            if bound is not None and steps > warmup and wall > bound:
                self._anomalies += 1
                # emission cooldown is step- AND time-based: on fast
                # noisy steps an anomaly storm must not bill ring-write
                # time to the armed step
                t_now = self._now()
                if (self._last_anomaly_step is None
                        or step - self._last_anomaly_step >= 10) and \
                        (self._last_anomaly_t is None
                         or t_now - self._last_anomaly_t >= 1.0):
                    self._last_anomaly_step = step
                    self._last_anomaly_t = t_now
                    self._emit("perf.anomaly", step=step,
                               wall_s=round(wall, 6),
                               ewma_s=round(ewma, 6),
                               factor=self.anomaly_factor,
                               phase=self._dominant_locked())
        self._steps = steps
        self._wall_total = wall_total
        self._unattributed_total = un_total
        self._overshoot_total = overshoot
        self._win_steps, self._win_wall = win_steps, win_wall
        mean = batch_wall / len(pending)
        if ewma is None:
            self._ewma = mean
        else:
            if bound is not None and mean > bound:
                mean = bound                 # one spike must not poison
            self._ewma = ewma + min(1.0, 0.05 * len(pending)) \
                * (mean - ewma)

    def _record_window_locked(self, last_step=None):
        # lifetime totals fold in per window, not per step
        totals = self._totals
        for p, v in self._win_phases.items():
            totals[p] += v
        phases = {p: round(v, 6) for p, v in self._win_phases.items()
                  if v > 0.0}
        dominant = max(phases, key=phases.get) if phases else None
        self._emit("perf.phases",
                   step_first=self._win_first,
                   step_last=last_step if last_step is not None
                   else self._open_step,
                   steps=self._win_steps,
                   wall_s=round(self._win_wall, 6),
                   phases=phases,
                   phase=dominant)
        # registry histograms: per-step means per phase, once per window
        # (the registry instrument cost amortizes over ring_every steps)
        try:
            from .metrics import registry as _registry
            reg = _registry()
            h = reg.histogram("mxtpu_step_phase_seconds",
                              "per-step phase seconds (window means)")
            n = max(1, self._win_steps)
            for p, v in phases.items():
                h.observe(v / n, phase=p)
            reg.histogram("mxtpu_step_time_seconds",
                          "per-step wall seconds (window means)").observe(
                self._win_wall / n)
        except Exception:
            pass
        self._win_first = None
        self._win_steps = 0
        self._win_wall = 0.0
        self._last_win_phases = self._win_phases
        self._win_phases = {}

    def _emit(self, kind, **fields):
        """Flight-record (armed rings only) — never raises into the
        training loop."""
        try:
            from . import record as _record
            _record(kind, **fields)
        except Exception:
            pass

    # -- queue growth ------------------------------------------------------
    def note_queue_depth(self, name, depth):
        """Feed one queue-depth sample (pipeline reorder queue, in-flight
        dispatch ring).  A fast-EWMA rising ``queue_growth_factor``×
        above the slow baseline flags ``perf.queue_growth`` — the
        dying-slow signature (work arriving faster than it drains)."""
        depth = float(depth)
        with self._lock:
            st = self._queues.get(name)
            if st is None:
                st = self._queues[name] = [depth, depth, 0, 0]
            st[0] += 0.3 * (depth - st[0])    # fast
            st[1] += 0.03 * (depth - st[1])   # slow baseline
            st[2] += 1
            if st[2] > 50 and st[0] >= 4.0 and \
                    st[0] > self.queue_growth_factor * max(st[1], 1.0) and \
                    st[2] - st[3] >= 200:
                st[3] = st[2]
                self._queue_growth += 1
                self._emit("perf.queue_growth", queue=name,
                           depth=depth, fast=round(st[0], 2),
                           baseline=round(st[1], 2))

    # -- queries -----------------------------------------------------------
    def _dominant_locked(self):
        merged = dict(self._last_win_phases)
        for p, v in self._win_phases.items():
            merged[p] = merged.get(p, 0.0) + v
        # dict() snapshot: the open window is mutated lock-free by the
        # training thread (one C-level copy is GIL-atomic)
        for p, v in dict(self._cur).items():
            merged[p] = merged.get(p, 0.0) + v
        best, best_v = None, 0.0
        for p, v in merged.items():
            if v > best_v:
                best, best_v = p, v
        return best

    def dominant_phase(self):
        """The phase with the largest time share over the recent ~2
        flight windows, or None before any phase time accrued (what a
        worker's heartbeat reports so the server's straggler event can
        name it)."""
        with self._lock:
            self._drain_locked()
            return self._dominant_locked()

    def step_p50(self):
        """The rank's SELF-MEASURED per-step wall p50 over the recent
        window, or None before any step completed — what the worker's
        heartbeat ``p50_fn`` reports (kvstore_ps.py) so the server-side
        straggler verdict rides the worker's own step clock instead of
        beat-arrival deltas (which jitter with host load)."""
        with self._lock:
            self._drain_locked()
            recent = list(self._recent_wall)
        if not recent:
            return None
        return _percentile(recent, 50)

    def snapshot(self):
        """Aggregate view (what ``fit``'s metrics dump embeds and the
        doctor reads): lifetime totals, per-step p50/p99, dominant phase,
        anomaly counters and the reconciliation residuals."""
        with self._lock:
            self._drain_locked()
            recent = list(self._recent_wall)
            win = self._win_phases
            return {
                "steps": self._steps,
                "wall_s": round(self._wall_total, 6),
                "phases_s": {p: round(v + win.get(p, 0.0), 6)
                             for p, v in self._totals.items()},
                "unattributed_s": round(self._unattributed_total, 6),
                "overshoot_s": round(self._overshoot_total, 6),
                "step_p50_s": round(_percentile(recent, 50), 6),
                "step_p99_s": round(_percentile(recent, 99), 6),
                "dominant_phase": self._dominant_locked(),
                "anomalies": self._anomalies,
                "queue_growth_events": self._queue_growth,
                "context": dict(self._context),
            }

    def _metrics_samples(self):
        snap = self.snapshot()
        out = [
            ("mxtpu_steps_total", {}, snap["steps"]),
            ("mxtpu_step_wall_seconds_total", {}, snap["wall_s"]),
            ("mxtpu_step_unattributed_seconds_total", {},
             snap["unattributed_s"]),
            ("mxtpu_step_time_p50_seconds", {}, snap["step_p50_s"]),
            ("mxtpu_step_time_p99_seconds", {}, snap["step_p99_s"]),
            ("mxtpu_perf_anomalies_total", {}, snap["anomalies"]),
            ("mxtpu_perf_queue_growth_total", {},
             snap["queue_growth_events"]),
        ]
        for p, v in snap["phases_s"].items():
            out.append(("mxtpu_step_phase_seconds_total", {"phase": p}, v))
        return out


class EnqueueSplit:
    """Splits the wall time of a call that enqueues device programs into
    the host's own cost and the time the runtime held the call back.

    The runtime keeps its own limit of programs in flight, below the
    engine's run-ahead ring; a call beyond it returns only when a step
    retires, a whole step later, and that wait sits *inside* the call
    (PERF.md, Findings of PR 25 and 26).  The program cannot see the
    wait, but it can see what surrounds it:

    - ``in_flight``: how many of the ring's steps had not finished when
      the call was made.  With none in flight nothing can hold the call
      back: its whole time is the host's, and is a sample of the call's
      own cost whatever it took;
    - any other call is held back if it takes more than ``FACTOR`` times
      the median of the last ``KEEP`` samples plus ``SLACK_S``: the host
      is then billed that median and the wait the rest, and the call is
      no sample.  One that takes no longer than that was not held back
      and is a sample too, so the estimate follows the host in a steady
      run whose ring never drains.

    Until a call has been made with nothing in flight there is no sample
    to hold a call against, and calls are billed whole to the host.
    The three numbers were checked on the chip against a run in which
    nothing can be held back (``benchmark/host_control.py``), on two
    device-bound cells.  Stdlib-only."""

    KEEP, FACTOR, SLACK_S = 32, 2.0, 3e-4

    def __init__(self):
        self._samples = deque(maxlen=self.KEEP)

    def own_cost_s(self):
        """The call's own cost as the samples have it (their median), or
        None before the first."""
        if not self._samples:
            return None
        ranked = sorted(self._samples)
        return ranked[len(ranked) // 2]

    def split(self, seconds, in_flight):
        """``(own_s, blocked_s)`` of one call that took ``seconds`` with
        ``in_flight`` steps unfinished before it."""
        own = self.own_cost_s()
        if in_flight == 0 or (
                own is not None
                and seconds <= self.FACTOR * own + self.SLACK_S):
            self._samples.append(seconds)
            return seconds, 0.0
        if own is None:
            return seconds, 0.0
        return own, seconds - own


_ATTR = None
_ATTR_LOCK = threading.Lock()


def attribution():
    """The process-wide :class:`StepAttribution` (created on first use —
    instrumented sites reach it only behind the telemetry-enabled
    check)."""
    global _ATTR
    # double-checked locking: the bare fast-path read is GIL-atomic and
    # either sees the fully-constructed singleton or falls to the lock
    a = _ATTR  # mxlint: disable=RACE001
    if a is None:
        with _ATTR_LOCK:
            a = _ATTR
            if a is None:
                a = _ATTR = StepAttribution()
    return a


def reset_attribution():
    """Drop the process accumulator (test isolation); the old collector
    drops out of the registry scrape via its weakref."""
    global _ATTR
    with _ATTR_LOCK:
        _ATTR = None


def dominant_phase_or_none():
    """The dominant phase when telemetry is armed, else None — the
    worker-side ``phase_fn`` heartbeats report (kvstore.py)."""
    from . import enabled as _enabled
    # one GIL-atomic read of the singleton ref (the heartbeat hot
    # path); a concurrent reset simply means this beat reports None
    a = _ATTR  # mxlint: disable=RACE001
    if not _enabled() or a is None:
        return None
    return a.dominant_phase()


def step_p50_or_none():
    """The self-measured step-time p50 when telemetry is armed, else
    None — the worker-side ``p50_fn`` heartbeats report so the server's
    :class:`StragglerDetector` judges measured step time, not arrival
    jitter."""
    from . import enabled as _enabled
    # one GIL-atomic read of the singleton ref (the heartbeat hot
    # path); a concurrent reset simply means this beat reports None
    a = _ATTR  # mxlint: disable=RACE001
    if not _enabled() or a is None:
        return None
    return a.step_p50()


class StragglerDetector:
    """Server-side per-rank step-time skew detector.

    Fed from heartbeat RPCs: each beat carries ``(rank, step)`` plus —
    when the client ran ``sync_clock`` — the beat's send time already
    shifted onto the *server's* monotonic clock (``local_perf_ns +
    clock_offset_ns``, the PR-9 NTP-midpoint offset), so per-rank step
    durations are measured free of network-arrival jitter; an unsynced
    client falls back to server arrival time.  Per rank, successive
    ``(t, step)`` observations yield per-step durations; when one rank's
    p50 exceeds the fleet median by ``factor``, a ``perf.straggler``
    flight event (rank, lag, dominant phase) + counter fire — re-emitted
    at most once per ``cooldown_s`` while the skew persists, except that
    a CHANGED dominant phase re-emits immediately (the verdict's named
    bottleneck moved — e.g. the warmup window's jit compile giving way
    to input wait — and the stale event would name the wrong knob).
    ``min_gap_s`` (``MXTPU_STRAGGLER_MIN_GAP_S``, default 0) adds an
    absolute-gap floor on top of the ratio — see ``__init__``.

    A beat that carries the worker's SELF-MEASURED step-time p50
    (``p50_s``, from :func:`step_p50_or_none` — the rank's own
    ``StepAttribution`` clock) takes precedence over the arrival-delta
    derivation for that rank: the worker's clock sees exactly the step
    wall the doctor reconciles, so the verdict is deterministic under
    host contention where beat scheduling jitters.  The min-samples
    discipline still applies, gated on the rank's reported step count.
    """

    def __init__(self, factor=None, window=64, min_samples=None,
                 cooldown_s=5.0, now_ns=None, min_gap_s=None):
        self.factor = float(factor or os.environ.get(
            "MXTPU_STRAGGLER_FACTOR", "2.0"))
        # absolute-gap floor: a verdict needs p50 - med > min_gap_s ON
        # TOP of the ratio.  Ratio alone misfires on millisecond-scale
        # steps, where scheduler jitter yields large RATIOS over tiny
        # absolute skew (two workers time-slicing one CI core hit 2-3x
        # on a ~3ms step with no fault anywhere); a real straggler's
        # gap is orders of magnitude above it.  Default 0: ratio-only.
        self.min_gap_s = float(min_gap_s if min_gap_s is not None
                               else os.environ.get(
                                   "MXTPU_STRAGGLER_MIN_GAP_S", "0"))
        self.min_samples = int(min_samples or os.environ.get(
            "MXTPU_STRAGGLER_MIN_SAMPLES", "5"))
        self.cooldown_s = float(cooldown_s)
        self._now_ns = now_ns or time.perf_counter_ns
        self._lock = threading.Lock()
        self._last = {}       # rank -> (t_ns, step)
        self._durs = {}       # rank -> deque of per-step seconds
        self._self_p50 = {}   # rank -> self-measured step p50 (beats)
        self._phase = {}      # rank -> last reported dominant phase
        self._window = int(window)
        self._flagged = {}    # rank -> (last emit t_ns, emitted phase)
        self.events = []      # (rank, lag, phase) — for assertions

    def observe(self, rank, step, t_ns=None, phase=None, p50_s=None):
        """Record one step-clock observation; runs a scan and returns
        newly-emitted straggler events (possibly empty).  ``p50_s``:
        the worker's self-measured step p50 — preferred over deriving
        from beat-arrival deltas once the rank has stepped
        ``min_samples`` times."""
        if step is None:
            return []
        now = self._now_ns()
        t = int(t_ns) if t_ns is not None else now
        with self._lock:
            if phase is not None:
                self._phase[rank] = phase
            if p50_s is not None and float(p50_s) > 0 \
                    and int(step) >= self.min_samples:
                self._self_p50[rank] = float(p50_s)
            prev = self._last.get(rank)
            # the reference point moves only when the step clock moves:
            # a rank stepping SLOWER than the beat interval must bill the
            # whole no-progress interval to its steps, or its measured
            # step time clamps at the beat interval and the skew hides
            if prev is None:
                self._last[rank] = (t, int(step))
            elif step > prev[1] and t > prev[0]:
                per_step = (t - prev[0]) / (step - prev[1]) / 1e9
                durs = self._durs.get(rank)
                if durs is None:
                    # the rank's FIRST interval spans connect + jit
                    # compile — a warmup artifact, not a step time; it
                    # only resets the reference point (under host
                    # contention it otherwise flags whichever rank
                    # compiled second as a straggler)
                    self._durs[rank] = deque(maxlen=self._window)
                else:
                    durs.append(per_step)
                self._last[rank] = (t, int(step))
            return self._scan_locked(now)

    def _p50s_locked(self):
        out = {r: _percentile(list(d), 50)
               for r, d in self._durs.items()
               if len(d) >= self.min_samples}
        # a rank's own measurement wins over the arrival-delta estimate
        out.update(self._self_p50)
        return out

    def _scan_locked(self, now_ns):
        p50s = self._p50s_locked()
        if len(p50s) < 2:
            return []
        med = _percentile(list(p50s.values()), 50)
        if med <= 0:
            return []
        emitted = []
        for rank, p50 in p50s.items():
            if p50 > self.factor * med and p50 - med > self.min_gap_s:
                phase = self._phase.get(rank)
                last = self._flagged.get(rank)
                if last is not None and \
                        (now_ns - last[0]) / 1e9 < self.cooldown_s \
                        and phase == last[1]:
                    continue
                self._flagged[rank] = (now_ns, phase)
                ev = {"rank": rank, "lag": round(p50 / med, 3),
                      "p50_s": round(p50, 6),
                      "fleet_p50_s": round(med, 6),
                      "phase": phase}
                self.events.append(ev)
                emitted.append(ev)
            else:
                self._flagged.pop(rank, None)
        for ev in emitted:
            try:
                from . import record as _record
                from .metrics import registry as _registry
                _record("perf.straggler", **ev)
                _registry().counter(
                    "mxtpu_perf_stragglers_total",
                    "straggler verdicts by rank").inc(rank=str(ev["rank"]))
            except Exception:
                pass
        return emitted

    def snapshot(self):
        """Per-rank p50s + current verdicts (the doctor's online view)."""
        with self._lock:
            p50s = self._p50s_locked()
            med = _percentile(list(p50s.values()), 50) if len(p50s) >= 2 \
                else None
            return {
                "rank_step_p50_s": {str(r): round(v, 6)
                                    for r, v in p50s.items()},
                "fleet_p50_s": round(med, 6) if med else None,
                "stragglers": sorted(
                    str(r) for r, v in p50s.items()
                    if med and v > self.factor * med),
                "phases": {str(r): p for r, p in self._phase.items()},
                "events": list(self.events),
            }


# ---------------------------------------------------------------------------
# the doctor: offline bottleneck analysis over a telemetry directory
# ---------------------------------------------------------------------------
_METRICS_RANK_RE = _re.compile(r"metrics-[a-z]+(\d+)-\d+\.json$")


def _rank_label(meta):
    rank = meta.get("rank")
    role = meta.get("role", "worker")
    return "%s%s" % (role, "" if rank is None else rank)


def _span_summary(spans):
    """``{"steps", "dropped", "seconds": {name: total}}`` of the span
    buffer as ``telemetry.dump_metrics`` wrote it (``trace.SPAN_FIELDS``
    tuples), or None where the dump has no spans."""
    events = spans.get("events") or []
    fields = spans.get("fields") or []
    if not events or not {"name", "start_ns", "end_ns"} <= set(fields):
        return None
    name, start, end = (fields.index(f)
                        for f in ("name", "start_ns", "end_ns"))
    seconds = {}
    for ev in events:
        seconds[ev[name]] = seconds.get(ev[name], 0.0) \
            + (ev[end] - ev[start]) / 1e9
    return {"steps": sum(1 for ev in events if ev[name] == "train.step"),
            "dropped": int(spans.get("dropped") or 0),
            "seconds": seconds}


def doctor_report(directory, factor=None):
    """Read a fleet's telemetry directory (metrics dumps + flight rings)
    and diagnose: per rank, the bottleneck phase + hint; fleet-wide, the
    straggler verdict (offline recomputation of the same p50-vs-median
    rule the online detector applies, plus any ``perf.straggler`` /
    ``perf.anomaly`` / ``perf.queue_growth`` events the run recorded).

    Sources, in preference order per rank: the ``attribution`` snapshot
    embedded in the metrics JSON (a clean exit), else the ``perf.phases``
    windows recovered from the rank's flight ring (a SIGKILLed rank
    still gets a verdict — that is the point of ring attribution).  The
    metrics JSON's span buffer says which spans the step's host time is
    made of (``dispatch`` is three of them), its compile counters whether
    steps compiled."""
    from .flight import RING_SUFFIX, read_ring
    factor = float(factor or os.environ.get("MXTPU_STRAGGLER_FACTOR",
                                            "2.0"))
    ranks = {}       # label -> record
    events = {"straggler": [], "anomaly": [], "queue_growth": [],
              "fault": []}
    for path in sorted(_glob.glob(os.path.join(str(directory),
                                               "metrics-*.json"))):
        try:
            with open(path) as f:
                doc = _json.load(f)
        except (OSError, ValueError):
            continue
        attr = doc.get("attribution")
        if not attr:
            continue
        m = _METRICS_RANK_RE.search(os.path.basename(path))
        label = "worker%s" % m.group(1) if m else os.path.basename(path)
        rec = ranks.setdefault(label, {"source": []})
        rec.update(
            steps=attr.get("steps", 0),
            wall_s=attr.get("wall_s", 0.0),
            phases_s=dict(attr.get("phases_s") or {}),
            unattributed_s=attr.get("unattributed_s", 0.0),
            step_p50_s=attr.get("step_p50_s", 0.0),
            anomalies=attr.get("anomalies", 0),
            context=dict(attr.get("context") or {}),
        )
        summary = _span_summary(doc.get("spans") or {})
        if summary and summary["steps"]:
            rec["spans"] = summary
        if doc.get("compiles"):
            rec["compiles"] = dict(doc["compiles"])
        rec["source"].append(os.path.basename(path))
    for path in sorted(_glob.glob(os.path.join(str(directory),
                                               "*" + RING_SUFFIX))):
        try:
            meta, ring_events = read_ring(path)
        except (OSError, ValueError):
            continue
        label = _rank_label(meta)
        for ev in ring_events:
            kind = ev.get("kind", "")
            if kind == "perf.straggler":
                events["straggler"].append(dict(ev, seen_by=label))
            elif kind == "perf.anomaly":
                events["anomaly"].append(dict(ev, seen_by=label))
            elif kind == "perf.queue_growth":
                events["queue_growth"].append(dict(ev, seen_by=label))
            elif kind == "chaos.fault":
                events["fault"].append(dict(ev, seen_by=label))
        if meta.get("role") == "server":
            continue
        rec = ranks.setdefault(label, {"source": []})
        rec["source"].append(os.path.basename(path))
        if "cursor_step" in meta:
            rec.setdefault("cursor_step", meta["cursor_step"])
        if rec.get("steps"):
            continue   # the metrics dump already told the full story
        phases = {}
        steps = 0
        wall = 0.0
        for ev in ring_events:
            if ev.get("kind") != "perf.phases":
                continue
            steps += int(ev.get("steps") or 0)
            wall += float(ev.get("wall_s") or 0.0)
            for p, v in (ev.get("phases") or {}).items():
                phases[p] = phases.get(p, 0.0) + float(v)
        if steps:
            rec.update(steps=steps, wall_s=round(wall, 6),
                       phases_s=phases,
                       step_p50_s=round(wall / steps, 6),
                       from_ring=True)
    for label, rec in ranks.items():
        phases = rec.get("phases_s") or {}
        dominant = None
        if phases:
            dominant = max(phases, key=phases.get)
            if phases[dominant] <= 0:
                dominant = None
        rec["dominant_phase"] = dominant
        hint = HINTS.get(dominant) if dominant else None
        if dominant:
            tag = (rec.get("context") or {}).get(dominant)
            if tag is not None:
                hint = CONTEXT_HINTS.get((dominant, tag), hint)
        rec["hint"] = hint
        wall = rec.get("wall_s") or 0.0
        if wall and dominant:
            rec["dominant_share"] = round(phases[dominant] / wall, 4)
        if wall and rec.get("steps"):
            rec["step_mean_s"] = round(wall / rec["steps"], 6)
    # offline straggler recomputation: MEAN step time per rank (wall /
    # steps — what the online detector's beat-derived dt/dsteps measures
    # too; a per-step median would hide waits that concentrate on a few
    # steps behind prefetch buffering), compared against the fleet
    # median of those means
    p50s = {label: rec.get("step_mean_s") or rec.get("step_p50_s")
            for label, rec in ranks.items()
            if rec.get("step_mean_s") or rec.get("step_p50_s")}
    stragglers = []
    fleet_p50 = None
    if len(p50s) >= 2:
        fleet_p50 = _percentile(list(p50s.values()), 50)
        if fleet_p50 > 0:
            stragglers = sorted(
                label for label, v in p50s.items()
                if v > factor * fleet_p50)
    return {
        "directory": str(directory),
        "factor": factor,
        "ranks": ranks,
        "fleet_step_p50_s": round(fleet_p50, 6) if fleet_p50 else None,
        "stragglers": stragglers,
        "balanced": not stragglers and not events["straggler"],
        "events": events,
    }


def render_doctor(report):
    """Human-readable doctor verdict (the CLI's default output)."""
    lines = ["== performance doctor: %s" % report["directory"]]
    ranks = report["ranks"]
    if not ranks:
        lines.append("   no attribution data found (was the fleet armed "
                     "with MXTPU_TELEMETRY_DIR and attribution enabled?)")
    for label in sorted(ranks):
        rec = ranks[label]
        steps = rec.get("steps", 0)
        src = " [ring]" if rec.get("from_ring") else ""
        lines.append("-- %s: %d steps, mean step %.1f ms "
                     "(p50 %.1f ms)%s"
                     % (label, steps,
                        1e3 * (rec.get("step_mean_s") or 0.0),
                        1e3 * (rec.get("step_p50_s") or 0.0), src))
        phases = rec.get("phases_s") or {}
        wall = rec.get("wall_s") or 0.0
        for p in PHASES:
            v = phases.get(p, 0.0)
            if v > 0:
                share = (100.0 * v / wall) if wall else 0.0
                lines.append("   %-16s %8.3f s  (%5.1f%%)" % (p, v, share))
        if wall:
            un = rec.get("unattributed_s", 0.0)
            lines.append("   %-16s %8.3f s  (%5.1f%%)"
                         % ("(unattributed)", un, 100.0 * un / wall))
        if rec.get("dominant_phase"):
            lines.append("   bottleneck: %s (%.0f%% of step) -> %s"
                         % (rec["dominant_phase"],
                            100.0 * rec.get("dominant_share", 0.0),
                            rec["hint"]))
        spans = rec.get("spans")
        if spans:
            per_step = sorted(
                ((v / spans["steps"], k)
                 for k, v in spans["seconds"].items() if k != "train.step"),
                reverse=True)
            lines.append("   spans, ms per step over the last %d steps%s: %s"
                         % (spans["steps"],
                            " (%d older spans dropped)" % spans["dropped"]
                            if spans["dropped"] else "",
                            ", ".join("%s %.3f" % (k, 1e3 * v)
                                      for v, k in per_step)))
        compiled = rec.get("compiles") or {}
        if compiled.get("in_span_programs"):
            lines.append(
                "   %d program(s) compiled inside training steps (the "
                "process spent %.1f s tracing and lowering, %.1f s in the "
                "backend's compiler or cache): past each shape's first "
                "step that is recompilation -- a shape or a static "
                "argument changes between steps"
                % (compiled["in_span_programs"],
                   compiled.get("trace_s", 0.0)
                   + compiled.get("lower_s", 0.0),
                   compiled.get("backend_s", 0.0)))
        if compiled.get("blocked_bias_grads"):
            lines.append(
                "   %d convolution-bias gradient(s) left out of the traced "
                "programs: zero behind a training-mode BatchNorm"
                % compiled["blocked_bias_grads"])
        if compiled.get("ssm_layers"):
            lines.append(
                "   %d Mamba-2 layer(s) in the traced programs, the scan in "
                "%d chunk(s) a sequence, as a Pallas kernel pair in %d of "
                "them; %d layer(s) recomputed in the "
                "backward pass, %d of them with their projection products "
                "kept (%.2f GB)"
                % (compiled["ssm_layers"],
                   compiled.get("ssm_chunks_per_seq", 0),
                   compiled.get("ssm_kernel_layers", 0),
                   compiled.get("recomputed_layers", 0),
                   compiled.get("kept_product_layers", 0),
                   compiled.get("kept_product_bytes", 0) / 1e9))
        if compiled.get("linear_attention_layers"):
            lines.append(
                "   %d linear-attention layer(s) in the traced programs, the "
                "delta rule in %d chunk(s) a sequence, as a Pallas kernel "
                "pair in %d of them"
                % (compiled["linear_attention_layers"],
                   compiled.get("kda_chunks_per_seq", 0),
                   compiled.get("kda_kernel_layers", 0)))
        if compiled.get("attention_layers"):
            lines.append(
                "   %d attention layer(s) in the traced programs, the scores "
                "as Pallas flash kernels in %d of them"
                % (compiled["attention_layers"],
                   compiled.get("flash_attention_layers", 0)))
        if rec.get("anomalies"):
            lines.append("   %d step-time anomaly event(s) flagged"
                         % rec["anomalies"])
    if report["stragglers"]:
        lines.append("== STRAGGLERS (mean step > %.1fx fleet median "
                     "%.1f ms): %s"
                     % (report["factor"],
                        1e3 * (report["fleet_step_p50_s"] or 0.0),
                        ", ".join(report["stragglers"])))
        for label in report["stragglers"]:
            rec = ranks.get(label, {})
            if rec.get("dominant_phase"):
                lines.append("   %s dominant phase: %s -> %s"
                             % (label, rec["dominant_phase"], rec["hint"]))
    elif len(ranks) >= 2:
        lines.append("== ranks balanced (no p50 exceeds %.1fx the fleet "
                     "median)" % report["factor"])
    ev = report["events"]
    for kind in ("straggler", "anomaly", "queue_growth", "fault"):
        for e in ev[kind]:
            detail = {k: v for k, v in e.items()
                      if k not in ("kind", "ts_ns", "wall_ns", "seq",
                                   "seen_by")}
            lines.append("   EVENT perf.%s (ring of %s): %s"
                         % (kind if kind != "fault" else "chaos",
                            e.get("seen_by"), detail))
    return "\n".join(lines) + "\n"
