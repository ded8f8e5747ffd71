"""Deterministic fault injection: replay a seeded fault schedule.

Every failure mode this repo has met in production-shaped form — a
SIGKILLed pipeline worker, a dropped/delayed kvstore push, an
overloaded serving queue — gets a
*reproducible* tier-1 test instead of a flaky prod story.  The pieces:

- **probe sites**: code at failure-relevant points calls
  ``chaos.maybe_inject("site.name", count, ctx=...)``.  When no schedule
  is installed this is one module-global ``None`` check — zero overhead
  in production.  Shipped sites: ``trainer.step`` (count = step number),
  ``pipeline.dispatch`` (count = batch index, ctx = the iterator),
  ``kvstore.request`` (count = request number, ctx = message tuple),
  ``kvstore.server_apply`` (count = applied-push ordinal on the PS
  server, ctx = (rank, step, key) — the SIGKILL-the-server site),
  ``kvstore.snapshot`` (server snapshot write), ``serving.batch``
  (count = batch number; a ``delay`` here is the runner-stall /
  queue-overload injection), ``serving.route`` (count = routed-request
  ordinal on the model fleet, ctx = (model, tier)), ``serving.swap``
  (fleet hot swap, ctx = model name), ``engine.flush``,
  ``checkpoint.save`` (mid-write, for atomicity tests).
- **faults**: ``Fault(site, at, action, arg)`` — trigger the ``at``-th
  probe hit (1-based; or the probe's explicit ``count``) at ``site`` and
  perform ``action``:

  =========  ==========================================================
  action     effect
  =========  ==========================================================
  raise      raise ``arg`` (an exception instance/class; default
             ``ChaosError``) out of the probe site
  delay      ``time.sleep(arg)`` seconds (stall injection)
  kill       ``os.kill(os.getpid(), SIGKILL)`` — the hard-crash case
  call       ``arg(ctx)`` — site-specific sabotage (e.g. SIGKILL a
             pipeline worker process through ``ctx``)
  =========  ==========================================================

- **schedules**: an explicit ``ChaosSchedule([Fault, ...])``, a seeded
  one (``ChaosSchedule.seeded`` — same seed, same schedule, forever), or
  ``install_from_env()`` parsing ``MXTPU_CHAOS="site:at:action[:arg]"``
  so a *subprocess* under test can be armed from its parent.

Faults fire once each (``repeat=True`` re-arms).  ``triggered()`` lists
what actually fired, for assertions.
"""
from __future__ import annotations

import os
import signal
import time

__all__ = ["Fault", "ChaosSchedule", "ChaosError", "install", "uninstall",
           "installed", "maybe_inject", "triggered", "install_from_env",
           "SITES"]

# The registered fault model: every probe site shipped in mxnet_tpu/ with
# a one-line contract.  This dict is the source of truth the TEL001 lint
# checks BOTH ways against the code and docs/observability.md — a probe
# site used but not registered here, or registered but never probed, is
# silent drift between the fault model and the trace and fails
# ``--self-check``.  Every fault that fires at any of these sites is
# stamped as a telemetry instant event + flight-ring record by
# ``maybe_inject`` (see ``telemetry.fault_event``) before its action
# runs, so even a ``kill`` leaves the evidence behind.
SITES = {
    "trainer.step": "count = trainer step number; fires before dispatch",
    "pipeline.dispatch": "per dispatched batch; ctx = (iter, wid, idx)",
    "kvstore.request": "per client RPC; ctx = the message tuple",
    "kvstore.server_apply": "count = applied-push ordinal on the PS "
                            "server; ctx = (rank, step, key)",
    "kvstore.snapshot": "PS server snapshot capture",
    "serving.batch": "count = batch number; delay = runner stall",
    "serving.route": "count = routed-request ordinal; ctx = (model, tier)",
    "serving.swap": "fleet hot swap; ctx = model name",
    "mlops.decision": "count = promotion evaluate tick; "
                      "ctx = (model, state)",
    "engine.flush": "run-ahead ring drain",
    "checkpoint.save": "mid-checkpoint-write (atomicity tests)",
    "ckpt.shard_write": "before each shard install of a shard-parallel "
                        "snapshot; ctx = (step, rank)",
    "train.step": "elastic worker per-rank step probe "
                  "(tools/train_elastic.py); count = (step-1)*world + "
                  "rank position + 1; ctx = (rank, step)",
    "supervisor.decision": "before each elastic-supervisor decision "
                           "commit; count = decision seq; ctx = the "
                           "decision dict",
}


class ChaosError(RuntimeError):
    """Default injected failure (the 'dropped RPC' stand-in)."""


class Fault:
    """One scheduled fault: at the ``at``-th hit of ``site``, do ``action``."""

    __slots__ = ("site", "at", "action", "arg", "repeat", "_armed")

    def __init__(self, site, at, action="raise", arg=None, repeat=False):
        if action not in ("raise", "delay", "kill", "call"):
            raise ValueError("unknown chaos action %r" % (action,))
        self.site = str(site)
        self.at = int(at)
        self.action = action
        self.arg = arg
        self.repeat = bool(repeat)
        self._armed = True

    def spec(self):
        return (self.site, self.at, self.action, self.arg)

    def __repr__(self):
        return "Fault(%s@%d:%s)" % (self.site, self.at, self.action)


class ChaosSchedule:
    """An ordered set of faults plus per-site hit counters."""

    def __init__(self, faults=()):
        self.faults = list(faults)
        self._hits = {}
        self._triggered = []

    @classmethod
    def seeded(cls, seed, sites, n_faults=3, max_at=50, action="raise",
               arg=None):
        """Deterministic random schedule: ``n_faults`` faults spread over
        ``sites`` with hit indices in [1, max_at], fully determined by
        ``seed`` (same seed -> byte-identical schedule — the property
        tests/test_resilience.py pins)."""
        import random as _random
        rng = _random.Random(int(seed))
        sites = list(sites)
        faults = [Fault(sites[rng.randrange(len(sites))],
                        rng.randint(1, int(max_at)), action, arg)
                  for _ in range(int(n_faults))]
        return cls(faults)

    def specs(self):
        return [f.spec() for f in self.faults]

    def hits(self, site):
        return self._hits.get(site, 0)


_active = None  # the installed ChaosSchedule, or None (the fast path)


def install(schedule):
    """Install a schedule (replacing any active one); returns it."""
    global _active
    if isinstance(schedule, (list, tuple)):
        schedule = ChaosSchedule(schedule)
    _active = schedule
    return schedule


def uninstall():
    """Deactivate fault injection; returns the previous schedule."""
    global _active
    prev, _active = _active, None
    return prev


def installed():
    return _active


def triggered():
    """Specs of faults that actually fired (empty when inactive)."""
    return list(_active._triggered) if _active is not None else []


def maybe_inject(site, count=None, ctx=None):
    """Probe: called from instrumented sites.  No-op (one ``None`` check)
    unless a schedule is installed.  ``count`` overrides the internal
    per-site hit counter (e.g. the trainer passes its step number so the
    schedule is phrased in steps, not probe executions)."""
    sched = _active
    if sched is None:
        return
    if count is None:
        count = sched._hits[site] = sched._hits.get(site, 0) + 1
    else:
        sched._hits[site] = int(count)
    for f in sched.faults:
        if not f._armed or f.site != site or int(count) != f.at:
            continue
        if not f.repeat:
            f._armed = False
        sched._triggered.append(f.spec())
        # stamp the injection BEFORE the action runs: the flight-ring
        # record and trace instant survive even a SIGKILL action, which
        # is exactly when the evidence matters (lazy import: chaos stays
        # importable before the package finishes initializing)
        try:
            from .. import telemetry as _tele
            _tele.fault_event(site, f.at, f.action, ctx=ctx)
        except Exception:
            pass  # telemetry must never mask or reorder the fault itself
        if f.action == "delay":
            time.sleep(float(f.arg or 0.05))
        elif f.action == "kill":
            os.kill(int(f.arg) if f.arg else os.getpid(), signal.SIGKILL)
        elif f.action == "call":
            f.arg(ctx)
        else:  # raise
            exc = f.arg if f.arg is not None else ChaosError(
                "chaos: injected failure at %s hit %d" % (site, f.at))
            if isinstance(exc, type):
                exc = exc("chaos: injected failure at %s hit %d"
                          % (site, f.at))
            raise exc


def install_from_env(var="MXTPU_CHAOS"):
    """Arm faults from an env spec — the subprocess chaos hook.

    Format: comma-separated ``site:at:action[:arg]`` entries, e.g.
    ``MXTPU_CHAOS="trainer.step:7:kill"`` or
    ``"kvstore.request:3:raise,kvstore.request:5:delay:0.2"``.
    Returns the installed schedule, or None when the var is unset/empty.
    """
    spec = os.environ.get(var, "").strip()
    if not spec:
        return None
    faults = []
    for entry in spec.split(","):
        parts = entry.strip().split(":")
        if len(parts) < 3:
            raise ValueError("bad %s entry %r (want site:at:action[:arg])"
                             % (var, entry))
        site, at, action = parts[0], int(parts[1]), parts[2]
        arg = None
        if len(parts) > 3 and parts[3]:
            arg = float(parts[3]) if action == "delay" else parts[3]
        faults.append(Fault(site, at, action, arg))
    return install(ChaosSchedule(faults))
