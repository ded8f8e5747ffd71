"""From a profiler trace (``.xplane.pb``) to device-op intervals by name.

``load`` reads the file with ``jax.profiler.ProfileData`` and keeps, for
every device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
line as ``(name, start_ns, end_ns)``, and from the host plane the spans the
benchmark itself wrote (``StepTraceAnnotation`` named ``STEP_SPAN``).  The
functions below it reduce such intervals and take plain lists, so that the
tests can hand them hand-made ones.

    python benchmark/trace_reduce.py <file-or-directory> [part-of-a-name ...]
"""
from __future__ import annotations

import glob
import os
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
STEP_SPAN = "bench_step"


def find_trace_file(directory):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % directory)
    return found[-1]


def load(path):
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "steps": [...]}"""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_trace_file(path)
    data = ProfileData.from_file(path)
    devices, steps = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                steps.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name == STEP_SPAN)
    return {"devices": devices, "steps": sorted(steps, key=lambda e: e[1])}


def merged(intervals):
    """The union of ``(name, start, end)`` intervals as sorted, disjoint
    ``[start, end]`` pairs: overlapping and nested events count once."""
    out = []
    for _, start, end in sorted(intervals, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_ns(intervals):
    return sum(end - start for start, end in merged(intervals))


def window_ns(intervals):
    """First start to last end: the traced window as the device saw it."""
    if not intervals:
        return 0
    return max(e[2] for e in intervals) - min(e[1] for e in intervals)


def idle_share(intervals):
    """1 - busy/window, or None where nothing ran."""
    window = window_ns(intervals)
    if window <= 0:
        return None
    return 1.0 - busy_ns(intervals) / window


def named(intervals, part):
    """The events whose name contains ``part`` (a kernel's ``name=``)."""
    return [e for e in intervals if part in e[0]]


def kernel_runs(run, kernels):
    """[[(kernel, ns)] a chip]: every run in the run's trace of a kernel
    whose ``name=`` is one of ``kernels``, found in the instruction's own
    name (an operation that reads a kernel's result names it too, among
    its operands).  None where there is no trace, or a chip ran none."""
    trace = run.get("trace")
    if not trace or not trace["devices"] or not run.get("traced_steps"):
        return None
    per_chip = []
    for ops in trace["devices"].values():
        found = [(k, op[2] - op[1]) for op in ops for k in kernels
                 if k in own_name(op[0])]
        if not found:
            return None
        per_chip.append(found)
    return per_chip


def own_name(name):
    """The trace names a TPU operation by its whole HLO instruction; its
    own name is what stands before `` = `` (``%fusion.9 = ...`` ->
    ``fusion.9``)."""
    return name.split(" = ")[0].lstrip("%")


def top_ops(intervals, n=10):
    """[[name, seconds]] of the ``n`` names with most summed time.  An
    operation that comes with its ``op_name`` (the fourth field of
    ``scope_reduce.load``'s operations) is named by where it is from, its
    innermost two program scopes and its phase, before the instruction's
    own name: ``l3/gated_mlp bwd fusion.2457``, ``(unscoped) other
    copy.12``; one that comes without, by its own name alone."""
    import scope_reduce

    total = {}
    for name, start, end, *scope in intervals:
        name = own_name(name)
        if scope:
            name = "%s %s" % (scope_reduce.where_from(scope[0]), name)
        total[name] = total.get(name, 0) + (end - start)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(intervals, steps, n=10):
    """[[what the host was doing, seconds]] of the ``n`` longest gaps
    between device operations.  A gap is named after the benchmark's step
    span that covers its start (``bench_step``: the host was inside
    ``trainer.step``), else ``between_steps``."""
    busy = merged(intervals)
    gaps = sorted(((start - end, end) for (_, end), (start, _)
                   in zip(busy, busy[1:])), reverse=True)[:n]
    return [[STEP_SPAN if any(s <= at < e for _, s, e in steps)
             else "between_steps", length / 1e9] for length, at in gaps]


def main(argv):
    trace = load(argv[1])
    print("step spans:", len(trace["steps"]))
    for plane, ops in trace["devices"].items():
        print(plane, "ops", len(ops), "busy_s", busy_ns(ops) / 1e9,
              "window_s", window_ns(ops) / 1e9)
        for name, seconds in top_ops(ops, 25):
            print("   %10.6f  %s" % (seconds, name))
        for part in argv[2:]:
            found = named(ops, part)
            print("   %d events contain %r, e.g. %s" % (len(found), part, [
                (e[0][:160], e[2] - e[1]) for e in found[:3]]))
    from jax.profiler import ProfileData
    path = argv[1] if os.path.isfile(argv[1]) else find_trace_file(argv[1])
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("   LINE %s: %d events, e.g. %s" % (
                line.name, len(events), [e.name for e in events[:4]]))


if __name__ == "__main__":
    main(sys.argv)
