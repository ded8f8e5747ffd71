"""From a profiler trace (``.xplane.pb``) to device time by program scope,
and to the program's own host spans on the same clock.

``trace_reduce.load`` keeps a device operation's name and interval and
drops the rest.  This module reads what it drops, from the same file:

- each device operation's **scope**: the ``op_name`` of its HLO
  instruction (the trace's ``tf_op`` statistic), e.g.
  ``jit(pure_step)/transpose(jvp(resnetv10))/stage1/conv2d0/conv_general_dilated``.
  The parts between the transforms and the primitive are the program's
  ``jax.named_scope`` names (``docs/observability.md`` "Program scopes").
  **A fusion is billed to the scope of its root instruction**: XLA gives a
  fusion the metadata of its root, so a convolution fused with the
  BatchNorm reductions that follow it is billed to whichever of them is
  the root.
- the ``XLA Modules`` line: one event per device program that ran.
- the program's host spans (``telemetry/trace.py::span``:
  ``train.step``, ``step.*``, ``train.flush``), written into the host plane
  by ``jax.profiler.TraceAnnotation`` with ``span_id``, ``parent_id`` and
  ``step``.

``jax.profiler.ProfileData`` does not hand out the statistics of an event's
metadata, where the scope lives (a test in ``tests/benchmark/test_scope_reduce.py``
fails once it does), so the file is read by a small reader of
the ``XSpace`` wire format (``tsl/profiler/protobuf/xplane.proto``), which
decodes only the lines named above.  One file is parsed once per process.

Rules (``phase_of``): ``transpose(`` in the path = backward (a
rematerialised forward included); ``jvp(`` without it = forward; under
``optimizer_update`` = update; everything else = other.  Time is
**self time**: where events nest, an instant belongs to the innermost one,
so the phases add up to the device's busy time.

    python benchmark/scope_reduce.py <file-or-directory> [--depth N]

prints device time by scope prefix and phase, and every device gap over
100 us with the program span that covers its start.
"""
from __future__ import annotations

import functools
import glob
import os
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(HERE, "_out", "trace")
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
SPAN_PREFIXES = ("train.", "step.")
PHASES = ("forward", "backward", "update", "other")
PHASE_TAGS = {"forward": "fwd", "backward": "bwd", "update": "upd",
              "other": "other"}
UNSCOPED = "(unscoped)"
# names jax itself puts into an ``op_name`` around a ``jax.checkpoint``
JAX_OWN_SCOPES = ("checkpoint", "rematted_computation")
# ``telemetry/trace.py`` names a span by sixteen hexadecimal digits; where
# all sixteen are decimal the profile hands the id back as an integer
SPAN_ID_WIDTH = 16
UPDATE_SCOPE = "optimizer_update"
CONVOLUTION = "conv_general_dilated"
GAP_NS = 100_000


# -- the XSpace wire format --------------------------------------------------
def _varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos, end):
    """(field number, value) of one message: an int for a varint, a
    ``(start, end)`` pair into ``buf`` for a length-delimited field, the
    eight raw bytes for a fixed64 (a double)."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif kind == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif kind == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError("wire type %d in an XSpace" % kind)
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """(name, value) of one ``XStat``."""
    name = value = None
    for number, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf, span):
    key = value = None
    for number, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _event_metadata(buf, span, stat_names):
    """(name, {statistic: value}) of one ``XEventMetadata``."""
    name, stats = "", {}
    for number, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 5:
            key, value = _stat(buf, v, stat_names)
            stats[key] = value
    return name, stats


def _line(buf, span, metadata, stat_names):
    """(name, [(event name, start_ns, end_ns, {statistic: value})]) of
    the events whose metadata is in ``metadata``."""
    name, origin_ns, raw = "", 0, []
    for number, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            origin_ns = _signed(v)
        elif number == 4:
            raw.append(v)
    events = []
    for event in raw:
        meta = offset_ps = duration_ps = 0
        own = None
        for number, v in _fields(buf, *event):
            if number == 1:
                meta = v
                if meta not in metadata:
                    break
            elif number == 2:
                offset_ps = _signed(v)
            elif number == 3:
                duration_ps = _signed(v)
            elif number == 4:
                key, value = _stat(buf, v, stat_names)
                if own is None:
                    own = {}
                own[key] = value
        if meta not in metadata:
            continue
        event_name, stats = metadata[meta]
        if own:
            stats = dict(stats, **own)
        start = origin_ns + offset_ps / 1000.0
        events.append((event_name, start, start + duration_ps / 1000.0,
                       stats))
    return name, events


def read_planes(path, wanted, named=None):
    """{plane name: {line name: events}} of the planes and lines that
    ``wanted(plane name, line name)`` asks for and, of those, the events
    that ``named(plane name, event name)`` asks for.  Nothing else is
    decoded: the host plane of a run holds hundreds of thousands of the
    runtime's own events that no metric reads."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        plane_name, lines, event_meta, stat_meta = "", [], [], []
        for n, v in _fields(buf, *plane):
            if n == 2:
                plane_name = _text(buf, v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                event_meta.append(v)
            elif n == 5:
                stat_meta.append(v)
        line_names = {}
        for span in lines:
            for n, v in _fields(buf, *span):
                if n == 2:
                    line_names[span] = _text(buf, v)
                    break
        keep = [s for s in lines
                if wanted(plane_name, line_names.get(s, ""))]
        if not keep:
            continue
        stat_names = {}
        for span in stat_meta:
            key, value = _map_entry(buf, span)
            for n, v in _fields(buf, *value):
                if n == 2:
                    stat_names[key] = _text(buf, v)
        metadata = {}
        for span in event_meta:
            key, value = _map_entry(buf, span)
            entry = _event_metadata(buf, value, stat_names)
            if named is None or named(plane_name, entry[0]):
                metadata[key] = entry
        out = planes.setdefault(plane_name, {})
        for span in keep:
            name, events = _line(buf, span, metadata, stat_names)
            out.setdefault(name, []).extend(events)
    return planes


# -- from planes to what the metrics read ------------------------------------
def find_trace_file(path):
    """``path`` itself if it is a file, else the newest ``.xplane.pb``
    below it."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return max(found, key=os.path.getmtime)


def _wanted(plane, line):
    if plane.startswith(DEVICE_PLANE):
        return line in (OPS_LINE, MODULES_LINE)
    return plane == HOST_PLANE


def scope_of(stats):
    """The ``op_name`` among an operation's statistics.  The TPU's trace
    writes it as ``tf_op``, in the form ``<op_name>:<op_type>`` with the
    type left empty."""
    return (stats.get(SCOPE_STAT) or "").rstrip(":")


def is_span(name):
    return name.startswith(SPAN_PREFIXES)


def _named(plane, name):
    return plane != HOST_PLANE or is_span(name)


_LOADED = {}


def load(path):
    """{"devices": {plane: [(name, start_ns, end_ns, scope)]},
    "modules": {plane: [(name, start_ns, end_ns)]},
    "spans": [(name, start_ns, end_ns, span_id, parent_id, step)]},
    each list sorted by start, the ids as text.  Parsed once per file and
    process."""
    path = find_trace_file(path)
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key in _LOADED:
        return _LOADED[key]
    devices, modules, spans = {}, {}, []
    for plane, lines in read_planes(path, _wanted, _named).items():
        if plane.startswith(DEVICE_PLANE):
            devices[plane] = sorted(
                ((name, start, end, scope_of(stats))
                 for name, start, end, stats in lines.get(OPS_LINE, [])),
                key=lambda e: e[1])
            modules[plane] = sorted(
                ((name, start, end)
                 for name, start, end, _ in lines.get(MODULES_LINE, [])),
                key=lambda e: e[1])
        else:
            for events in lines.values():
                spans.extend(
                    (name, start, end, _span_id(stats.get("span_id")),
                     _span_id(stats.get("parent_id")),
                     _step(stats.get("step")))
                    for name, start, end, stats in events)
    _LOADED.clear()
    _LOADED[key] = {"devices": devices, "modules": modules,
                    "spans": sorted(spans, key=lambda e: e[1])}
    return _LOADED[key]


def _span_id(value):
    """A span's id as the program wrote it: text.  The profile keeps a
    statistic that reads as a whole number as one, which drops the zeros
    an id began with."""
    if isinstance(value, int):
        return "%0*d" % (SPAN_ID_WIDTH, value)
    return value


def _step(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        return None


def _process_started():
    """When this process started, on ``os.path.getmtime``'s clock."""
    try:
        return os.stat("/proc/%d" % os.getpid()).st_mtime
    except OSError:
        return 0.0


def of_run(run):
    """What ``load`` gives for the trace that ``run.py`` has just written:
    the newest ``.xplane.pb`` under ``benchmark/_out/trace/`` that is
    younger than this process (``run`` does not carry the path).  None
    where the run traced nothing, no device plane came of it, or no such
    file is found."""
    trace = run.get("trace")
    if not trace or not trace.get("devices") or not run.get("traced_steps"):
        return None
    started = _process_started()
    found = [p for p in glob.glob(os.path.join(
        TRACE_ROOT, "*", "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(p) >= started - 1.0]
    if not found:
        return None
    return load(max(found, key=os.path.getmtime))


# -- reductions over plain lists (the tests hand them hand-made ones) --------
def path_parts(scope):
    """The ``/``-separated parts of an ``op_name``; a ``/`` inside
    parentheses does not separate."""
    return list(_path_parts(scope))


@functools.lru_cache(maxsize=None)
def _path_parts(scope):
    """A step's 160,000 traced operations carry some 500 ``op_name``s, and
    a dozen readers ask for each: split once."""
    parts, depth, part = [], 0, []
    for ch in scope:
        if ch == "/" and depth == 0:
            parts.append("".join(part))
            part = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        part.append(ch)
    parts.append("".join(part))
    return tuple(p for p in parts if p)


def named_scopes(scope):
    """The program's own scope names in an ``op_name``: what is left when
    the enclosing ``jit(...)``s, the transforms' wrappers (``jvp(x)`` ->
    ``x``) and the primitive at the end are taken away.  Empty for an
    operation that no ``jax.named_scope`` of the program encloses."""
    names = []
    for part in path_parts(scope)[:-1]:
        while part.endswith(")"):
            head, inner = part.split("(", 1)
            part = "" if head == "jit" else inner[:-1]
        if part:
            names.append(part)
    return names


def phase_of(scope):
    if "transpose(" in scope:
        return "backward"
    if "jvp(" in scope:
        return "forward"
    if UPDATE_SCOPE in named_scopes(scope):
        return "update"
    return "other"


def is_convolution(scope):
    parts = path_parts(scope)
    return bool(parts) and parts[-1].startswith(CONVOLUTION)


def self_times(ops):
    """[(op, self_ns)]: each operation's time less the time of the
    operations nested inside it, so that the whole adds up to the union
    of the intervals.  ``ops`` are ``(name, start, end, ...)`` tuples of
    one line: nested or disjoint, never crossing."""
    out, stack = [], []        # stack of [op, self time so far, resume at]

    def close(until):
        while stack and stack[-1][0][2] <= until:
            op, took, resume = stack.pop()
            out.append((op, took + max(0.0, op[2] - resume)))
            if stack:
                stack[-1][2] = max(stack[-1][2], op[2])

    for op in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(op[1])
        if stack:
            stack[-1][1] += max(0.0, op[1] - stack[-1][2])
            stack[-1][2] = max(stack[-1][2], op[1])
        stack.append([op, 0.0, op[1]])
    close(float("inf"))
    return out


def device_time(ops):
    """{"forward" | "backward" | "update" | "other" | "convolution" |
    "scoped" | "busy": ns} of one device's operations (self time)."""
    total = dict.fromkeys(PHASES + ("convolution", "scoped", "busy"), 0.0)
    for op, ns in self_times(ops):
        scope = op[3]
        total[phase_of(scope)] += ns
        total["busy"] += ns
        if is_convolution(scope):
            total["convolution"] += ns
        if named_scopes(scope):
            total["scoped"] += ns
    return total


def mean_device_time(reduced):
    """``device_time`` averaged over the chips of a ``load`` result, or
    None where no device operation was traced."""
    times = [device_time(ops) for ops in reduced["devices"].values() if ops]
    if not times:
        return None
    return {k: sum(t[k] for t in times) / len(times) for k in times[0]}


def per_step_ms(run, key):
    """``device_time``'s ``key`` of the run's trace in ms per traced step,
    or None where there is nothing to read: no trace, no operation that
    carries an ``op_name``, or none under ``key``."""
    reduced = of_run(run)
    times = mean_device_time(reduced) if reduced else None
    if not times or not times[key]:
        return None
    return times[key] / run["traced_steps"] / 1e6


def scope_ms(run, names):
    """Device time of the run's trace in ms per traced step, averaged over
    the chips, of the operations whose ``named_scopes`` hold any of
    ``names``: self time, forward, re-run and backward together.  None
    where there is no trace or no operation under such a scope."""
    reduced = of_run(run)
    if not reduced:
        return None
    wanted = frozenset(names)
    per_chip = [sum(ns for op, ns in self_times(ops)
                    if wanted.intersection(named_scopes(op[3])))
                for ops in reduced["devices"].values() if ops]
    if not per_chip or not sum(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / run["traced_steps"] / 1e6


def where_from(scope):
    """Where an operation is from, for a reader of the ledger: the
    innermost two of the program's scopes around it and its phase
    (``l3/gated_mlp bwd``, ``(unscoped) other``).  A re-run forward pass
    is ``bwd``, as ``phase_of`` has it."""
    names = [n for n in named_scopes(scope) if n not in JAX_OWN_SCOPES]
    return "%s %s" % ("/".join(names[-2:]) or UNSCOPED,
                      PHASE_TAGS[phase_of(scope)])


def programs_per_step(reduced, traced_steps):
    """Device programs that ran per traced step, averaged over the chips:
    the events of the ``XLA Modules`` line / steps."""
    counts = [len(m) for m in reduced["modules"].values()]
    if not counts or not traced_steps:
        return None
    return sum(counts) / len(counts) / traced_steps


def by_prefix(ops, depth):
    """{(scope prefix of ``depth`` names, phase): self ns}."""
    table = {}
    for op, ns in self_times(ops):
        names = named_scopes(op[3])
        key = ("/".join(names[:depth]) or UNSCOPED, phase_of(op[3]))
        table[key] = table.get(key, 0.0) + ns
    return table


def gaps(ops, spans, least_ns=GAP_NS):
    """[(start_ns, length_ns, span name or None)] of the device's idle
    gaps of at least ``least_ns``, each with the innermost program span
    that covers the gap's start."""
    out, busy_until = [], None
    for op in sorted(ops, key=lambda e: e[1]):
        if busy_until is not None and op[1] - busy_until >= least_ns:
            covering = [s for s in spans if s[1] <= busy_until < s[2]]
            inner = max(covering, key=lambda s: s[1]) if covering else None
            out.append((busy_until, op[1] - busy_until,
                        inner[0] if inner else None))
        busy_until = op[2] if busy_until is None else max(busy_until, op[2])
    return out


def main(argv):
    depth = 2
    if "--depth" in argv:
        at = argv.index("--depth")
        depth = int(argv[at + 1])
        del argv[at:at + 2]
    reduced = load(argv[1])
    spans = reduced["spans"]
    print("program spans: %d" % len(spans))
    by_name = {}
    for name, start, end, _, _, _ in spans:
        n, ns = by_name.get(name, (0, 0.0))
        by_name[name] = (n + 1, ns + end - start)
    for name, (n, ns) in sorted(by_name.items()):
        print("   %-20s %6d x %10.3f ms" % (name, n, ns / n / 1e6))
    for plane, ops in reduced["devices"].items():
        total = device_time(ops)
        print("%s: %d operations, %d programs, busy %.3f ms" % (
            plane, len(ops), len(reduced["modules"][plane]),
            total["busy"] / 1e6))
        for key in PHASES + ("convolution", "scoped"):
            print("   %-12s %10.3f ms  %5.1f%%" % (
                key, total[key] / 1e6,
                100.0 * total[key] / max(total["busy"], 1.0)))
        print("   by scope prefix (depth %d) and phase:" % depth)
        for (prefix, phase), ns in sorted(by_prefix(ops, depth).items(),
                                          key=lambda kv: -kv[1]):
            print("   %10.3f ms  %5.1f%%  %-9s %s" % (
                ns / 1e6, 100.0 * ns / max(total["busy"], 1.0), phase,
                prefix))
        print("   device gaps of %d us and more:" % (GAP_NS // 1000))
        for start, length, name in gaps(ops, spans):
            print("   %10.3f ms  at %.0f ns  host in %s" % (
                length / 1e6, start, name or "no program span"))


if __name__ == "__main__":
    main(sys.argv)
