"""Crash-surviving flight recorder: an mmap-backed bounded ring of recent
structured events per process.

The PR-6/7 chaos scenarios SIGKILL ranks mid-training; until now a killed
process left *zero* telemetry behind — its profiler buffer, stats and
logs all died with it.  This ring does not: events are written into an
``mmap`` of a regular file, so the bytes live in the page cache the
moment the store instruction retires — a SIGKILL (or any process death
short of kernel panic/power loss) leaves them durable on disk with no
flush on the hot path.

File layout (all little-endian)::

    [header 48B]  magic "MXTPURNG" | u32 version | u32 slot_size
                  | u32 n_slots | u32 meta_len | u64 seq
                  | u64 cursor_step | u64 cursor_ts_ns
    [meta]        meta_len bytes of JSON (rank/role/pid/clock origin)
    [slots]       n_slots fixed-size slots:
                  u32 payload_len | u32 crc32(payload) | payload JSON

Write protocol (single process, lock-guarded): write the slot at
``seq % n_slots``, then store the incremented ``seq`` into the header.
A reader orders slots by the header ``seq`` and drops any slot whose CRC
fails — the one event a crash tore mid-write is lost, every older event
survives intact.

The header also carries a **progress cursor** (``cursor_step`` /
``cursor_ts_ns``): a fixed-size struct-packed store updated by
:meth:`FlightRecorder.set_cursor` with no JSON, no allocation and no
slot consumed — cheap enough for a *per-training-step* probe on the
trainer's dispatch path (a full ``record()`` per step measurably is
not, on a 1-core host where host python competes with XLA compute).  A
SIGKILLed worker's ring thus answers "how far did it train" exactly.

:func:`postmortem` reconstructs the last-N-events-per-rank story of a
dead fleet from a directory of rings — the ``python -m mxnet_tpu.telemetry
postmortem <dir>`` CLI.

Stdlib-only (no jax/numpy): rings must be writable from the PS server,
launchers and pipeline workers alike.
"""
from __future__ import annotations

import glob
import json
import mmap
import os
import struct
import threading
import time
import zlib

__all__ = ["FlightRecorder", "read_ring", "postmortem",
           "render_postmortem", "RING_SUFFIX"]

_MAGIC = b"MXTPURNG"
_VERSION = 1
# magic, version, slot_bytes, n_slots, meta_len, seq, cursor_step,
# cursor_ts_ns
_HEADER = struct.Struct("<8sIIIIQQQ")
_SLOT_HDR = struct.Struct("<II")       # payload_len, crc32
_SEQ_OFFSET = 8 + 4 + 4 + 4 + 4        # byte offset of the u64 seq field
_CURSOR_OFFSET = _SEQ_OFFSET + 8       # u64 step | u64 ts_ns
_CURSOR = struct.Struct("<QQ")

RING_SUFFIX = ".mxring"

DEFAULT_SLOTS = 512
DEFAULT_SLOT_BYTES = 512


class FlightRecorder:
    """Single-writer event ring over one mmap'd file.

    ``meta`` identifies the process (rank/role) and records the clock
    origin: event ``ts_ns`` is ``time.perf_counter_ns()`` (the clock the
    profiler and the PS clock-offset estimation use), ``wall_ns`` is
    ``time.time_ns()`` for humans.  ``record()`` is the hot path: one
    dict -> compact JSON -> memcpy + header seq store, a few µs."""

    def __init__(self, path, slots=DEFAULT_SLOTS,
                 slot_bytes=DEFAULT_SLOT_BYTES, meta=None):
        if slots < 1 or slot_bytes < _SLOT_HDR.size + 2:
            raise ValueError("ring needs >=1 slot of >=%d bytes"
                             % (_SLOT_HDR.size + 2))
        self.path = str(path)
        self._slots = int(slots)
        self._slot_bytes = int(slot_bytes)
        self._lock = threading.Lock()
        self._seq = 0
        meta = dict(meta or {})
        meta.setdefault("pid", os.getpid())
        meta.setdefault("perf_origin_ns", time.perf_counter_ns())
        meta.setdefault("wall_origin_ns", time.time_ns())
        self.meta = meta
        meta_blob = json.dumps(meta, separators=(",", ":")).encode()
        total = _HEADER.size + len(meta_blob) \
            + self._slots * self._slot_bytes
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # O_EXCL-free: a respawned process reuses pid-suffixed names only
        # by collision; truncating an old ring of the same name is the
        # documented overwrite semantic
        self._f = open(self.path, "w+b")
        self._f.truncate(total)
        self._mm = mmap.mmap(self._f.fileno(), total)
        self._meta_len = len(meta_blob)
        self._data_off = _HEADER.size + self._meta_len
        self._mm[:_HEADER.size] = _HEADER.pack(
            _MAGIC, _VERSION, self._slot_bytes, self._slots,
            self._meta_len, 0, 0, 0)
        self._mm[_HEADER.size:self._data_off] = meta_blob
        self._closed = False

    def set_cursor(self, step, ts_ns=None):
        """The per-step fast path: store the progress cursor into the
        fixed header field — one struct pack + mmap store, no JSON, no
        slot.  Torn reads are impossible for a post-SIGKILL reader
        because the process is dead when the ring is read; the lock is
        against ``close()`` invalidating the mmap mid-store (an
        uncontended acquire is noise next to the pack+store)."""
        with self._lock:
            if self._closed:
                return
            self._mm[_CURSOR_OFFSET:_CURSOR_OFFSET + _CURSOR.size] = \
                _CURSOR.pack(int(step),
                             time.perf_counter_ns() if ts_ns is None
                             else int(ts_ns))

    def record(self, kind, **fields):
        """Append one event; returns its sequence number.  Oversized
        payloads are truncated to the slot (``"truncated": 1`` marks
        it) — the ring never blocks and never grows."""
        payload = dict(fields)
        payload["kind"] = str(kind)
        payload["ts_ns"] = time.perf_counter_ns()
        payload["wall_ns"] = time.time_ns()
        blob = json.dumps(payload, separators=(",", ":"),
                          default=str).encode()
        cap = self._slot_bytes - _SLOT_HDR.size
        if len(blob) > cap:
            payload["truncated"] = 1
            for key in sorted(fields, key=lambda k: -len(str(fields[k]))):
                payload.pop(key, None)
                blob = json.dumps(payload, separators=(",", ":"),
                                  default=str).encode()
                if len(blob) <= cap:
                    break
            blob = blob[:cap]
        with self._lock:
            if self._closed:
                return -1
            payload_seq = self._seq
            off = self._data_off \
                + (payload_seq % self._slots) * self._slot_bytes
            self._mm[off:off + _SLOT_HDR.size] = _SLOT_HDR.pack(
                len(blob), zlib.crc32(blob) & 0xFFFFFFFF)
            self._mm[off + _SLOT_HDR.size:
                     off + _SLOT_HDR.size + len(blob)] = blob
            self._seq = payload_seq + 1
            # the seq store is the commit point: a reader never trusts a
            # slot the header does not yet cover
            self._mm[_SEQ_OFFSET:_SEQ_OFFSET + 8] = struct.pack(
                "<Q", self._seq)
        return payload_seq

    def flush(self):
        """msync the ring (only needed for machine-death durability; a
        SIGKILL'd process keeps its page-cache writes without this)."""
        with self._lock:
            if not self._closed:
                self._mm.flush()

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._mm.flush()
            self._mm.close()
            self._f.close()


def read_ring(path):
    """Read one ring file -> ``(meta, events)`` with events in write
    order (oldest surviving first).  Torn or overwritten-in-flight slots
    are dropped via CRC; a truncated/garbage file raises ValueError."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < _HEADER.size:
        raise ValueError("%s: not a flight ring (too short)" % path)
    magic, version, slot_bytes, n_slots, meta_len, seq, cur_step, \
        cur_ts = _HEADER.unpack_from(raw, 0)
    if magic != _MAGIC:
        raise ValueError("%s: bad magic %r" % (path, magic))
    if version != _VERSION:
        raise ValueError("%s: unsupported ring version %d" % (path, version))
    meta = json.loads(raw[_HEADER.size:_HEADER.size + meta_len] or b"{}")
    if cur_ts:
        meta["cursor_step"] = cur_step
        meta["cursor_ts_ns"] = cur_ts
    data_off = _HEADER.size + meta_len
    first = max(0, seq - n_slots)
    events = []
    for s in range(first, seq):
        off = data_off + (s % n_slots) * slot_bytes
        if off + _SLOT_HDR.size > len(raw):
            continue
        plen, crc = _SLOT_HDR.unpack_from(raw, off)
        body = raw[off + _SLOT_HDR.size:off + _SLOT_HDR.size + plen]
        if len(body) != plen or (zlib.crc32(body) & 0xFFFFFFFF) != crc:
            continue   # torn write (the crash point) — drop just this one
        try:
            ev = json.loads(body)
        except ValueError:
            continue
        ev["seq"] = s
        events.append(ev)
    return meta, events


def postmortem(directory, last=None):
    """Reconstruct the fleet's last moments from every ring under
    ``directory``: ``{"rings": [{"file", "meta", "events", "last_apply",
    "faults"}, ...]}`` with per-ring derived fields —

    - ``last_apply``: the newest ``ps.apply`` event (the PS server's
      last applied ``(rank, push_step)`` — the headline question after a
      server SIGKILL);
    - ``faults``: every ``chaos.fault`` event (what the chaos harness
      injected, with its trace context).
    """
    out = []
    for path in sorted(glob.glob(os.path.join(str(directory),
                                              "*" + RING_SUFFIX))):
        try:
            meta, events = read_ring(path)
        except (OSError, ValueError) as e:
            out.append({"file": path, "error": str(e)})
            continue
        if last:
            events = events[-int(last):]
        applies = [e for e in events if e.get("kind") == "ps.apply"]
        out.append({
            "file": path,
            "meta": meta,
            "events": events,
            "last_apply": applies[-1] if applies else None,
            "faults": [e for e in events if e.get("kind") == "chaos.fault"],
        })
    return {"rings": out}


def render_postmortem(report):
    """Human-readable postmortem (the CLI's default output)."""
    lines = []
    for ring in report["rings"]:
        if "error" in ring:
            lines.append("== %s: UNREADABLE (%s)" % (ring["file"],
                                                     ring["error"]))
            continue
        meta = ring["meta"]
        who = "%s rank=%s pid=%s" % (meta.get("role", "?"),
                                     meta.get("rank", "?"),
                                     meta.get("pid", "?"))
        lines.append("== %s (%s): %d surviving events"
                     % (os.path.basename(ring["file"]), who,
                        len(ring["events"])))
        if "cursor_step" in meta:
            lines.append("   progress cursor: step %d" % meta["cursor_step"])
        la = ring["last_apply"]
        if la is not None:
            lines.append("   last applied push: rank=%s push_step=%s "
                         "key=%s" % (la.get("rank"), la.get("step"),
                                     la.get("key")))
        for f in ring["faults"]:
            lines.append("   FAULT %s@%s action=%s ctx=%s trace=%s"
                         % (f.get("site"), f.get("at"), f.get("action"),
                            f.get("ctx"), f.get("trace_id")))
        for e in ring["events"][-10:]:
            extra = {k: v for k, v in e.items()
                     if k not in ("kind", "ts_ns", "wall_ns", "seq")}
            lines.append("   [%6d] %-16s %s" % (e["seq"], e["kind"], extra))
    return "\n".join(lines) + "\n"
