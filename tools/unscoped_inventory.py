#!/usr/bin/env python3
"""What of a benchmark cell's step runs under no program scope, and where
it sits in the compiled step.

    python tools/unscoped_inventory.py --workload <cell> --seed <n>
        [--rows 40] [--json out.json] [--rehearsal]

Builds the cell through its family as ``benchmark/run.py`` does, takes a
few steps, traces ``TRACED_STEPS`` more, reads the trace through
``benchmark/scope_reduce.py`` and the step's optimized HLO through
``trainer.lower_step(x, y).compile().as_text()``.  Every device operation
that no program scope holds (``scope_reduce.named_scopes`` has nothing but
jax's own ``checkpoint``/``rematted_computation``) is printed with its ms
a step and its kind:

- ``program``: its ``op_name`` names the program's code (``jit(...)/...``)
  but no scope: a scope is missing (none after ISSUE 40);
- ``jax scope only``: the path holds ``checkpoint``/``rematted_computation``
  alone; ``unscoped_device_ms`` counts these as scoped;
- ``ragged-dot``: XLA's kernels for a ``lax.ragged_dot``;
- ``compiler <opcode>``: no metadata at all, made by XLA's passes (a
  fusion, ``copy``, ``copy-start``/``copy-done``, ``slice-done``, ``dot``);
- ``other program``: an operation of another device program than the step.

Beside each: the program scope of its nearest producer and of its nearest
consumer in the optimized HLO (innermost two scopes and the phase, as
``scope_reduce.where_from`` names an operation; ``arg <name>`` for a step
argument, ``(step output)`` past the last instruction).

With ``--rehearsal`` (any backend, the configuration's ``rehearsal_size``)
there is no device trace off a TPU: what is printed is the static part,
the step's instructions with program metadata and no scope.  The parser
(:func:`parse_hlo`, :func:`unnamed_program_instructions`) imports nothing
and is what ``tests/test_program_scopes.py`` holds the program to.
"""
from __future__ import annotations

import argparse
import collections
import gzip
import importlib.util
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH = os.path.join(REPO, "benchmark")
OUT_DIR = os.path.join(BENCH, "_out", "inventory")
WARM_STEPS = 3
TRACED_STEPS = 5        # the step's operations repeat: five steps show them

# attributes of an instruction that name the computations it runs as
# control flow (a fusion's ``calls=`` and a reduction's ``to_apply=`` name
# computations that are part of the one instruction)
_CONTROL = ("body", "condition", "true_computation", "false_computation",
            "branch_computations")
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ATTRIBUTE = re.compile(r"\b(%s)=(\{[^}]*\}|%%?[\w.\-]+)" % "|".join(
    _CONTROL + ("to_apply",)))


def _closing(text, at):
    """Index just past the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        depth += text[i] == "("
        depth -= text[i] == ")"
        if depth == 0:
            return i + 1
    return len(text)


def parse_instruction(line):
    """(name, fields) of one instruction of HLO text (a line of the
    module, or the name a TPU trace gives an operation); None for a line
    that is no instruction.  ``fields``: ``opcode``, ``operands`` (names),
    ``op_name`` ('' where there is no metadata), ``root`` and ``calls``
    ({attribute: [computation names]} of the control-flow attributes and
    ``to_apply``)."""
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    root, name, rest = m.group(1), m.group(2), m.group(3)
    at = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    rest = rest[at:].lstrip()
    opcode = re.match(r"[\w\-]+", rest)
    if not opcode:
        return None
    start = opcode.end()
    end = _closing(rest, start) if rest[start:start + 1] == "(" else start
    metadata = _OP_NAME.search(rest, end)
    calls = {}
    for key, value in _ATTRIBUTE.findall(rest[end:]):
        calls[key] = _OPERAND.findall(value) or [value.lstrip("%")]
    return name, {"opcode": opcode.group(0),
                  "operands": _OPERAND.findall(rest[start:end]),
                  "op_name": metadata.group(1) if metadata else "",
                  "root": bool(root), "calls": calls}


def parse_hlo(text):
    """{"entry": name, "computations": {name: {instruction: fields}}} of
    an HLO module's text; each instruction's fields also carry its
    ``computation``."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        header = _HEADER.match(line)
        if header:
            current = header.group(2)
            computations.setdefault(current, {})
            if header.group(1):
                entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        parsed = parse_instruction(line)
        if parsed:
            name, fields = parsed
            fields["computation"] = current
            computations[current][name] = fields
    return {"entry": entry, "computations": computations}


def control_computations(module):
    """The names of the entry computation and of every computation it
    reaches as control flow (loop bodies and conditions, branches, calls):
    the computations whose instructions run as device operations of their
    own, where a fusion's body does not."""
    comps = module["computations"]
    seen, todo = set(), [module["entry"]]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for fields in comps[name].values():
            for key, called in fields["calls"].items():
                if key in _CONTROL or (key == "to_apply"
                                       and fields["opcode"] == "call"):
                    todo.extend(called)
    return seen


def _scope_reduce():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import scope_reduce
    return scope_reduce


def program_scopes(op_name):
    """The program's own scopes in an ``op_name``, jax's
    ``checkpoint``/``rematted_computation`` left out."""
    sr = _scope_reduce()
    return [n for n in sr.named_scopes(op_name)
            if n not in sr.JAX_OWN_SCOPES]


def names_program(op_name):
    """True where the ``op_name`` is a path through the program's code
    (``jit(...)/...``); a step argument's is its name (``train_vals[3]``)
    and a compiler-made instruction has none."""
    return op_name.startswith("jit(")


def unnamed_program_instructions(module):
    """[(computation, instruction, op_name)] of the instructions of the
    entry and control-flow computations whose metadata names the program's
    code and no program scope; the step's arguments are exempt."""
    comps = module["computations"]
    return [(comp, name, fields["op_name"])
            for comp in sorted(control_computations(module))
            for name, fields in comps[comp].items()
            if fields["opcode"] != "parameter"
            and names_program(fields["op_name"])
            and not program_scopes(fields["op_name"])]


def _label(fields):
    """Where an instruction is from, or None where no program scope names
    it: ``l3/kda_conv bwd`` as ``where_from`` has it."""
    if not program_scopes(fields["op_name"]):
        return None
    return _scope_reduce().where_from(fields["op_name"])


class Neighbours:
    """The nearest producer and consumer with a program scope of an
    instruction of a parsed module, by breadth-first walks over operands
    and users; a walk leaves a loop body or branch through its caller."""

    LIMIT = 400          # instructions a walk visits before it gives up

    def __init__(self, module):
        self.module = module
        self.comps = module["computations"]
        self.where = {}
        self.users = collections.defaultdict(list)
        self.callers = collections.defaultdict(list)
        for comp, instructions in self.comps.items():
            for name, fields in instructions.items():
                self.where[name] = comp
                for operand in fields["operands"]:
                    self.users[operand].append(name)
                for called in fields["calls"].values():
                    for c in called:
                        self.callers[c].append(name)

    def _fields(self, name):
        comp = self.where.get(name)
        return None if comp is None else self.comps[comp][name]

    def _walk(self, start, step):
        seen, queue = {start}, collections.deque([start])
        while queue and len(seen) < self.LIMIT:
            for nxt in step(queue.popleft()):
                if nxt in seen:
                    continue
                seen.add(nxt)
                fields = self._fields(nxt)
                if fields is None:
                    continue
                label = _label(fields)
                if label:
                    return label
                queue.append(nxt)
        return None

    def _producers(self, name):
        fields = self._fields(name)
        if fields is None:
            return []
        if fields["opcode"] == "parameter":
            comp = fields["computation"]
            if comp == self.module["entry"]:
                return []
            return self.callers.get(comp, [])
        return fields["operands"]

    def _consumers(self, name):
        fields = self._fields(name)
        if fields is None:
            return []
        out = list(self.users.get(name, []))
        if fields["root"]:
            out += self.callers.get(fields["computation"], [])
        return out

    def producer(self, name):
        fields = self._fields(name)
        if fields is None:
            return None
        found = self._walk(name, self._producers)
        if found:
            return found
        args = [self._fields(n) for n in self._walk_args(name)]
        return "arg %s" % args[0]["op_name"] if args and args[0] else None

    def _walk_args(self, name):
        """The entry parameters a walk over operands reaches first."""
        entry, seen = self.module["entry"], {name}
        queue = collections.deque([name])
        while queue and len(seen) < self.LIMIT:
            cur = queue.popleft()
            fields = self._fields(cur)
            if fields and fields["opcode"] == "parameter" \
                    and fields["computation"] == entry:
                return [cur]
            for nxt in self._producers(cur):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return []

    def consumer(self, name):
        fields = self._fields(name)
        if fields is None:
            return None
        found = self._walk(name, self._consumers)
        if found:
            return found
        if fields["root"] and fields["computation"] == self.module["entry"]:
            return "(step output)"
        return None


def kind_of(own, fields, scopes, in_step):
    """The kind of an operation that no program scope holds."""
    if not in_step:
        return "other program"
    if own.startswith("ragged-dot"):
        return "ragged-dot"
    if names_program(scopes):
        return "jax scope only" if _scope_reduce().named_scopes(scopes) \
            else "program"
    return "compiler %s" % (fields["opcode"] if fields else "?")


def inventory(ops, modules, hlo, steps):
    """[row] of every operation in ``ops`` (``scope_reduce.load``'s
    operations of one chip) that no program scope holds, summed over the
    traced steps by instruction and divided by ``steps``: own name, kind,
    ms a step, producer, consumer, op_name.  ``modules`` are the chip's
    ``XLA Modules`` events; an operation outside the step's is another
    program's."""
    sr = _scope_reduce()
    module = parse_hlo(hlo)
    step_names = set()
    for instructions in module["computations"].values():
        step_names.update(instructions)
    near = Neighbours(module)
    step_module = _step_module(modules, module)
    rows = {}
    for op, ns in sr.self_times(ops):
        if program_scopes(op[3]):
            continue
        own = op[0].split(" = ")[0].lstrip("%")
        in_step = (own in step_names and
                   (step_module is None or _inside(op, modules, step_module)))
        row = rows.get((own, in_step))
        if row is None:
            fields = near._fields(own) if in_step else None
            if fields is None:
                parsed = parse_instruction(op[0])
                fields = parsed[1] if parsed else None
            row = rows[(own, in_step)] = {
                "op": own, "kind": kind_of(own, fields, op[3], in_step),
                "ns": 0.0, "runs": 0,
                "producer": near.producer(own) if in_step else None,
                "consumer": near.consumer(own) if in_step else None,
                "op_name": op[3]}
        row["ns"] += ns
        row["runs"] += 1
    out = []
    for row in rows.values():
        row["ms"] = row.pop("ns") / steps / 1e6
        row["runs"] = row["runs"] / steps
        out.append(row)
    return sorted(out, key=lambda r: -r["ms"])


def _step_module(modules, module):
    """The name the ``XLA Modules`` line gives the step's program (the
    module that ran longest), or None where there is no such line."""
    if not modules:
        return None
    total = collections.Counter()
    for name, start, end in modules:
        total[name] += end - start
    return total.most_common(1)[0][0]


def _inside(op, modules, name):
    for m_name, start, end in modules:
        if m_name == name and start <= op[1] < end:
            return True
    return False


def summary(rows):
    """[(kind, producer, consumer, ms, instructions)] by the three, the
    largest first."""
    table = collections.defaultdict(lambda: [0.0, 0])
    for r in rows:
        key = (r["kind"], r["producer"] or "-", r["consumer"] or "-")
        table[key][0] += r["ms"]
        table[key][1] += 1
    return sorted(((k + tuple(v)) for k, v in table.items()),
                  key=lambda t: -t[3])


def print_report(rows, unscoped_ms, n_rows, out=sys.stdout):
    total = sum(r["ms"] for r in rows)
    counted = sum(r["ms"] for r in rows if r["kind"] != "jax scope only")
    print("unscoped device time: %.3f ms a step (%d instructions); of it "
          "under jax's own scopes only %.3f; unscoped_device_ms's reading "
          "%s" % (total, len(rows), total - counted,
                  "%.3f" % unscoped_ms if unscoped_ms else "none"), file=out)
    by_kind = collections.Counter()
    for r in rows:
        by_kind[r["kind"]] += r["ms"]
    for kind, ms in by_kind.most_common():
        print("   %-28s %10.3f ms" % (kind, ms), file=out)
    print("by kind, nearest producer and consumer (ms a step, share of the "
          "unscoped, running share):", file=out)
    running = 0.0
    for kind, prod, cons, ms, n in summary(rows)[:n_rows]:
        running += ms
        print("   %9.3f %5.1f%% %5.1f%%  %-22s %4d  %s -> %s" % (
            ms, 100 * ms / max(total, 1e-12), 100 * running / max(total, 1e-12),
            kind, n, prod, cons), file=out)
    print("largest instructions:", file=out)
    for r in rows[:n_rows]:
        print("   %9.3f ms %6.1f runs  %-22s %-24s %s -> %s  %s" % (
            r["ms"], r["runs"], r["kind"], r["op"], r["producer"] or "-",
            r["consumer"] or "-", r["op_name"]), file=out)


def _optimized_hlo(trainer, x, y):
    """The step's optimized HLO, compiled again where the executable the
    persistent cache hands back carries no text of it."""
    import jax

    lowered = trainer.lower_step(x, y)
    try:
        text = lowered.compile().as_text()
    except Exception as e:          # noqa: BLE001 -- said, then compiled
        print("compiled step without text (%s): compiling again" % e,
              file=sys.stderr)
        text = None
    if not text:
        jax.config.update("jax_enable_compilation_cache", False)
        text = lowered.compile().as_text()
    return text


def hlo_protos_in_trace(path):
    """How many optimized HLO modules (``Hlo Proto`` statistics of the
    ``/host:metadata`` plane) the profile at ``path`` carries: what a
    reader could bill metadata-less instructions from without compiling."""
    sr = _scope_reduce()
    with open(sr.find_trace_file(path), "rb") as f:
        buf = memoryview(f.read())
    found = 0
    for number, plane in sr._fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, stat_names = "", set()
        for n, v in sr._fields(buf, *plane):
            if n == 2:
                name = sr._text(buf, v)
            elif n == 5:
                _, value = sr._map_entry(buf, v)
                stat_names.update(sr._text(buf, v2) for n2, v2 in
                                  sr._fields(buf, *value) if n2 == 2)
        if name == "/host:metadata" and "Hlo Proto" in stat_names:
            for n, v in sr._fields(buf, *plane):
                if n == 4:
                    found += 1
    return found


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=40)
    parser.add_argument("--json", default=None)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    run = _load_run()
    sr = _scope_reduce()
    sys.path.insert(0, REPO)
    cell, config, traffic, _, _ = run.resolve(
        run.load_json(REPO, "BENCHMARK.json"), args.workload)
    chips = int(cell["chips"])

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    mx.base.use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    size = run.cell_size(config, args.rehearsal)
    mesh = make_mesh((chips,), ("data",), jax.devices()[:chips])
    family = run.load_module("families", config["family"])
    kind = run.load_module("traffic_kinds", traffic["kind"])
    feed = kind.batches(config, size, mesh, args.seed, traffic)
    program = family.build(config, size, mesh, args.seed)
    for i in range(WARM_STEPS):
        program.step(*feed[i % len(feed)])
    program.flush()

    directory = os.path.join(OUT_DIR, args.workload)
    os.makedirs(OUT_DIR, exist_ok=True)
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        for i in range(TRACED_STEPS):
            program.step(*feed[i % len(feed)])
        program.flush()
    finally:
        jax.profiler.stop_trace()
    hlo = _optimized_hlo(program.trainer, *feed[0])
    with gzip.open(os.path.join(directory, "step.hlo.gz"), "wt") as f:
        f.write(hlo)

    module = parse_hlo(hlo)
    unnamed = unnamed_program_instructions(module)
    print("%s: the step's optimized HLO has %d instructions in %d "
          "computations; %d with program metadata and no scope" % (
              args.workload, sum(len(c) for c in
                                 module["computations"].values()),
              len(module["computations"]), len(unnamed)))
    for comp, name, op_name in unnamed:
        print("   %s  %s  %s" % (comp, name, op_name))
    try:
        reduced = sr.load(directory)
        print("the profile's /host:metadata plane holds %d program "
              "descriptions with an Hlo Proto" % hlo_protos_in_trace(
                  directory))
    except FileNotFoundError:
        reduced = {"devices": {}, "modules": {}}
    report = {"workload": args.workload, "seed": args.seed,
              "steps": TRACED_STEPS, "unnamed_program": unnamed, "chips": {}}
    for plane, ops in reduced["devices"].items():
        if not ops:
            continue
        times = sr.device_time(ops)
        unscoped_ms = (times["busy"] - times["scoped"]) / TRACED_STEPS / 1e6
        rows = inventory(ops, reduced["modules"].get(plane), hlo, TRACED_STEPS)
        print("%s: busy %.3f ms a step" % (
            plane, times["busy"] / TRACED_STEPS / 1e6))
        print_report(rows, unscoped_ms, args.rows)
        report["chips"][plane] = {
            "busy_ms": times["busy"] / TRACED_STEPS / 1e6,
            "unscoped_ms": unscoped_ms, "rows": rows}
    if not report["chips"]:
        print("no device plane in the trace (a TPU's is /device:TPU:<n>): "
              "the static part only")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f)
    program.close()
    return 1 if unnamed else 0


if __name__ == "__main__":
    sys.exit(main())
