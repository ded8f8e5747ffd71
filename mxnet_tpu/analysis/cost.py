"""mxcost: static per-op cost/memory analysis over closed jaxprs.

TVM (PAPERS.md) drives its optimizing compiler with a learned cost
model; XLA exposes a post-compile ``cost_analysis()`` — but both need a
working backend.  This module is the hardware-free counterpart: an
abstract interpreter over a ``ClosedJaxpr`` that never executes (and
never compiles) anything, so it runs on the 1-core CI host even when the
TPU is down.  It produces, per primitive
and per program:

- **flops** / **transcendentals** — counted with the same conventions as
  XLA's HLO cost analysis (2·M·N·K dots, padding-blind convs, tree-free
  ``in-out`` reduces, 1/elem arithmetic), cross-validated on CPU against
  ``jit(f).lower().compile().cost_analysis()`` within ``XLA_FLOP_RTOL``;
- **bytes read / written** — unfused upper bound: every eqn reads its
  operand avals and writes its outputs (XLA fusion only lowers this);
- **host↔device transfer bytes** — caller classifies which invars are
  host-fed and which outputs are fetched;
- **collective bytes per mesh axis** — ring formulas over explicit
  ``psum``/``all_gather``/… eqns (trace with ``axis_env`` to get them);
- **peak HBM** — liveness walk over the (recursively inlined) eqn tape:
  non-donated inputs and consts are resident for the whole program,
  donated inputs die at last use, intermediates die at last use.

Everything is deterministic (``--self-check`` asserts two runs produce
identical reports) and pure-Python over aval metadata, so the checked-in
``STATIC_BUDGETS.json`` can gate PRs in CI with no accelerator attached.
"""
from __future__ import annotations

import math

import numpy as _np

__all__ = ["CostReport", "TapeOp", "build_tape", "analyze_jaxpr",
           "analyze_fn", "analyze_symbol", "XLA_FLOP_RTOL",
           "collective_bytes", "ring_bytes_per_axis",
           "unpriced_findings", "TRANSCENDENTALS",
           "KERNEL_COSTS", "declare_kernel_cost", "kernel_name_of"]

# documented cross-validation tolerance: |modeled - xla| / xla for the
# golden single-primitive programs of tests/test_analysis.py on the CPU
# backend.  The residual is XLA being padding-aware for SAME convs and
# power-of-two rounding in tree reduces; dots match exactly.
XLA_FLOP_RTOL = 0.05

# elementwise primitives costed as transcendentals (XLA's separate
# counter), not flops
TRANSCENDENTALS = frozenset({
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "sin", "cos", "tan",
    "asin", "acos", "atan", "atan2", "sinh", "cosh", "asinh", "acosh",
    "atanh", "erf", "erfc", "erf_inv", "logistic", "rsqrt", "sqrt",
    "cbrt", "pow", "lgamma", "digamma",
})

# zero-arithmetic data movement: bytes, no flops
_MOVEMENT = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad", "rev",
    "gather", "convert_element_type", "bitcast_convert_type", "iota",
    "copy", "select_n", "stop_gradient", "split", "expand_dims",
    "device_put", "real", "imag", "sharding_constraint",
})

# collective primitives and their per-device wire-bytes model over a
# group of size K (ring algorithms; docs/analysis.md "Cost model").  A
# grouped reduction (``psum`` over several axes at once) is priced as ONE
# ring over the combined group (K = product of the axis sizes) — XLA
# lowers a multi-axis reduction to a single replica group, not a
# hierarchy — and the total is attributed per axis proportionally to
# each axis's (size − 1) share (the marginal ring length it adds):
#   psum (all-reduce)     2·(K-1)/K · payload
#   all_gather            (K-1)/K · output   (output = K · input)
#   reduce_scatter        (K-1)/K · input
#   all_to_all            (K-1)/K · payload
#   ppermute              payload  (one hop; a ring is K scanned hops)
_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "all_gather", "reduce_scatter", "all_to_all",
    "ppermute", "pbroadcast",
})

# primitives that carry a mesh-axis name but move nothing over the wire
# (axis arithmetic / replication-type casts) — they must NOT be flagged
# as unpriced collectives
_AXIS_LOCAL = frozenset({
    "axis_index", "pvary", "psum_invariant", "pbroadcast_invariant",
    "sharding_constraint",
})


def _numel(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _aval_bytes(aval):
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    try:
        itemsize = _np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (custom PRNG keys): key data is uint32[2]
        itemsize = 8
    return _numel(shape) * itemsize


def collective_bytes(prim, payload_bytes, axis_size, out_bytes=None):
    """Per-device wire bytes for one collective over a group of size K.

    ``payload_bytes`` is the operand (input) size; ``out_bytes`` the
    result size where the formula needs it (``all_gather`` moves the
    *output* — defaults to ``K · payload`` for it, tiled semantics).
    """
    k = max(int(axis_size), 1)
    if k == 1:
        return 0
    if prim in ("psum", "pmax", "pmin"):
        return int(2 * (k - 1) * payload_bytes // k)
    if prim == "all_gather":
        out = payload_bytes * k if out_bytes is None else out_bytes
        return int((k - 1) * out // k)
    if prim in ("reduce_scatter", "all_to_all", "pbroadcast"):
        return int((k - 1) * payload_bytes // k)
    return int(payload_bytes)


def ring_bytes_per_axis(prim, in_bytes, out_bytes, axis_sizes):
    """{axis: wire bytes} for one collective over the (possibly grouped)
    axes in ``axis_sizes`` — one ring over the combined group
    K = Π sizes, attributed per axis proportionally to (size − 1), the
    marginal ring length each axis contributes (remainder bytes go to
    the first axis in sorted order, keeping the split deterministic and
    the per-axis sum exactly equal to the group total)."""
    sizes = {ax: max(int(s), 1) for ax, s in axis_sizes.items()}
    group = 1
    for s in sizes.values():
        group *= s
    total = collective_bytes(prim, in_bytes, group, out_bytes=out_bytes)
    if total == 0 or not sizes:
        return {ax: 0 for ax in sizes}
    weights = {ax: s - 1 for ax, s in sizes.items()}
    wsum = sum(weights.values())
    if wsum == 0:
        return {ax: 0 for ax in sizes}
    out = {}
    assigned = 0
    for ax in sorted(sizes)[1:]:
        out[ax] = total * weights[ax] // wsum
        assigned += out[ax]
    first = sorted(sizes)[0]
    out[first] = total - assigned
    return out


def _axis_names(params):
    axes = params.get("axes", params.get("axis_name", ()))
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,)


# ---------------------------------------------------------------------------
# kernel-declared cost models for pallas_call
# ---------------------------------------------------------------------------
# kernel fn name -> cost fn(eqn) -> {"flops", "transcendentals",
# "bytes_read", "bytes_written"}.  A ``pallas_call`` severs jaxpr
# dataflow (the kernel body sees refs, not the call operands) and its
# body is traced once — not once per grid step — so walking it prices
# the kernel wrong in BOTH directions.  A shipped kernel therefore
# DECLARES its cost here (shape arithmetic over the eqn's operand avals
# + grid, deterministic); the tape consults the registry BEFORE falling
# back to the body-walk + zero-cost connector, and an unannotated
# shipped kernel is NAMED (``Tape.unpriced_kernels`` -> COST005) instead
# of silently costing near-zero.  Keying is the kernel *function name*,
# which every shipped ``pallas_call`` also passes as ``name=`` so that a
# device trace shows the kernel under it — the same name the
# ``lint_kernel_costs`` AST sweep resolves.
KERNEL_COSTS = {}


def declare_kernel_cost(kernel_name):
    """Decorator: register ``fn(eqn) -> cost dict`` for a Pallas kernel
    (keyed by the kernel function's name as it appears in the traced
    ``pallas_call`` eqn)."""
    def wrap(fn):
        KERNEL_COSTS[str(kernel_name)] = fn
        return fn
    return wrap


def kernel_name_of(eqn):
    """The registry key of a traced ``pallas_call`` eqn: its ``name=``
    or, for an unnamed call, the kernel function's own name from the
    kernel jaxpr's debug info (stable through ``functools.partial``)."""
    return eqn.params["name"] or eqn.params["jaxpr"].debug_info.func_name


def _grid_of(eqn):
    grid = getattr(eqn.params.get("grid_mapping"), "grid", ()) or ()
    return tuple(int(g) for g in grid if isinstance(g, int))


# ---------------------------------------------------------------------------
# per-primitive flop models
# ---------------------------------------------------------------------------
def _dot_general_flops(eqn):
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = _numel([lhs.shape[d] for d in lb])
    contract = _numel([lhs.shape[d] for d in lc])
    lfree = _numel([d for i, d in enumerate(lhs.shape)
                    if i not in set(lc) | set(lb)])
    rfree = _numel([d for i, d in enumerate(rhs.shape)
                    if i not in set(rc) | set(rb)])
    return 2 * batch * lfree * rfree * contract


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    rhs_spec = dn.rhs_spec  # (out_c, in_c, *spatial)
    in_c = int(rhs.shape[rhs_spec[1]])
    kernel_spatial = _numel([rhs.shape[d] for d in rhs_spec[2:]])
    groups = int(eqn.params.get("feature_group_count", 1)) or 1
    # in_c here is already per-group (rhs carries IC/groups), so no
    # further division; batch_group_count folds into the out numel
    del groups
    return 2 * _numel(out.shape) * in_c * kernel_spatial


def _eqn_cost(eqn):
    """(flops, transcendentals) for one eqn — shapes only, no values."""
    prim = eqn.primitive.name
    out_n = sum(_numel(getattr(v.aval, "shape", ())) for v in eqn.outvars)
    in_n = sum(_numel(getattr(v.aval, "shape", ())) for v in eqn.invars)
    if prim == "dot_general":
        return _dot_general_flops(eqn), 0
    if prim == "conv_general_dilated":
        return _conv_flops(eqn), 0
    if prim in TRANSCENDENTALS:
        return 0, out_n
    if prim in _MOVEMENT:
        return 0, 0
    if prim.startswith("reduce_window"):
        window = _numel(eqn.params.get("window_dimensions", ()))
        return out_n * max(window - 1, 1), 0
    if prim.startswith("reduce_") or prim in ("argmax", "argmin"):
        return max(in_n - out_n, 0), 0
    if prim == "select_and_scatter_add":
        return in_n, 0
    if prim.startswith("scatter"):
        updates = _numel(getattr(eqn.invars[-1].aval, "shape", ()))
        return updates if prim != "scatter" else 0, 0
    if prim.startswith("cum"):
        return in_n, 0
    if prim == "sort":
        n = max(out_n, 2)
        return int(n * math.ceil(math.log2(n))), 0
    if prim in _COLLECTIVES:
        # the arithmetic of an all-reduce is counted; wire bytes are
        # tracked separately in TapeOp.collective
        return out_n if prim in ("psum", "pmax", "pmin") else 0, 0
    if prim == "integer_pow":
        return out_n, 0
    # default: one arithmetic op per output element (add/mul/compare/...)
    return out_n, 0


# ---------------------------------------------------------------------------
# the tape: recursively inlined eqn sequence with stable var ids
# ---------------------------------------------------------------------------
class TapeOp:
    """One (inlined) eqn: primitive, scaled cost, operand/result ids."""
    __slots__ = ("prim", "scale", "in_ids", "out_ids", "flops",
                 "transcendentals", "bytes_read", "bytes_written",
                 "collective", "axes", "params")

    def __init__(self, prim, scale, in_ids, out_ids, flops, trans,
                 bytes_read, bytes_written, collective, axes, params):
        self.prim = prim
        self.scale = scale
        self.in_ids = in_ids
        self.out_ids = out_ids
        self.flops = flops
        self.transcendentals = trans
        self.bytes_read = bytes_read
        self.bytes_written = bytes_written
        self.collective = collective  # {axis_name: bytes}
        self.axes = axes
        self.params = params


class Tape:
    """Flat program tape + var table, shared by the cost totals, the
    liveness walk, the DST variance pass and the mxshard propagation."""

    def __init__(self):
        self.ops = []            # [TapeOp]
        self.avals = {}          # id -> aval
        self.invar_ids = []      # program inputs, in order
        self.outvar_ids = []     # program outputs, in order
        self.const_ids = []      # closure constants
        self.literal_ids = set()  # inline literals (e.g. the 1 in psum(1))
        self.literal_values = {}  # id -> literal value (mxgen emits these)
        self.unpriced = []       # [(prim, axis, reason)] — COST004 feed
        self.unpriced_kernels = []  # [kernel name] — COST005 feed
        self.unbounded_loops = False
        self._next = 0

    def fresh(self, aval, literal=False):
        i = self._next
        self._next += 1
        self.avals[i] = aval
        if literal:
            self.literal_ids.add(i)
        return i


def _sub_jaxprs(params):
    """(name, ClosedJaxpr/Jaxpr) children of an eqn's params."""
    out = []
    for k, v in params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                out.append((k, item.jaxpr, item.consts))
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                out.append((k, item, ()))
    return out


def build_tape(closed_jaxpr, axis_sizes=None):
    """Inline a ClosedJaxpr (through pjit / custom_jvp / remat / scan /
    cond / while) into a flat Tape.  ``axis_sizes`` maps mesh-axis name →
    size for the collective-bytes model (defaults to the jaxpr's bound
    axis sizes where visible, else 1)."""
    from jax.extend.core import ClosedJaxpr, Literal

    axis_sizes = dict(axis_sizes or {})
    tape = Tape()

    def read(env, atom):
        if isinstance(atom, Literal):
            i = tape.fresh(atom.aval, literal=True)
            tape.literal_values[i] = atom.val
            return i
        return env[atom]

    def bind_out(env, var):
        i = tape.fresh(var.aval)
        env[var] = i
        return i

    def walk(jaxpr, consts, env, scale):
        for cv, cval in zip(jaxpr.constvars, consts):
            if cv not in env:
                i = tape.fresh(cv.aval)
                env[cv] = i
                tape.const_ids.append(i)
        for eqn in jaxpr.eqns:
            subs = _sub_jaxprs(eqn.params)
            prim = eqn.primitive.name
            if subs:
                _walk_call(prim, eqn, subs, env, scale)
                continue
            in_ids = tuple(read(env, a) for a in eqn.invars)
            out_ids = tuple(bind_out(env, v) for v in eqn.outvars)
            flops, trans = _eqn_cost(eqn)
            br = sum(_aval_bytes(a.aval) for a in eqn.invars)
            bw = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            coll = {}
            eqn_axes = _axis_names(eqn.params)
            if prim in _COLLECTIVES:
                payload = sum(_aval_bytes(a.aval) for a in eqn.invars)
                out_payload = sum(_aval_bytes(v.aval) for v in eqn.outvars)
                declared = {ax: axis_sizes[ax] for ax in eqn_axes
                            if ax in axis_sizes}
                for ax in eqn_axes:
                    if ax not in axis_sizes:
                        # an undeclared axis defaults to size 1: the
                        # collective would silently price at ZERO bytes —
                        # name it so COST004 can surface the hole
                        tape.unpriced.append(
                            (prim, ax, "axis size undeclared"))
                coll = ring_bytes_per_axis(prim, payload, out_payload,
                                           declared)
            elif eqn_axes and prim not in _AXIS_LOCAL:
                # a primitive that names mesh axes but has no wire-bytes
                # model: whatever it moves contributes zero to the
                # collective totals — flag instead of staying silent
                for ax in eqn_axes:
                    tape.unpriced.append((prim, ax, "no cost model"))
            tape.ops.append(TapeOp(
                prim, scale, in_ids, out_ids, flops * scale, trans * scale,
                br * scale, bw * scale,
                {k: v * scale for k, v in coll.items()},
                _axis_names(eqn.params), eqn.params))

    def _walk_call(prim, eqn, subs, env, scale):
        """Inline one call-like eqn.  The common case (pjit, custom_jvp,
        custom_vjp primal, remat, closed_call) maps call operands 1:1
        onto the sub-jaxpr's invars; scan/while/cond get structural
        handling; anything else is traversed with fresh inner inputs
        (cost still counted, liveness approximate)."""
        if prim == "pallas_call":
            # declared-cost fast path: one priced op with REAL dataflow
            # (in place of the body walk, whose once-not-per-grid-step
            # trace misprices the kernel, plus the zero-cost connector)
            kname = kernel_name_of(eqn)
            cost_fn = KERNEL_COSTS.get(kname)
            if cost_fn is not None:
                cost = cost_fn(eqn)
                in_ids = tuple(read(env, a) for a in eqn.invars)
                out_ids = tuple(bind_out(env, v) for v in eqn.outvars)
                tape.ops.append(TapeOp(
                    prim, scale, in_ids, out_ids,
                    int(cost.get("flops", 0)) * scale,
                    int(cost.get("transcendentals", 0)) * scale,
                    int(cost.get("bytes_read", 0)) * scale,
                    int(cost.get("bytes_written", 0)) * scale,
                    {}, (), {"kernel": kname}))
                return
            tape.unpriced_kernels.append(kname or "<anonymous>")

        sub_scale = scale
        if prim == "scan":
            sub_scale = scale * max(int(eqn.params.get("length", 1)), 1)
        elif prim == "while":
            tape.unbounded_loops = True
        if prim == "cond":
            # deterministic: charge the most expensive branch
            best, best_cost = None, -1
            for _, sj, sc in subs:
                t2 = build_tape(ClosedJaxpr(sj, list(sc)), axis_sizes)
                cost = sum(op.flops for op in t2.ops)
                if cost > best_cost:
                    best, best_cost = (sj, sc), cost
            subs = [("branches", best[0], best[1])]
            operand_atoms = eqn.invars[1:]  # drop the predicate
        else:
            operand_atoms = eqn.invars

        connected = True
        for si, (_, sj, sc) in enumerate(subs):
            inner_env = {}
            n = len(sj.invars)
            if prim == "while":
                # cond_jaxpr and body_jaxpr both take the carry
                atoms = operand_atoms[-n:] if len(operand_atoms) >= n else ()
            elif prim == "custom_jvp_call" and si > 0:
                atoms = ()   # only the primal call_jaxpr is costed
            else:
                atoms = operand_atoms[:n] \
                    if len(operand_atoms) >= n else ()
            def _same_aval(a, b):
                return (getattr(a, "shape", None) == getattr(b, "shape",
                                                             None)
                        and getattr(a, "dtype", None) == getattr(
                            b, "dtype", None))

            if len(atoms) == n:
                for var, atom in zip(sj.invars, atoms):
                    if _same_aval(var.aval, getattr(atom, "aval", None)):
                        inner_env[var] = read(env, atom)
                    else:
                        # aval mismatch (scan's full-xs operand vs the
                        # body's per-iteration slice var): binding them
                        # to ONE id would fake dataflow — e.g. a chain
                        # "reading" the stacked array inside the body.
                        # Sever the edge; the connector op below keeps
                        # liveness sound
                        inner_env[var] = tape.fresh(var.aval)
                        if si == 0:
                            connected = False
            else:
                for var in sj.invars:
                    inner_env[var] = tape.fresh(var.aval)
                if si == 0:
                    connected = False
            walk(sj, list(sc), inner_env, sub_scale)
            if si == 0 and len(sj.outvars) == len(eqn.outvars):
                for outer, inner in zip(eqn.outvars, sj.outvars):
                    if isinstance(inner, Literal) or \
                            not _same_aval(outer.aval, inner.aval):
                        # stacked scan output vs the body's slice var:
                        # same severing rule as the operands above
                        env[outer] = tape.fresh(outer.aval)
                        if not isinstance(inner, Literal):
                            connected = False
                    else:
                        env[outer] = inner_env.get(
                            inner, tape.fresh(inner.aval))
            elif si == 0:
                for outer in eqn.outvars:
                    env[outer] = tape.fresh(outer.aval)
                connected = False
            if prim == "custom_jvp_call":
                break   # don't double-count the jvp rule
        if not connected:
            # a call whose operands/results could not be mapped 1:1
            # onto its sub-jaxpr (pallas_call's ref-passing kernels):
            # the body's COST is already on the tape, but its dataflow
            # is severed — append a zero-cost connector op so liveness
            # and the shard/variance propagation still see that the
            # outputs derive from the operands
            tape.ops.append(TapeOp(
                prim, scale,
                tuple(read(env, a) for a in eqn.invars),
                tuple(env[v] for v in eqn.outvars),
                0, 0, 0, 0, {}, (), {}))

    env = {}
    jaxpr = closed_jaxpr.jaxpr
    for v in jaxpr.invars:
        i = tape.fresh(v.aval)
        env[v] = i
        tape.invar_ids.append(i)
    walk(jaxpr, list(closed_jaxpr.consts), env, 1)
    for v in jaxpr.outvars:
        if isinstance(v, Literal):
            tape.outvar_ids.append(tape.fresh(v.aval))
        else:
            tape.outvar_ids.append(env[v])
    return tape


# ---------------------------------------------------------------------------
# liveness → peak-HBM estimate
# ---------------------------------------------------------------------------
def _peak_hbm(tape, donated_ids):
    """Max over program points of resident bytes: consts + non-donated
    inputs live throughout; donated inputs and intermediates die at their
    last use; outputs live from definition to program end."""
    donated = set(donated_ids)
    out_ids = set(tape.outvar_ids)
    last_use = {}
    for t, op in enumerate(tape.ops):
        for i in op.in_ids:
            last_use[i] = t
    for i in tape.outvar_ids:
        last_use[i] = len(tape.ops)  # outputs survive the program

    resident = 0   # consts + non-donated inputs: the whole program
    for i in tape.const_ids:
        resident += _aval_bytes(tape.avals[i])
    live = {}
    for i in tape.invar_ids:
        b = _aval_bytes(tape.avals[i])
        if i in donated:
            live[i] = b
        else:
            resident += b
    peak = resident + sum(live.values())
    for t, op in enumerate(tape.ops):
        for i in op.out_ids:
            if i in last_use or i in out_ids:
                live[i] = _aval_bytes(tape.avals[i])
        cur = resident + sum(live.values())
        if cur > peak:
            peak = cur
        for i in list(live):
            if last_use.get(i, -1) <= t and i not in out_ids:
                del live[i]
    return peak


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------
class CostReport:
    """Deterministic cost/memory summary of one program.

    ``as_dict()`` is the stable JSON surface (documented in
    docs/analysis.md): all counters are plain ints, dict keys sorted.
    """

    def __init__(self, per_primitive, flops, transcendentals, bytes_read,
                 bytes_written, transfer_h2d_bytes, transfer_d2h_bytes,
                 collective_bytes_per_axis, peak_hbm_bytes, input_bytes,
                 output_bytes, const_bytes, n_eqns, axis_sizes,
                 unbounded_loops=False, unpriced_collectives=(),
                 unpriced_kernels=()):
        self.per_primitive = per_primitive
        self.flops = flops
        self.transcendentals = transcendentals
        self.bytes_read = bytes_read
        self.bytes_written = bytes_written
        self.transfer_h2d_bytes = transfer_h2d_bytes
        self.transfer_d2h_bytes = transfer_d2h_bytes
        self.collective_bytes_per_axis = collective_bytes_per_axis
        self.peak_hbm_bytes = peak_hbm_bytes
        self.input_bytes = input_bytes
        self.output_bytes = output_bytes
        self.const_bytes = const_bytes
        self.n_eqns = n_eqns
        self.axis_sizes = axis_sizes
        self.unbounded_loops = unbounded_loops
        # [(prim, axis, reason)]: collectives whose modeled wire bytes
        # are silently zero (unknown primitive / undeclared axis size)
        self.unpriced_collectives = list(unpriced_collectives)
        # [kernel name]: pallas_call kernels with no declared cost model
        # (priced off a once-per-trace body walk — wrong both ways)
        self.unpriced_kernels = list(unpriced_kernels)

    @property
    def transfer_bytes(self):
        return self.transfer_h2d_bytes + self.transfer_d2h_bytes

    @property
    def collective_bytes(self):
        return sum(self.collective_bytes_per_axis.values())

    def as_dict(self):
        return {
            "flops": int(self.flops),
            "transcendentals": int(self.transcendentals),
            "bytes_read": int(self.bytes_read),
            "bytes_written": int(self.bytes_written),
            "transfer_h2d_bytes": int(self.transfer_h2d_bytes),
            "transfer_d2h_bytes": int(self.transfer_d2h_bytes),
            "transfer_bytes": int(self.transfer_bytes),
            "collective_bytes": int(self.collective_bytes),
            "collective_bytes_per_axis": {
                k: int(v) for k, v in
                sorted(self.collective_bytes_per_axis.items())},
            "peak_hbm_bytes": int(self.peak_hbm_bytes),
            "input_bytes": int(self.input_bytes),
            "output_bytes": int(self.output_bytes),
            "const_bytes": int(self.const_bytes),
            "n_eqns": int(self.n_eqns),
            "axis_sizes": {k: int(v)
                           for k, v in sorted(self.axis_sizes.items())},
            "unbounded_loops": bool(self.unbounded_loops),
            "unpriced_collectives": [
                {"prim": p, "axis": a, "reason": r}
                for p, a, r in sorted(set(self.unpriced_collectives))],
            "unpriced_kernels": sorted(set(self.unpriced_kernels)),
            "per_primitive": {
                prim: {k: int(v) for k, v in sorted(row.items())}
                for prim, row in sorted(self.per_primitive.items())},
        }

    def render(self, title="mxcost"):
        d = self.as_dict()
        lines = ["%s: %d eqn(s), %.3f GFLOP, peak HBM %.1f MiB" % (
            title, d["n_eqns"], d["flops"] / 1e9,
            d["peak_hbm_bytes"] / (1 << 20))]
        lines.append("  transfer %.2f MiB h2d + %.2f MiB d2h; collectives %s"
                     % (d["transfer_h2d_bytes"] / (1 << 20),
                        d["transfer_d2h_bytes"] / (1 << 20),
                        {k: "%.2f MiB" % (v / (1 << 20)) for k, v in
                         d["collective_bytes_per_axis"].items()} or "none"))
        top = sorted(self.per_primitive.items(),
                     key=lambda kv: (-kv[1]["flops"], kv[0]))[:12]
        for prim, row in top:
            lines.append("  %-24s x%-4d %12d flops %12d bytes" % (
                prim, row["count"], row["flops"],
                row["bytes_read"] + row["bytes_written"]))
        return "\n".join(lines)


def analyze_tape(tape, donated_ids=(), host_invar_ids=None,
                 fetched_outvar_ids=None):
    """Aggregate a Tape into a CostReport."""
    per_prim = {}
    flops = trans = br = bw = 0
    coll = {}
    for op in tape.ops:
        row = per_prim.setdefault(op.prim, {
            "count": 0, "flops": 0, "transcendentals": 0,
            "bytes_read": 0, "bytes_written": 0, "collective_bytes": 0})
        row["count"] += op.scale
        row["flops"] += op.flops
        row["transcendentals"] += op.transcendentals
        row["bytes_read"] += op.bytes_read
        row["bytes_written"] += op.bytes_written
        row["collective_bytes"] += sum(op.collective.values())
        flops += op.flops
        trans += op.transcendentals
        br += op.bytes_read
        bw += op.bytes_written
        for ax, b in op.collective.items():
            coll[ax] = coll.get(ax, 0) + b

    host = set(tape.invar_ids if host_invar_ids is None else host_invar_ids)
    fetched = set(tape.outvar_ids if fetched_outvar_ids is None
                  else fetched_outvar_ids)
    h2d = sum(_aval_bytes(tape.avals[i]) for i in tape.invar_ids
              if i in host)
    d2h = sum(_aval_bytes(tape.avals[i]) for i in set(tape.outvar_ids)
              if i in fetched)
    in_bytes = sum(_aval_bytes(tape.avals[i]) for i in tape.invar_ids)
    out_bytes = sum(_aval_bytes(tape.avals[i])
                    for i in set(tape.outvar_ids))
    const_bytes = sum(_aval_bytes(tape.avals[i]) for i in tape.const_ids)
    axis_sizes = {}
    for op in tape.ops:
        for ax in op.axes:
            axis_sizes.setdefault(ax, 0)
    return CostReport(
        per_primitive=per_prim, flops=flops, transcendentals=trans,
        bytes_read=br, bytes_written=bw, transfer_h2d_bytes=h2d,
        transfer_d2h_bytes=d2h, collective_bytes_per_axis=coll,
        peak_hbm_bytes=_peak_hbm(tape, donated_ids),
        input_bytes=in_bytes, output_bytes=out_bytes,
        const_bytes=const_bytes, n_eqns=len(tape.ops),
        axis_sizes=axis_sizes, unbounded_loops=tape.unbounded_loops,
        unpriced_collectives=tape.unpriced,
        unpriced_kernels=tape.unpriced_kernels)


def analyze_jaxpr(closed_jaxpr, axis_sizes=None, donated_invars=(),
                  host_invars=None, fetched_outvars=None):
    """CostReport for a ClosedJaxpr.

    ``donated_invars``/``host_invars``: iterables of flat invar indices
    (donated: freed at last use for the HBM walk; host: counted as
    host→device transfer).  ``fetched_outvars``: flat outvar indices
    fetched back to the host (default: all).
    """
    tape = build_tape(closed_jaxpr, axis_sizes=axis_sizes)
    don = [tape.invar_ids[i] for i in donated_invars
           if 0 <= i < len(tape.invar_ids)]
    host = None if host_invars is None else [
        tape.invar_ids[i] for i in host_invars
        if 0 <= i < len(tape.invar_ids)]
    fetched = None if fetched_outvars is None else [
        tape.outvar_ids[i] for i in fetched_outvars
        if 0 <= i < len(tape.outvar_ids)]
    report = analyze_tape(tape, donated_ids=don, host_invar_ids=host,
                          fetched_outvar_ids=fetched)
    if axis_sizes:
        report.axis_sizes = {k: int(v) for k, v in axis_sizes.items()}
    return report


def _flat_arg_ranges(args):
    """[(start, stop)) flat-leaf index range per positional arg."""
    import jax
    ranges = []
    start = 0
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        ranges.append((start, start + len(leaves)))
        start += len(leaves)
    return ranges


def analyze_fn(fn, *args, axis_env=None, axis_sizes=None,
               donate_argnums=(), host_argnums=None, **kwargs):
    """Trace ``fn(*args, **kwargs)`` with ``jax.make_jaxpr`` (no
    execution, no compilation) and analyze the result.

    ``axis_env``: [(axis_name, size)] so explicit collectives
    (``lax.psum``/``pmean``) trace without a mesh; their sizes feed the
    collective-bytes model unless ``axis_sizes`` overrides.
    ``donate_argnums``/``host_argnums`` classify whole positional args.
    """
    import jax

    closed = jax.make_jaxpr(fn, axis_env=axis_env)(*args, **kwargs)
    # kwargs leaves flatten after the positionals; argnum classification
    # addresses positionals only (kwargs default to device-resident)
    ranges = _flat_arg_ranges(args)
    donated = [i for n in donate_argnums if n < len(ranges)
               for i in range(*ranges[n])]
    host = None
    if host_argnums is not None:
        host = [i for n in host_argnums if n < len(ranges)
                for i in range(*ranges[n])]
    sizes = dict(axis_env or [])
    sizes.update(axis_sizes or {})
    return analyze_jaxpr(closed, axis_sizes=sizes,
                         donated_invars=donated, host_invars=host)


def symbol_closed_jaxpr(symbol, shapes, type_dict=None, train=False):
    """Trace a Symbol's forward program at concrete ``shapes``:
    ``(closed_jaxpr, args, aux)`` with args/aux the name→
    ShapeDtypeStruct dicts (flat invar order follows their sorted
    keys), or None when the graph is underspecified or does not trace.
    Shared by :func:`analyze_symbol` and the fusion pass."""
    import jax

    from ..symbol.symbol import _infer_entry_shapes, make_graph_fn
    known = {k: tuple(v) for k, v in (shapes or {}).items()
             if v is not None}
    tdict = {k: _np.dtype(v) for k, v in (type_dict or {}).items()}
    entry_shapes, ok = _infer_entry_shapes(symbol._outputs, known, tdict)
    if not ok:
        return None
    args, aux = {}, {}
    for n in symbol._nodes():
        if n.op is not None:
            continue
        s = entry_shapes.get((id(n), 0))
        if s is None:
            return None
        (aux if n._is_aux else args)[n.name] = jax.ShapeDtypeStruct(
            tuple(s.shape), s.dtype)
    graph_fn = make_graph_fn(symbol, train=train)
    try:
        closed = jax.make_jaxpr(graph_fn)(
            args, aux, jax.random.PRNGKey(0))
    except Exception:
        return None
    return closed, args, aux


def analyze_symbol(symbol, shapes, type_dict=None, train=False,
                   host_names=None):
    """CostReport for a Symbol's forward program at concrete ``shapes``.

    ``shapes`` must make the graph fully inferable (same contract as the
    GRF006 trace).  ``host_names``: argument names fed from the host each
    call (default: exactly the names in ``shapes`` — data/label; derived
    parameter arguments are device-resident).  Returns None when the
    graph is underspecified or does not trace.
    """
    traced = symbol_closed_jaxpr(symbol, shapes, type_dict=type_dict,
                                 train=train)
    if traced is None:
        return None
    closed, args, aux = traced
    # flat invar order follows the pytree flattening of (args, aux, key):
    # classify host-fed leaves by arg-dict key order (sorted by jax)
    known = {k for k, v in (shapes or {}).items() if v is not None}
    host = set(host_names if host_names is not None else known)
    flat_names = sorted(args) + sorted(aux)
    host_idx = [i for i, name in enumerate(flat_names) if name in host]
    return analyze_jaxpr(closed, host_invars=host_idx,
                         fetched_outvars=range(
                             len(closed.jaxpr.outvars)
                             - len(aux)))


def unpriced_findings(report_or_tape, subject="<program>", disable=()):
    """COST004 findings for every collective the model could not price.

    A ``ppermute`` traced without its axis declared (or a collective
    primitive this module has no formula for) contributes ZERO modeled
    wire bytes — a budget gate built on that number would pass a PR that
    floods the interconnect.  The fallback therefore *names* the hole.
    """
    from .findings import Finding, filter_findings

    rows = getattr(report_or_tape, "unpriced_collectives", None)
    if rows is None:
        rows = getattr(report_or_tape, "unpriced", [])
    findings = []
    for prim, axis, reason in sorted(set(tuple(r) for r in rows)):
        findings.append(Finding(
            "COST004", subject,
            "collective %r over axis %r contributes zero modeled wire "
            "bytes (%s): declare the axis size (axis_env / mesh) or "
            "teach analysis/cost.py its ring formula — an unpriced "
            "collective makes every collective-byte budget a lie"
            % (prim, axis, reason)))
    kernels = getattr(report_or_tape, "unpriced_kernels", [])
    for kname in sorted(set(kernels)):
        findings.append(Finding(
            "COST005", subject,
            "pallas_call kernel %r declares no cost model: its body is "
            "costed once (not once per grid step) and its dataflow is "
            "severed behind a zero-cost connector — register a "
            "declare_kernel_cost(%r) model (analysis/cost.py) so the "
            "budget gate prices it" % (kname, kname)))
    return filter_findings(findings, disable)
