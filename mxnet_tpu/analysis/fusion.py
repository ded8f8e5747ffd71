"""mxfuse: mine the mxcost tape for memory-bound fusable chains.

TVM's operator fusion (PAPERS.md arxiv 1802.04799) groups injective /
broadcast / reduction-epilogue operators into one kernel so the
intermediates never round-trip through DRAM; XLA does the same invisibly
at compile time.  This pass is the *hardware-free planning* counterpart:
it walks the mxcost flat tape (whose per-eqn ``bytes_read`` /
``bytes_written`` are exactly the unfused upper bound a fused pass
elides), segments it into fusable **chains** — elementwise / broadcast /
cast / reduction-epilogue sequences connected by producer→consumer
dataflow, broken at dots, convs, collectives and layout-changing
movement (reshape/transpose/gather/...) — and ranks every chain by
modeled **bytes-saved-if-fused**:

    unfused = Σ over chain eqns (bytes_read + bytes_written)
    fused   = Σ unique external-input buffers + Σ unique chain outputs
    saved   = unfused − fused

(one fused pass reads each external buffer once and writes each
chain output once, however many chain eqns touch it — which is also why
a donated/in-place buffer is never double-counted).  The report is
byte-deterministic for a given tape, so the fusion plan can be gated
like every other modeled number.

The loop is closed the repo's own way: the top-ranked shipped chains
have real Pallas kernels (``ops/fused_optimizer.py`` — the fused ZeRO-1
/ replicated optimizer update — and the fused layernorm), those kernels
*declare* their cost with the cost pass (:data:`~.cost.KERNEL_COSTS`),
and the ``fused_optimizer_update`` budget model pins that the fused
spelling realizes the bytes this pass models (FUS001; the
``FUSED_OPTIMIZER`` seam kill).  :func:`lint_kernel_costs` is the
``--self-check`` sweep that keeps every shipped ``pallas_call``
annotated (COST005).

Entry points: ``python -m mxnet_tpu.analysis --cost --fusion``,
``Symbol.fusion_report()``, ``trainer.fusion_report()``; the doctor
names the fusion knob when a dominant dispatch/collective phase
coincides with a top chain covering more than
:data:`FUSION_HINT_MIN_PCT` of step bytes (docs/fusion.md).
"""
from __future__ import annotations

import ast
import glob
import os

from .cost import (TRANSCENDENTALS, _MOVEMENT, _COLLECTIVES, _AXIS_LOCAL,
                   _aval_bytes, build_tape, KERNEL_COSTS)
from .findings import Finding, filter_findings

__all__ = ["FUSION_HINT_MIN_PCT", "FusionChain", "FusionReport",
           "is_fusable", "segment_chains", "analyze_tape_fusion",
           "fusion_from_jaxpr", "fusion_from_fn", "fusion_for_symbol",
           "lint_kernel_costs", "pallas_kernels_used"]

# a top-ranked chain covering more than this share of the step's total
# HBM bytes makes the performance doctor name the fusion knob when
# dispatch / collective_or_ps dominates (CONTEXT_HINTS tag "fusable")
FUSION_HINT_MIN_PCT = 20.0

# cheap data-movement that fuses INTO a single pass (no relayout): a
# broadcast materializes nothing, a cast is one convert per element, a
# select is elementwise.  Everything else in cost._MOVEMENT (reshape,
# transpose, gather, concatenate, slicing, padding ...) changes layout
# or addressing and BREAKS a chain — a fused loop nest cannot stream
# through it with one index function.
_FUSABLE_MOVEMENT = frozenset({
    "broadcast_in_dim", "convert_element_type", "select_n", "copy",
    "stop_gradient", "squeeze", "expand_dims", "real", "imag",
})

# call-like / opaque primitives that can appear on the tape as connector
# or declared-cost ops: never chain members
_OPAQUE = frozenset({
    "pallas_call", "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "remat", "scan", "while", "cond",
})


def is_fusable(prim):
    """Can one fused memory pass absorb this primitive?  Elementwise
    arithmetic, transcendentals, casts, broadcasts and plain reductions
    (the epilogue class) fuse; dots, convs, collectives, layout-changing
    movement, scatters, sorts, windows and opaque calls break."""
    if prim in _FUSABLE_MOVEMENT:
        return True
    if prim in TRANSCENDENTALS:
        return True
    if prim in _COLLECTIVES or prim in _AXIS_LOCAL or prim in _OPAQUE:
        return False
    if prim in _MOVEMENT:        # the layout-changing remainder
        return False
    if prim in ("dot_general", "conv_general_dilated", "sort",
                "select_and_scatter_add"):
        return False
    if prim.startswith("reduce_window"):
        return False
    if prim.startswith("reduce_") or prim in ("argmax", "argmin"):
        return True              # reduction epilogue (and its broadcast
        # back into the chain — the normalization pattern)
    if prim.startswith("scatter") or prim.startswith("cum"):
        return False
    # default elementwise (add/mul/clamp/compare/...): one op per output
    return True


class FusionChain:
    """One fusable chain: contiguous dataflow-connected tape eqns that a
    single fused pass could execute with one read of every external
    input and one write of every chain output."""
    __slots__ = ("first_op", "op_indices", "prims", "kind", "scale",
                 "unfused_bytes", "fused_bytes", "bytes_saved",
                 "external_in_bytes", "external_out_bytes",
                 "pct_of_step_bytes")

    def __init__(self, first_op, op_indices, prims, kind, scale,
                 unfused_bytes, fused_bytes, external_in_bytes,
                 external_out_bytes, pct_of_step_bytes):
        self.first_op = first_op
        self.op_indices = op_indices
        self.prims = prims
        self.kind = kind
        self.scale = scale
        self.unfused_bytes = unfused_bytes
        self.fused_bytes = fused_bytes
        self.bytes_saved = unfused_bytes - fused_bytes
        self.external_in_bytes = external_in_bytes
        self.external_out_bytes = external_out_bytes
        self.pct_of_step_bytes = pct_of_step_bytes

    def as_dict(self):
        return {
            "first_op": int(self.first_op),
            "n_ops": len(self.op_indices),
            "prims": list(self.prims),
            "kind": self.kind,
            "scale": int(self.scale),
            "unfused_bytes": int(self.unfused_bytes),
            "fused_bytes": int(self.fused_bytes),
            "bytes_saved": int(self.bytes_saved),
            "external_in_bytes": int(self.external_in_bytes),
            "external_out_bytes": int(self.external_out_bytes),
            "pct_of_step_bytes": float(self.pct_of_step_bytes),
        }


def _chain_kind(prims):
    s = set(prims)
    reduces = any(p.startswith("reduce_") or p in ("argmax", "argmin")
                  for p in prims)
    if reduces and (s & {"rsqrt", "sqrt"}):
        return "normalization"
    if reduces:
        return "reduction_epilogue"
    if s <= _FUSABLE_MOVEMENT:
        return "cast"
    return "elementwise"


def segment_chains(tape):
    """Union-find over the tape's fusable eqns along producer→consumer
    edges (same ``scale`` only — a chain never crosses a scan boundary).
    Returns chains as sorted lists of op indices, ≥ 2 ops each, in
    first-op order (deterministic)."""
    n = len(tape.ops)
    fusable = [is_fusable(op.prim) for op in tape.ops]
    parent = list(range(n))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            # deterministic: smaller index wins the root
            if ra < rb:
                parent[rb] = ra
            else:
                parent[ra] = rb

    producer = {}
    for idx, op in enumerate(tape.ops):
        if not fusable[idx]:
            continue
        for oid in op.out_ids:
            producer[oid] = idx
    for idx, op in enumerate(tape.ops):
        if not fusable[idx]:
            continue
        for iid in op.in_ids:
            j = producer.get(iid)
            if j is not None and j != idx \
                    and tape.ops[j].scale == op.scale:
                union(idx, j)
    groups = {}
    for idx in range(n):
        if fusable[idx]:
            groups.setdefault(find(idx), []).append(idx)
    return [sorted(g) for _, g in sorted(groups.items())
            if len(g) >= 2]


def _chain_stats(tape, idxs, total_bytes):
    idx_set = set(idxs)
    ops = [tape.ops[i] for i in idxs]
    scale = ops[0].scale
    produced = set()
    for op in ops:
        produced.update(op.out_ids)
    ext_in = set()
    for op in ops:
        for iid in op.in_ids:
            if iid not in produced and iid not in tape.literal_ids:
                ext_in.add(iid)
    prog_outs = set(tape.outvar_ids)
    consumed_outside = set()
    for k, op in enumerate(tape.ops):
        if k in idx_set:
            continue
        for iid in op.in_ids:
            if iid in produced:
                consumed_outside.add(iid)
    ext_out = {oid for oid in produced
               if oid in consumed_outside or oid in prog_outs}
    # unique buffers, counted ONCE each (chain ops re-reading a donated
    # or shared operand do not double-bill the fused pass)
    in_bytes = sum(_aval_bytes(tape.avals[i]) for i in sorted(ext_in))
    out_bytes = sum(_aval_bytes(tape.avals[i]) for i in sorted(ext_out))
    unfused = sum(op.bytes_read + op.bytes_written for op in ops)
    fused = (in_bytes + out_bytes) * scale
    if fused > unfused:
        fused = unfused          # a chain can never cost more fused
    prims = [op.prim for op in ops]
    pct = round(100.0 * (unfused - fused) / total_bytes, 4) \
        if total_bytes else 0.0
    return FusionChain(
        first_op=idxs[0], op_indices=list(idxs), prims=prims,
        kind=_chain_kind(prims), scale=scale, unfused_bytes=unfused,
        fused_bytes=fused, external_in_bytes=in_bytes * scale,
        external_out_bytes=out_bytes * scale, pct_of_step_bytes=pct)


class FusionReport:
    """Deterministic ranking of a program's fusable chains by modeled
    bytes-saved-if-fused.  ``as_dict()`` is the stable JSON surface
    (docs/fusion.md); chains are ranked ``(-bytes_saved, first_op)``."""

    def __init__(self, chains, total_tape_bytes, n_eqns):
        self.chains = sorted(chains,
                             key=lambda c: (-c.bytes_saved, c.first_op))
        self.total_tape_bytes = int(total_tape_bytes)
        self.n_eqns = int(n_eqns)
        self.total_bytes_saved = sum(c.bytes_saved for c in self.chains)

    @property
    def bytes_saved_pct(self):
        if not self.total_tape_bytes:
            return 0.0
        return round(100.0 * self.total_bytes_saved
                     / self.total_tape_bytes, 4)

    @property
    def top_chain(self):
        return self.chains[0] if self.chains else None

    @property
    def top_chain_pct(self):
        """The top chain's share of the program's total HBM bytes —
        what the doctor hint thresholds on (FUSION_HINT_MIN_PCT)."""
        top = self.top_chain
        if top is None or not self.total_tape_bytes:
            return 0.0
        return round(100.0 * top.unfused_bytes / self.total_tape_bytes,
                     4)

    def as_dict(self):
        return {
            "n_eqns": self.n_eqns,
            "total_tape_bytes": self.total_tape_bytes,
            "total_bytes_saved": int(self.total_bytes_saved),
            "bytes_saved_pct": self.bytes_saved_pct,
            "top_chain_pct": self.top_chain_pct,
            "n_chains": len(self.chains),
            "chains": [c.as_dict() for c in self.chains],
        }

    def render(self, title="mxfuse"):
        lines = ["%s: %d chain(s) over %d eqns, %.2f MiB saved-if-fused "
                 "(%.1f%% of %.2f MiB tape bytes)"
                 % (title, len(self.chains), self.n_eqns,
                    self.total_bytes_saved / (1 << 20),
                    self.bytes_saved_pct,
                    self.total_tape_bytes / (1 << 20))]
        for rank, c in enumerate(self.chains[:8]):
            prims = ",".join(c.prims[:6])
            if len(c.prims) > 6:
                prims += ",…(%d)" % len(c.prims)
            lines.append(
                "  #%-2d %-18s %4d ops  saves %10d B (%.1f%% of step)"
                "  [%s]" % (rank + 1, c.kind, len(c.op_indices),
                            c.bytes_saved, c.pct_of_step_bytes, prims))
        return "\n".join(lines)


def analyze_tape_fusion(tape):
    """FusionReport for a built Tape."""
    total = sum(op.bytes_read + op.bytes_written for op in tape.ops)
    chains = [_chain_stats(tape, idxs, total)
              for idxs in segment_chains(tape)]
    chains = [c for c in chains if c.bytes_saved > 0]
    return FusionReport(chains, total, len(tape.ops))


def fusion_from_jaxpr(closed_jaxpr, axis_sizes=None):
    """FusionReport for a ClosedJaxpr (tape built exactly like the cost
    pass: inlined through pjit/remat/scan; declared-cost pallas kernels
    appear as single opaque ops and never join chains)."""
    return analyze_tape_fusion(build_tape(closed_jaxpr,
                                          axis_sizes=axis_sizes))


def fusion_from_fn(fn, *args, axis_env=None, axis_sizes=None, **kwargs):
    """Trace ``fn`` with ``jax.make_jaxpr`` (no execution) and analyze."""
    import jax

    closed = jax.make_jaxpr(fn, axis_env=axis_env)(*args, **kwargs)
    sizes = dict(axis_env or [])
    sizes.update(axis_sizes or {})
    return fusion_from_jaxpr(closed, axis_sizes=sizes)


def fusion_for_symbol(symbol, shapes, type_dict=None, train=False):
    """FusionReport for a Symbol's forward program (the
    ``Symbol.fusion_report()`` implementation; same tracing contract as
    ``analyze_symbol``).  Returns None when the graph does not trace."""
    from .cost import symbol_closed_jaxpr

    traced = symbol_closed_jaxpr(symbol, shapes, type_dict=type_dict,
                                 train=train)
    if traced is None:
        return None
    closed, _, _ = traced
    return fusion_from_jaxpr(closed)


# ---------------------------------------------------------------------------
# the declared-cost lint: every shipped pallas_call must price itself
# ---------------------------------------------------------------------------
def pallas_kernels_used(root=None):
    """AST sweep of ``mxnet_tpu/ops/*.py`` for ``pallas_call(...)``
    call sites, resolving each one's kernel function name: a direct
    ``Name``, a ``functools.partial(name, ...)`` argument, or a local
    variable assigned from either inside the enclosing function.
    Returns ``(kernels, dynamic)``: ``kernels`` maps kernel name →
    ``file:line`` use sites; ``dynamic`` lists call sites whose kernel
    could not be resolved (findings too — an unresolvable kernel can
    never be checked against the registry)."""
    root = root or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ops")
    kernels, dynamic = {}, []

    def _partial_target(node):
        """name for functools.partial(<name>, ...) / partial(<name>,...)"""
        if not isinstance(node, ast.Call) or not node.args:
            return None
        fn = node.func
        callee = fn.attr if isinstance(fn, ast.Attribute) else \
            getattr(fn, "id", None)
        if callee != "partial":
            return None
        first = node.args[0]
        if isinstance(first, ast.Name):
            return first.id
        if isinstance(first, ast.Attribute):
            return first.attr
        return None

    def _local_map(fnode):
        """var name -> kernel fn name for partial assignments."""
        local = {}
        for sub in ast.walk(fnode):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name):
                target = _partial_target(sub.value)
                if target is None and isinstance(sub.value, ast.Name):
                    target = local.get(sub.value.id)
                if target:
                    local[sub.targets[0].id] = target
        return local

    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        if os.path.basename(path) == "generated_kernels.py":
            # mxgen kernels are exec'd from generated source — the AST
            # sweep cannot see them; the registry-driven COST006 check
            # in lint_kernel_costs covers that module instead
            continue
        rel = os.path.join("ops", os.path.basename(path))
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        fdefs = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef,
                                   ast.AsyncFunctionDef))]
        # (helper fn name, kernel param index, where): pallas_call on a
        # parameter — resolved one hop up through the helper's callers
        deferred = []
        for fnode in fdefs:
            local = _local_map(fnode)
            params = [a.arg for a in fnode.args.args]
            for sub in ast.walk(fnode):
                if not isinstance(sub, ast.Call):
                    continue
                fn = sub.func
                callee = fn.attr if isinstance(fn, ast.Attribute) else \
                    getattr(fn, "id", None)
                if callee != "pallas_call" or not sub.args:
                    continue
                where = "%s:%d" % (rel, sub.lineno)
                first = sub.args[0]
                name = None
                if isinstance(first, ast.Name):
                    name = local.get(first.id)
                    if name is None and first.id in params:
                        deferred.append((fnode.name,
                                         params.index(first.id), where))
                        continue
                    name = name or first.id
                elif isinstance(first, ast.Attribute):
                    name = first.attr
                else:
                    name = _partial_target(first)
                if name:
                    kernels.setdefault(name, []).append(where)
                else:
                    dynamic.append(where)
        for helper, argpos, where in deferred:
            resolved_any = False
            for fnode in fdefs:
                local = _local_map(fnode)
                for sub in ast.walk(fnode):
                    if not isinstance(sub, ast.Call):
                        continue
                    fn = sub.func
                    callee = fn.attr if isinstance(fn, ast.Attribute) \
                        else getattr(fn, "id", None)
                    if callee != helper or len(sub.args) <= argpos:
                        continue
                    arg = sub.args[argpos]
                    name = None
                    if isinstance(arg, ast.Name):
                        name = local.get(arg.id, arg.id)
                    else:
                        name = _partial_target(arg)
                    if name:
                        kernels.setdefault(name, []).append(
                            "%s (via %s:%d)" % (where, helper,
                                                sub.lineno))
                        resolved_any = True
                    else:
                        dynamic.append("%s (caller %s:%d)"
                                       % (where, helper, sub.lineno))
            if not resolved_any:
                dynamic.append(where)
    return kernels, dynamic


def lint_kernel_costs(disable=(), root=None):
    """COST005 sweep (``--self-check``): every ``pallas_call`` in the
    shipped op sources must name a kernel with a registered
    ``declare_kernel_cost`` model — otherwise the cost pass prices it
    off a once-per-trace body walk and every byte/FLOP budget the
    kernel participates in silently lies."""
    # importing the op modules runs their declare_kernel_cost
    # registrations; the AST names below are checked against the result
    from ..ops import pallas_kernels as _pk          # noqa: F401
    from ..ops import fused_optimizer as _fo         # noqa: F401
    from ..ops import ssd_kernels as _ssd            # noqa: F401

    kernels, dynamic = pallas_kernels_used(root)
    findings = []
    for name in sorted(set(kernels) - set(KERNEL_COSTS)):
        findings.append(Finding(
            "COST005", name,
            "pallas_call kernel %r (used at %s) has no "
            "declare_kernel_cost model — the cost pass prices it off a "
            "once-per-trace body walk; declare its flops/bytes so the "
            "budget gate stops lying about it"
            % (name, ", ".join(kernels[name]))))
    for where in dynamic:
        findings.append(Finding(
            "COST005", where,
            "pallas_call whose kernel argument cannot be resolved to a "
            "function name — the declared-cost registry cannot be "
            "checked for it; pass the kernel fn (or a functools."
            "partial of it) directly"))
    # generated kernels (ops/generated_kernels.py) are exec'd source the
    # AST sweep above cannot see: check the REGISTRY instead — a mxgen
    # kernel that lost its auto-declared cost entry is a gate error
    # (COST006), not a silent skip
    from ..ops import generated_kernels as _gen
    for name in sorted(set(_gen.GENERATED_KERNELS) - set(KERNEL_COSTS)):
        findings.append(Finding(
            "COST006", name,
            "generated kernel %r is in GENERATED_KERNELS but has no "
            "KERNEL_COSTS entry — register_generated auto-declares one; "
            "something deleted or bypassed it, so the cost pass would "
            "price the kernel off the once-per-trace body walk"
            % (name,)))
    return filter_findings(findings, disable)
