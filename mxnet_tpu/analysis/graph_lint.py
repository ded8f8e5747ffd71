"""Graph lint: whole-DAG checks over a Symbol before it is bound.

Reference: the nnvm shape/type fixpoints (``src/executor/
infer_graph_attr_pass.cc``) only prove inferability; the classes caught
here — gradient-cutting ops on a loss path, aux state read as a plain
tensor, accidental float64 promotion, recompile-forcing static shapes,
megabyte constants folded into the jaxpr — surface in the reference as
runtime asserts or, worse, silent slowness inside ``jax.jit``.
"""
from __future__ import annotations

import numpy as _np

from ..ops import registry as _reg
from .findings import Finding, filter_findings

__all__ = ["lint_graph", "LOSS_OPS", "LARGE_CONST_BYTES"]

# output heads that start a gradient (the reference marks these via
# MakeLoss/grad_scale semantics); ancestors of these carry the backward pass
LOSS_OPS = frozenset({
    "SoftmaxOutput", "LinearRegressionOutput", "MAERegressionOutput",
    "LogisticRegressionOutput", "SVMOutput", "MakeLoss",
    "IdentityAttachKLSparseReg", "softmax_cross_entropy", "CTCLoss",
    "_contrib_CTCLoss",
})

# constants above this folded into the compiled program get copied into
# every executable and resident in HBM per-donation — flag them
LARGE_CONST_BYTES = 1 << 20

# Reshape dim codes (0 = copy, -1 = infer, -2.. = advanced) keep the graph
# batch-polymorphic; a fully positive literal shape does not
_RESHAPE_OPS = frozenset({"Reshape", "reshape"})


def _node_params(op, node):
    from ..symbol.symbol import _attr_params
    return _attr_params(op, node.attrs)


def _n_outputs(node):
    op = _reg.get(node.op)
    try:
        return op.n_outputs(_node_params(op, node))
    except Exception:
        return 1


def _ancestors(roots):
    """All nodes reachable upward (through inputs) from ``roots``."""
    seen = set()
    stack = list(roots)
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        stack.extend(c for c, _ in n.inputs)
    return seen


def _lint_dead_outputs(nodes, heads):
    consumed = {(id(n), oi) for node in nodes for n, oi in node.inputs}
    consumed |= {(id(n), oi) for n, oi in heads}
    out = []
    for n in nodes:
        if n.op is None:
            continue
        for i in range(_n_outputs(n)):
            if (id(n), i) not in consumed:
                out.append(Finding(
                    "GRF001", n.name,
                    "output %d of %s is neither consumed nor a head; the "
                    "subgraph computing it is dead weight" % (i, n.op)))
    return out


def _lint_nondiff_path(nodes, heads):
    loss_nodes = [n for n, _ in heads if n.op in LOSS_OPS]
    if not loss_nodes:
        return []
    above_loss = _ancestors(loss_nodes)
    out = []
    for n in nodes:
        if n.op is None or id(n) not in above_loss or n.op in LOSS_OPS:
            continue
        op = _reg.get(n.op)
        if op.differentiable:
            continue
        # only a problem if a trainable argument sits beneath the cut
        below = _ancestors([c for c, _ in n.inputs])
        has_param_below = any(a.op is None and not a._is_aux
                              for a in nodes if id(a) in below)
        if has_param_below:
            out.append(Finding(
                "GRF002", n.name,
                "%s is differentiable=False yet sits on the path from "
                "trainable arguments to a loss head — their gradient "
                "through this node is zero" % (n.op,)))
    return out


def _lint_aux_reads(nodes):
    out = []
    for n in nodes:
        if n.op is None:
            continue
        op = _reg.get(n.op)
        for pos, (child, _) in enumerate(n.inputs):
            if child.op is None and child._is_aux and pos not in op.aux:
                out.append(Finding(
                    "GRF003", n.name,
                    "aux state %r feeds non-aux input slot %d of %s; its "
                    "value differs between training and inference and this "
                    "read will not see in-place updates" %
                    (child.name, pos, n.op)))
    return out


def _lint_float64(nodes, type_dict):
    """Mirror Symbol.infer_type's promotion walk, flagging the node that
    first widens to float64 from narrower inputs."""
    f64 = _np.dtype(_np.float64)
    env = {}
    out = []
    for n in nodes:
        if n.op is None:
            dt = type_dict.get(n.name)
            if dt is None and "__dtype__" in n.attrs:
                dt = n.attrs["__dtype__"]
            env[id(n)] = _np.dtype(dt) if dt is not None else \
                _np.dtype(_np.float32)
            continue
        if n.op in ("Cast", "cast"):
            env[id(n)] = _np.dtype(
                _reg.canonicalize(n.attrs.get("dtype", "float32")))
            if env[id(n)] == f64:
                ins = [env.get(id(c)) for c, _ in n.inputs]
                if all(d != f64 for d in ins if d is not None):
                    out.append(Finding(
                        "GRF004", n.name,
                        "Cast widens %s to float64; on TPU float64 is "
                        "emulated and an order of magnitude slower" %
                        ([str(d) for d in ins if d is not None],)))
            continue
        ins = [env.get(id(c)) for c, _ in n.inputs]
        ins = [d for d in ins if d is not None]
        dt = _np.dtype(_np.result_type(*ins)) if ins else \
            _np.dtype(_np.float32)
        env[id(n)] = dt
        if dt == f64 and ins and any(d != f64 for d in ins):
            out.append(Finding(
                "GRF004", n.name,
                "%s promotes %s to float64 (weak-type surprise: check "
                "variable dtypes %s)" %
                (n.op, sorted({str(d) for d in ins if d != f64}),
                 sorted({c.name for c, _ in n.inputs if c.op is None}))))
    return out


def _lint_static_reshape(nodes):
    out = []
    for n in nodes:
        if n.op not in _RESHAPE_OPS:
            continue
        shape = _reg.canonicalize(n.attrs.get("shape", ()))
        if not isinstance(shape, (tuple, list)) or len(shape) < 2:
            continue
        if all(isinstance(d, int) and d > 0 for d in shape):
            out.append(Finding(
                "GRF005", n.name,
                "Reshape target %r is fully static; use 0 (copy) or -1 "
                "(infer) dim codes so a batch-size change does not break "
                "the graph or force a recompile" % (tuple(shape),)))
    return out


def _lint_large_consts(symbol, shapes, type_dict):
    """Trace the graph with jax.make_jaxpr and flag closure-captured
    constants above LARGE_CONST_BYTES (they are baked into every compiled
    executable)."""
    import jax

    from ..symbol.symbol import _infer_entry_shapes, make_graph_fn
    known = {k: tuple(v) for k, v in (shapes or {}).items() if v is not None}
    entry_shapes, ok = _infer_entry_shapes(symbol._outputs, known, type_dict)
    if not ok:
        return []   # underspecified graph: nothing to trace
    nodes = symbol._nodes()
    args, aux = {}, {}
    for n in nodes:
        if n.op is not None:
            continue
        s = entry_shapes.get((id(n), 0))
        if s is None:
            return []
        (aux if n._is_aux else args)[n.name] = s
    graph_fn = make_graph_fn(symbol, train=False)
    try:
        closed = jax.make_jaxpr(graph_fn)(args, aux, jax.random.PRNGKey(0))
    except Exception:
        return []   # graph doesn't trace — execution will report it
    out = []
    for const in closed.consts:
        # shape x itemsize: jax's typed-constant wrapper has no .nbytes
        nbytes = int(_np.prod(const.shape)) * _np.dtype(const.dtype).itemsize
        if nbytes > LARGE_CONST_BYTES:
            out.append(Finding(
                "GRF006", symbol.name or "<graph>",
                "constant of shape %s (%s, %.1f MiB) is folded into the "
                "jaxpr; pass it as an argument instead of closing over it" %
                (tuple(const.shape), const.dtype, nbytes / (1 << 20))))
    return out


def lint_graph(symbol, shapes=None, type_dict=None, disable=(),
               check_consts=True):
    """Run every graph rule over ``symbol``.

    ``shapes``: {arg_name: shape} enabling the trace-based GRF006 check;
    ``type_dict``: {arg_name: dtype} for the float64 promotion walk.
    """
    nodes = symbol._nodes()
    heads = symbol._outputs
    tdict = {k: _np.dtype(v) for k, v in (type_dict or {}).items()}
    findings = []
    findings += _lint_dead_outputs(nodes, heads)
    findings += _lint_nondiff_path(nodes, heads)
    findings += _lint_aux_reads(nodes)
    findings += _lint_float64(nodes, tdict)
    findings += _lint_static_reshape(nodes)
    if check_consts:
        findings += _lint_large_consts(symbol, shapes, tdict)
    # node-level suppression: a __mxlint_disable__ attr on the node mutes
    # the listed rules for findings it subjects
    by_name = {n.name: n for n in nodes}
    kept = []
    for f in findings:
        node = by_name.get(f.subject)
        muted = ()
        if node is not None and "__mxlint_disable__" in node.attrs:
            muted = [r.strip() for r in
                     str(node.attrs["__mxlint_disable__"]).split(",")]
        if f.rule_id not in muted:
            kept.append(f)
    return filter_findings(kept, disable)
