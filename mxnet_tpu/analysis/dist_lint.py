"""DST rules: static checks over the distributed training step.

The automatic cross-replica sharding literature (PAPERS.md) treats the
weight-update as the property worth proving: every trainable parameter's
gradient must cross the data axis **exactly once** or replicas silently
diverge (missing reduction) or train with K-scaled gradients (duplicate
``psum``).  Under ``jax.jit`` + ``NamedSharding`` the reduction is
compiler-inserted, so nothing in the executed program is inspectable
before launch; the checkable surface is the *per-replica spelling* of
the step — the same computation with the collective written out
(``DataParallelTrainer._build_replica_step``), traced hardware-free via
``jax.make_jaxpr(..., axis_env=[(axis, K)])``.

The core is a variance propagation over the inlined tape
(:mod:`.cost`): program inputs are marked *varying* (different value on
every replica: the batch shard) or *invariant* (identical everywhere:
replicated params, optimizer state, the step's rng key, lr, t).  Any op
with a varying operand produces varying outputs; ``psum``/``pmean``
over the data axis makes its output invariant.  Then:

- **DST001** (error): a new-parameter output is still varying — its
  gradient was never reduced over the data axis; replicas desync.
- **DST002** (warning): a ``psum`` over the axis whose operand is
  already invariant — a duplicate reduction (``psum`` multiplies by K;
  a ``pmean`` spelled through it is a dead collective).
- **DST003** (error): ``NamedSharding`` mismatches between the mesh
  helpers and the step inputs — a parameter PartitionSpec that uses the
  data axis, names an axis the mesh lacks, or outranks the parameter;
  a batch axis the mesh cannot split evenly.
- **DST004**: collective reduction dtype.  A sub-f32 float (bf16/f16)
  reduced over the data axis is an **error** — a ring reduction
  accumulates one rounding per hop, so gradients must be cast to f32
  BEFORE the collective (the mixed-precision contract,
  docs/precision.md; ``precision.PRECISION_F32_GRAD_REDUCE`` is the
  seam proving this gate bites).  An operand already ≥f32 that was
  *widened* right before the collective (f32→f64) stays a warning:
  wider wire bytes than the math needs.
- **DST005** (warning): a Python value was baked into the step program
  as a closure constant.  A step program should be constant-free
  (everything iteration-dependent enters as an argument); a baked value
  traced at different times on different hosts is a cross-host
  divergence hazard (and a retrace trap).
"""
from __future__ import annotations

import numpy as _np

from .cost import build_tape, _aval_bytes
from .findings import ERROR, Finding, filter_findings

__all__ = ["lint_dist_step", "lint_trainer", "dist_summary"]

# collectives that make their output invariant over the reduced axes
_REDUCING = frozenset({"psum", "pmax", "pmin"})
# collectives that touch the axis without establishing invariance
_NON_REDUCING = frozenset({"all_gather", "ppermute", "all_to_all",
                           "reduce_scatter", "pbroadcast"})


def _is_float(dtype):
    import jax.numpy as jnp
    try:
        # jnp.issubdtype knows the extended float lattice (bfloat16,
        # float8_*) that numpy's own issubdtype rejects
        return bool(jnp.issubdtype(jnp.dtype(dtype), jnp.floating))
    except TypeError:
        return False


def _dtype_findings(op, tape, producer, data_axis, subject):
    """DST004 over one reducing collective's operands (module
    docstring): sub-f32 float on the wire is an ERROR, a ≥f32 operand
    widened immediately before the collective stays a WARNING."""
    out = []
    for i in op.in_ids:
        dt = tape.avals[i].dtype
        if not _is_float(dt):
            continue
        if _np.dtype(dt).itemsize < 4:
            out.append(Finding(
                "DST004", subject,
                "%s over axis %r reduces %s on the wire: a ring "
                "reduction accumulates one rounding per hop, so "
                "gradients must be cast to float32 BEFORE the "
                "collective and only narrowed after (the "
                "mixed-precision contract, docs/precision.md)"
                % (op.prim, data_axis, _np.dtype(dt).name),
                severity=ERROR))
            continue
        src = producer.get(i)
        if src is not None and src.prim == "convert_element_type":
            in_dt = tape.avals[src.in_ids[0]].dtype \
                if src.in_ids else dt
            if (_is_float(in_dt)
                    and 4 <= _np.dtype(in_dt).itemsize
                    < _np.dtype(dt).itemsize):
                out.append(Finding(
                    "DST004", subject,
                    "%s over axis %r reduces a value widened "
                    "%s->%s immediately before the collective: "
                    "%.2f MiB on the wire where %.2f would do — "
                    "reduce in %s and widen after (or make the "
                    "promotion explicit)"
                    % (op.prim, data_axis, _np.dtype(in_dt).name,
                       _np.dtype(dt).name,
                       _aval_bytes(tape.avals[i]) / (1 << 20),
                       _aval_bytes(tape.avals[src.in_ids[0]])
                       / (1 << 20), _np.dtype(in_dt).name)))
    return out


def lint_dist_step(closed_jaxpr, data_axis, varying_invars,
                   param_outvars=None, param_names=None, axis_size=None,
                   disable=(), subject="<step>"):
    """Run DST001/002/004/005 over a traced step.

    ``varying_invars``: flat invar indices holding per-replica values
    (the batch shard).  ``param_outvars``: flat outvar indices that are
    the *new parameter values* (checked invariant); default: every
    outvar.  ``param_names``: display names aligned with
    ``param_outvars``.
    """
    tape = build_tape(closed_jaxpr,
                      axis_sizes={data_axis: axis_size or 1})
    varying = set()
    for i in varying_invars:
        if 0 <= i < len(tape.invar_ids):
            varying.add(tape.invar_ids[i])

    findings = []
    producer = {}
    for op in tape.ops:
        for o in op.out_ids:
            producer[o] = op
        touches_axis = data_axis in op.axes
        any_varying = any(i in varying for i in op.in_ids)
        if op.prim in _REDUCING and touches_axis:
            if not any_varying:
                findings.append(Finding(
                    "DST002", subject,
                    "%s over axis %r applied to a value already invariant "
                    "over it — a duplicate reduction: psum multiplies by "
                    "the axis size, pmean is a dead collective"
                    % (op.prim, data_axis)))
            # reduced over the data axis: output identical on every
            # replica regardless of operand variance
            findings.extend(_dtype_findings(op, tape, producer,
                                            data_axis, subject))
            continue
        if op.prim in _NON_REDUCING and touches_axis:
            if op.prim == "reduce_scatter":
                # a reduce_scatter sums over the wire exactly like psum
                # (only the result layout differs): same dtype contract
                findings.extend(_dtype_findings(op, tape, producer,
                                                data_axis, subject))
            # value still differs per replica (gathered/permuted layout)
            if any_varying:
                varying.update(op.out_ids)
            continue
        if any_varying:
            varying.update(op.out_ids)

    out_idx = (range(len(tape.outvar_ids)) if param_outvars is None
               else param_outvars)
    names = list(param_names or [])
    for j, oi in enumerate(out_idx):
        if not (0 <= oi < len(tape.outvar_ids)):
            continue
        if tape.outvar_ids[oi] in varying:
            name = names[j] if j < len(names) else "output %d" % oi
            findings.append(Finding(
                "DST001", name,
                "new value of %r still varies over mesh axis %r: its "
                "gradient is never psum/pmean-reduced over the data "
                "axis, so replicas silently diverge after one step"
                % (name, data_axis)))

    for i in tape.const_ids:
        aval = tape.avals[i]
        findings.append(Finding(
            "DST005", subject,
            "step program closes over a baked constant %s%s (%d bytes): "
            "iteration-dependent Python values captured at trace time "
            "diverge across hosts that trace at different moments — "
            "pass it as an argument instead"
            % (getattr(aval, "dtype", "?"),
               tuple(getattr(aval, "shape", ())), _aval_bytes(aval))))
    return filter_findings(findings, disable)


def _check_shardings(mesh, data_axis, param_specs, batch_dims,
                     disable=(), subject="<trainer>"):
    """DST003: mesh/PartitionSpec consistency between the mesh helpers
    and the step inputs."""
    findings = []
    axis_names = tuple(mesh.axis_names)
    axis_sizes = dict(zip(axis_names, mesh.devices.shape))
    if data_axis not in axis_names:
        findings.append(Finding(
            "DST003", subject,
            "data axis %r is not an axis of the mesh %r — the batch "
            "cannot be sharded and the gradient reduction has no axis "
            "to ride" % (data_axis, axis_names)))
        return filter_findings(findings, disable)
    for name, (shape, spec) in sorted(param_specs.items()):
        spec_axes = [a for part in tuple(spec) if part is not None
                     for a in ((part,) if isinstance(part, str)
                               else tuple(part))]
        if len(tuple(spec)) > len(shape):
            findings.append(Finding(
                "DST003", name,
                "PartitionSpec %r has %d entries but parameter %r is "
                "rank %d" % (tuple(spec), len(tuple(spec)), name,
                             len(shape))))
            continue
        for a in spec_axes:
            if a not in axis_names:
                findings.append(Finding(
                    "DST003", name,
                    "PartitionSpec %r names axis %r which the mesh %r "
                    "does not have" % (tuple(spec), a, axis_names)))
        if data_axis in spec_axes:
            findings.append(Finding(
                "DST003", name,
                "parameter %r is sharded over the data axis %r: the "
                "data axis carries the batch and the gradient psum — a "
                "weight laid out over it desyncs with the replicated "
                "update (use a model/tensor axis for weight sharding)"
                % (name, data_axis)))
        for dim, a in zip(shape, tuple(spec)):
            for ax in ((a,) if isinstance(a, str) else tuple(a or ())):
                sz = axis_sizes.get(ax)
                if sz and int(dim) % int(sz) != 0:
                    findings.append(Finding(
                        "DST003", name,
                        "dim %d of %r is not divisible by axis %r "
                        "(size %d)" % (int(dim), name, ax, int(sz))))
    ksize = int(axis_sizes[data_axis])
    for name, dim in sorted(batch_dims.items()):
        if int(dim) % ksize != 0:
            findings.append(Finding(
                "DST003", name,
                "batch input %r has leading dim %d, not divisible by "
                "data axis %r (size %d) — NamedSharding placement "
                "fails at step time" % (name, int(dim), data_axis,
                                        ksize)))
    return filter_findings(findings, disable)


def lint_trainer(trainer, data_shape=None, label_shape=None,
                 data_dtype="float32", label_dtype="int32",
                 declared_axis_size=None, disable=()):
    """Full DST pass over a ``DataParallelTrainer``.

    Traces the trainer's per-replica step (explicit collectives) with
    ``make_jaxpr(axis_env=...)`` — no devices beyond the trainer's own
    mesh are needed — and combines the jaxpr rules with the DST003
    sharding-consistency checks.  ``data_shape`` is the whole batch's
    (``label_shape`` defaults to one label a row); a trainer that has
    not seen a batch is set up from zeros of it.
    """
    import jax

    data_shape, label_shape = trainer._setup_from_shapes(
        data_shape, label_shape, data_dtype, label_dtype)
    mesh = trainer._mesh
    axis = trainer._data_axis
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ksize = int(declared_axis_size or axis_sizes.get(axis, 1))

    param_specs = {
        name: (tuple(p.shape),
               trainer._param_spec_fn(name, p.shape))
        for name, p in trainer._params_by_name.items()
        if p.grad_req != "null"}
    findings = _check_shardings(
        mesh, axis, param_specs,
        {"data": data_shape[0], "label": label_shape[0]},
        disable=disable, subject="DataParallelTrainer")

    # the per-replica spelling sees the batch SHARD
    args = trainer._trace_args(data_shape, label_shape, data_dtype,
                               label_dtype, axis_size=ksize)
    train_vals, states, aux_vals = args[:3]
    try:
        closed = jax.make_jaxpr(trainer._build_replica_step(),
                                axis_env=[(axis, ksize)])(*args)
    except Exception as e:
        findings.append(Finding(
            "DST001", "DataParallelTrainer",
            "per-replica step does not trace (%s: %s) — the distributed "
            "step cannot be verified statically"
            % (type(e).__name__, str(e)[:200])))
        return filter_findings(findings, disable)

    # flat layout of the step args: train_vals, states, aux, x, y, key,
    # lr, t — only the batch (x, y) varies per replica
    n_train = len(jax.tree_util.tree_leaves(train_vals))
    n_states = len(jax.tree_util.tree_leaves(states))
    n_aux = len(jax.tree_util.tree_leaves(aux_vals))
    varying = [n_train + n_states + n_aux,
               n_train + n_states + n_aux + 1]
    # outputs: loss, new_vals..., new_states..., muts... — the new
    # parameter values are outvars [1, 1 + n_train)
    param_out = list(range(1, 1 + n_train))
    findings += lint_dist_step(
        closed, axis, varying_invars=varying, param_outvars=param_out,
        param_names=list(trainer._train_names), axis_size=ksize,
        disable=disable, subject="DataParallelTrainer")
    # the loss every rank reports must also be the global (invariant)
    # mean — checked as a pseudo-parameter
    findings += [
        Finding("DST001", "loss",
                f.message.replace("gradient", "value"))
        for f in lint_dist_step(
            closed, axis, varying_invars=varying, param_outvars=[0],
            param_names=["loss"], axis_size=ksize, disable=("DST002",
                                                            "DST004",
                                                            "DST005"))
        if f.rule_id == "DST001"]
    return filter_findings(findings, disable)


def dist_summary(findings, axis_sizes=None, params_checked=0):
    """Machine-readable ``dist`` section for the CLI ``--json`` output."""
    return {
        "rules": ["DST001", "DST002", "DST003", "DST004", "DST005",
                  "DST006", "DST007", "DST008", "DST009", "DST010",
                  "DST011", "DST012"],
        "axis_sizes": {k: int(v)
                       for k, v in sorted((axis_sizes or {}).items())},
        "params_checked": int(params_checked),
        "findings": [f.as_dict() for f in findings
                     if f.rule_id.startswith("DST")],
    }
