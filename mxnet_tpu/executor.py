"""Executor: a bound, compiled symbol.

Reference: ``include/mxnet/executor.h:53-152`` / ``src/executor/
graph_executor.cc:514`` (GraphExecutor::Init builds the full fwd+bwd nnvm
graph, infers shapes, plans memory, attaches engine ops) and the Python
wrapper ``python/mxnet/executor.py``.

TPU-native design: binding builds a pure jax function over the DAG
(``symbol.make_graph_fn``) and hands it to ``jax.jit`` — XLA is the memory
planner, op fuser and scheduler.  ``backward`` compiles the ``jax.vjp`` of
the same function (the ``nnvm::pass::Gradient`` analogue); the forward is
rematerialized inside the backward program, which XLA CSEs/schedules for
HBM reuse — the TPU equivalent of the reference's memory-sharing passes.

Data parallelism: pass ``ctx`` as a device list — the executor builds a
``Mesh`` over it, shards the data arguments on the batch axis and
replicates parameters; GSPMD inserts the gradient ``psum`` over ICI
(replacing DataParallelExecutorGroup + KVStore 'device',
``python/mxnet/module/executor_group.py:143``).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray import NDArray
from .symbol.symbol import make_graph_fn

__all__ = ["Executor"]


def _fit_spec(spec, shape, mesh):
    """Best-effort fit of a group PartitionSpec onto a tensor: keep an axis
    assignment only where the dimension divides evenly (GSPMD-style; one
    group covers tensors of many ranks, as ctx_group did placement-wise)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for d, ax in enumerate(tuple(spec)[:len(shape)]):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        out.append(ax if shape[d] % total == 0 else None)
    return PartitionSpec(*out)


def _as_device_list(ctx):
    if ctx is None:
        ctx = current_context()
    if isinstance(ctx, Mesh):
        return list(ctx.devices.flat)
    if isinstance(ctx, Context):
        return [ctx.jax_device()]
    if isinstance(ctx, (list, tuple)):
        return [c.jax_device() if isinstance(c, Context) else c for c in ctx]
    return [ctx]


class Executor:
    def __init__(self, symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None, data_names=None,
                 group2ctx=None):
        self._symbol = symbol
        self._devices = _as_device_list(ctx)
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = list(data_names) if data_names else []

        # ---- argument arrays -------------------------------------------
        if args is None:
            raise MXNetError("bind requires args")
        if isinstance(args, dict):
            arg_dict = dict(args)
        else:
            arg_dict = dict(zip(self._arg_names, args))
        missing = [n for n in self._arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("missing arguments: %r" % (missing,))
        self.arg_dict = {n: _as_nd(arg_dict[n]) for n in self._arg_names}
        self.arg_arrays = [self.arg_dict[n] for n in self._arg_names]

        # ---- aux arrays -------------------------------------------------
        if aux_states is None:
            aux_states = {}
        if not isinstance(aux_states, dict):
            aux_states = dict(zip(self._aux_names, aux_states))
        self.aux_dict = {n: _as_nd(aux_states[n]) for n in self._aux_names}
        self.aux_arrays = [self.aux_dict[n] for n in self._aux_names]

        # ---- grad arrays / grad_req ------------------------------------
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        if args_grad is None:
            args_grad = {n: NDArray(jnp.zeros_like(self.arg_dict[n]._data))
                         for n in self._arg_names
                         if self._grad_req.get(n, "null") != "null"}
        elif not isinstance(args_grad, dict):
            args_grad = dict(zip(self._arg_names, args_grad))
        self.grad_dict = {n: _as_nd(g) for n, g in args_grad.items()
                          if g is not None and self._grad_req.get(n) != "null"}
        self.grad_arrays = [self.grad_dict.get(n) for n in self._arg_names]

        self._wrt = [n for n in self._arg_names
                     if self._grad_req.get(n, "null") != "null"]

        # ---- sharding across the device mesh ---------------------------
        self._mesh = None
        if isinstance(ctx, Mesh):
            self._mesh = ctx
        elif len(self._devices) > 1:
            self._mesh = Mesh(_np.asarray(self._devices), ("data",))

        # group2ctx consumption (reference: the PlaceDevice pass turns
        # ctx_group attrs into placement, graph_executor.cc:408; here the
        # groups map to PartitionSpecs and GSPMD plans the collectives):
        # {group: PartitionSpec} shards every node/arg tagged with that
        # ctx_group.  Context values (reference API) mean replicated.
        self._group_specs = {}
        if group2ctx:
            for g, spec in group2ctx.items():
                if isinstance(spec, PartitionSpec):
                    self._group_specs[g] = spec
                elif isinstance(spec, (tuple, list)):
                    self._group_specs[g] = PartitionSpec(*spec)
                else:  # Context — placement only, replicate
                    self._group_specs[g] = PartitionSpec()
        self._arg_groups = {}
        sharding_map = None
        if self._group_specs and self._mesh is not None:
            sharding_map = {}
            for node in symbol._nodes():
                g = node.attrs.get("ctx_group")
                if g is None or g not in self._group_specs:
                    continue
                if node.op is None:
                    self._arg_groups[node.name] = g
                else:
                    # fitted per-output at trace time (shapes unknown here)
                    sharding_map[node.name] = (self._mesh,
                                               self._group_specs[g])
        self._place_arrays()

        # ---- compiled programs -----------------------------------------
        self._graph_infer = make_graph_fn(symbol, train=False,
                                          sharding_map=sharding_map)
        self._graph_train = make_graph_fn(symbol, train=True,
                                          sharding_map=sharding_map)
        self._jit_infer = jax.jit(self._graph_infer)
        self._jit_train = jax.jit(self._graph_train)

        def _bwd(arg_vals, aux_vals, head_grads, rng_key):
            fixed = {n: v for n, v in arg_vals.items() if n not in self._wrt}

            def f(wrt_vals):
                ad = dict(fixed)
                ad.update(wrt_vals)
                outs, new_aux = self._graph_train(ad, aux_vals, rng_key)
                return outs, new_aux

            (outs, new_aux), vjp = jax.vjp(
                f, {n: arg_vals[n] for n in self._wrt}, has_aux=False)
            grads = vjp((head_grads, jax.tree_util.tree_map(jnp.zeros_like, new_aux)))[0]
            return outs, new_aux, grads

        self._jit_bwd = jax.jit(_bwd)

        self.outputs = []
        self._out_raw = None
        self._last_key = _fresh_key()
        # executed jit signatures: one entry per compiled program variant
        # (shape/dtype of every arg + aux, train flag).  The serving layer
        # asserts recompile-free steady state against this set.
        self._jit_cache_keys = set()

    # ------------------------------------------------------------------
    def _sharding(self, name):
        if self._mesh is None:
            return None
        if name in self._arg_groups:
            spec = self._group_specs[self._arg_groups[name]]
            arr = self.arg_dict.get(name)
            if arr is None:
                arr = self.aux_dict.get(name)
            if arr is not None:
                spec = _fit_spec(spec, arr.shape, self._mesh)
            return NamedSharding(self._mesh, spec)
        if name in self._data_names or name.endswith("_label"):
            if "data" in self._mesh.axis_names:
                return NamedSharding(self._mesh, PartitionSpec("data"))
        return NamedSharding(self._mesh, PartitionSpec())

    def _place_arrays(self):
        if self._mesh is None:
            dev = self._devices[0]
            for d in (self.arg_dict, self.aux_dict, self.grad_dict):
                for n, a in d.items():
                    if not _on_device(a._data, dev):
                        a._set_data(jax.device_put(a._data, dev))
            return
        for d in (self.arg_dict, self.aux_dict, self.grad_dict):
            for n, a in d.items():
                a._set_data(jax.device_put(a._data, self._sharding(n)))

    # ------------------------------------------------------------------
    @classmethod
    def simple_bind(cls, symbol, ctx=None, grad_req="write", type_dict=None,
                    shapes=None, data_names=None, group2ctx=None,
                    lint=False):
        shapes = shapes or {}
        if lint:
            # opt-in static pass (mxnet_tpu.analysis) before any trace:
            # error findings abort the bind, warnings go through warnings
            from .analysis import ERROR as _LINT_ERROR
            from .analysis import lint_symbol, render_text
            findings = lint_symbol(symbol, shapes=shapes,
                                   type_dict=type_dict)
            errors = [f for f in findings if f.severity == _LINT_ERROR]
            if errors:
                raise MXNetError("simple_bind lint failed:\n%s"
                                 % render_text(errors))
            if findings:
                import warnings
                warnings.warn("simple_bind lint:\n%s" % render_text(findings))
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        if arg_shapes is None:
            raise MXNetError(
                "simple_bind: cannot infer all shapes from %r" % (shapes,))
        type_dict = type_dict or {}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        args = {}
        for n, s in zip(arg_names, arg_shapes):
            dt = np_dtype(type_dict.get(n, "float32"))
            args[n] = NDArray(jnp.zeros(s, dtype=dt))
        aux = {}
        for n, s in zip(aux_names, aux_shapes):
            init = jnp.ones(s, _np.float32) if n.endswith("_var") else \
                jnp.zeros(s, _np.float32)
            aux[n] = NDArray(init)
        if data_names is None:
            data_names = [n for n in shapes if n in arg_names]
        return cls(symbol, ctx, args=args, grad_req=grad_req,
                   aux_states=aux, data_names=data_names,
                   group2ctx=group2ctx)

    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for n, v in kwargs.items():
            if n not in self.arg_dict:
                raise MXNetError("unknown argument %r" % n)
            raw = _raw(v)
            # feeds land on the executor's device/sharding (async transfer
            # overlaps with compute — the PrefetcherIter copy analogue)
            target = self._sharding(n) or self._devices[0]
            if not _on_device(raw, self._devices[0]) or self._mesh is not None:
                raw = jax.device_put(raw, target)
            # dtype-stable feed: a float-bound slot fed uint8 (the narrow
            # uint8 pipeline) or a mismatched float width would change the
            # jit signature and recompile every program — cast on device
            # AFTER the (narrow) transfer instead.  Integer feeds into
            # integer slots pass through untouched.
            bound = self.arg_dict[n]._data.dtype
            if raw.dtype != bound and jnp.issubdtype(bound, jnp.floating) \
                    and (raw.dtype == jnp.uint8
                         or jnp.issubdtype(raw.dtype, jnp.floating)):
                raw = raw.astype(bound)
            self.arg_dict[n]._set_data(raw)
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        self._jit_cache_keys.add((
            bool(is_train),
            tuple(sorted((n, tuple(v.shape), str(v.dtype))
                         for n, v in arg_vals.items())),
            tuple(sorted((n, tuple(v.shape), str(v.dtype))
                         for n, v in aux_vals.items()))))
        fn = self._jit_train if is_train else self._jit_infer
        # draw the key eagerly; backward reuses it so dropout masks match
        # between the forward pass and the rematerialized one in the vjp
        self._last_key = _fresh_key()
        outs, new_aux = fn(arg_vals, aux_vals, self._last_key)
        if is_train:
            for n, v in new_aux.items():
                self.aux_dict[n]._set_data(v)
        self._out_raw = outs
        self.outputs = [NDArray(o) for o in outs]
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        if self._out_raw is None:
            raise MXNetError("backward called before forward")
        if out_grads is None:
            head = [jnp.ones_like(o) for o in self._out_raw]
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head = [_raw(g) for g in out_grads]
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        _outs, _new_aux, grads = self._jit_bwd(arg_vals, aux_vals, head,
                                               self._last_key)
        for n, g in grads.items():
            req = self._grad_req.get(n, "null")
            if req == "null":
                continue
            dst = self.grad_dict.get(n)
            if dst is None:
                self.grad_dict[n] = NDArray(g)
            elif req == "add":
                dst._set_data(dst._data + g)
            else:
                dst._set_data(g)
        self.grad_arrays = [self.grad_dict.get(n) for n in self._arg_names]
        return [self.grad_dict.get(n) for n in self._wrt]

    # ------------------------------------------------------------------
    def jit_cache_keys(self):
        """Signatures executed so far — the jit-cache keys.  jax.jit caches
        one compiled program per signature, so a stable set across a load
        window proves zero steady-state recompiles (serving contract)."""
        return set(self._jit_cache_keys)

    def jit_cache_size(self):
        """Number of compiled program variants: the jits' own cache
        counters, which count actual XLA traces."""
        return int(self._jit_infer._cache_size()
                   + self._jit_train._cache_size()
                   + self._jit_bwd._cache_size())

    # ------------------------------------------------------------------
    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for n, v in (arg_params or {}).items():
            if n in self.arg_dict:
                self.arg_dict[n]._set_data(
                    _raw(v).astype(self.arg_dict[n]._data.dtype))
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % n)
        for n, v in (aux_params or {}).items():
            if n in self.aux_dict:
                self.aux_dict[n]._set_data(
                    _raw(v).astype(self.aux_dict[n]._data.dtype))
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %r" % n)
        self._place_arrays()

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new data shapes, keeping parameter arrays
        (reference: executor.h:120; jit recompiles per shape — cached)."""
        shapes = {n: kwargs.get(n, self.arg_dict[n].shape)
                  for n in self._data_names} if self._data_names else dict(kwargs)
        new = Executor.simple_bind(
            self._symbol, None,
            grad_req={n: r for n, r in self._grad_req.items()},
            shapes=shapes, data_names=self._data_names)
        for n, a in self.arg_dict.items():
            if n not in self._data_names and n in new.arg_dict and \
                    new.arg_dict[n].shape == a.shape:
                new.arg_dict[n]._set_data(a._data)
        for n, a in self.aux_dict.items():
            if n in new.aux_dict and new.aux_dict[n].shape == a.shape:
                new.aux_dict[n]._set_data(a._data)
        return new

    def __repr__(self):
        return "<Executor %s on %d device(s)>" % (
            self._symbol.name or "group", len(self._devices))


def _fresh_key():
    from . import _rng
    return _rng.next_key()


def _as_nd(x):
    if isinstance(x, NDArray):
        return x
    return NDArray(jnp.asarray(x))


def _raw(x):
    if isinstance(x, NDArray):
        return x._data
    return jnp.asarray(x)


def _on_device(arr, dev):
    try:
        return next(iter(arr.devices())) == dev
    except (AttributeError, TypeError):
        return True
