"""Gluon Block / HybridBlock and the CachedOp (hybridized) executor.

Reference: ``python/mxnet/gluon/block.py:126`` (Block), ``:669``
(HybridBlock), ``hybridize:830``; CachedOp ``src/imperative/cached_op.cc:94``
with static/dynamic memory planning (``:684,756``).

TPU-native design: ``hybridize()`` compiles the block's forward into ONE
``jax.jit`` program per (input shapes/dtypes, train-flag) key — XLA's fusion
and buffer assignment replace the reference's nnvm graph caching and
PlanMemory pass.  Tracing runs the same eager Python ``hybrid_forward`` with
NDArrays wrapping tracers, so there is no separate symbolic dialect.
Mutable aux states (BatchNorm moving stats) touched during tracing are
captured via the NDArray mutation tracker and returned as extra jit outputs,
then written back — the functional analogue of FMutateInputs
(op_attr_types.h).  RNG inside the trace draws from a per-call key argument
(see mxnet_tpu/_rng.py), keeping the compiled program pure.
"""
from __future__ import annotations

import re

import numpy as np

import jax

from .. import autograd, _rng
from .. import ndarray as nd
from ..ndarray import NDArray
from ..ndarray import ndarray as _ndmod
from .parameter import Parameter, ParameterDict, DeferredInitializationError


class _BlockScope:
    _current = None
    _global_counter = {}

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = _BlockScope._current
        if current is None:
            if prefix is None:
                # global NameManager analogue (reference: python/mxnet/name.py)
                count = _BlockScope._global_counter.get(hint, 0)
                _BlockScope._global_counter[hint] = count + 1
                prefix = "%s%d_" % (hint, count) if hint else ""
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        # empty-prefix blocks are naming-transparent: the parent scope stays
        # active so sibling counters continue (reference: block.py:73-75)
        if self._block._empty_prefix:
            return self
        self._old_scope = _BlockScope._current
        _BlockScope._current = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _BlockScope._current = self._old_scope


def _in_cached_trace():
    return bool(_ndmod._MUTATION_TRACKERS)


class Block:
    """Base building block (reference: gluon/block.py:126)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        # the name this block's operations carry inside a compiled
        # program (``jax.named_scope`` in ``__call__``): its own name
        # less the enclosing block's prefix, so that nested scopes read
        # ``resnetv10/stage1/conv2d0``, each part once.  A block with an
        # empty prefix adds no part, as it adds none to a parameter's name
        outer = _BlockScope._current
        outer = outer._block.prefix if outer is not None else ""
        self._scope_name = "" if self._empty_prefix else (
            self._name[len(outer):] if self._name.startswith(outer)
            else self._name)
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer
        self.collect_params().initialize(init or initializer.Uniform(), ctx,
                                         verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self._reg_params.items():
            param.cast(dtype)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, format="mxtpu"):
        """Reference: gluon/block.py:313.  format="mxnet" writes the
        reference dmlc-stream .params layout."""
        params = self._collect_params_with_prefix()
        nd.save(filename, {k: v.data() for k, v in params.items()
                           if v._data is not None}, format=format)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        """Reference: gluon/block.py:355."""
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError("missing parameter %r in %s" % (name, filename))
        for name, data in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError("unknown parameter %r in %s" % (name, filename))
                continue
            params[name].set_data(data)

    save_params = save_parameters
    load_params = load_parameters

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def summary(self, *inputs):
        from ..visualization import block_summary
        return block_summary(self, *inputs)

    def __call__(self, *args, **kwargs):
        # every operation traced below carries this block's name in its
        # HLO metadata (``op_name``), which is what lets a device trace
        # say which layer an operation belongs to
        # (docs/observability.md "Program scopes").  Metadata only: it
        # costs nothing at run time; an eager call pays the context
        # manager, about a microsecond.  Blocks with an empty prefix are
        # naming-transparent here as they are for parameters.
        if not self._scope_name:
            return self.forward(*args, **kwargs)
        with jax.named_scope(self._scope_name):
            return self.forward(*args, **kwargs)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __repr__(self):
        s = "{name}(\n{modstr}\n)" if self._children else "{name}()"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    return "\n".join([first] + [(num_spaces * " ") + line for line in lines])


class CachedOp:
    """Hybrid-graph executor: one jit program per input signature.

    Reference: src/imperative/cached_op.cc:94 (Forward:834 →
    StaticForward/DynamicForward, Backward:1046).  The signature→compiled
    cache replaces the reference's static/dynamic memory plans: XLA buffer
    assignment handles allocation; jax.vjp over the same traced callable
    provides Backward.
    """

    def __init__(self, block):
        self._block = block
        self._cache = {}
        self._remat = bool(getattr(block, "_remat", False))

    def cache_keys(self):
        """The jit-cache keys compiled so far: one per (input shapes/dtypes,
        train flag, kwargs) signature.  Stable set == no recompiles."""
        return set(self._cache.keys())

    def cache_size(self):
        return len(self._cache)

    def _make_body(self, params, param_names, kwargs, train):
        block = self._block

        def body(param_vals, input_vals, rng_key):
            """Pure function of (params, inputs, key) -> outputs + mutated aux."""
            mutations = []
            wrapped_inputs = [NDArray(v) for v in input_vals]
            _ndmod._MUTATION_TRACKERS.append(
                lambda obj, val: mutations.append((obj, val)))
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(train)
            saved = {}
            try:
                with _rng.trace_scope(rng_key):
                    for name, val in zip(param_names, param_vals):
                        saved[name] = params[name]._data._data
                        params[name]._data._data = val
                    try:
                        out = block.hybrid_forward_wrapper(*wrapped_inputs,
                                                           **kwargs)
                    finally:
                        mut_ids, mut_vals = [], []
                        for obj, new_val in mutations:
                            for name in param_names:
                                if params[name]._data is obj:
                                    mut_ids.append(name)
                                    mut_vals.append(new_val)
                                    break
                        for name in param_names:
                            params[name]._data._data = saved[name]
            finally:
                _ndmod._MUTATION_TRACKERS.pop()
                autograd.set_recording(prev_rec)
                autograd.set_training(prev_train)
            single = not isinstance(out, (list, tuple))
            outs = [out] if single else list(out)
            body.mut_ids = mut_ids        # static side-channel, set at trace
            body.single = single
            return tuple(o._data for o in outs) + tuple(mut_vals)

        body.mut_ids = None
        body.single = True
        return body

    def __call__(self, params, inputs, train, kwargs):
        key = (
            tuple((tuple(i.shape), str(i.dtype)) for i in inputs),
            bool(train),
            tuple(sorted(kwargs.items())) if kwargs else (),
        )
        entry = self._cache.get(key)
        if entry is None:
            param_names = list(params.keys())
            body = self._make_body(params, param_names, kwargs, train)
            fn = jax.checkpoint(body) if (self._remat and train) else body
            entry = {"body": body, "jitted": jax.jit(fn),
                     "param_names": param_names}
            self._cache[key] = entry

        body = entry["body"]
        param_nds = [params[n].data() for n in entry["param_names"]]
        param_vals = tuple(p._data for p in param_nds)
        input_vals = tuple(i._data for i in inputs)
        rng_key = _rng.next_key()

        if autograd.is_recording():
            jfn = entry["jitted"]

            def fwd(pv, iv):
                return jfn(pv, iv, rng_key)

            all_out, vjp_fn = jax.vjp(fwd, param_vals, input_vals)

            def node_vjp(cotangents):
                pg, ig = vjp_fn(tuple(cotangents))
                return list(pg) + list(ig)

            node = autograd.record_op(node_vjp, param_nds + list(inputs),
                                      list(all_out))
        else:
            all_out = entry["jitted"](param_vals, input_vals, rng_key)
            node = None

        n_mut = len(body.mut_ids or ())
        n_out = len(all_out) - n_mut
        out_nds = [NDArray(o) for o in all_out[:n_out]]
        if node is not None:
            for i, o in enumerate(out_nds):
                o._entry = (node, i)
        for name, val in zip(body.mut_ids or (), all_out[n_out:]):
            params[name]._data._set_data(val)
        return out_nds[0] if body.single else out_nds


class HybridBlock(Block):
    """Block that can be hybridized into a jit-compiled CachedOp
    (reference: gluon/block.py:669)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  remat=False, **kwargs):
        """remat=True rematerializes this block's forward in the backward
        pass (jax.checkpoint) — the MXNET_BACKWARD_DO_MIRROR /
        docs/faq/env_var.md memory-mirroring analogue: sublinear activation
        memory for extra FLOPs."""
        self._active = active
        self._cached_op = None
        self._remat = remat
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_param_shapes(self, *args):
        """Resolve deferred parameter shapes from input shapes.
        Layers with deferred params override this (reference: generic
        infer_shape pass; here each layer knows its own rule)."""

    def hybrid_forward_wrapper(self, *args, **kwargs):
        pkw = {name: p.data() for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **pkw, **kwargs)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def forward(self, *args, **kwargs):
        if any(p._deferred_init for p in self._reg_params.values()):
            self.infer_param_shapes(*args)
            for p in self._reg_params.values():
                if p._deferred_init:
                    p._finish_deferred_init()
        if self._active and not _in_cached_trace():
            if any(p._deferred_init
                   for p in self.collect_params().values()):
                # children still deferred: one eager pass resolves shapes
                # (the reference runs infer_shape over the graph instead)
                with autograd.pause(train_mode=autograd.is_training()):
                    self.hybrid_forward_wrapper(*args, **kwargs)
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            all_params = self._collect_all_reg_params()
            return self._cached_op(all_params, list(args),
                                   autograd.is_training(), kwargs)
        return self.hybrid_forward_wrapper(*args, **kwargs)

    def jit_cache_keys(self):
        """Jit-cache keys across this block and its hybridized children
        (reference: the CachedOp signature cache, cached_op.cc:94).  A
        serving ModelRunner snapshots this after warmup; any growth under
        traffic is a steady-state recompile."""
        keys = set()
        if self._cached_op is not None:
            keys |= {(self.name, k) for k in self._cached_op.cache_keys()}
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                keys |= child.jit_cache_keys()
        return keys

    def jit_cache_size(self):
        return len(self.jit_cache_keys())

    def _collect_all_reg_params(self):
        out = {}
        for p in self._reg_params.values():
            out[p.name] = p
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                out.update(child._collect_all_reg_params())
        return out

    def export(self, path, epoch=0):
        """Save graph JSON + params for deployment (reference: block.py:866).
        The params file uses arg:/aux: key prefixes like the reference's
        HybridBlock.export."""
        import json
        params = self._collect_params_with_prefix()
        arg_dict = {}
        for name, p in params.items():
            if p._data is not None:
                prefix = "aux:" if p.grad_req == "null" else "arg:"
                arg_dict[prefix + name] = p.data()
        nd.save("%s-%04d.params" % (path, epoch), arg_dict)
        sym = {"nodes": [{"op": "cached_op_subgraph", "name": self.name,
                          "params": sorted(params.keys())}],
               "format": "mxnet_tpu-0.1"}
        with open("%s-symbol.json" % path, "w") as f:
            json.dump(sym, f, indent=2)


class SymbolBlock(HybridBlock):
    """Run a loaded Symbol graph as a Gluon block
    (reference: gluon/block.py SymbolBlock)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._symbol = outputs
        self._sym_inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]

    def hybrid_forward(self, F, *args, **kwargs):
        from ..symbol import eval_symbol
        names = [i.name for i in self._sym_inputs]
        feed = dict(zip(names, args))
        out = eval_symbol(self._symbol, feed)
        return out
