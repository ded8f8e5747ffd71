"""``mx.nd`` — the imperative NDArray namespace.

Reference: ``python/mxnet/ndarray/``.  Functions are generated from the op
registry (see register.py); creation helpers mirror ndarray.py's public API.
"""
from __future__ import annotations

import sys
import types

import numpy as _np
import jax.numpy as _jnp

from ..base import np_dtype
from ..context import current_context
from ..ops import registry as _reg
from .ndarray import NDArray, array, empty, concatenate, invoke, imperative_invoke

# generated namespace -------------------------------------------------------
_internal = types.ModuleType(__name__ + "._internal")
sys.modules[_internal.__name__] = _internal

from . import register as _register  # noqa: E402

_register.populate(sys.modules[__name__], _internal)

# `nd.contrib` / `nd.linalg` / `nd.random` sub-namespaces: _contrib_*-style
# registered names exposed with the prefix stripped (reference:
# python/mxnet/ndarray/contrib.py generated namespaces)
contrib = types.ModuleType(__name__ + ".contrib")
linalg = types.ModuleType(__name__ + ".linalg")
sys.modules[contrib.__name__] = contrib
sys.modules[linalg.__name__] = linalg
for _name in _reg.list_ops():
    if _name.startswith("_contrib_"):
        setattr(contrib, _name[len("_contrib_"):],
                getattr(_internal, _name))
    elif _name.startswith("_linalg_"):
        setattr(linalg, _name[len("_linalg_"):], getattr(_internal, _name))

from ..ops.control_flow import foreach, while_loop, cond  # noqa: E402
contrib.foreach = foreach
contrib.while_loop = while_loop
contrib.cond = cond


def Custom(*args, **kwargs):
    """Run a Python CustomOp (reference: generated nd.Custom over
    src/operator/custom/custom.cc; see mxnet_tpu/operator.py)."""
    from ..operator import _custom_entry
    return _custom_entry(*args, **kwargs)


# creation helpers (reference: python/mxnet/ndarray/utils.py + ndarray.py) --
def zeros(shape, ctx=None, dtype=None, stype=None, **kwargs):
    if stype not in (None, "default"):
        from . import sparse as _sp
        return _sp.zeros(stype, shape, ctx=ctx, dtype=dtype)
    return _internal._zeros(shape=shape if isinstance(shape, (list, tuple)) else (shape,),
                            dtype=(dtype or "float32"), ctx=ctx or current_context())


def ones(shape, ctx=None, dtype=None, **kwargs):
    return _internal._ones(shape=shape if isinstance(shape, (list, tuple)) else (shape,),
                           dtype=(dtype or "float32"), ctx=ctx or current_context())


def full(shape, val, ctx=None, dtype=None, **kwargs):
    return _internal._full(shape=shape if isinstance(shape, (list, tuple)) else (shape,),
                           value=float(val), dtype=(dtype or "float32"),
                           ctx=ctx or current_context())


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    return _internal._arange(start=start, stop=stop, step=step, repeat=repeat,
                             dtype=(dtype or "float32"), ctx=ctx or current_context())


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    return _internal._linspace(start=start, stop=stop, num=num, endpoint=endpoint,
                               dtype=(dtype or "float32"), ctx=ctx or current_context())


def eye(N, M=0, k=0, ctx=None, dtype=None):
    return _internal._eye(N=N, M=M, k=k, dtype=(dtype or "float32"),
                          ctx=ctx or current_context())


def zeros_like(data):
    return imperative_invoke("zeros_like", data)


def ones_like(data):
    return imperative_invoke("ones_like", data)


def waitall():
    """Block until all async computation completes (reference engine WaitForAll)."""
    import jax
    (_jnp.zeros(()) + 0).block_until_ready()
    jax.effects_barrier()


def load(fname):
    """Load NDArrays saved by save() (reference: NDArray::Load, ndarray.cc)."""
    from ..serialization import load_ndarrays
    return load_ndarrays(fname)


def save(fname, data, format="mxtpu"):
    """Save list or dict of NDArrays (reference: NDArray::Save, ndarray.cc).
    format="mxnet" writes the reference dmlc-stream layout so stock MXNet
    ``mx.nd.load`` can read the file."""
    from ..serialization import save_ndarrays
    save_ndarrays(fname, data, format=format)


# random namespace ----------------------------------------------------------
random = types.ModuleType(__name__ + ".random")
sys.modules[random.__name__] = random


def _rand_fn(op_name, pub_name):
    def fn(*args, **kwargs):
        kwargs.setdefault("ctx", None)
        ctx = kwargs.pop("ctx", None)
        # positional params map (low/high etc.) — accept positionally
        op = _reg.get(op_name)
        if args and not isinstance(args[0], NDArray):
            # treat positionals as the op's leading scalar params
            pmap = _POSITIONAL.get(pub_name, ())
            for v, k in zip(args, pmap):
                kwargs.setdefault(k, v)
            args = ()
        out = invoke(op, args, kwargs)
        return out

    fn.__name__ = pub_name
    return fn


_POSITIONAL = {
    "uniform": ("low", "high", "shape"),
    "normal": ("loc", "scale", "shape"),
    "gamma": ("alpha", "beta", "shape"),
    "exponential": ("lam", "shape"),
    "poisson": ("lam", "shape"),
    "negative_binomial": ("k", "p", "shape"),
    "generalized_negative_binomial": ("mu", "alpha", "shape"),
    "randint": ("low", "high", "shape"),
    "multinomial": (),
}

for _pub, _opn in [
    ("uniform", "_random_uniform"), ("normal", "_random_normal"),
    ("gamma", "_random_gamma"), ("exponential", "_random_exponential"),
    ("poisson", "_random_poisson"),
    ("negative_binomial", "_random_negative_binomial"),
    ("generalized_negative_binomial", "_random_generalized_negative_binomial"),
    ("randint", "_random_randint"),
]:
    setattr(random, _pub, _rand_fn(_opn, _pub))

random.multinomial = _rand_fn("_sample_multinomial", "multinomial")
random.shuffle = _rand_fn("_shuffle", "shuffle")


def randn(*shape, ctx=None, dtype=None):
    return random.normal(0.0, 1.0, shape=shape, dtype=dtype or "float32")


random.randn = randn


def seed(s):
    from .. import _rng
    _rng.seed(s)


random.seed = seed
