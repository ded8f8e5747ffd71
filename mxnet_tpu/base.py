"""Base utilities: error type, dtype handling, registries, env-var config.

TPU-native rebuild of the reference's base layer. The reference funnels
everything through a 187-function C ABI (``include/mxnet/c_api.h``) with string
kwargs and a dmlc parameter registry; here the frontend is pure Python over JAX,
so "base" reduces to dtype plumbing, a typed env config (reference:
``docs/faq/env_var.md``, ~40 MXNET_* vars read via dmlc::GetEnv), and the
generic registry used for optimizers/metrics/initializers (reference:
``python/mxnet/registry.py``).
"""
from __future__ import annotations

import os
import numpy as np

__all__ = ["MXNetError", "string_types", "numeric_types", "integer_types"]


class MXNetError(RuntimeError):
    """Error raised by the framework (reference: python/mxnet/base.py:83)."""


string_types = (str,)
numeric_types = (float, int, np.generic)
integer_types = (int, np.integer)

# ---------------------------------------------------------------------------
# dtype handling.  The reference maps numpy dtypes to int codes across the C
# ABI (python/mxnet/base.py _DTYPE_NP_TO_MX).  We keep the same public names
# and codes for serialization parity, backed by numpy/jax dtypes.
# ---------------------------------------------------------------------------
_DTYPE_NP_TO_MX = {
    None: -1,
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int8): 5,
    np.dtype(np.int64): 6,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}

try:  # bfloat16 is first-class on TPU; the reference has no such type.
    import ml_dtypes

    bfloat16 = np.dtype(ml_dtypes.bfloat16)
    _DTYPE_NP_TO_MX[bfloat16] = 7
    _DTYPE_MX_TO_NP[7] = bfloat16
except ImportError:  # pragma: no cover
    bfloat16 = None


def mx_dtype_code(dtype) -> int:
    return _DTYPE_NP_TO_MX[np.dtype(dtype) if dtype is not None else None]


def np_dtype(code_or_dtype):
    if isinstance(code_or_dtype, int):
        return _DTYPE_MX_TO_NP[code_or_dtype]
    return np.dtype(code_or_dtype)


# ---------------------------------------------------------------------------
# Typed env config — replaces scattered dmlc::GetEnv reads.  Names keep the
# MXNET_ prefix so reference run scripts keep working.
# ---------------------------------------------------------------------------
class _Config:
    """Typed view over MXNET_* environment variables.

    Reference reads these lazily at point of use (e.g.
    src/storage/pooled_storage_manager.h:57, src/engine/engine.cc:32);
    we centralize them.  Unknown vars are ignored.
    """

    _SPECS = {
        # name -> (type, default)
        "MXNET_ENGINE_TYPE": (str, "XLA"),  # informational; XLA schedules ops
        "MXNET_EXEC_BULK_EXEC_TRAIN": (int, 1),
        "MXNET_EXEC_BULK_EXEC_INFERENCE": (int, 1),
        "MXNET_KVSTORE_BIGARRAY_BOUND": (int, 1000000),
        "MXNET_ENABLE_GPU_P2P": (int, 1),
        "MXNET_PROFILER_AUTOSTART": (int, 0),
        "MXNET_PROFILER_MODE": (int, 0),
        "MXNET_BACKWARD_DO_MIRROR": (int, 0),  # maps to jax.checkpoint policy
        "MXNET_CPU_WORKER_NTHREADS": (int, 1),
        "MXNET_DEFAULT_DTYPE": (str, "float32"),
        "MXNET_SAFE_ACCUMULATION": (int, 1),
    }

    def get(self, name, default=None):
        spec = self._SPECS.get(name)
        raw = os.environ.get(name)
        if raw is None:
            return spec[1] if spec else default
        typ = spec[0] if spec else (type(default) if default is not None else str)
        try:
            return typ(raw)
        except (TypeError, ValueError):
            return spec[1] if spec else default

    def __getattr__(self, name):
        if name.startswith("MXNET_"):
            return self.get(name)
        raise AttributeError(name)


config = _Config()


# ---------------------------------------------------------------------------
# Persistent compilation cache, placed from outside the program.
# ---------------------------------------------------------------------------
def use_compilation_cache():
    """Give this process JAX's persistent compilation cache; returns the
    directory.  Entry points (``chip_smoke.py``, ``bench.py``,
    ``tools/serve.py``) call it from ``main`` — never at import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key and a directory that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Generic object registry (reference: python/mxnet/registry.py) used by
# optimizer/metric/initializer subsystems.
# ---------------------------------------------------------------------------
class Registry:
    def __init__(self, nickname):
        self._nickname = nickname
        self._registry = {}

    def register(self, klass, name=None):
        name = (name or klass.__name__).lower()
        self._registry[name] = klass
        return klass

    def alias(self, klass, *names):
        for n in names:
            self._registry[n.lower()] = klass
        return klass

    def create(self, name, *args, **kwargs):
        if callable(name) and not isinstance(name, str):
            return name
        key = name.lower()
        if key not in self._registry:
            raise MXNetError(
                "Cannot find %s %r. Registered: %s"
                % (self._nickname, name, sorted(self._registry))
            )
        return self._registry[key](*args, **kwargs)

    def find(self, name):
        return self._registry[name.lower()]

    def __contains__(self, name):
        return name.lower() in self._registry

    def keys(self):
        return sorted(self._registry)
