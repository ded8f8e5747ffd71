"""Fused on-device pipeline tail: normalize + cast + layout in one program.

The reference normalizes on the host (``mean_r/std_r`` inside the C++
augmenter chain, ``image_aug_default.cc``) because its device copy is a
plain memcpy.  On TPU the economics invert: shipping the batch as raw
uint8 NHWC makes the host→HBM transfer 4× narrower and leaves zero float
math on the host; the mean/std subtract, dtype cast and layout transpose
then fuse into the device program (XLA fuses them into the first conv's
prologue when traced inside the training step).

Every distinct ``(mean, std, dtype, layout)`` tail is built ONCE and
cached module-wide, so two iterators with the same normalization share one
jitted callable — a stable jit identity is what makes the tail provably
recompile-free (`tail_cache_sizes()` exposes per-tail trace counts the
same way Executor/Module ``jit_cache_keys()`` does for the step program).
"""
from __future__ import annotations

import threading

import numpy as _np

__all__ = ["make_device_tail", "tail_cache_keys", "tail_cache_sizes",
           "clear_tail_cache"]

_CACHE = {}
_LOCK = threading.Lock()


def _key(mean, std, dtype, layout, input_layout):
    def tup(v):
        if v is None:
            return None
        return tuple(float(x) for x in _np.asarray(v).reshape(-1))
    return (tup(mean), tup(std), str(dtype), str(layout), str(input_layout))


def make_device_tail(mean=None, std=None, dtype="float32", layout="NHWC",
                     input_layout="NHWC"):
    """Build (or fetch) the jitted tail ``uint8[B,H,W,C] -> dtype[batch]``.

    mean, std : per-channel (or scalar) normalization constants, applied in
        float32 before the cast so bf16 targets round once, not twice.
    dtype : output dtype (``bfloat16`` for the mixed-precision trainer).
    layout : output layout; ``NCHW`` adds the transpose on device.
    input_layout : layout the host ships (``NHWC`` — the decoder's own).

    The returned callable is a ``jax.jit`` function: applied eagerly (e.g.
    by ``DeviceFeedIter``) it compiles once per input shape; traced inside
    a larger jit (``DataParallelTrainer(input_transform=...)``) it inlines
    into that program, adding no dispatch of its own.
    """
    key = _key(mean, std, dtype, layout, input_layout)
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            return fn
    import jax
    import jax.numpy as jnp
    mean_c = None if mean is None else jnp.asarray(
        _np.asarray(mean, _np.float32))
    std_c = None if std is None else jnp.asarray(
        _np.asarray(std, _np.float32))

    def tail(x):
        y = x.astype(jnp.float32)
        if mean_c is not None:
            y = y - mean_c
        if std_c is not None:
            y = y / std_c
        y = y.astype(dtype)
        if layout == "NCHW" and input_layout == "NHWC":
            y = jnp.transpose(y, (0, 3, 1, 2))
        elif layout == "NHWC" and input_layout == "NCHW":
            y = jnp.transpose(y, (0, 2, 3, 1))
        return y

    fn = jax.jit(tail)
    fn.tail_key = key
    with _LOCK:
        # a racing builder may have landed first; keep the canonical one
        fn = _CACHE.setdefault(key, fn)
    return fn


def tail_cache_keys():
    """The set of distinct tail configurations built so far."""
    with _LOCK:
        return set(_CACHE)


def tail_cache_sizes():
    """{tail key: number of XLA traces}.  Steady-state feeding must hold
    every count at 1 per input geometry — the zero-recompile proof the
    serving layer makes for the step program (PR-2 ``jit_cache_keys``)."""
    out = {}
    with _LOCK:
        items = list(_CACHE.items())
    for key, fn in items:
        out[key] = int(fn._cache_size())
    return out


def clear_tail_cache():
    with _LOCK:
        _CACHE.clear()
