"""Device contexts mapped onto jax devices.

Reference: ``python/mxnet/context.py:29`` (Context with devtype ids
cpu/gpu/cpu_pinned/cpu_shared).  Here the accelerator is the TPU: ``mx.tpu(i)``
is the native device, ``mx.gpu(i)`` is kept as a compatibility alias so
reference scripts run unchanged, and ``cpu_pinned``/``cpu_shared`` collapse to
host memory (XLA manages transfer pinning itself).
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A device context.

    Usable as ``with mx.tpu(0):`` to set the default device for array
    creation, matching reference semantics (context.py:119 ``__enter__``).
    """

    _default_ctx = threading.local()

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        Context._default_ctx.value = self._old_ctx

    # -- jax integration ---------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _backend_devices("cpu")
            if not devs:
                # no host backend registered (JAX_PLATFORMS pinned to an
                # accelerator): context is advisory in this design — every
                # array is a jax array — so fall through to the accelerator
                devs = _accelerator_devices()
        else:
            devs = _accelerator_devices()
        if not devs:
            raise RuntimeError("no %s devices available" % self.device_type)
        return devs[self.device_id % len(devs)]

    def empty_cache(self):
        """Release cached device memory (reference frees the GPU pool)."""
        # XLA owns the HBM allocator; nothing to do but keep the API.
        return None


def _backend_devices(platform):
    """Addressable devices of a platform — under multi-host jax the global
    list contains other hosts' (non-addressable) devices; placement must
    use this process's own (reference: each worker owns its GPUs)."""
    try:
        devs = jax.devices(platform)
    except RuntimeError:
        return []
    if jax.process_count() > 1:
        devs = [d for d in devs if d.process_index == jax.process_index()]
    return devs


_ACCEL_CACHE = None


def _cpu_requested():
    """Was the CPU backend asked for by name (``JAX_PLATFORMS=cpu``, as
    tests/conftest.py pins it)?"""
    return "cpu" in (jax.config.jax_platforms or "").split(",")


def _accelerator_devices():
    """Local non-CPU jax devices.  The CPU devices stand in for them only
    where the CPU was asked for (:func:`_cpu_requested` — host testing);
    otherwise a missing accelerator raises instead of ``mx.tpu(0)``
    silently training on the host."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not devs:
            if not _cpu_requested():
                raise RuntimeError(
                    "no accelerator device present (jax found only %r) and "
                    "JAX_PLATFORMS does not name cpu: refusing to resolve "
                    "an accelerator context to the host"
                    % sorted({d.platform for d in jax.local_devices()}))
            devs = _backend_devices("cpu")
        _ACCEL_CACHE = devs
    return _ACCEL_CACHE


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compatibility alias: reference scripts use mx.gpu(); maps to the TPU."""
    return Context("tpu", device_id)


def num_tpus():
    """This process's accelerator count — consistent with Context placement
    (jax_device/list_gpus resolve locally under multi-host)."""
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return len(devs)


def num_gpus():
    return num_tpus()


_IMPLICIT_DEFAULT = None


def _implicit_default():
    """Default context follows jax's default backend: cpu() in CPU builds,
    tpu(0) when an accelerator owns the default device.  Keeping the two in
    agreement avoids mixed-device programs when users never pass ctx (the
    reference defaults to cpu() because its CPU build has no choice)."""
    global _IMPLICIT_DEFAULT
    if _IMPLICIT_DEFAULT is None:
        platform = jax.default_backend()
        _IMPLICIT_DEFAULT = Context("cpu" if platform == "cpu" else "tpu", 0)
    return _IMPLICIT_DEFAULT


def current_context():
    cur = getattr(Context._default_ctx, "value", None)
    return cur if cur is not None else _implicit_default()


def gpu_memory_info(device_id=0):
    """(free, total) bytes on the accelerator (reference: context.py
    gpu_memory_info over cudaMemGetInfo; here the XLA allocator stats —
    the Storage-manager stats facade, SURVEY.md §2.1)."""
    devs = [d for d in _accelerator_devices() if d.platform != "cpu"]
    if not devs:
        raise RuntimeError("no accelerator device present")
    if device_id >= len(devs):
        raise ValueError("device_id %d out of range (%d local accelerators)"
                         % (device_id, len(devs)))
    stats = devs[device_id].memory_stats() or {}
    if "bytes_limit" not in stats:
        raise RuntimeError("memory stats unavailable for %r"
                           % devs[device_id])
    total = stats["bytes_limit"]
    return (total - stats.get("bytes_in_use", 0), total)
