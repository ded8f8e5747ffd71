"""Compile counters: what tracing, lowering and compiling cost this process.

One ``jax.monitoring`` duration listener, registered when
``mxnet_tpu.telemetry`` is imported, accumulates per process
(docs/observability.md "Compile counters"):

- ``trace_s``: seconds tracing Python to a jaxpr
  (``/jax/core/compile/jaxpr_trace_duration``).  JAX reports a function
  traced inside another one as well as the outer one; a report that ended
  inside a later report's interval is taken out of the sum, so the total
  is the union;
- ``lower_s``: seconds lowering a jaxpr to an MLIR module
  (``.../jaxpr_to_mlir_module_duration``);
- ``backend_s``: seconds in the backend's compiler **or**, on a hit of the
  persistent cache, retrieving the executable
  (``.../backend_compile_duration``; JAX reports both under it);
- ``in_span_programs``: executables compiled or loaded (reports of the
  last) while a telemetry span was open on the compiling thread, i.e.
  inside ``train.step``: a steady training loop makes none;
- ``blocked_bias_grads``: convolution-bias gradients left out of the
  programs traced so far because the bias feeds only a training-mode
  BatchNorm, which makes the gradient identically zero
  (``gluon/nn/basic_layers.py::HybridSequential``).  Counted by the
  container while a program is traced, once per bias and trace: 32 for
  one trace of a ``resnet50_v1`` training step;
- ``ssm_layers``, ``recomputed_layers``: Mamba-2 layers, and layers under
  a ``jax.checkpoint`` boundary, whose backward pass re-runs the layer's
  forward (all of it, or all but the kept projection products), in the
  programs traced so far (``transformer/hybrid.py``, once per layer and
  trace: 9 and 10 for one trace of the ten-layer ``granite-4.0-h-micro``
  step);
- ``ssm_kernel_layers``: of the ``ssm_layers``, the ones whose chunked scan
  was traced as the Pallas kernel pair of ``ops/ssd_kernels.py``
  (``ssd_kernels.tiles`` decides from the scan's shapes and the compute
  dtype: 9 in that step, 0 at a size that does not tile, where the scan is
  ``ssd_chunked``'s einsums);
- ``kept_product_layers``: of the ``recomputed_layers``, the ones traced
  with their projection products kept for the backward pass
  (``transformer/hybrid.py::keeps_products`` decides from the shapes and
  the device's memory: 10 in that step on a v5e, 0 where they do not fit);
- ``ssm_chunks_per_seq``, ``kept_product_bytes``: chunks the state-space
  scan of the last traced program cuts a sequence into, and the bytes of
  the products it keeps (2.16e9 in that step; no sums: the newest values);
- ``latent_attention_layers``, ``moe_layers``, ``mtp_modules``: layers whose
  mixer is latent attention, layers whose feed-forward is sparse experts
  (the prediction module's layer counts in both) and prediction modules in
  the programs traced so far (6, 5 and 1 for one trace of the
  ``JoyAI-LLM-Flash`` step);
- ``attention_layers``, ``flash_attention_layers``: layers whose mixer is
  grouped-query or latent attention in the programs traced so far, and of
  them the ones whose scores were traced as the flash kernels of
  ``ops/pallas_kernels.py`` (``flash_tiles`` decides from the sequence, the
  heads, the widths and the compute dtype: 6 of 6 for one trace of the
  ``JoyAI-LLM-Flash`` step, 1 of 1 for ``granite-4.0-h-micro``'s, 0 at the
  rehearsal sizes, where attention is blocks of rows in ``jax.numpy``);
- ``experts_held``, ``router_width``, ``moe_grouped_rows``,
  ``moe_expected_rows``: of the last traced program with sparse experts,
  the experts held here and the router's width (16 of 256), the static
  rows of the buffer the grouped products are handed, a layer a step, and
  the rows an even router sends here (``tokens x experts a token x held /
  width``: 24,576 and 8,192 in that step; no sums: the newest values);
- ``linear_attention_layers``, ``kda_chunks_per_seq``, ``moe_groups_kept``:
  layers whose mixer is delta-rule linear attention (``transformer/kda.py``)
  in the programs traced so far (6 for one trace of the seven-layer
  ``Ling-3.0-flash`` step), the chunks its scan cuts a sequence of the last
  traced program into (256 at 16,384 tokens), and the groups of experts a
  token may choose from in the last traced program with sparse experts (4 of
  8 in that step; 0 where the router has no group step);
- ``kda_kernel_layers``: of the ``linear_attention_layers``, the ones whose
  delta-rule scan was traced as the Pallas kernel pair of
  ``ops/kda_kernels.py`` (``kda_kernels.tiles`` decides from the chunk, the
  heads, their width and the compute dtype: 6 of 6 in that step, 0 at a
  size that does not tile, where the scan is ``kda.kda_chunked``).

Always on: the listener fires only when something is traced, lowered or
compiled, which a steady step never does.  ``telemetry.enable()`` calls
:func:`mark_armed`, so "during set-up" is :func:`at_armed` and "since
armed" is :func:`since_armed`, a subtraction.
"""
from __future__ import annotations

import threading
import time

from . import trace as _trace

__all__ = ["counters", "mark_armed", "at_armed", "since_armed", "install",
           "count", "note"]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_NAMES = ("trace_s", "lower_s", "backend_s", "in_span_programs",
         "blocked_bias_grads", "ssm_layers", "recomputed_layers",
         "ssm_chunks_per_seq", "kept_product_layers", "kept_product_bytes",
         "ssm_kernel_layers", "latent_attention_layers", "moe_layers",
         "experts_held", "router_width", "moe_grouped_rows",
         "moe_expected_rows", "mtp_modules", "attention_layers",
         "flash_attention_layers", "linear_attention_layers",
         "kda_chunks_per_seq", "moe_groups_kept", "kda_kernel_layers")

_lock = threading.Lock()
_totals = dict.fromkeys(_NAMES, 0)
# (end, seconds) of the trace reports not yet found inside a later one
_open_traces = []
_armed_at = None
_installed = False


def _on_duration(event, seconds, **_):
    if event == TRACE_EVENT:
        end = time.perf_counter()
        with _lock:
            inside = [t for t in _open_traces if t[0] >= end - seconds]
            _totals["trace_s"] += seconds - sum(t[1] for t in inside)
            del _open_traces[len(_open_traces) - len(inside):]
            _open_traces.append((end, seconds))
            if len(_open_traces) > 64:
                del _open_traces[:32]
    elif event == LOWER_EVENT:
        with _lock:
            _totals["lower_s"] += seconds
    elif event == BACKEND_EVENT:
        in_span = _trace.current() is not None
        with _lock:
            _totals["backend_s"] += seconds
            _totals["in_span_programs"] += in_span


def count(name, by=1):
    """Add ``by`` to the counter ``name``: called while a program is
    traced, by the code that shapes it."""
    with _lock:
        _totals[name] += by


def note(name, value):
    """Set ``name`` to ``value``: a reading of the newest traced program."""
    with _lock:
        _totals[name] = value


def install():
    """Register the listener, once.  False where this process has no
    jax (a postmortem host): the counters then stay at nought."""
    global _installed
    if _installed:
        return True
    try:
        from jax import monitoring
    except ImportError:
        return False
    monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True
    return True


def counters():
    """The totals since the process began."""
    with _lock:
        return dict(_totals)


def mark_armed():
    global _armed_at
    snapshot = counters()
    with _lock:
        _armed_at = snapshot


def at_armed():
    """The totals as they stood when telemetry was last armed (what
    set-up cost), or None if it never was."""
    with _lock:
        return None if _armed_at is None else dict(_armed_at)


def since_armed():
    """The sums accumulated since telemetry was last armed, or None."""
    then = at_armed()
    if then is None:
        return None
    now = counters()
    return {k: now[k] - then[k] for k in now}
