"""DataParallelTrainer: one jitted SPMD program = forward + backward +
optimizer step over a device mesh.

The reference splits this across four subsystems — per-device executors
(``module/executor_group.py:143``), KVStore push/pull
(``src/kvstore/comm.h:451``), the updater loop (``python/mxnet/model.py:157``)
and the dependency engine ordering it all.  On TPU the whole iteration is a
single XLA program: batch sharded over the ``data`` mesh axis, parameters
replicated, gradients reduced by compiler-inserted psum over ICI,
parameters donated so updates happen in place in HBM.

Tensor/sequence parallelism (a ``model`` axis sharding parameters, a
``sequence`` axis sharding tokens) is NOT this replicated tier's job:
pass ``mesh_plan=``/``model_parallel=``/``sequence_parallel=`` to route
a mesh-program block (``mxnet_tpu.transformer.TransformerLM``) through
the multi-axis tier instead — docs/transformer.md.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import autograd
from .. import engine as engine_mod
from .. import telemetry as _tele
from ..telemetry import trace as _trace
from ..ndarray import NDArray
from ..resilience import chaos as _chaos
from . import mesh as mesh_mod
from . import step as _step
from .functional import (functionalize_forward, functional_optimizer_update,
                         tree_raw)

__all__ = ["DataParallelTrainer", "DEFAULT_CHECKPOINT_EVERY"]

# auto-checkpoint cadence when ``fit(checkpoint_dir=...)`` is given without
# an explicit ``checkpoint_every`` — the bench's ``resilience`` stage gates
# checkpoint overhead (< 5% step time) at exactly this cadence
DEFAULT_CHECKPOINT_EVERY = 50

# optimizers whose update rule is purely per-scalar (no cross-element or
# per-layer reductions), so concatenated flat buckets are numerically
# identical to per-param updates.  LBSGD (layer-wise lr from norms) and
# DCASGD (uses previous-weight deltas per layer) stay per-param.
_ELEMENTWISE_OPTIMIZERS = {
    "SGD", "NAG", "Signum", "FTML", "SGLD", "Adam", "AdaGrad", "RMSProp",
    "AdaDelta", "Ftrl", "Adamax", "Nadam",
}


# what the step's ``with span(...)`` blocks enter while telemetry is
# disarmed: one shared no-op, so that a disarmed step builds no span, no
# annotation and no record (docs/observability.md "Spans")
_OFF = contextlib.nullcontext()


def _no_span(name):
    return _OFF


class DataParallelTrainer:
    """Train a Gluon block data-parallel (optionally tensor-parallel) on a mesh.

    With ``kvstore`` set to a multi-worker ``dist_sync`` store, gradients
    are additionally averaged across processes each step (one fused
    collective over a single flat key).  Aux states (BatchNorm running
    statistics) stay per-worker, exactly like the reference's dist
    training — the kvstore moves gradients/weights only and rank 0's aux
    is what a checkpoint records (python/mxnet/model.py:157).

    Parameters
    ----------
    block : gluon.Block — the model; will be run in train mode.
    loss : gluon.loss.Loss or callable(pred, label)->NDArray.
    optimizer : str or Optimizer (same registry as the eager path).
    mesh : jax.sharding.Mesh, default = all devices on one ``data`` axis.
    param_spec_fn : callable(name, shape)->PartitionSpec overriding the
        placement of individual parameters on the data mesh; default
        replicates every parameter.  (Real tensor parallelism lives in
        the mesh tier below, not here.)
    data_axis : mesh axis name the batch is sharded over.
    mesh_plan / model_parallel / sequence_parallel : the multi-axis
        tier (docs/transformer.md): a ``MeshPlan`` over
        ``data × model × sequence`` (or per-axis sizes) training a
        mesh-program block (``mxnet_tpu.transformer.TransformerLM``)
        with Megatron-style tensor-parallel layers over ``model`` and
        ring/Ulysses attention over ``sequence``, composing with
        ``zero=1`` on the ``data`` axis.
    kvstore : str or KVStore, optional — a ``dist_sync`` store for
        multi-process gradient averaging (every process must construct
        its trainers in the same order).
    input_transform : callable(jnp array)->jnp array, optional — traced
        INSIDE the step jit and applied to the data batch first, so e.g.
        the fused uint8 pipeline tail (``mx.io.make_device_tail``) becomes
        part of the one compiled step program: XLA fuses the normalize/
        cast/layout into the first layer's prologue, the host ships raw
        uint8, and the step signature stays fixed (uint8 in — zero added
        steady-state recompiles, assertable via ``jit_cache_keys`` hooks).
    """

    # distinct flat-gradient key per trainer instance (same construction
    # order on every rank, which the collectives require anyway), so two
    # trainers on one store never collide
    _KV_UID = 0

    def __init__(self, block, loss, optimizer, optimizer_params=None,
                 mesh=None, param_spec_fn=None, data_axis="data",
                 kvstore=None, input_transform=None, run_id=None,
                 zero=0, mesh_plan=None, model_parallel=None,
                 sequence_parallel=None, dtype=None, grad_accum=1):
        from .. import kvstore as kvs
        from .. import optimizer as opt_mod
        from .. import precision as _precision
        # mixed precision (docs/precision.md): dtype="bf16" trains with
        # bf16 params/activations, f32 master weights (inside the
        # ZeRO-1 shard under zero=1), f32 gradient reduction and
        # dynamic loss scaling.  dtype=None/"float32" is the historical
        # f32 path, byte-identical to before the knob existed.
        self._dtype = _precision.resolve_dtype(dtype)
        self._reduced = _precision.is_reduced(self._dtype)
        # what every step builder takes: None spells the plain f32 step
        self._compute_dtype = self._dtype if self._reduced else None
        self._block = block
        self._loss = loss
        self._input_transform = input_transform
        # multi-axis mesh tier (docs/transformer.md): a MeshPlan routes
        # a mesh-program block through the tensor/sequence-parallel
        # step instead of the replicated gluon path.  Mesh construction
        # is DEFERRED (first step / batch_sharding): the analysis path
        # (mesh_report, the tp_transformer_train_step budget model)
        # declares axis sizes and never needs devices.
        plan = mesh_mod.MeshPlan.coerce(mesh_plan)
        if plan is None and (model_parallel or sequence_parallel):
            plan = mesh_mod.MeshPlan(model=model_parallel or 1,
                                     sequence=sequence_parallel or 1)
        if plan is None and hasattr(block, "mesh_program"):
            plan = mesh_mod.MeshPlan()
        self._plan = plan
        if plan is not None:
            if not hasattr(block, "mesh_program"):
                raise ValueError(
                    "mesh_plan/model_parallel/sequence_parallel train a "
                    "mesh-program block (mxnet_tpu.transformer."
                    "TransformerLM — docs/transformer.md); %r does not "
                    "implement mesh_program()" % type(block).__name__)
            if mesh is not None:
                raise ValueError("pass either mesh= or mesh_plan=, not "
                                 "both: the plan builds its own mesh")
            if kvstore is not None:
                raise ValueError("the multi-axis mesh tier is "
                                 "single-process (in-process mesh "
                                 "collectives only); kvstore is not "
                                 "supported")
            if param_spec_fn is not None or input_transform is not None:
                raise ValueError(
                    "param_spec_fn/input_transform do not apply to the "
                    "mesh tier: the mesh program owns its own sharding "
                    "and feed (docs/transformer.md)")
        # training-run identity carried into every checkpoint's
        # provenance (ISSUE 12): the promotion audit trail names the run
        # that produced the bytes it promoted.  Deterministic by
        # construction — caller-supplied or MXTPU_RUN_ID; never a
        # timestamp (reruns must produce identical provenance).
        self.run_id = run_id if run_id is not None else \
            os.environ.get("MXTPU_RUN_ID")
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self._opt = optimizer
        # plan tier: mesh deferred to _ensure_mesh (devices may not even
        # exist on an analysis-only host)
        self._mesh = None if self._plan is not None else (
            mesh if mesh is not None else mesh_mod.data_parallel_mesh())
        self._param_spec_fn = param_spec_fn or (lambda name, shape:
                                                PartitionSpec())
        self._data_axis = data_axis
        # multi-process data parallelism (reference: dist_sync training in
        # python/mxnet/model.py:157 — grads pushed to the PS, summed across
        # workers, pulled back, updater applied locally).  Within a process
        # the mesh psum rides ICI; across processes the kvstore rides the
        # network: the two compose exactly like the reference's
        # device-comm + dist-kvstore split (src/kvstore/comm.h:451).
        if isinstance(kvstore, str):
            kvstore = kvs.create(kvstore)
        self._kv = kvstore if (kvstore is not None
                               and kvstore.num_workers > 1) else None
        if self._reduced and self._kv is not None:
            raise ValueError(
                "dtype='bf16' is not supported with a multi-process "
                "kvstore: the flat-key push/pull path reduces gradients "
                "in f32 on the PS without the loss-scale/finite "
                "bookkeeping (train bf16 in-process, or f32 with the "
                "kvstore)")
        if self._kv is not None:
            # the split-step protocol needs replace-with-sum push semantics:
            # dist_async applies pushes per-arrival on the PS (no
            # cross-worker sum) and a store-side updater would apply the
            # optimizer to the gradient keys — both would silently train
            # unsynchronized
            if self._kv.type not in ("dist_sync", "dist_device_sync",
                                     "tpu_dist"):
                raise ValueError(
                    "DataParallelTrainer needs a synchronous kvstore "
                    "(dist_sync/dist_device_sync/tpu_dist), got %r"
                    % self._kv.type)
            if getattr(self._kv, "has_updater", False):
                raise ValueError(
                    "kvstore has an updater/optimizer set; the trainer "
                    "applies its own optimizer — use a plain dist_sync "
                    "store for gradient aggregation")
            if getattr(self._kv, "compression", None) is not None:
                raise ValueError(
                    "kvstore gradient compression would quantize the "
                    "trainer's fused flat gradient (and the loss scalar "
                    "riding on it) — use an uncompressed store here")
            DataParallelTrainer._KV_UID += 1
            self._kv_prefix = "dpt%d::" % DataParallelTrainer._KV_UID
            # the kvstore already owns the cross-process reduction; the
            # mesh must therefore stay process-local or the collective
            # would be counted twice (and device_put would target
            # non-addressable devices)
            if mesh is None:
                local = jax.local_devices()
                self._mesh = mesh_mod.make_mesh(
                    (len(local),), (data_axis,), local)
            else:
                pidx = jax.process_index()
                if any(d.process_index != pidx
                       for d in self._mesh.devices.flat):
                    raise ValueError(
                        "with kvstore set the mesh must span only this "
                        "process's devices (cross-process reduction rides "
                        "the kvstore, not the mesh)")
        # ZeRO-1 (docs/elastic.md, arxiv 2004.13336): optimizer states
        # sharded over the data axis — reduce-scatter grads, shard-local
        # update, all-gather params.  The sharding rides the in-process
        # mesh; a multi-process kvstore already owns the cross-process
        # reduction and composing the two would double-count it.
        self._zero = int(zero or 0)
        if self._zero not in (0, 1):
            raise ValueError("zero must be 0 (replicated optimizer "
                             "state) or 1 (ZeRO-1 sharded), got %r"
                             % (zero,))
        if self._zero:
            if self._kv is not None:
                raise ValueError(
                    "zero=1 shards optimizer state over the mesh data "
                    "axis; combining it with a multi-process kvstore is "
                    "not supported (the kvstore path keeps the full "
                    "flat gradient per rank)")
            if type(self._opt).__name__ not in _ELEMENTWISE_OPTIMIZERS:
                raise ValueError(
                    "zero=1 updates a flat concatenated parameter shard "
                    "and therefore needs a purely elementwise optimizer "
                    "(%s); got %s"
                    % (", ".join(sorted(_ELEMENTWISE_OPTIMIZERS)),
                       type(self._opt).__name__))
        # gradient accumulation (docs/distributed.md): the step splits
        # its (per-replica) batch into ``grad_accum`` microbatches and
        # left-fold sums their gradients before the ONE optimizer
        # update — the ``parallel/functional.accumulate_grads``
        # spelling, shared with the analysis twin.  Collective count is
        # unchanged (grads reduce once, after accumulation).
        self._grad_accum = 1 if grad_accum is None else int(grad_accum)
        if self._grad_accum < 1:
            raise ValueError("grad_accum must be >= 1, got %r"
                             % (grad_accum,))
        if self._grad_accum > 1:
            if self._plan is not None:
                raise ValueError(
                    "grad_accum does not apply to the mesh tier: a "
                    "pipelined plan microbatches through the 1F1B "
                    "schedule (TransformerLMConfig(microbatches=...), "
                    "docs/pipeline.md)")
            if self._kv is not None:
                raise ValueError(
                    "grad_accum with a multi-process kvstore is not "
                    "supported: the split-step protocol pushes one "
                    "flat gradient per step")
            if self._reduced:
                raise ValueError(
                    "grad_accum with dtype='bf16' is not supported: "
                    "the loss-scale finite check is defined over one "
                    "backward pass (accumulate in f32, or use the "
                    "pipelined mesh tier for bf16 microbatching)")
        self._zero_plan = None
        self._zero_treedef = None
        self._zero_grad_fn = None
        self._zero_update_fn = None
        self._ready = False
        self._step_fn = None
        self._grad_fn = None
        self._update_fn = None
        self._step_count = 0
        # run-ahead dispatch (engine.py): every dispatched step's loss
        # handle rides this ring; waiting on it waits on the WHOLE step
        # (one program).  ``engine.bulk_size()`` bounds the ring — the
        # backpressure that keeps host run-ahead (and the HBM its queued
        # batches pin) finite.  ``engine.flush()``/``bulk()`` exit drain it.
        self._inflight = collections.deque()
        from .. import profiler as _prof
        self.dispatch_stats = _prof.PipelineStats(name="engine.dispatch")
        # armed only: tells the enqueue's own cost from the time the
        # runtime held the jitted call back (telemetry/attribution.py)
        self._enqueue_split = _tele.EnqueueSplit()
        # step.prepare enqueues small programs of its own (the learning
        # rate, the step count, the key) and can be held back the same way
        self._prepare_split = _tele.EnqueueSplit()
        engine_mod.register_flusher(self.flush)

    # -- setup -------------------------------------------------------------
    @staticmethod
    def _desc_of(v):
        raw = v._data if isinstance(v, NDArray) else np.asarray(v)
        return (tuple(int(d) for d in raw.shape), str(raw.dtype))

    def _setup(self, data, label):
        block, mesh = self._block, self._mesh
        # recorded so ``restore_checkpoint`` can re-run setup from zeros
        # of the same geometry before any real batch arrives
        self._setup_desc = {"data": self._desc_of(data),
                            "label": self._desc_of(label)}
        if any(p._deferred_init
               for p in block.collect_params().values()):
            x0 = (data if isinstance(data, NDArray)
                  else NDArray(jnp.asarray(np.asarray(data))))
            x0 = x0[:1]
            if self._input_transform is not None:
                # the block only ever sees transformed batches; infer its
                # shapes from the post-tail geometry
                x0 = NDArray(self._input_transform(x0._data))
            with autograd.pause():
                block(x0)
        params = block.collect_params()
        self._params_by_name = dict(params.items())
        self._train_names = [n for n, p in params.items()
                             if p.grad_req != "null"]
        self._aux_names = [n for n, p in params.items() if p.grad_req == "null"]

        # place every param on the mesh per its PartitionSpec
        self._param_shardings = {}
        for name, p in params.items():
            spec = self._param_spec_fn(name, p.shape)
            sh = NamedSharding(mesh, spec)
            self._param_shardings[name] = sh
            p._data._set_data(jax.device_put(p.data()._data, sh))

        if self._zero:
            self._setup_zero_states()
        else:
            # group parameters into update buckets (reference precedent:
            # multi-tensor optimizer launches, docs/faq/perf.md:214-216):
            # every elementwise optimizer applies the identical
            # per-scalar rule, so same-hyper same-dtype replicated params
            # can be updated as ONE flat concatenated vector.  The FUSED
            # Pallas update (docs/fusion.md) rides the buckets: one flat
            # f32 space, one kernel pass; on by default on TPU for
            # SGD/Adam, forced elsewhere via MXTPU_FUSED_OPTIMIZER=1.
            # Without it a group is one parameter: the concat barriers
            # the backward->optimizer overlap that XLA otherwise
            # schedules per gradient (docs/perf_resnet50_tpu.md "levers
            # measured and rejected").
            from ..ops import fused_optimizer as _fused
            bucketed = (_fused.fused_update_enabled()
                        and _fused.supports(self._opt) is not None
                        and type(self._opt).__name__
                        in _ELEMENTWISE_OPTIMIZERS)
            buckets = {}
            self._groups = []  # list of [name, ...]
            for name in self._train_names:
                p = self._params_by_name[name]
                if not bucketed or \
                        self._param_spec_fn(name, p.shape) != PartitionSpec():
                    self._groups.append([name])
                    continue
                key = (float(p.lr_mult), float(p.wd_mult),
                       str(np.dtype(p.dtype) if p.dtype else "float32"))
                buckets.setdefault(key, []).append(name)
            self._groups = [v for v in buckets.values()] + self._groups

            # optimizer states live next to their (possibly sharded)
            # params; grouped buckets get one state over the flat concat
            self._states_raw = []
            self._group_shardings = []
            for gi, names in enumerate(self._groups):
                ps = [self._params_by_name[n] for n in names]
                if len(names) == 1:
                    wflat = ps[0].data()._data
                    sh = self._param_shardings[names[0]]
                else:
                    wflat = jnp.concatenate([p.data()._data.ravel()
                                             for p in ps])
                    sh = NamedSharding(mesh, PartitionSpec())
                self._group_shardings.append(sh)
                state = self._opt.create_state_multi_precision(
                    gi, NDArray(wflat))
                raw = tree_raw(state)
                self._states_raw.append(jax.tree_util.tree_map(
                    lambda v: jax.device_put(v, sh), raw))
                if ps[0].lr_mult != 1.0:
                    self._opt.lr_mult.setdefault(gi, ps[0].lr_mult)
                if ps[0].wd_mult != 1.0:
                    self._opt.wd_mult.setdefault(gi, ps[0].wd_mult)

        def run(x, y):
            if self._input_transform is not None:
                # traced here, inside the step jit: the pipeline tail
                # (normalize/cast/layout) fuses into the step program and
                # the program's input signature stays the host's narrow
                # uint8 batch
                x = NDArray(self._input_transform(x._data))
            out = block(x)
            # program scope (docs/observability.md): the loss block and
            # its mean, under one stable name whatever the loss class
            with jax.named_scope("loss"):
                l = self._loss(out, y)
                return l.mean() if hasattr(l, "mean") else l

        self._fwd = functionalize_forward(
            run, self._params_by_name, self._train_names, self._aux_names,
            train=True)

        # dist: ONE flat key holds every gradient plus the loss scalar, so
        # each step is a single cross-worker collective instead of one per
        # parameter (no server-side updater: each sync round replaces the
        # value with the sum of that round's pushes, which is exactly
        # gradient aggregation)
        if self._kv is not None:
            sizes = []
            for name in self._train_names:
                p = self._params_by_name[name]
                n = 1
                for d in p.shape:
                    n *= int(d)
                sizes.append(n)
            self._flat_sizes = sizes
            self._flat_key = self._kv_prefix + "flat"
            total = sum(sizes) + 1  # +1: the loss scalar rides along
            self._kv.init(self._flat_key, NDArray(jnp.zeros((total,),
                                                            jnp.float32)))
            self._flat_out = NDArray(jnp.zeros((total,), jnp.float32))
            self._validate_flat_key(total)
        if self._reduced:
            self._init_loss_scale_state()
        self._ready = True

    def _init_loss_scale_state(self):
        """Device-resident loss-scale machine state (docs/precision.md):
        scale, consecutive-good-step counter, skipped-step total.  Held
        as lazy device scalars so the step never syncs; ``flush()``
        publishes them through the telemetry registry."""
        from .. import precision as _precision
        self._ls_scale, self._ls_good = _precision.init_loss_scale()
        self._ls_skipped = jnp.zeros((), jnp.int32)
        self._ls_reported_skipped = 0

    def _validate_flat_key(self, total):
        """Detect cross-rank trainer desync before any gradient mixes.

        The flat-key scheme assumes identical trainer construction order
        on every rank; two equal-length flat keys from *different*
        trainers would otherwise sum silently (the cross-process
        collective is unkeyed).  One signature round catches it: every
        rank pushes a layout fingerprint in slot 0; the pulled sum must be
        num_workers * sig (sig < 2^16 keeps k*sig inside fp32's 24
        significand bits for k <= 256 workers, so healthy sums compare
        exactly; a desync shifts the sum by ~|sigA - sigB| >> 1)."""
        if self._kv.num_workers <= 1:
            return
        import zlib
        sig = float(zlib.crc32(repr(
            (self._flat_key, tuple(self._flat_sizes))).encode())
            % (1 << 16) + 1)
        probe = jnp.zeros((total,), jnp.float32).at[0].set(sig)
        self._kv.push(self._flat_key, NDArray(probe))
        out = NDArray(jnp.zeros((total,), jnp.float32))
        self._kv.pull(self._flat_key, out=out)
        got = float(out.asnumpy()[0])
        want = sig * self._kv.num_workers
        # tolerant compare: beyond 256 workers the fp32 partial sums may
        # round by a few ulps; any real desync moves the sum by >= ~1
        if abs(got - want) > 0.5:
            raise RuntimeError(
                "DataParallelTrainer flat-key desync: rank %d pushed "
                "signature %.0f for key %r sizes %r but the cross-worker "
                "sum was %.0f (expected %.0f) — trainers were constructed "
                "in a different order on some rank, which would silently "
                "sum gradients from different models"
                % (self._kv.rank, sig, self._flat_key,
                   tuple(self._flat_sizes), got, want))

    # -- ZeRO-1 sharded optimizer runtime (parallel/zero.py) ---------------
    @property
    def zero(self):
        return self._zero

    def _zero_axis_size(self):
        sizes = dict(zip(self._mesh.axis_names, self._mesh.devices.shape))
        return int(sizes.get(self._data_axis, 1))

    def _zero_param_dtypes(self):
        """Per-param dtype strings for the flat plan.  Mixed precision
        runs the LIVE params (the all_gather reassembly targets) in the
        compute dtype; the f32 masters live outside the plan, as the
        explicit ``(shard,)`` master argument."""
        if self._reduced:
            return [str(jnp.dtype(self._dtype))] * len(self._train_names)
        return [str(np.dtype(self._params_by_name[n].dtype or "float32"))
                for n in self._train_names]

    def _setup_zero_states(self):
        """Build the flat ZeRO-1 plan and the sharded optimizer state:
        one ``(padded,)`` f32 array per state leaf, ``P(data)``-sharded
        over the mesh so each device physically holds its 1/K shard."""
        from . import zero as _zero
        mesh = self._mesh
        for name in self._train_names:
            p = self._params_by_name[name]
            if self._param_spec_fn(name, p.shape) != PartitionSpec():
                raise ValueError(
                    "zero=1 flattens the trainable parameters over the "
                    "data axis and needs them replicated; param %r has "
                    "a non-trivial PartitionSpec" % (name,))
            if p.lr_mult != 1.0 or p.wd_mult != 1.0:
                raise ValueError(
                    "zero=1 applies one flat optimizer update and "
                    "cannot honor per-parameter lr_mult/wd_mult "
                    "(param %r)" % (name,))
        k = self._zero_axis_size()
        plan = _zero.Zero1Plan(
            self._train_names,
            [self._params_by_name[n].shape for n in self._train_names],
            self._zero_param_dtypes(),
            self._data_axis, k)
        self._zero_plan = plan
        state_sh = NamedSharding(mesh, PartitionSpec(self._data_axis))
        if self._reduced:
            # f32 MASTER weights, stored ONLY as the P(data)-sharded
            # flat vector (arxiv 2004.13336's layout): seeded from the
            # still-f32 initial params, then the live bf16 params are
            # cast FROM them.  After this point no unsharded f32 copy
            # of the weights exists anywhere (docs/precision.md;
            # addressable_shards-asserted in tests/test_precision.py).
            from . import zero as _zmod
            master = _zmod._flatten_pad(
                [self._params_by_name[n].data()._data
                 for n in self._train_names], plan, jnp)
            self._zero_master = jax.device_put(master, state_sh)
            for n in self._train_names:
                p = self._params_by_name[n]
                p._data._set_data(jax.device_put(
                    p.data()._data.astype(self._dtype),
                    self._param_shardings[n]))
        else:
            self._zero_master = None
        flat_w = jnp.zeros((plan.padded,), jnp.float32)
        state = self._opt.create_state_multi_precision(0, NDArray(flat_w))
        raw = tree_raw(state)
        leaves, treedef = jax.tree_util.tree_flatten(raw)
        for li, leaf in enumerate(leaves):
            shape = tuple(getattr(leaf, "shape", ()))
            if shape != (plan.padded,):
                raise ValueError(
                    "zero=1 needs every optimizer-state leaf shaped "
                    "like the flat weight vector; leaf %d of %s has "
                    "shape %r (flat is (%d,))"
                    % (li, type(self._opt).__name__, shape, plan.padded))
        self._zero_treedef = treedef
        raw = jax.tree_util.tree_map(
            lambda v: jax.device_put(v, state_sh), raw)
        self._groups = [list(self._train_names)]
        self._group_shardings = [state_sh]
        self._states_raw = [raw]

    def _zero_leaves(self):
        return tuple(jax.tree_util.tree_leaves(self._states_raw[0]))

    def _enqueue_zero(self, args, attr, span):
        """ZeRO-1 split step: local grads + reduce-scatter (one jitted
        shard_map program), then shard-local update + all-gather (a
        second one).  The split mirrors ``_enqueue_dist``'s
        grad→exchange→update shape: the collective program's host time
        bills to the ``collective_or_ps`` attribution phase, so the
        doctor sees the reduce-scatter/all-gather shift under zero=1.
        The states live as one sharded flat tree, not per group."""
        from . import zero as _zero
        train_vals, _, aux_vals, x, y, rng, lr, count = args[:8]
        if self._zero_grad_fn is None:
            self._zero_grad_fn, self._zero_update_fn = \
                _zero.build_runtime_fns(
                    self._fwd, self._opt, self._zero_plan,
                    self._zero_treedef, self._mesh,
                    compute_dtype=self._compute_dtype,
                    grad_accum=self._grad_accum)
            if attr:
                attr.set_context("collective_or_ps", "zero1")
                if self._grad_accum > 1:
                    attr.set_context("dispatch", "grad_accum")
        # reduced: the loss-scale scalars ride the arguments, the finite
        # flag goes from the first program to the second, and the f32
        # master shard is threaded through the update
        loss_scale = args[8:]
        (g_sh, loss_val, muts, *fin), own = self._enqueue(
            span, self._zero_grad_fn,
            train_vals, aux_vals, x, y, rng, *loss_scale[:1])
        if attr:
            attr.add_phase("dispatch", own)
        master = (self._zero_master,) if self._reduced else ()
        out, own = self._enqueue(
            span, self._zero_update_fn, train_vals, *master,
            self._zero_leaves(), g_sh, lr, count, *loss_scale, *fin)
        if self._reduced:
            (new_vals, self._zero_master, new_leaves, self._ls_scale,
             self._ls_good, self._ls_skipped) = out
        else:
            new_vals, new_leaves = out
        self._states_raw = [jax.tree_util.tree_unflatten(
            self._zero_treedef, list(new_leaves))]
        if attr:
            attr.add_phase("collective_or_ps", own)
        return loss_val, new_vals, muts

    def _build_zero_replica_step(self, declared_k=None):
        """The per-replica spelling of the zero=1 step at a *declared*
        axis size (no devices needed): the analysis twin of the runtime
        shard_map programs, built from the same ``parallel/zero.py``
        parts so the executed and analyzed programs cannot drift.
        Returns ``(step_fn, plan)``."""
        from . import zero as _zero
        k = int(declared_k or self._zero_axis_size())
        plan = _zero.Zero1Plan(
            self._train_names,
            [self._params_by_name[n].shape for n in self._train_names],
            self._zero_param_dtypes(),
            self._data_axis, k)
        return _zero.build_replica_step(
            self._fwd, self._opt, plan, self._zero_treedef,
            compute_dtype=self._compute_dtype,
            grad_accum=self._grad_accum), plan

    def zero_report(self, data_shape=None, label_shape=None,
                    data_dtype="float32", label_dtype="int32",
                    declared_axis_size=None):
        """Static proof bundle of the zero=1 runtime step:
        ``(CostReport, [Finding], ShardReport)`` over the REAL runtime
        spelling traced at a declared axis size — the mxcost tape
        (peak HBM with params/states donated, batch host-fed), the
        mixed-axis DST lint (a deleted runtime all-gather is DST007)
        and the priced reduce-scatter/all-gather schedule.  Hardware-
        free; what ``analysis/budget_models.zero1_mlp_train_step``
        gates against ``STATIC_BUDGETS.json``."""
        import numpy as _onp

        from ..analysis import cost as _cost
        from ..analysis import shard_prop as _sp

        if not self._zero:
            raise ValueError("zero_report needs a zero=1 trainer")
        data_shape, label_shape = self._setup_from_shapes(
            data_shape, label_shape, data_dtype, label_dtype)
        k = int(declared_axis_size or self._zero_axis_size())
        step, plan = self._build_zero_replica_step(k)
        shard_local = max(data_shape[0] // max(k, 1), 1)
        dtypes = self._zero_param_dtypes()
        train_avals = tuple(
            jax.ShapeDtypeStruct(
                tuple(self._params_by_name[n].shape), _onp.dtype(dt))
            for n, dt in zip(self._train_names, dtypes))
        n_leaves = len(self._zero_leaves())
        state_avals = tuple(
            jax.ShapeDtypeStruct((plan.shard,), _onp.float32)
            for _ in range(n_leaves))
        aux_avals = tuple(
            jax.ShapeDtypeStruct(
                tuple(self._params_by_name[n].shape),
                _onp.dtype(self._params_by_name[n].dtype or "float32"))
            for n in self._aux_names)
        xs = jax.ShapeDtypeStruct((shard_local,) + data_shape[1:],
                                  _onp.dtype(data_dtype))
        ys = jax.ShapeDtypeStruct((shard_local,) + label_shape[1:],
                                  _onp.dtype(label_dtype))
        key = jax.ShapeDtypeStruct((2,), _onp.uint32)
        n_train = len(train_avals)
        if self._reduced:
            # reduced spelling adds the (shard,) f32 master invar after
            # the params and the three loss-scale scalars at the tail
            master_aval = jax.ShapeDtypeStruct((plan.shard,),
                                               _onp.float32)
            closed = jax.make_jaxpr(
                step, axis_env=[(self._data_axis, k)])(
                train_avals, master_aval, state_avals, aux_avals,
                xs, ys, key, jnp.float32(0.01), jnp.int32(1),
                jnp.float32(2.0 ** 15), jnp.int32(0), jnp.int32(0))
            n_sharded = n_train + 1 + n_leaves
            host = [n_sharded + len(aux_avals),
                    n_sharded + len(aux_avals) + 1]
            shard_dims = {n_train: {0: (self._data_axis,)}}
            shard_dims.update({n_train + 1 + li: {0: (self._data_axis,)}
                               for li in range(n_leaves)})
        else:
            closed = jax.make_jaxpr(
                step, axis_env=[(self._data_axis, k)])(
                train_avals, state_avals, aux_avals, xs, ys, key,
                jnp.float32(0.01), jnp.int32(1))
            n_sharded = n_train + n_leaves
            host = [n_sharded + len(aux_avals),
                    n_sharded + len(aux_avals) + 1]
            shard_dims = {n_train + li: {0: (self._data_axis,)}
                          for li in range(n_leaves)}
        donated = list(range(n_sharded))
        report = _cost.analyze_jaxpr(
            closed, axis_sizes={self._data_axis: k},
            donated_invars=donated, host_invars=host)
        report.transfer_d2h_bytes = 4    # only the loss comes back
        mesh = _sp.MeshSpec({self._data_axis: k})
        findings = _sp.lint_sharded_step(
            closed, mesh, data_axes=(self._data_axis,),
            varying_invars=host,
            shard_dims=shard_dims,
            param_outvars=list(range(1, 1 + n_train)),
            param_names=list(self._train_names),
            subject="DataParallelTrainer(zero=1)")
        findings += _cost.unpriced_findings(
            report, subject="DataParallelTrainer(zero=1)")
        shard = _sp.collective_schedule(
            closed, mesh, subject="DataParallelTrainer(zero=1)")
        shard.extras.update({
            "zero1_plan": plan.describe(),
            "runtime_peak_hbm_bytes": int(report.peak_hbm_bytes),
        })
        # traced program + axis sizes for fusion_report (private: the
        # fusion pass re-walks the same tape the cost pass priced)
        shard._fusion_ctx = (closed, {self._data_axis: k})
        return report, findings, shard

    # -- multi-axis mesh tier (mxnet_tpu.transformer) ----------------------
    @property
    def mesh_plan(self):
        return self._plan

    def _ensure_mesh(self):
        """Resolve the plan against the live device pool and build the
        collapsed mesh (deferred from __init__ so analysis-only hosts
        never need the devices)."""
        if self._mesh is not None:
            return
        self._plan = self._plan.resolve(len(jax.devices()))
        self._mesh = self._plan.build_mesh()

    def _mesh_apply_update(self, treedefs):
        """The gluon optimizer as the mesh step's shard-local update:
        the SAME ``Optimizer.update`` numerics as every other tier,
        traced through ``functional_optimizer_update`` over the local
        shard (elementwise rules are shard-invariant)."""
        opt = self._opt

        def apply_update(i, w, g, leaves, lr, t):
            state = jax.tree_util.tree_unflatten(treedefs[i],
                                                 list(leaves))
            nw, ns = functional_optimizer_update(opt, i, w, g, state,
                                                 lr, t)
            return nw, tuple(jax.tree_util.tree_leaves(ns))

        return apply_update

    def _setup_mesh(self, data, label):
        """Materialize the mesh tier: params placed per the program's
        PartitionSpecs, optimizer state per-param (or ZeRO-1 flat over
        ``model × data`` under ``zero=1``), the jitted ``shard_map``
        program built from the ONE per-replica spelling
        (``transformer/step.py``)."""
        from ..transformer import step as _tstep
        self._ensure_mesh()
        plan, mesh = self._plan, self._mesh
        program = self._block.mesh_program(plan)
        self._mesh_program = program
        self._setup_desc = {"data": self._desc_of(data),
                            "label": self._desc_of(label)}
        dshape = self._setup_desc["data"][0]
        if len(dshape) != 2 or dshape[1] != program.cfg.seq_len:
            raise ValueError(
                "mesh-tier batches are (batch, tokens) int32 with "
                "tokens == cfg.seq_len (%d); got shape %r"
                % (program.cfg.seq_len, tuple(dshape)))
        if dshape[0] % plan.size("data"):
            raise ValueError(
                "global batch %d must divide by the data axis %d "
                "(plan %r)" % (dshape[0], plan.size("data"), plan))
        if program.pipelined:
            b_local = dshape[0] // plan.size("data")
            if b_local % program.n_micro:
                raise ValueError(
                    "pipeline=%d runs %d microbatches: the per-replica "
                    "batch %d must divide by them (global batch %d, "
                    "data axis %d)"
                    % (plan.size("pipe"), program.n_micro, b_local,
                       dshape[0], plan.size("data")))
        params = program.init_params()
        self._mesh_param_names = list(program.param_names)
        self._mesh_params = {
            name: jax.device_put(
                params[name],
                NamedSharding(mesh, program.partition_spec(name)))
            for name in self._mesh_param_names}

        from jax.sharding import PartitionSpec as P
        if self._zero:
            zp = _tstep.TPZeroPlan(program, plan.size("data"))
            self._mesh_zero_plan = zp
            template = self._opt.create_state_multi_precision(
                0, NDArray(jnp.zeros((zp.shard,), jnp.float32)))
            raw = tree_raw(template)
            leaves, treedef = jax.tree_util.tree_flatten(raw)
            for li, leaf in enumerate(leaves):
                if tuple(getattr(leaf, "shape", ())) != (zp.shard,):
                    raise ValueError(
                        "zero=1 needs flat-shaped optimizer state "
                        "leaves; leaf %d of %s has shape %r"
                        % (li, type(self._opt).__name__,
                           tuple(getattr(leaf, "shape", ()))))
            self._mesh_state_treedefs = [treedef]
            flat_axes = tuple(a for a in ("pipe", "model", "data")
                              if plan.present(a))
            spec = P(flat_axes) if flat_axes else P()
            self._mesh_state_specs = [spec] * len(leaves)
            kpm = plan.size("pipe") * plan.size("model")
            self._mesh_state_leaves = tuple(
                jax.device_put(jnp.zeros((kpm * zp.padded,), jnp.float32),
                               NamedSharding(mesh, spec))
                for _ in leaves)
            self._mesh_leaf_counts = None
        else:
            self._mesh_zero_plan = None
            treedefs, leaf_counts, state_leaves, state_specs = \
                [], [], [], []
            for i, name in enumerate(self._mesh_param_names):
                w = self._mesh_params[name]
                state = self._opt.create_state_multi_precision(
                    i, NDArray(jnp.asarray(params[name])))
                raw = tree_raw(state)
                leaves, treedef = jax.tree_util.tree_flatten(raw)
                treedefs.append(treedef)
                leaf_counts.append(len(leaves))
                spec = program.partition_spec(name)
                for leaf in leaves:
                    state_leaves.append(jax.device_put(
                        jnp.asarray(leaf), NamedSharding(mesh, spec)))
                    state_specs.append(spec)
            self._mesh_state_treedefs = treedefs
            self._mesh_leaf_counts = leaf_counts
            self._mesh_state_specs = state_specs
            self._mesh_state_leaves = tuple(state_leaves)

        apply_update = self._mesh_apply_update(self._mesh_state_treedefs)
        self._mesh_step_fn = _tstep.build_runtime_fn(
            program, apply_update, self._mesh_leaf_counts, mesh,
            self._mesh_state_specs, zero=self._zero,
            zero_plan=self._mesh_zero_plan,
            compute_dtype=self._compute_dtype)
        self._ready = True

    def _enqueue_mesh(self, args, attr, span):
        """The mesh tier's enqueue: one ``shard_map`` program a step
        (``transformer/step.py::build_runtime_fn``), billed to
        ``dispatch``.  The mesh program mutates no statistics."""
        (loss_val, new_vals, new_leaves), own = self._enqueue(
            span, self._mesh_step_fn, *args)
        if attr:
            attr.add_phase("dispatch", own)
        self._mesh_state_leaves = tuple(new_leaves)
        return loss_val, new_vals, ()

    def mesh_report(self, data_shape=None, label_shape=None,
                    declared_plan=None):
        """Static proof bundle of the multi-axis step:
        ``(CostReport, [Finding], ShardReport)`` over the REAL runtime
        spelling traced at the plan's declared axis sizes — hardware
        free.  The ShardReport prices the mixed-axis collective
        schedule (``collective_bytes_per_axis`` splits ``model`` vs
        ``sequence`` wire bytes); the findings run the mixed-axis DST
        rules (a deleted row-parallel psum surfaces as a pending
        partial-sum DST001 per parameter) and, under ring attention,
        the DST009 ring proof over ``sequence``.  What the
        ``tp_transformer_train_step`` budget model gates against
        ``STATIC_BUDGETS.json``."""
        import numpy as _onp

        from ..analysis import cost as _cost
        from ..analysis import shard_prop as _sp
        from ..transformer import step as _tstep
        from . import pipeline as _pp

        if self._plan is None:
            raise ValueError("mesh_report needs a mesh_plan trainer")
        plan = mesh_mod.MeshPlan.coerce(declared_plan) or self._plan
        if plan.data is None:
            raise ValueError(
                "mesh_report needs fully-declared axis sizes: pass "
                "declared_plan=MeshPlan(data=K, ...) (the runtime plan "
                "has a deferred data axis)")
        program = self._block.mesh_program(plan)
        if data_shape is None:
            data_shape = (8 * plan.size("data"),
                          program.cfg.seq_len)
        b_local, t_local = program.local_batch_shape(int(data_shape[0]))

        # optimizer-state leaf structure from a host-side template
        if self._zero:
            zp = _tstep.TPZeroPlan(program, plan.size("data"))
            template = self._opt.create_state_multi_precision(
                0, NDArray(jnp.zeros((zp.shard,), jnp.float32)))
            leaves, treedef = jax.tree_util.tree_flatten(
                tree_raw(template))
            treedefs, leaf_counts = [treedef], None
            state_avals = tuple(
                jax.ShapeDtypeStruct((zp.shard,), _onp.float32)
                for _ in leaves)
            flat_axes = tuple(a for a in ("pipe", "model", "data")
                              if plan.present(a))
            state_dims = {0: flat_axes} if flat_axes else {}
            state_shard_dims = [state_dims] * len(leaves)
        else:
            zp = None
            treedefs, leaf_counts = [], []
            state_avals, state_shard_dims = [], []
            for i, name in enumerate(program.param_names):
                lshape = program.local_shape(name)
                template = self._opt.create_state_multi_precision(
                    i, NDArray(jnp.zeros(lshape, jnp.float32)))
                leaves, treedef = jax.tree_util.tree_flatten(
                    tree_raw(template))
                treedefs.append(treedef)
                leaf_counts.append(len(leaves))
                spec = program.partition_spec(name)
                dims = {d: (e,) for d, e in enumerate(spec)
                        if e is not None}
                for leaf in leaves:
                    state_avals.append(jax.ShapeDtypeStruct(
                        tuple(leaf.shape), _onp.float32))
                    state_shard_dims.append(dims)
            state_avals = tuple(state_avals)

        step = _tstep.build_replica_step(
            program, self._mesh_apply_update(treedefs), leaf_counts,
            zero=self._zero, zero_plan=zp,
            compute_dtype=self._compute_dtype)
        train_avals = tuple(
            jax.ShapeDtypeStruct(program.local_shape(n), _onp.float32)
            for n in program.param_names)
        xs = jax.ShapeDtypeStruct((b_local, t_local), _onp.int32)
        ys = jax.ShapeDtypeStruct((b_local, t_local), _onp.int32)
        key = jax.ShapeDtypeStruct((2,), _onp.uint32)
        closed = jax.make_jaxpr(step, axis_env=plan.axis_env())(
            train_avals, state_avals, xs, ys, key,
            jnp.float32(0.01), jnp.int32(1))

        n_train = len(train_avals)
        n_state = len(state_avals)
        host = [n_train + n_state, n_train + n_state + 1]
        report = _cost.analyze_jaxpr(
            closed, axis_sizes=plan.axis_sizes(),
            donated_invars=list(range(n_train + n_state)),
            host_invars=host)
        report.transfer_d2h_bytes = 4    # only the loss comes back

        mesh_spec = _sp.MeshSpec(plan.axis_sizes())
        shard_dims = {}
        for i, name in enumerate(program.param_names):
            spec = program.partition_spec(name)
            dims = {d: (e,) for d, e in enumerate(spec)
                    if e is not None}
            if dims:
                shard_dims[i] = dims
        for li, dims in enumerate(state_shard_dims):
            if dims:
                shard_dims[n_train + li] = dims
        findings = _sp.lint_sharded_step(
            closed, mesh_spec, data_axes=plan.batch_axes(),
            varying_invars=host, shard_dims=shard_dims,
            param_outvars=list(range(1, 1 + n_train)),
            param_names=list(program.param_names),
            subject="DataParallelTrainer(mesh_plan=%s)"
                    % (plan.describe()["axes"],))
        if plan.present("sequence") and \
                program.attention_mode == "ring":
            # under pipeline=K the block (and its attention ring) runs
            # inside the tick scan: one full ring per tick
            ring_outer = (_pp.pipeline_ticks(plan.size("pipe"),
                                             program.n_micro)
                          if program.pipelined else 1)
            findings += _sp.lint_ring_schedule(
                closed, "sequence", plan.size("sequence"),
                subject="DataParallelTrainer.mesh ring attention",
                outer_scale=ring_outer)
        if program.pipelined:
            act_itemsize = 2 if self._reduced else 4
            stash_bytes = (b_local * t_local
                           * program.cfg.d_model * act_itemsize)
            pipe_sharded = [
                i for i, name in enumerate(program.param_names)
                if "pipe" in {e for e in program.partition_spec(name)
                              if e is not None}]
            findings += _sp.lint_pipeline_step(
                closed, plan.axis_sizes(), program.n_micro,
                stash_bytes=stash_bytes,
                peak_hbm_bytes=report.peak_hbm_bytes,
                # the ZeRO-1 flat concat mixes every param into one
                # vector — the taint half only proves the per-param
                # spelling (lint_pipeline_step docstring)
                param_outvars=([] if self._zero
                               else list(range(1, 1 + n_train))),
                param_names=list(program.param_names),
                pipe_sharded=pipe_sharded,
                subject="DataParallelTrainer(mesh_plan pipeline)")
        findings += _cost.unpriced_findings(
            report, subject="DataParallelTrainer(mesh_plan)")
        shard = _sp.collective_schedule(
            closed, mesh_spec,
            subject="DataParallelTrainer(mesh_plan)")
        per_axis = shard.collective_bytes_per_axis
        shard.extras.update({
            "plan": plan.describe(),
            "program": program.describe(),
            "attention_mode": program.attention_mode,
            "tp_modeled_model_axis_bytes": int(per_axis.get("model", 0)),
            "tp_modeled_sequence_axis_bytes": int(
                per_axis.get("sequence", 0)),
            "runtime_peak_hbm_bytes": int(report.peak_hbm_bytes),
        })
        if program.pipelined:
            kp, m = plan.size("pipe"), program.n_micro
            ticks = _pp.pipeline_ticks(kp, m)
            act_itemsize = 2 if self._reduced else 4
            hop = ((b_local // m) * t_local * program.cfg.d_model
                   * act_itemsize)
            shard.extras.update({
                "pp_modeled_pipe_axis_bytes": int(
                    per_axis.get("pipe", 0)),
                "pp_modeled_bubble_frac": _pp.bubble_fraction(kp, m),
                "pp_microbatches": int(m),
                "pp_ticks": int(ticks),
                "pp_hop_bytes": int(hop),
                "pp_stash_bytes": int(b_local * t_local
                                      * program.cfg.d_model
                                      * act_itemsize),
            })
        if zp is not None:
            shard.extras["tp_zero1_plan"] = zp.describe()
        # traced program + axis sizes for fusion_report (private)
        shard._fusion_ctx = (closed, dict(plan.axis_sizes()))
        return report, findings, shard

    def mesh_params(self):
        """The trained GLOBAL parameter arrays, name -> float32 ndarray
        in ``MeshProgram.param_names`` order — exactly the layout
        ``init_params`` produces and the serving tier's ``DecodeRunner``
        consumes.  Sharded device values gather to their global shape
        here; only meaningful on the mesh tier after the first step."""
        if getattr(self, "_mesh_params", None) is None:
            raise RuntimeError(
                "mesh_params() needs the mesh tier set up (train at "
                "least one step with mesh_plan=...)")
        return {name: np.asarray(self._mesh_params[name])
                for name in self._mesh_param_names}

    # -- mesh-tier checkpointing -------------------------------------------
    def _save_mesh(self, directory, epoch=None, nbatch=None, keep=3):
        """Monolithic snapshot of the mesh tier (program param names are
        deterministic — no gensym mapping needed; states are the flat
        global leaves, fleet-size-free because the mesh is in-process)."""
        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        payload = {
            "mesh_params": {
                name: _ckpt.encode_array(self._mesh_params[name])
                for name in self._mesh_param_names},
            "mesh_states": [_ckpt.encode_array(v)
                            for v in self._mesh_state_leaves],
            "step_count": self._step_count,
            "rng": _rng.get_state(),
            "numpy_global": np.random.get_state(),
            "cursor": {"epoch": epoch, "nbatch": nbatch},
            "setup_desc": self._setup_desc,
            "plan": self._plan.describe(),
            "program": self._mesh_program.describe(),
        }
        return _ckpt.save_checkpoint(
            directory, payload, self._step_count, keep=keep,
            provenance={"epoch": epoch, "train_run_id": self.run_id,
                        "digest": _ckpt.payload_digest(payload)})

    def _restore_mesh(self, rec):
        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        payload = rec["payload"]
        if "mesh_params" not in payload:
            raise RuntimeError(
                "checkpoint is not a mesh-tier snapshot (trained by a "
                "different trainer tier?)")
        if not self._ready:
            dshape, ddt = payload["setup_desc"]["data"]
            lshape, ldt = payload["setup_desc"]["label"]
            self._setup_mesh(NDArray(jnp.zeros(dshape, np.dtype(ddt))),
                             NDArray(jnp.zeros(lshape, np.dtype(ldt))))
        if payload["program"] != self._mesh_program.describe():
            raise RuntimeError(
                "checkpoint program %r does not match this trainer's "
                "%r (different config/plan)"
                % (payload["program"], self._mesh_program.describe()))
        mesh = self._mesh
        for name in self._mesh_param_names:
            self._mesh_params[name] = jax.device_put(
                jnp.asarray(_ckpt.decode_array(
                    payload["mesh_params"][name])),
                NamedSharding(mesh,
                              self._mesh_program.partition_spec(name)))
        encs = payload["mesh_states"]
        if len(encs) != len(self._mesh_state_leaves):
            raise RuntimeError(
                "optimizer state leaf count mismatch (%d vs %d): "
                "different optimizer?"
                % (len(encs), len(self._mesh_state_leaves)))
        self._mesh_state_leaves = tuple(
            jax.device_put(jnp.asarray(_ckpt.decode_array(e)),
                           NamedSharding(mesh, spec))
            for e, spec in zip(encs, self._mesh_state_specs))
        self._step_count = int(payload["step_count"])
        self._opt.num_update = self._step_count
        _rng.set_state(payload["rng"])
        np.random.set_state(payload["numpy_global"])
        self._inflight.clear()
        return dict(payload["cursor"], step=self._step_count)

    # -- the compiled step -------------------------------------------------
    @jax.named_scope("optimizer_update")
    def _apply_groups(self, train_vals, states, grads, lr, t,
                      inv_scale=None, ok=None):
        """Optimizer update for every group — traced inside the step jit
        (single-process) or the update jit (dist split-step).  With the
        fused Pallas update enabled (docs/fusion.md) a group's update
        runs as ONE kernel pass over its flat f32 space instead of the
        unfused elementwise eqn chain; numerics mirror
        ``Optimizer.update`` exactly.  Mixed precision threads the
        loss-scale reciprocal and the finite flag through (``inv_scale``
        / ``ok`` f32 scalars): the fused kernel unscales + select-skips
        in the same pass, the unfused fallback spells the same algebra
        around ``functional_optimizer_update``.

        The fused update of a replicated group runs under a
        ``shard_map`` over ``self._mesh`` with replicated specs — every
        device updates its own full copy, which is what replication
        meant anyway — because the step is partitioned by GSPMD, and
        GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot
        be automatically partitioned" — first met on four v5e chips,
        CHANGES.md PR 21).  The runtime builders and their analysis
        twins trace this one spelling.  A group whose parameter is
        sharded keeps the unfused spelling, which GSPMD does
        partition.

        Everything traced here — the unfused XLA group, the fused Pallas
        group, the casts between master and compute dtype — carries the
        program scope ``optimizer_update`` (docs/observability.md)."""
        from ..ops import fused_optimizer as _fused
        from .ring_attention import _shard_map

        opt, groups = self._opt, self._groups
        fused_on = (_fused.fused_update_enabled()
                    and _fused.supports(opt) is not None)
        scaled = inv_scale is not None
        name_to_idx = {n: i for i, n in enumerate(self._train_names)}
        new_vals = [None] * len(train_vals)
        new_states = []

        def _fusable(gi, w):
            return (fused_on and w.dtype == jnp.float32
                    and self._group_shardings[gi].spec == PartitionSpec())

        def _fused_flat(gi, wf, gf):
            sf = jax.tree_util.tree_map(jnp.ravel, states[gi])
            scale_args = (inv_scale, ok) if scaled else ()

            def update(wf, gf, sf, lr, t, *scale_args):
                return _fused.fused_optimizer_update(
                    opt, gi, wf, gf, sf, lr, t,
                    **dict(zip(("inv_scale", "ok"), scale_args)))

            rep = PartitionSpec()
            nwf, nsf = _shard_map(
                update, self._mesh,
                (rep,) * (5 + len(scale_args)), (rep, rep))(
                    wf.ravel(), gf.ravel(), sf, lr, t, *scale_args)
            ns = jax.tree_util.tree_map(
                lambda n, o: n.reshape(o.shape), nsf, states[gi])
            return nwf, ns

        def _unfused(gi, wf, gf):
            if not scaled:
                return functional_optimizer_update(
                    opt, gi, wf, gf, states[gi], lr, t)
            nw, ns = functional_optimizer_update(
                opt, gi, wf, gf * inv_scale, states[gi], lr, t)
            okb = ok > 0.0
            nw = jnp.where(okb, nw, wf)
            ns = jax.tree_util.tree_map(
                lambda n, o: jnp.where(okb, n, o), ns, states[gi])
            return nw, ns

        for gi, names in enumerate(groups):
            idxs = [name_to_idx[n] for n in names]
            if len(idxs) == 1:
                i = idxs[0]
                if _fusable(gi, train_vals[i]):
                    nwf, ns = _fused_flat(gi, train_vals[i], grads[i])
                    nw = nwf.reshape(train_vals[i].shape)
                else:
                    nw, ns = _unfused(gi, train_vals[i], grads[i])
                new_vals[i] = nw
            else:
                # fused bucket: one flat update for the whole group
                # instead of len(group) small fusions — a single Pallas
                # pass when the fused kernels are enabled
                wf = jnp.concatenate(
                    [train_vals[i].ravel() for i in idxs])
                gf = jnp.concatenate([grads[i].ravel() for i in idxs])
                if _fusable(gi, wf):
                    nwf, ns = _fused_flat(gi, wf, gf)
                else:
                    nwf, ns = _unfused(gi, wf, gf)
                off = 0
                for i in idxs:
                    sz = train_vals[i].size
                    new_vals[i] = nwf[off:off + sz].reshape(
                        train_vals[i].shape)
                    off += sz
            new_states.append(ns)
        return tuple(new_vals), tuple(new_states)

    def _pure_step(self, axis=None):
        """The replicated tier's step as one pure function, composed of
        ``parallel/step.py``'s parts around this trainer's forward and
        ``_apply_groups``: what ``_step_fn`` jits and, with ``axis``, the
        same step seen from one shard of the data axis.  Its arguments
        are ``_step_args``'; ``_trace_args`` sets a fresh trainer up."""
        if not self._ready:
            raise RuntimeError("the step is built over the set-up "
                               "trainer: assemble _trace_args first")
        return _step.build_replica_step(
            self._fwd, self._apply_groups, axis=axis,
            compute_dtype=self._compute_dtype,
            grad_accum=self._grad_accum)

    def _build_replica_step(self):
        """Per-replica spelling of the compiled step for static analysis:
        the step ``_step_fn`` runs, seen from one shard of the data
        axis, with the cross-replica collectives written out (grads, the
        reported loss, and BatchNorm batch statistics are all global
        under GSPMD).  Traced with ``jax.make_jaxpr(axis_env=[(data_axis,
        K)])`` over ``_trace_args``: no hardware, no compilation.  By
        ``lint()``/``cost_report()`` and the ``python -m
        mxnet_tpu.analysis --cost`` budget models."""
        return self._pure_step(self._data_axis)

    # -- static analysis hooks (mxnet_tpu.analysis) ------------------------
    def lint(self, data_shape=None, label_shape=None,
             data_dtype="float32", label_dtype="int32",
             declared_axis_size=None, disable=()):
        """DST lint of the distributed step (analysis/dist_lint.py):
        every trainable gradient reduced over the data axis exactly
        once, sharding-spec consistency, collective dtype promotion,
        baked step constants.  Hardware-free; returns Finding records.
        A zero=1 trainer routes to the mixed-axis rules over the real
        runtime spelling instead (``zero_report``); a mesh_plan trainer
        to ``mesh_report``."""
        if self._plan is not None:
            _, findings, _ = self.mesh_report(data_shape=data_shape)
            from ..analysis.findings import filter_findings
            return filter_findings(findings, disable)
        if self._zero:
            _, findings, _ = self.zero_report(
                data_shape=data_shape, label_shape=label_shape,
                data_dtype=data_dtype, label_dtype=label_dtype,
                declared_axis_size=declared_axis_size)
            from ..analysis.findings import filter_findings
            return filter_findings(findings, disable)
        from ..analysis.dist_lint import lint_trainer
        return lint_trainer(self, data_shape=data_shape,
                            label_shape=label_shape,
                            data_dtype=data_dtype,
                            label_dtype=label_dtype,
                            declared_axis_size=declared_axis_size,
                            disable=disable)

    def cost_report(self, data_shape=None, label_shape=None,
                    data_dtype="float32", label_dtype="int32",
                    declared_axis_size=None):
        """Static CostReport of one training step (analysis/cost.py):
        FLOPs/bytes/peak-HBM of the full-batch program (params + states
        donated, batch host-fed, loss fetched) plus per-axis collective
        bytes from the per-replica trace.  Never executes or compiles.
        A zero=1 trainer reports over the real runtime spelling
        (``zero_report``), whose collectives are explicit."""
        from ..analysis import cost as _cost

        if self._plan is not None:
            report, _, _ = self.mesh_report(data_shape=data_shape)
            return report
        if self._zero:
            report, _, _ = self.zero_report(
                data_shape=data_shape, label_shape=label_shape,
                data_dtype=data_dtype, label_dtype=label_dtype,
                declared_axis_size=declared_axis_size)
            return report

        shapes = (data_shape, label_shape, data_dtype, label_dtype)
        args = self._trace_args(*shapes)
        report = _cost.analyze_fn(
            self._pure_step(), *args,
            donate_argnums=(0, 1), host_argnums=(3, 4))
        # loss is the only fetched output; new params/states stay put
        report.transfer_d2h_bytes = 4
        # collective bytes from the per-replica spelling (the full-batch
        # jaxpr has no explicit collectives — GSPMD inserts them)
        ksize = int(declared_axis_size or self._zero_axis_size())
        try:
            rep = _cost.analyze_fn(
                self._build_replica_step(),
                *self._trace_args(*shapes, axis_size=ksize),
                axis_env=[(self._data_axis, ksize)])
            report.collective_bytes_per_axis = \
                rep.collective_bytes_per_axis
        except Exception:
            pass
        report.axis_sizes = {self._data_axis: ksize}
        return report

    def shard_report(self, data_shape=None, label_shape=None,
                     data_dtype="float32", label_dtype="int32",
                     declared_axis_size=None):
        """mxshard global-view report of one training step
        (analysis/shard_prop.py): the full-batch step program with the
        trainer's declared input shardings (params/states per
        ``param_spec_fn``, batch over the data axis) propagated
        GSPMD-style — the returned schedule holds the collectives the
        compiler would INSERT (the gradient psum appears as an inferred
        partial-sum reduction, without the per-replica spelling) plus
        any forced activation reshards (DST010 material).  Hardware-
        free; never executes or compiles.  A mesh_plan trainer returns
        its ``mesh_report`` ShardReport instead — the per-replica
        EXPLICIT mixed-axis schedule, priced per axis."""
        from ..analysis import shard_prop as _sp

        if self._plan is not None:
            _, _, shard = self.mesh_report(data_shape=data_shape)
            return shard

        args = self._trace_args(data_shape, label_shape, data_dtype,
                                label_dtype)
        closed = jax.make_jaxpr(self._pure_step())(*args)
        axis_sizes = dict(zip(self._mesh.axis_names,
                              self._mesh.devices.shape))
        axis_sizes[self._data_axis] = int(
            declared_axis_size or axis_sizes.get(self._data_axis, 1))
        mesh = _sp.MeshSpec(axis_sizes)
        # flat in_specs follow the step's arg order: params get their
        # PartitionSpec, optimizer states their group sharding, the
        # batch shards over the data axis, everything else replicates
        in_specs = [self._param_spec_fn(
            n, self._params_by_name[n].shape) for n in self._train_names]
        for gi, raw in enumerate(self._states_raw):
            spec = self._group_shardings[gi].spec
            in_specs += [spec] * len(jax.tree_util.tree_leaves(raw))
        in_specs += [self._param_spec_fn(
            n, self._params_by_name[n].shape) for n in self._aux_names]
        # x, y, then the key and the scalars
        in_specs += [PartitionSpec(self._data_axis)] * 2 \
            + [None] * (len(args) - 5)
        return _sp.propagate(closed, mesh, in_specs,
                             subject="DataParallelTrainer")

    def fusion_report(self, data_shape=None, label_shape=None,
                      data_dtype="float32", label_dtype="int32",
                      declared_axis_size=None):
        """mxfuse FusionReport of one training step
        (``analysis/fusion.py``): the step tape segmented into fusable
        chains ranked by modeled bytes-saved-if-fused.  Hardware-free;
        a zero=1 trainer analyzes the runtime reduce-scatter/update/
        all-gather spelling, a mesh_plan trainer the mesh-tier replica
        step.  When telemetry is armed and the top chain covers more
        than ``FUSION_HINT_MIN_PCT`` of step bytes, the dispatch /
        collective phases are context-tagged ``fusable`` so ``telemetry
        doctor`` names the fusion knob (docs/fusion.md)."""
        from ..analysis import fusion as _fusion

        if self._plan is not None:
            _, _, shard = self.mesh_report(data_shape=data_shape)
            closed, axis_sizes = shard._fusion_ctx
            report = _fusion.fusion_from_jaxpr(closed,
                                               axis_sizes=axis_sizes)
        elif self._zero:
            _, _, shard = self.zero_report(
                data_shape=data_shape, label_shape=label_shape,
                data_dtype=data_dtype, label_dtype=label_dtype,
                declared_axis_size=declared_axis_size)
            closed, axis_sizes = shard._fusion_ctx
            report = _fusion.fusion_from_jaxpr(closed,
                                               axis_sizes=axis_sizes)
        else:
            args = self._trace_args(data_shape, label_shape, data_dtype,
                                    label_dtype)
            report = _fusion.fusion_from_fn(self._pure_step(), *args)

        self._last_fusion_report = report
        # doctor follow-through: a dominant dispatch/collective phase
        # plus a big fusable chain means the fusion knob is the hint
        top = report.top_chain_pct
        if _tele._ENABLED and top > _fusion.FUSION_HINT_MIN_PCT:
            attr = _tele.attribution()
            context = attr.snapshot().get("context") or {}
            for phase in ("dispatch", "collective_or_ps"):
                if phase not in context:
                    attr.set_context(phase, "fusable")
        return report

    # -- public API --------------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    @property
    def batch_sharding(self):
        """The NamedSharding step inputs are placed with (batch sharded
        over the data axis; under a MeshPlan, ``(batch, tokens)`` over
        ``data × sequence``).  A feeder that pre-places batches with this
        sharding (``mx.io.PrefetchToDeviceIter``) hits ``step``'s
        fast path: the transfer is reused, not redone."""
        if self._plan is not None:
            self._ensure_mesh()
            return NamedSharding(self._mesh, self._plan.batch_spec())
        return NamedSharding(self._mesh, PartitionSpec(self._data_axis))

    def device_arrays(self):
        """``(params, optimizer_leaves)``: the live device arrays the next
        step will consume — name -> ``jax.Array`` and a flat list — so a
        caller can see where the training state sits (devices,
        shardings) without gathering it to the host.  Only meaningful
        after the first step."""
        if self._plan is not None:
            return dict(self._mesh_params), list(self._mesh_state_leaves)
        params = {n: p.data()._data
                  for n, p in self._params_by_name.items()}
        return params, jax.tree_util.tree_leaves(self._states_raw)

    def lower_step(self, data, label):
        """``jax.stages.Lowered`` of the jitted function :meth:`step`
        dispatches, at this batch geometry and the live training state
        (replicated single-program tier, after the first step) —
        ``.as_text()`` shows what the step lowers to, e.g. a Pallas
        kernel as a Mosaic ``tpu_custom_call``.  It lowers the same
        function with arguments assembled by the same ``_step_args``;
        it is not a handle on the executable that already ran.  Nothing
        executes, no buffer is donated."""
        if self._step_fn is None:
            raise RuntimeError(
                "lower_step() needs the replicated tier's compiled step "
                "(take one step first; the kvstore, zero=1 and mesh_plan "
                "tiers run split programs)")
        batch_sh = self.batch_sharding
        train_vals, aux_vals = self._live_vals()
        return self._step_fn.lower(*self._step_args(
            train_vals, aux_vals, self._put_batch(data, batch_sh),
            self._put_batch(label, batch_sh), jax.random.PRNGKey(0),
            *self._step_scalars(self._opt.lr)))

    def _live_vals(self):
        """``(train_vals, aux_vals)``: the live parameter arrays in the
        compiled programs' argument order."""
        return (tuple(self._params_by_name[n].data()._data
                      for n in self._train_names),
                tuple(self._params_by_name[n].data()._data
                      for n in self._aux_names))

    def _step_scalars(self, lr_host):
        """``(lr, count)``: the learning rate and the step count as the
        device scalars every tier's update program takes.  Each convert
        enqueues a small program of its own, so ``step.prepare`` makes
        them, not the argument list of a ``step.enqueue`` call."""
        return jnp.float32(lr_host), jnp.int32(self._step_count)

    def _step_args(self, train_vals, aux_vals, x, y, rng, lr, count):
        """Positional arguments of the gluon tiers' step (``_pure_step``;
        the split tiers take theirs from it) — the one assembly
        :meth:`step` dispatches with, :meth:`lower_step` lowers with
        and ``_trace_args`` traces with."""
        args = (train_vals, tuple(self._states_raw), aux_vals, x, y, rng,
                lr, count)
        if self._reduced:
            args += (self._ls_scale, self._ls_good, self._ls_skipped)
        return args

    def _setup_from_shapes(self, data_shape, label_shape=None,
                           data_dtype="float32", label_dtype="int32"):
        """``(data_shape, label_shape)`` as tuples, the label's defaulting
        to one per row; a trainer that has not stepped is set up from
        zeros of that geometry first (the analysis hooks need the
        parameters and optimizer states, never a real batch)."""
        if data_shape is None:
            raise ValueError("pass data_shape (and label_shape): the "
                             "step is traced at a declared batch geometry")
        data_shape = tuple(data_shape)
        label_shape = tuple(label_shape or (data_shape[0],))
        if not self._ready:
            self._setup(
                NDArray(jnp.zeros(data_shape, np.dtype(data_dtype))),
                NDArray(jnp.zeros(label_shape, np.dtype(label_dtype))))
        return data_shape, label_shape

    def _trace_args(self, data_shape, label_shape=None,
                    data_dtype="float32", label_dtype="int32",
                    axis_size=None):
        """``_step_args`` to TRACE the replicated tier's step with
        (``_pure_step``, ``_build_replica_step``): the live parameters
        and optimizer states, and shapes in place of the batch and the
        key.  With ``axis_size`` the batch is one replica's shard at that
        declared size of the data axis, else the whole batch.  The one
        assembly behind ``cost_report``, ``shard_report``,
        ``fusion_report``, ``analysis/dist_lint.lint_trainer`` and the
        replicated twins of the ZeRO-1 proofs."""
        data_shape, label_shape = self._setup_from_shapes(
            data_shape, label_shape, data_dtype, label_dtype)
        rows = data_shape[0] if axis_size is None else \
            max(data_shape[0] // max(int(axis_size), 1), 1)
        return self._step_args(
            *self._live_vals(),
            jax.ShapeDtypeStruct((rows,) + data_shape[1:],
                                 np.dtype(data_dtype)),
            jax.ShapeDtypeStruct((rows,) + label_shape[1:],
                                 np.dtype(label_dtype)),
            jax.ShapeDtypeStruct((2,), np.uint32),
            jnp.float32(0.01), jnp.int32(1))

    def _put_batch(self, arr, sharding):
        """``device_put`` with a fast path: a committed ``jax.Array``
        already laid out per ``sharding`` (the prefetcher's work) is used
        as-is instead of being re-put — ``device_put`` is cheap for a
        matching layout but not free (it still walks shards and can copy
        on layout mismatch), and skipping it keeps the prefetch transfer
        the only one."""
        raw = arr._data if isinstance(arr, NDArray) else arr
        if isinstance(raw, jax.Array) and raw.committed and \
                raw.sharding.is_equivalent_to(sharding, raw.ndim):
            return raw
        if not isinstance(raw, jax.Array):
            raw = np.asarray(raw)
        return jax.device_put(raw, sharding)

    def _unfinished(self):
        """How many of the ring's steps have not finished.  Steps retire
        in order, so the unfinished ones are the newest."""
        count = 0
        for value in reversed(self._inflight):
            if value.is_ready():
                break
            count += 1
        return count

    def _own_cost(self, split, seconds, in_flight):
        """Of a call that enqueued programs and took ``seconds`` with
        ``in_flight`` steps unfinished before it: the host's own part.
        What the runtime held the call back for, because its own limit
        of programs in flight was reached, is billed to
        ``runahead_stall`` here (``EnqueueSplit``)."""
        own, held = split.split(seconds, in_flight)
        _tele.attribution().add_phase("runahead_stall", held)
        return own

    def _enqueue(self, span, fn, *args):
        """``(fn(*args), own_s)``: one jitted program enqueued under a
        ``step.enqueue`` span; the caller bills ``own_s``, the call's
        time less what the runtime held it back for, to its phase.
        Disarmed it reads 0 and nothing is counted."""
        if span is _no_span:
            return fn(*args), 0.0
        in_flight = self._unfinished()
        sp = span("step.enqueue")
        with sp:
            out = fn(*args)
        return out, self._own_cost(self._enqueue_split, sp.seconds,
                                   in_flight)

    def _wait_for(self, values, span_name):
        """Block until every one of ``values`` is ready; returns the
        seconds it took.  Armed, the wait is a span billed to
        ``runahead_stall``."""
        if _tele._ENABLED:
            sp = _trace.span(span_name, step=self._step_count)
            with sp:
                for value in values:
                    value.block_until_ready()
            _tele.attribution().add_phase("runahead_stall", sp.seconds)
            return sp.seconds
        t0 = time.perf_counter()
        for value in values:
            value.block_until_ready()
        return time.perf_counter() - t0

    def _track_inflight(self, loss_val):
        """Run-ahead bookkeeping: ring the dispatched step's output and
        apply backpressure — wait on the OLDEST in-flight step when the
        ring exceeds ``engine.bulk_size()`` (span ``step.backpressure``).
        Dispatch order never changes, so any window size is
        bitwise-identical; only where the host blocks moves."""
        self._inflight.append(loss_val)
        limit = engine_mod.bulk_size()
        while len(self._inflight) > limit:
            self.dispatch_stats.on_backpressure(self._wait_for(
                (self._inflight.popleft(),), "step.backpressure"))
        self.dispatch_stats.on_dispatch(len(self._inflight))

    def flush(self):
        """Drain the in-flight ring: block until every dispatched step has
        executed (span ``train.flush``).  Called by
        ``engine.flush()``/``bulk()`` exit and at ``fit`` epoch
        boundaries; after it returns, params/optimizer states are fully
        materialized (donation already retired)."""
        if self._inflight:
            waiting = list(self._inflight)
            self._inflight.clear()
            self.dispatch_stats.on_backpressure(
                self._wait_for(waiting, "train.flush"))
        if self._reduced and self._ready and self._plan is None:
            # everything dispatched has retired, so the loss-scale
            # scalars are cheap to read: publish the live scale and any
            # newly-skipped steps (docs/observability.md).  The mesh
            # tier scales no loss (transformer/step.py) and keeps none
            from .. import precision as _precision
            skipped = int(self._ls_skipped)
            _precision.record_loss_scale(
                float(self._ls_scale),
                skipped - self._ls_reported_skipped)
            self._ls_reported_skipped = skipped

    def step(self, data, label):
        """Run one training step; returns the (scalar) loss NDArray.

        Non-blocking by construction: the jitted step is dispatched into
        XLA's async queue and the loss comes back as a lazy device value —
        the host only blocks when the engine's run-ahead window
        (``mx.engine.set_bulk_size``) is full, and then on the *oldest*
        in-flight step (backpressure), not the newest.

        Telemetry (docs/observability.md "Spans", "Performance doctor"):
        one flag check when it is off.  Armed, the step is a
        ``train.step`` span with disjoint children (``step.h2d``,
        ``step.prepare``, ``step.enqueue``, ``step.commit``,
        ``step.backpressure``); the ``on_step`` mark closes the previous
        step's attribution window and stores the flight-ring progress
        cursor, and each child's duration feeds its phase."""
        if not self._ready:
            (self._setup if self._plan is None else self._setup_mesh)(
                data, label)
        return self._under_step_span(self._step, data, label)

    def _under_step_span(self, body, data, label):
        """``body(data, label, attr, span)``: disarmed with no attribution
        and ``_no_span``; armed inside a ``train.step`` span, after the
        ``on_step`` mark, with ``span(name)`` opening that step's
        children."""
        if not _tele._ENABLED:
            return body(data, label, None, _no_span)
        number = self._step_count + 1
        attr = _tele.attribution()
        attr.on_step(number)
        with _trace.span("train.step", step=number):
            return body(data, label, attr,
                        functools.partial(_trace.span, step=number))

    def _step(self, data, label, attr, span):
        """The step itself, the one host driver of every tier: h2d ->
        prepare -> the tier's enqueue -> commit -> run-ahead
        bookkeeping.  ``attr`` is the armed ``StepAttribution`` or None;
        ``span(name)`` opens a child span of this step, or is
        ``_no_span``."""
        from .. import _rng
        in_flight = self._unfinished() if attr else 0
        batch_sh = self.batch_sharding
        sp = span("step.h2d")
        with sp:
            x = self._put_batch(data, batch_sh)
            y = self._put_batch(label, batch_sh)
        if attr:
            attr.add_phase("h2d_transfer", sp.seconds)

        sp = span("step.prepare")
        with sp:
            self._step_count += 1
            # chaos probe: a scheduled fault (SIGKILL at step k, injected
            # failure, stall) fires HERE — before dispatch, so a killed
            # step never half-applies (tests/test_resilience.py
            # end-to-end crash)
            _chaos.maybe_inject("trainer.step", self._step_count, ctx=self)
            self._opt.num_update = self._step_count
            lr_host = (self._opt.lr_scheduler(self._step_count)
                       if self._opt.lr_scheduler else self._opt.lr)
            rng = _rng.next_key()
            lr, count = self._step_scalars(lr_host)
            if self._plan is not None:
                args = (tuple(self._mesh_params[n]
                              for n in self._mesh_param_names),
                        self._mesh_state_leaves, x, y, rng, lr, count)
            else:
                args = self._step_args(*self._live_vals(), x, y, rng, lr,
                                       count)
        if attr:
            # step bookkeeping (arg tuples, lr, the key) is host work; the
            # small programs it enqueues can be held back like the step
            attr.add_phase("dispatch", self._own_cost(
                self._prepare_split, sp.seconds, in_flight))

        # the tier enqueues its program(s), keeps the new optimizer
        # states and bills the calls' own cost to its phases
        loss_val, new_vals, muts = self._tier_enqueue()(args, attr, span)

        sp = span("step.commit")
        with sp:
            if self._plan is not None:
                self._mesh_params.update(zip(self._mesh_param_names,
                                             new_vals))
            else:
                for name, val in zip(self._train_names, new_vals):
                    self._params_by_name[name]._data._set_data(val)
                for name, val in zip(self._fwd.mut_names or (), muts):
                    self._params_by_name[name]._data._set_data(val)
        if attr:
            attr.add_phase("dispatch", sp.seconds)
        self._track_inflight(loss_val)
        return NDArray(loss_val)

    def _tier_enqueue(self):
        """The tier this trainer runs, as its enqueue method:
        ``enqueue(args, attr, span) -> (loss, new_vals, muts)`` over the
        arguments ``step.prepare`` assembled."""
        if self._plan is not None:
            return self._enqueue_mesh
        if self._kv is not None:
            return self._enqueue_dist
        return self._enqueue_zero if self._zero else \
            self._enqueue_replicated

    def _enqueue_replicated(self, args, attr, span):
        """The replicated tier's enqueue: ONE jitted program a step
        (``parallel/step.py::build_runtime_fn``; ``jax.jit`` itself
        retraces and caches per input shape/dtype)."""
        if self._step_fn is None:
            self._step_fn = _step.build_runtime_fn(
                self._fwd, self._apply_groups,
                compute_dtype=self._compute_dtype,
                grad_accum=self._grad_accum)
            if attr and self._grad_accum > 1:
                attr.set_context("dispatch", "grad_accum")
        out, own = self._enqueue(span, self._step_fn, *args)
        if attr:
            # the jitted call's own cost is host work; what the
            # runtime held it back for is a wait on the device
            attr.add_phase("dispatch", own)
        loss_val, new_vals, new_states, muts = out[:4]
        if self._reduced:
            self._ls_scale, self._ls_good, self._ls_skipped = out[4:]
        self._states_raw = list(new_states)
        return loss_val, new_vals, muts

    # -- checkpoint / resume (mxnet_tpu.resilience) ------------------------
    def save_checkpoint(self, directory, epoch=None, nbatch=None, keep=3):
        """Atomic snapshot of the full training state: params + optimizer
        states + RNG + iterator cursor (``epoch``/``nbatch``), written
        via ``resilience.checkpoint`` (write-rename — a crash mid-save
        leaves the previous snapshot intact).  The in-flight run-ahead
        ring is flushed FIRST, so a snapshot taken inside an
        ``engine.bulk`` window never records run-ahead state — the
        crash-mid-window case resumes from fully-materialized params."""
        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        if not self._ready:
            raise RuntimeError("trainer has not stepped yet: nothing to "
                               "checkpoint")
        self.flush()
        # attribution: the flush above bills its wait to runahead_stall;
        # only the encode + atomic write below is checkpoint time (the
        # phases stay disjoint, so per-window sums reconcile)
        t_ckpt = time.perf_counter() if _tele._ENABLED else 0.0
        if self._plan is not None:
            path = self._save_mesh(directory, epoch=epoch,
                                   nbatch=nbatch, keep=keep)
            if _tele._ENABLED:
                _tele.attribution().add_phase(
                    "checkpoint", time.perf_counter() - t_ckpt)
            return path
        if self._zero:
            path = self._save_sharded(directory, epoch=epoch,
                                      nbatch=nbatch, keep=keep)
            if _tele._ENABLED:
                _tele.attribution().add_phase(
                    "checkpoint", time.perf_counter() - t_ckpt)
            return path
        params = {name: _ckpt.encode_array(p.data()._data)
                  for name, p in self._params_by_name.items()}
        states = []
        for raw in self._states_raw:
            leaves = jax.tree_util.tree_leaves(raw)
            states.append([_ckpt.encode_array(v) for v in leaves])
        payload = {
            "params": params,
            "states": states,
            "step_count": self._step_count,
            "rng": _rng.get_state(),
            "numpy_global": np.random.get_state(),
            "cursor": {"epoch": epoch, "nbatch": nbatch},
            "setup_desc": self._setup_desc,
            "groups": [list(g) for g in self._groups],
        }
        if self._reduced:
            payload["loss_scale"] = {
                "scale": float(self._ls_scale),
                "good_steps": int(self._ls_good),
                "skipped": int(self._ls_skipped),
            }
        # provenance digest over NAME-CANONICALIZED content: gluon
        # gensyms shift per process (dense0 vs dense12 for the same
        # architecture — the positional-mapping case restore_checkpoint
        # already handles), so the digest maps param names to their
        # position before hashing.  Two reruns of the same training
        # therefore name the same bytes — what makes promotion audit
        # trails replayable.
        order = {name: "p%05d" % i for i, name in enumerate(params)}
        canon = dict(payload,
                     params={order[n]: enc for n, enc in params.items()},
                     groups=[[order[n] for n in g] for g in self._groups])
        path = _ckpt.save_checkpoint(
            directory, payload, self._step_count, keep=keep,
            provenance={"epoch": epoch, "train_run_id": self.run_id,
                        "digest": _ckpt.payload_digest(canon)})
        if _tele._ENABLED:
            _tele.attribution().add_phase(
                "checkpoint", time.perf_counter() - t_ckpt)
        return path

    def _save_sharded(self, directory, epoch=None, nbatch=None, keep=3):
        """Shard-parallel snapshot of a zero=1 trainer: the rank-
        agnostic payload (params, RNG, cursor, flat-layout plan) rides
        the manifest; every rank's 1/K optimizer-state slice is its own
        atomically-installed shard file (``resilience.checkpoint``
        sharded format, docs/elastic.md).  A fleet of a *different*
        size restores by reassembling the full flat state and
        re-sharding deterministically."""
        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        plan = self._zero_plan
        params = {name: _ckpt.encode_array(p.data()._data)
                  for name, p in self._params_by_name.items()}
        leaves = [np.asarray(v) for v in self._zero_leaves()]
        payload = {
            "params": params,
            "step_count": self._step_count,
            "rng": _rng.get_state(),
            "numpy_global": np.random.get_state(),
            "cursor": {"epoch": epoch, "nbatch": nbatch},
            "setup_desc": self._setup_desc,
            "zero_plan": plan.describe(),
            "state_leaf_count": len(leaves),
        }
        master = None
        if self._reduced:
            # the f32 masters shard exactly like the state leaves; the
            # loss-scale machine state is three host scalars.  Both must
            # survive resize-on-resume BITWISE (docs/precision.md).
            master = np.asarray(self._zero_master)
            payload["has_master"] = True
            payload["loss_scale"] = {
                "scale": float(self._ls_scale),
                "good_steps": int(self._ls_good),
                "skipped": int(self._ls_skipped),
            }
        shards = []
        for r in range(plan.k):
            sl = slice(r * plan.shard, (r + 1) * plan.shard)
            rec = {"states": [_ckpt.encode_array(leaf[sl])
                              for leaf in leaves]}
            if master is not None:
                rec["master"] = _ckpt.encode_array(master[sl])
            shards.append(rec)
        # provenance digest over NAME-CANONICALIZED content (the
        # monolithic discipline): gensym-shifted reruns name the same
        # bytes, and the digest covers the FULL state — independent of
        # the fleet size it happens to be sharded at
        order = {name: "p%05d" % i for i, name in enumerate(params)}
        canon = dict(payload,
                     params={order[n]: enc for n, enc in params.items()},
                     zero_plan=dict(plan.describe(),
                                    names=[order[n] for n in
                                           plan.names]))
        canon.pop("state_leaf_count", None)
        canon["full_state"] = [
            _ckpt.encode_array(leaf[:plan.total]) for leaf in leaves]
        if master is not None:
            canon["full_master"] = _ckpt.encode_array(
                master[:plan.total])
        for key in ("k", "padded", "shard"):
            canon["zero_plan"].pop(key, None)
        return _ckpt.save_sharded_checkpoint(
            directory, payload, shards, self._step_count, keep=keep,
            provenance={"epoch": epoch, "train_run_id": self.run_id,
                        "digest": _ckpt.payload_digest(canon)})

    def _restore_sharded(self, rec):
        """Restore a sharded-checkpoint record into this (zero=1)
        trainer, re-sharding the optimizer state for the CURRENT axis
        size: shards are concatenated in rank order, the zero padding
        tail truncated at the recorded ``total`` (provably zero — see
        ``parallel/zero.py``), re-padded for the new K and placed
        ``P(data)``-sharded.  1→2→4→1 round-trips bitwise."""
        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        payload = rec["payload"]
        if not self._ready:
            dshape, ddt = payload["setup_desc"]["data"]
            lshape, ldt = payload["setup_desc"]["label"]
            self._setup(NDArray(jnp.zeros(dshape, np.dtype(ddt))),
                        NDArray(jnp.zeros(lshape, np.dtype(ldt))))
        if not self._zero:
            raise RuntimeError(
                "sharded checkpoint (ZeRO-1 optimizer shards) cannot "
                "restore into a zero=0 trainer — construct with zero=1")
        mapping = self._map_checkpoint_params(payload["params"])
        for cn, enc in payload["params"].items():
            name = mapping[cn]
            p = self._params_by_name[name]
            p._data._set_data(jax.device_put(
                jnp.asarray(_ckpt.decode_array(enc)),
                self._param_shardings[name]))
        plan_old = payload["zero_plan"]
        plan = self._zero_plan
        if int(plan_old["total"]) != plan.total:
            raise RuntimeError(
                "sharded checkpoint's flat parameter space has %d "
                "elements, this trainer's has %d — different model"
                % (int(plan_old["total"]), plan.total))
        n_leaves = int(payload["state_leaf_count"])
        cur_leaves = self._zero_leaves()
        if n_leaves != len(cur_leaves):
            raise RuntimeError(
                "optimizer state leaf count mismatch (%d vs %d): "
                "different optimizer?" % (n_leaves, len(cur_leaves)))
        if bool(payload.get("has_master")) != bool(self._reduced):
            raise RuntimeError(
                "mixed-precision mismatch: checkpoint %s f32 masters "
                "but this trainer was constructed with dtype=%r"
                % ("has" if payload.get("has_master") else "has no",
                   str(jnp.dtype(self._dtype))))
        from . import zero as _zero
        state_sh = self._group_shardings[0]
        new_leaves = []
        for li in range(n_leaves):
            full = _zero.reassemble_state(
                [_ckpt.decode_array(sh["states"][li])
                 for sh in rec["shards"]], plan.total)
            arr = np.zeros((plan.padded,), np.float32)
            arr[:plan.total] = full
            new_leaves.append(jax.device_put(jnp.asarray(arr), state_sh))
        self._states_raw = [jax.tree_util.tree_unflatten(
            self._zero_treedef, new_leaves)]
        if self._reduced:
            # masters restore BITWISE through the same reassemble/re-pad
            # path as the state leaves; live params are then re-derived
            # by exact cast so the param == cast(master) invariant holds
            # across any save-K -> restore-K' resize
            full_m = _zero.reassemble_state(
                [_ckpt.decode_array(sh["master"])
                 for sh in rec["shards"]], plan.total)
            arr = np.zeros((plan.padded,), np.float32)
            arr[:plan.total] = full_m
            self._zero_master = jax.device_put(jnp.asarray(arr),
                                               state_sh)
            vals = _zero._unflatten(jnp.asarray(
                arr.astype(np.float32)), plan, jnp)
            for name, val in zip(self._train_names, vals):
                self._params_by_name[name]._data._set_data(
                    jax.device_put(val.astype(self._dtype),
                                   self._param_shardings[name]))
            ls = payload["loss_scale"]
            self._ls_scale = jnp.asarray(ls["scale"], jnp.float32)
            self._ls_good = jnp.asarray(ls["good_steps"], jnp.int32)
            self._ls_skipped = jnp.asarray(ls["skipped"], jnp.int32)
            self._ls_reported_skipped = int(ls["skipped"])
        self._step_count = int(payload["step_count"])
        self._opt.num_update = self._step_count
        _rng.set_state(payload["rng"])
        np.random.set_state(payload["numpy_global"])
        self._inflight.clear()
        return dict(payload["cursor"], step=self._step_count)

    def _map_checkpoint_params(self, params_ckpt):
        """checkpoint-name -> live-name mapping: exact names when they
        match, else positional (gluon gensyms shift per process) with a
        per-param shape check — a genuinely different model fails."""
        names_ckpt = list(params_ckpt)
        names_cur = list(self._params_by_name)
        if set(names_ckpt) == set(names_cur):
            return {n: n for n in names_ckpt}
        if len(names_ckpt) == len(names_cur):
            mapping = dict(zip(names_ckpt, names_cur))
            for cn, name in mapping.items():
                shape = tuple(params_ckpt[cn][2])
                cur = tuple(int(d) for d in
                            self._params_by_name[name].shape)
                if shape != cur:
                    raise RuntimeError(
                        "checkpoint param %r %r does not match model "
                        "param %r %r (different architecture)"
                        % (cn, shape, name, cur))
            return mapping
        raise RuntimeError(
            "checkpoint has %d params, model has %d — different "
            "architecture" % (len(names_ckpt), len(names_cur)))

    def restore_checkpoint(self, path_or_dir):
        """Restore a :meth:`save_checkpoint` snapshot (a file, or a
        directory whose newest loadable checkpoint is taken).  Re-runs
        setup from the recorded batch geometry when the trainer has not
        stepped yet, so a *fresh* trainer resumes standalone.  Restores
        params/optimizer states onto their shardings, the step counter
        and the RNG state — with a deterministic data iterator the
        continued run is bitwise-identical to the uncrashed one
        (tests/test_resilience.py).  Returns the cursor dict
        (``epoch``/``nbatch``/``step``)."""
        import os as _os

        from .. import _rng
        from ..resilience import checkpoint as _ckpt
        if _os.path.isdir(path_or_dir):
            if self._plan is not None:
                found = _ckpt.latest_checkpoint(path_or_dir)
                if found is None:
                    raise FileNotFoundError(
                        "no loadable checkpoint under %r"
                        % (path_or_dir,))
                return self._restore_mesh(found[1])
            if self._zero:
                found = _ckpt.latest_sharded_checkpoint(path_or_dir)
                if found is None:
                    raise FileNotFoundError(
                        "no loadable sharded checkpoint (manifest) "
                        "under %r" % (path_or_dir,))
                return self._restore_sharded(found[1])
            found = _ckpt.latest_checkpoint(path_or_dir)
            if found is None:
                raise FileNotFoundError(
                    "no loadable checkpoint under %r" % (path_or_dir,))
            _, rec = found
        elif str(path_or_dir).endswith(_ckpt.MANIFEST_SUFFIX):
            return self._restore_sharded(
                _ckpt.load_sharded_checkpoint(path_or_dir))
        else:
            rec = _ckpt.load_checkpoint(path_or_dir)
        if self._plan is not None:
            return self._restore_mesh(rec)
        payload = rec["payload"]
        if not self._ready:
            dshape, ddt = payload["setup_desc"]["data"]
            lshape, ldt = payload["setup_desc"]["label"]
            self._setup(NDArray(jnp.zeros(dshape, np.dtype(ddt))),
                        NDArray(jnp.zeros(lshape, np.dtype(ldt))))
        # name mapping: gluon gensyms block names per process (dense0,
        # dense1, ...), so the same architecture rebuilt in one process
        # gets shifted names.  Exact names map directly; otherwise map
        # positionally (collect_params order is construction order) with
        # a per-param shape check — a genuinely different model fails.
        mapping = self._map_checkpoint_params(payload["params"])
        groups_ckpt = [[mapping[n] for n in g] for g in payload["groups"]]
        if groups_ckpt != [list(g) for g in self._groups]:
            raise RuntimeError(
                "checkpoint was taken from a trainer with different "
                "parameter groups (optimizer/grouping mismatch): %r vs %r"
                % (groups_ckpt, self._groups))
        for cn, enc in payload["params"].items():
            name = mapping[cn]
            p = self._params_by_name[name]
            p._data._set_data(jax.device_put(
                jnp.asarray(_ckpt.decode_array(enc)),
                self._param_shardings[name]))
        new_states = []
        for gi, (raw, encs) in enumerate(zip(self._states_raw,
                                             payload["states"])):
            leaves, treedef = jax.tree_util.tree_flatten(raw)
            if len(leaves) != len(encs):
                raise RuntimeError(
                    "optimizer state leaf count mismatch for group %d "
                    "(%d vs %d): different optimizer?"
                    % (gi, len(leaves), len(encs)))
            sh = self._group_shardings[gi]
            vals = [jax.device_put(jnp.asarray(_ckpt.decode_array(e)), sh)
                    for e in encs]
            new_states.append(jax.tree_util.tree_unflatten(treedef, vals))
        self._states_raw = new_states
        if self._reduced and "loss_scale" in payload:
            ls = payload["loss_scale"]
            self._ls_scale = jnp.asarray(ls["scale"], jnp.float32)
            self._ls_good = jnp.asarray(ls["good_steps"], jnp.int32)
            self._ls_skipped = jnp.asarray(ls["skipped"], jnp.int32)
            self._ls_reported_skipped = int(ls["skipped"])
        self._step_count = int(payload["step_count"])
        self._opt.num_update = self._step_count
        _rng.set_state(payload["rng"])
        np.random.set_state(payload["numpy_global"])
        self._inflight.clear()
        return dict(payload["cursor"], step=self._step_count)

    def fit(self, train_data, num_epoch=1, eval_metric="loss",
            batch_end_callback=None, epoch_end_callback=None,
            prefetch_depth=2, bulk_size=None, logger=None,
            checkpoint_dir=None, checkpoint_every=None, resume=False,
            checkpoint_keep=3, metrics_path=None):
        """Overlapped training loop over a ``DataIter``: device prefetch +
        run-ahead dispatch + lazy metrics — the three stages of the step
        pipelined (reference: the engine keeps ``model.py:157``'s loop
        async; here ``PrefetchToDeviceIter`` ships batch *k+1* while step
        *k* executes and the metric accumulates device-resident).

        ``train_data`` yielding host batches is wrapped in a
        ``PrefetchToDeviceIter`` targeting ``batch_sharding`` so ``step``'s
        fast path reuses the prefetched transfer; an iterator that is
        already a ``DeviceFeedIter`` is consumed as-is.  ``bulk_size``
        scopes ``engine.bulk`` around each epoch (None keeps the global
        window).  The loss is accumulated via ``EvalMetric.update_lazy`` —
        no per-step host fetch; callbacks that read the metric
        (``Speedometer``) fetch at their own flush boundaries.

        Fault tolerance (``docs/resilience.md``): with ``checkpoint_dir``
        set, the full training state (params + optimizer state + RNG +
        epoch/batch cursor) is snapshotted atomically every
        ``checkpoint_every`` steps (default ``DEFAULT_CHECKPOINT_EVERY``)
        and at each epoch end; ``resume=True`` restores the newest
        loadable checkpoint and continues from its cursor — with a
        deterministic iterator the post-crash run converges
        bitwise-identically to the uncrashed one.  Snapshots are taken
        after an explicit flush, so a crash mid-``bulk()`` window never
        checkpoints run-ahead state.

        Observability (docs/observability.md): ``metrics_path`` writes a
        versioned telemetry-metrics JSON at the end of training (also
        written automatically under the telemetry directory when
        ``mx.telemetry.enable(dir)`` is armed); ``tools/parse_log.py``
        reads it back.  Returns the metric."""
        import logging

        from .. import metric as _metric
        from ..io import DeviceFeedIter, PrefetchToDeviceIter
        from ..module.base_module import BatchEndParam, _as_list

        log = logger or logging
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        if checkpoint_dir and checkpoint_every is None:
            checkpoint_every = DEFAULT_CHECKPOINT_EVERY
        start_epoch, skip_batches = 0, 0
        if checkpoint_dir and resume:
            from ..resilience import checkpoint as _ckpt
            if (_ckpt.latest_sharded_checkpoint(checkpoint_dir)
                    if (self._zero and self._plan is None) else
                    _ckpt.latest_checkpoint(checkpoint_dir)) is not None:
                cursor = self.restore_checkpoint(checkpoint_dir)
                if cursor.get("epoch") is not None:
                    start_epoch = int(cursor["epoch"])
                    nb = cursor.get("nbatch")
                    skip_batches = (int(nb) + 1) if nb is not None else 0
                log.info("resumed from %s at step %d (epoch %d, skipping "
                         "%d replayed batches)", checkpoint_dir,
                         self._step_count, start_epoch, skip_batches)
        it = train_data
        if not isinstance(it, DeviceFeedIter):
            it = PrefetchToDeviceIter(train_data, sharding=self.batch_sharding,
                                      depth=prefetch_depth)
        for epoch in range(start_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            if epoch > start_epoch:
                it.reset()
            with engine_mod.bulk(bulk_size or engine_mod.bulk_size()):
                batches = iter(it)
                nbatch = -1
                while True:
                    # input wait: time the loop blocks on the feed — the
                    # doctor's input_wait phase (a slow pipeline shows up
                    # HERE, not inside step()).  One bool check when
                    # telemetry is off.
                    tele_on = _tele._ENABLED
                    t_in = time.perf_counter() if tele_on else 0.0
                    try:
                        batch = next(batches)
                    except StopIteration:
                        break
                    if tele_on:
                        _tele.attribution().add_phase(
                            "input_wait", time.perf_counter() - t_in)
                    nbatch += 1
                    if epoch == start_epoch and nbatch < skip_batches:
                        # replayed batch: consumed (keeps any iterator
                        # RNG in phase) but already trained pre-crash
                        continue
                    loss = self.step(batch.data[0], batch.label[0])
                    t_m = time.perf_counter() if tele_on else 0.0
                    eval_metric.update_lazy(batch.label, [loss])
                    if batch_end_callback is not None:
                        params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                               eval_metric=eval_metric,
                                               locals=None)
                        for cb in _as_list(batch_end_callback):
                            cb(params)
                    if tele_on:
                        # metric updates + callback fetches (Speedometer
                        # drains the lazy metric at its own boundaries)
                        _tele.attribution().add_phase(
                            "metric_drain", time.perf_counter() - t_m)
                    if checkpoint_dir and checkpoint_every and \
                            self._step_count % checkpoint_every == 0:
                        self.save_checkpoint(checkpoint_dir, epoch=epoch,
                                             nbatch=nbatch,
                                             keep=checkpoint_keep)
            # bulk exit flushed the ring: everything below sees finished
            # steps, so the epoch log's fetch is the window's ONE sync
            for name, val in eval_metric.get_name_value():
                log.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            log.info("Epoch[%d] Time cost=%.3f", epoch, time.time() - tic)
            if checkpoint_dir and self._ready:
                # epoch boundary: cursor points at the NEXT epoch's start
                self.save_checkpoint(checkpoint_dir, epoch=epoch + 1,
                                     nbatch=None, keep=checkpoint_keep)
            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, None, None, None)
        self._dump_metrics(metrics_path, log)
        return eval_metric

    def _dump_metrics(self, metrics_path, log):
        """Versioned metrics JSON at the end of ``fit``: the registry
        scrape (pipeline/dispatch gauges registered by PipelineStats,
        anything else armed in-process) written to ``metrics_path``, or
        — when telemetry is armed with a directory — to
        ``<dir>/metrics-<role><rank>-<pid>.json``.  The document
        ``tools/parse_log.py`` reads (``telemetry.SCHEMA_VERSION``)."""
        import os as _os
        path = metrics_path
        if path is None and _tele.enabled() and _tele.telemetry_dir():
            rank = _tele.rank()
            path = _os.path.join(
                _tele.telemetry_dir(),
                "metrics-worker%s-%d.json"
                % ("" if rank is None else rank, _os.getpid()))
        if not path:
            return
        try:
            attr = _tele.attribution()
            # close the open attribution window first: the run's tail
            # steps (and the partial flight window) must reach both the
            # dump and the ring before the process exits
            attr.flush_window()
            _tele.dump_metrics(path, source="trainer.fit", extra={
                "step_count": self._step_count,
                "dispatch_stats": self.dispatch_stats.snapshot(),
                "attribution": attr.snapshot()})
        except OSError:
            log.exception("metrics dump to %s failed", path)

    def _enqueue_dist(self, args, attr, span):
        """Split step for multi-process data parallelism: local grads ->
        kvstore push/pull (summed across workers by the PS sync round) ->
        average -> donated optimizer update.  Averaging the per-worker
        mean-loss gradients reproduces the single-process full-batch
        gradient exactly (equal shards), so N workers with batch B/N match
        one process with batch B to float tolerance — the property
        tests/test_dist.py asserts (reference: tests/nightly/dist_lenet.py)."""
        train_vals, states, aux_vals, x, y, rng, lr, count = args
        if self._grad_fn is None:
            self._grad_fn, self._update_fn = _step.build_split_fns(
                self._fwd, self._apply_groups, self._flat_sizes,
                self._kv.num_workers)
        (flat, muts), own = self._enqueue(
            span, self._grad_fn, train_vals, aux_vals, x, y, rng)
        if attr:
            attr.add_phase("dispatch", own)
        sp = span("step.exchange")
        with sp:
            self._kv.push(self._flat_key, NDArray(flat))
            self._kv.pull(self._flat_key, out=self._flat_out)
        if attr:
            attr.add_phase("collective_or_ps", sp.seconds)
        (loss_val, new_vals, new_states), own = self._enqueue(
            span, self._update_fn, train_vals, states,
            self._flat_out._data, lr, count)
        if attr:
            attr.add_phase("dispatch", own)
        self._states_raw = list(new_states)
        return loss_val, new_vals, muts

    def set_learning_rate(self, lr):
        self._opt.set_learning_rate(lr)
