"""The gluon tiers' training step has one spelling (``parallel/step.py``):

(a) every tier builds its gradients from ``local_grads``, once;
(b) ``cost_report``/``fusion_report`` trace the step that runs, whatever
    the trainer's dtype and ``grad_accum``;
(c) the per-replica step the DST lint reads is the runtime step plus its
    reductions, equation for equation.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from mxnet_tpu.parallel import step as step_mod

BATCH, FEAT, CLASSES = 16, 12, 10
SHAPES = dict(data_shape=(BATCH, FEAT), label_shape=(BATCH,))


class _TwoWorkers:
    """What the split tier needs of a ``dist_sync`` store: two workers
    that push the same gradient."""
    type = "dist_sync"
    num_workers = 2
    rank = 0
    has_updater = False
    compression = None

    def __init__(self):
        self._store = {}

    def init(self, key, value):
        self._store[key] = value._data

    def push(self, key, value):
        self._store[key] = value._data * self.num_workers

    def pull(self, key, out=None):
        out._set_data(self._store[key])


def _trainer(devices=1, **kw):
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"))
    net.add(gluon.nn.Dense(CLASSES))
    net.initialize(mx.init.Xavier())
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh((devices,), ("data",), jax.devices()[:devices]),
        **kw)


def _batch():
    rng = np.random.RandomState(0)
    return (NDArray(jnp.asarray(rng.rand(BATCH, FEAT).astype(np.float32))),
            NDArray(jnp.asarray(rng.randint(0, CLASSES, BATCH)
                                .astype(np.int32))))


# -- (a) one spelling, used once a step --------------------------------------
TIERS = {
    "replicated": dict(),
    "grad_accum": dict(grad_accum=2),
    "bf16": dict(dtype="bf16"),
    "kvstore_split": dict(kvstore=_TwoWorkers),
    "zero1": dict(zero=1),
    "zero1_accum": dict(zero=1, grad_accum=2),
    "zero1_bf16": dict(zero=1, dtype="bf16"),
}


@pytest.mark.parametrize("tier", list(TIERS))
def test_every_tier_builds_its_gradients_from_local_grads(monkeypatch,
                                                          tier):
    built, traced = [], []
    real = step_mod.local_grads

    def counted(fwd, *args, **kw):
        built.append((args, kw))
        grads_of = real(fwd, *args, **kw)

        def traced_grads(*a):
            traced.append(len(a))
            return grads_of(*a)

        return traced_grads

    monkeypatch.setattr(step_mod, "local_grads", counted)
    kw = {k: (v() if isinstance(v, type) else v)
          for k, v in TIERS[tier].items()}
    trainer = _trainer(devices=2, **kw)
    x, y = _batch()
    first = float(trainer.step(x, y).asnumpy())
    # built once for the tier's program(s) and traced once by them
    assert len(built) == 1, built
    assert len(traced) == 1, traced
    second = float(trainer.step(x, y).asnumpy())
    trainer.flush()
    assert len(built) == 1 and np.isfinite(first) and second < first
    # the tier says whose the trained values are: the replicated tiers'
    # arguments are the masters, to cast inside the backward where the
    # dtype is reduced; ZeRO-1's are in the compute dtype already
    assert bool(built[0][1].get("cast_trained")) == \
        (not tier.startswith("zero1"))
    assert traced[0] == (6 if "bf16" in tier else 5)


# -- (b) the reports trace the step that runs --------------------------------
@pytest.mark.parametrize("variant", ["bf16", "grad_accum"])
def test_reports_trace_the_step_that_runs(variant):
    plain = _trainer()
    kw = dict(dtype="bf16") if variant == "bf16" else dict(grad_accum=4)
    trainer = _trainer(**kw)
    base = plain.cost_report(**SHAPES).per_primitive
    cost = trainer.cost_report(**SHAPES).per_primitive
    fuse = trainer.fusion_report(**SHAPES)
    chained = {p for c in fuse.chains for p in c.prims}
    if variant == "bf16":
        # the casts at the forward boundary, the finite check and the
        # select that skips a step with a non-finite gradient
        assert cost["convert_element_type"]["count"] > \
            base["convert_element_type"]["count"] + 8
        assert cost["select_n"]["count"] > base["select_n"]["count"]
        assert "is_finite" in cost and "is_finite" in chained
        assert cost["dot_general"]["bytes_read"] < \
            base["dot_general"]["bytes_read"]        # bf16 operands
    else:
        assert "scan" in cost and "scan" not in base
        # four microbatches of a quarter of the batch: the same products
        assert cost["dot_general"]["flops"] == base["dot_general"]["flops"]
        assert cost["dot_general"]["count"] == \
            4 * base["dot_general"]["count"]
    assert fuse.n_eqns > plain.fusion_report(**SHAPES).n_eqns
    # and the whole-batch tape is the runtime function's own
    x, y = _batch()
    trainer.step(x, y)
    trainer.flush()
    args = trainer._trace_args(**SHAPES)
    runtime = jax.make_jaxpr(trainer._step_fn)(*args).eqns[0].params["jaxpr"]
    assert [e.primitive.name for e in runtime.eqns] == \
        [e.primitive.name
         for e in jax.make_jaxpr(trainer._pure_step())(*args).eqns]


# -- (c) replica step = runtime step + reductions -----------------------------
_REDUCTIONS = {"psum", "pmin", "pmax", "psum_scatter", "all_gather"}


def _primitives(jaxpr, drop=()):
    """Primitive names in order, sub-jaxprs flattened in place."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in drop:
            continue
        out.append(eqn.primitive.name)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                out.extend(_primitives(inner, drop))
    return out


@pytest.mark.parametrize("variant", ["plain", "grad_accum", "bf16"])
def test_replica_step_is_the_runtime_step_plus_its_reductions(variant):
    kw = {"plain": {}, "grad_accum": dict(grad_accum=2),
          "bf16": dict(dtype="bf16")}[variant]
    trainer = _trainer(**kw)
    args = trainer._trace_args(**SHAPES, axis_size=1)
    runtime = jax.make_jaxpr(trainer._pure_step())(*args)
    replica = jax.make_jaxpr(trainer._build_replica_step(),
                             axis_env=[("data", 1)])(*args)
    reductions = [p for p in _primitives(replica.jaxpr)
                  if p in _REDUCTIONS]
    # one mean per trained parameter and one for the loss (a Dense
    # network mutates no statistics); a mean over one replica is a psum
    # and a division by 1
    assert reductions == ["psum"] * (len(trainer._train_names) + 1)
    assert len(runtime.jaxpr.outvars) == len(replica.jaxpr.outvars)
    ours = _primitives(runtime.jaxpr)
    theirs = _primitives(replica.jaxpr, drop=_REDUCTIONS)
    # what pmean adds beside the psum is its division
    extra = len(reductions)
    assert len(theirs) == len(ours) + extra
    it = iter(theirs)
    assert all(p in it for p in ours), "the runtime step's primitives, " \
        "in its order, are not a subsequence of the replica step's"
    leftover = sorted(theirs)
    for p in ours:
        leftover.remove(p)
    assert leftover == ["div"] * extra


def test_the_cast_of_floating_leaves_has_one_definition():
    from mxnet_tpu import precision
    x = jnp.ones((2,), jnp.float32)
    ids = jnp.ones((2,), jnp.int32)
    assert precision._to_compute(x, jnp.bfloat16).dtype == jnp.bfloat16
    assert precision._to_compute(ids, jnp.bfloat16).dtype == jnp.int32
    assert precision._to_compute(3, jnp.bfloat16) == 3
