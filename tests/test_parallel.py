"""Parallel training over a virtual 8-device mesh.

Mirrors the reference's distributed tests run without a cluster
(SURVEY.md §4: tests/nightly/dist_sync_kvstore.py via launch.py --launcher
local); here GSPMD over xla_force_host_platform_device_count=8.
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh


def _mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"))
    net.add(nn.Dense(10))
    return net


def test_data_parallel_training_decreases_loss():
    net = _mlp()
    net.initialize(mx.init.Xavier())
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(net, loss, "sgd",
                                  {"learning_rate": 0.5, "momentum": 0.9})
    rng = np.random.RandomState(0)
    x = rng.randn(64, 16).astype(np.float32)
    y = (rng.rand(64) * 10).astype(np.int64) % 10
    first = trainer.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
    for _ in range(20):
        last = trainer.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
    assert last < first * 0.5, (first, last)


def test_data_parallel_matches_single_device():
    """DP on 8 devices must match a 1-device mesh bit-for-bit-ish —
    the analogue of the reference's check_consistency (test_utils.py:1207)."""
    rng = np.random.RandomState(42)
    x = rng.randn(32, 8).astype(np.float32)
    y = (rng.rand(32) * 4).astype(np.int64) % 4

    losses = {}
    for tag, num in [("one", 1), ("eight", 8)]:
        mx.random.seed(7)
        net = nn.Dense(4)
        net.initialize(mx.init.Xavier())
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        mesh = make_mesh((num,), ("data",), jax.devices()[:num])
        tr = DataParallelTrainer(net, loss, "sgd", {"learning_rate": 0.1},
                                 mesh=mesh)
        vals = [tr.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
                for _ in range(5)]
        losses[tag] = vals
    np.testing.assert_allclose(losses["one"], losses["eight"],
                               rtol=1e-4, atol=1e-5)


def test_tensor_parallel_param_sharding():
    """Shard Dense weights over a model axis (dp=2 x tp=4 mesh) — the
    new-capability analogue of group2ctx model parallelism
    (graph_executor.cc:408)."""
    net = _mlp()
    net.initialize(mx.init.Xavier())
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh((2, 4), ("data", "model"))

    def spec(name, shape):
        if name.endswith("weight") and shape and shape[0] % 4 == 0:
            return PartitionSpec("model", None)
        return PartitionSpec()

    tr = DataParallelTrainer(net, loss, "sgd", {"learning_rate": 0.5},
                             mesh=mesh, param_spec_fn=spec)
    rng = np.random.RandomState(1)
    x = rng.randn(16, 16).astype(np.float32)
    y = (rng.rand(16) * 10).astype(np.int64) % 10
    first = tr.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
    for _ in range(10):
        last = tr.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
    assert last < first


def test_batchnorm_aux_updates_under_parallel_step():
    net = nn.HybridSequential()
    net.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(4))
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = DataParallelTrainer(net, loss, "sgd", {"learning_rate": 0.1})
    rng = np.random.RandomState(3)
    x = rng.randn(16, 8).astype(np.float32) + 2.0
    y = (rng.rand(16) * 4).astype(np.int64) % 4
    bn = [b for b in net._children.values()
          if isinstance(b, nn.BatchNorm)][0]
    tr.step(mx.nd.array(x), mx.nd.array(y))
    before = bn.running_mean.data().asnumpy().copy()
    tr.step(mx.nd.array(x), mx.nd.array(y))
    after = bn.running_mean.data().asnumpy()
    assert not np.allclose(before, after)


def test_maxpool_custom_vjp_optin_matches_default(monkeypatch):
    """MXTPU_MAXPOOL_VJP=1 (offset-sum backward) matches
    select_and_scatter gradients on tie-free data."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry

    op = registry.get("Pooling")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 9, 9).astype(np.float32))

    def grad_of(env):
        if env:
            monkeypatch.setenv("MXTPU_MAXPOOL_VJP", "1")
        else:
            monkeypatch.delenv("MXTPU_MAXPOOL_VJP", raising=False)
        f = lambda a: jnp.sum(op.fn(a, kernel=(3, 3), stride=(2, 2),
                                    pool_type="max") ** 2)
        return np.asarray(jax.grad(f)(x))

    np.testing.assert_allclose(grad_of(True), grad_of(False), rtol=1e-6,
                               atol=1e-6)
