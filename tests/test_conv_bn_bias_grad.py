"""A convolution bias in front of a training-mode BatchNorm has a zero
gradient, and ``HybridSequential`` does not compute it (tier-1, ISSUE 27;
docs/observability.md "Compile counters": ``blocked_bias_grads``).

Contract points:
(a) engaged (conv with bias -> BatchNorm over the conv's channel axis,
    training mode): output and running statistics are bitwise those of the
    two layers called outside a container, the bias gradient is exactly
    zero, every other gradient is the unpaired spelling's;
(b) not engaged: inference, ``use_global_stats``, a fused activation, no
    bias, BatchNorm over another axis, another consumer, no consumer: the
    container is the unpaired spelling, bias gradient and all;
(c) the gradient program of a ``BottleneckV1`` reduces over each biased
    convolution's output twice, BatchNorm's own two sums, and no more;
(d) ``blocked_bias_grads`` counts 32 / 0 / 8 for one traced training
    forward of ``resnet50_v1`` / ``resnet18_v1`` / ``vgg11_bn``;
(e) the trainer still decays the bias and gives it momentum;
(f) names, shapes and count of ``resnet50_v1``'s parameters are those of the
    tree before this change, and a file it saved loads.
"""
import json
import os
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1
from mxnet_tpu.parallel import DataParallelTrainer
from mxnet_tpu.parallel.functional import functionalize_forward
from mxnet_tpu.telemetry import compiles, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHANNELS_IN, CHANNELS = 3, 4
SHAPES = {"NCHW": (4, CHANNELS_IN, 5, 6), "NHWC": (4, 5, 6, CHANNELS_IN)}


class Unpaired(gluon.HybridBlock):
    """The same layers called one after the other outside a container:
    what ``HybridSequential`` was before it looked at its children."""

    def __init__(self, layers):
        super().__init__(prefix="")
        for i, layer in enumerate(layers):
            setattr(self, "layer%d" % i, layer)
        self._layers = layers

    def hybrid_forward(self, F, x):
        for layer in self._layers:
            x = layer(x)
        return x


def _layers(layout, case):
    """The children of one case, freshly built: the parameters' values
    depend on the case alone, so two builds hold the same numbers."""
    channel_axis = 1 if layout == "NCHW" else 3
    conv = dict(channels=CHANNELS, kernel_size=3, padding=1, layout=layout,
                in_channels=CHANNELS_IN)
    bn = dict(axis=channel_axis, in_channels=CHANNELS)
    if case == "activation":
        conv["activation"] = "relu"
    if case == "no_bias":
        conv["use_bias"] = False
    if case == "global_stats":
        bn["use_global_stats"] = True
    if case == "other_axis":
        bn.update(axis=2, in_channels=SHAPES[layout][2])
    layers = [nn.Conv2D(**conv)]
    if case == "other_consumer":
        layers += [nn.Activation("tanh"), nn.BatchNorm(**bn)]
    elif case != "last_child":
        layers += [nn.BatchNorm(**bn), nn.Activation("tanh")]
    rs = np.random.RandomState(11)
    for layer in layers:
        layer.initialize()
        for name, p in sorted(layer.collect_params().items()):
            low, high = (0.5, 1.5) if name.endswith(("gamma", "running_var")) \
                else (-0.5, 0.5)
            p.set_data(nd.array(rs.uniform(low, high, p.shape)
                                .astype("float32")))
    return layers


def _key(name):
    """``conv2d7_weight`` -> ``conv_weight``: the layer counters differ
    between two builds, the parameters do not."""
    return re.sub(r"^(conv|batchnorm)(2d)?\d+_", r"\1_", name)


def _run(net, layout, hybridized, train_mode=True):
    """One recorded forward and backward: the output, every parameter
    before and after it, and every gradient."""
    if hybridized:
        net.hybridize()
    x = nd.array(np.random.RandomState(5).randn(*SHAPES[layout])
                 .astype("float32"))
    weights = nd.array(np.random.RandomState(6).randn(*SHAPES[layout][:1])
                       .astype("float32"))
    params = {_key(n): p for n, p in net.collect_params().items()}
    before = {k: p.data().asnumpy() for k, p in params.items()}
    for p in params.values():
        if p.grad_req != "null":
            p.grad()[:] = 7.0           # stale: backward must overwrite it
    with autograd.record(train_mode=train_mode):
        out = net(x)
        loss = (out.reshape((out.shape[0], -1)) ** 2).sum(axis=1) * weights
    loss.backward()
    return {"out": out.asnumpy(), "before": before,
            "values": {k: p.data().asnumpy() for k, p in params.items()},
            "grads": {k: p.grad().asnumpy() for k, p in params.items()
                      if p.grad_req != "null"}}


def _container(layers):
    net = nn.HybridSequential(prefix="")
    net.add(*layers)
    return net


@pytest.mark.parametrize("hybridized", [False, True],
                         ids=["eager", "hybridized"])
@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_engaged_pair_keeps_the_forward_and_zeroes_the_bias_gradient(
        layout, hybridized):
    counted = compiles.counters()["blocked_bias_grads"]
    got = _run(_container(_layers(layout, "pair")), layout, hybridized)
    # a trace-time count: an eager call counts nothing
    assert compiles.counters()["blocked_bias_grads"] - counted == hybridized
    ref = _run(Unpaired(_layers(layout, "pair")), layout, hybridized)
    assert np.array_equal(got["out"], ref["out"])
    for name in ("batchnorm_running_mean", "batchnorm_running_var"):
        assert np.array_equal(got["values"][name], ref["values"][name]), name
        assert not np.array_equal(got["values"][name], got["before"][name])
    grads, ref_grads = got["grads"], ref["grads"]
    assert np.array_equal(grads["conv_bias"], np.zeros(CHANNELS, "float32"))
    # what the unpaired spelling holds there is round-off about nought
    assert np.abs(ref_grads["conv_bias"]).max() < 1e-4
    assert set(grads) == set(ref_grads) == {
        "conv_weight", "conv_bias", "batchnorm_gamma", "batchnorm_beta"}
    for name in set(grads) - {"conv_bias"}:
        assert np.abs(ref_grads[name]).max() > 1e-3, name
        np.testing.assert_allclose(grads[name], ref_grads[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)


@pytest.mark.parametrize("hybridized", [False, True],
                         ids=["eager", "hybridized"])
@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("case", [
    "inference", "global_stats", "activation", "no_bias", "other_axis",
    "other_consumer", "last_child"])
def test_not_engaged_is_the_unpaired_spelling(case, layout, hybridized):
    train_mode = case != "inference"
    build = "pair" if case == "inference" else case
    counted = compiles.counters()["blocked_bias_grads"]
    got = _run(_container(_layers(layout, build)), layout, hybridized,
               train_mode)
    assert compiles.counters()["blocked_bias_grads"] == counted
    ref = _run(Unpaired(_layers(layout, build)), layout, hybridized,
               train_mode)
    assert np.array_equal(got["out"], ref["out"])
    for kind in ("values", "grads"):
        assert set(got[kind]) == set(ref[kind])
        for name in got[kind]:
            assert np.array_equal(got[kind][name], ref[kind][name]), name
    assert ("conv_bias" in got["grads"]) == (case != "no_bias")
    if case != "no_bias":
        assert np.abs(got["grads"]["conv_bias"]).max() > 1e-3


class _OwnForwardConv(nn.Conv2D):
    def hybrid_forward(self, F, x, weight, bias=None):
        return super().hybrid_forward(F, x, weight, bias) * 2.0


class _OwnBatchNorm(nn.BatchNorm):
    pass


@pytest.mark.parametrize("conv,consumer,engages", [
    (lambda: nn.Conv1D(4, 3, layout="NCW"), lambda: nn.BatchNorm(axis=1), True),
    (lambda: nn.Conv1D(4, 3, layout="NWC"), lambda: nn.BatchNorm(axis=-1), True),
    (lambda: nn.Conv1D(4, 3, layout="NWC"), lambda: nn.BatchNorm(axis=2), True),
    (lambda: nn.Conv1D(4, 3, layout="NCW"), lambda: nn.BatchNorm(axis=2), False),
    (lambda: nn.Conv2D(4, 3, layout="NHWC"), lambda: nn.BatchNorm(axis=-1), True),
    (lambda: nn.Conv2D(4, 3, layout="NHWC"), lambda: nn.BatchNorm(axis=1), False),
    (lambda: nn.Conv3D(4, 3, layout="NCDHW"), lambda: nn.BatchNorm(), True),
    (lambda: nn.Conv3D(4, 3, layout="NDHWC"), lambda: nn.BatchNorm(axis=4), True),
    (lambda: nn.Conv3D(4, 3, layout="NDHWC"), lambda: nn.BatchNorm(axis=3), False),
    (lambda: nn.Conv2DTranspose(4, 3), lambda: nn.BatchNorm(), False),
    (lambda: nn.Conv2D(4, 3), lambda: nn.InstanceNorm(), False),
    (lambda: nn.Conv2D(4, 3), lambda: _OwnBatchNorm(), False),
    (lambda: _OwnForwardConv(4, 3), lambda: nn.BatchNorm(), False),
    (lambda: nn.Dense(4), lambda: nn.BatchNorm(), False),
], ids=["ncw", "nwc-1", "nwc2", "ncw-other-axis", "nhwc-1", "nhwc-other-axis",
        "ncdhw", "ndhwc", "ndhwc-other-axis", "transpose", "instance-norm",
        "batchnorm-subclass", "conv-own-forward", "dense"])
def test_which_pairs_engage_is_read_from_the_layers(conv, consumer, engages):
    from mxnet_tpu.gluon.nn.basic_layers import _bias_grad_is_zero
    assert _bias_grad_is_zero(conv(), consumer()) is engages


def test_a_convolution_with_its_own_forward_is_called_as_before():
    net = _container([_OwnForwardConv(4, 3, in_channels=3),
                      nn.BatchNorm(in_channels=4)])
    net.initialize()
    with autograd.record():
        out = net(nd.ones((2, 3, 5, 5)))
    out.backward()
    assert out.shape == (2, 4, 3, 3)


# -- (c), (d): what is traced ------------------------------------------------
def _pure_forward(net, x, train=True):
    """``net``'s forward as the trainer spells it
    (``functionalize_forward``), with its arguments.  Shapes are resolved
    by an eager pass in inference mode, which blocks nothing."""
    with autograd.pause():
        net(x)
    params = dict(net.collect_params().items())
    trained = [n for n, p in params.items() if p.grad_req != "null"]
    aux = [n for n, p in params.items() if p.grad_req == "null"]
    pure = functionalize_forward(net, params, trained, aux, train=train)
    return pure, ([params[n].data()._data for n in trained],
                  [params[n].data()._data for n in aux], [x._data],
                  jax.random.PRNGKey(0))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _channel_sums(fn, *args):
    """How many ``reduce_sum`` over all but the channel axis of a 4-d
    operand the jaxpr of ``fn`` holds, by the operand's shape."""
    counts = {}
    for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr):
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        if eqn.primitive.name == "reduce_sum" and len(shape) == 4 \
                and tuple(eqn.params["axes"]) == (0, 1, 2):
            counts[shape] = counts.get(shape, 0) + 1
    return counts


def test_bottleneck_gradient_reduces_only_for_batchnorm():
    """NHWC bottleneck, channels 16 -> 4 -> 4 -> 16 at 6x6: the two biased
    1x1s put out (2, 6, 6, 4) and (2, 6, 6, 16).  Backward, BatchNorm sums
    twice over its input (``_bn_train_bwd``); a bias gradient would be one
    sum more over the same shape."""
    block = BottleneckV1(16, 1, in_channels=16, layout="NHWC", prefix="")
    block.initialize()
    x = nd.array(np.random.RandomState(0).randn(2, 6, 6, 16)
                 .astype("float32"))
    pure, args = _pure_forward(block, x)

    def loss(train_vals):
        (out,), _ = pure(train_vals, *args[1:])
        return (out * out).sum()

    forward = _channel_sums(loss, args[0])
    both = _channel_sums(jax.grad(loss), args[0])
    backward = {shape: both[shape] - forward.get(shape, 0) for shape in both}
    # three BatchNorms: after the first 1x1 and the 3x3 (both (2, 6, 6, 4))
    # and after the last 1x1
    assert backward == {(2, 6, 6, 4): 4, (2, 6, 6, 16): 2}


@pytest.mark.parametrize("name,blocked", [
    ("resnet50_v1", 32), ("resnet18_v1", 0), ("vgg11_bn", 8)])
def test_blocked_bias_grads_counts_per_traced_program(name, blocked):
    kwargs = {"thumbnail": True} if name.startswith("resnet") else {}
    net = gluon.model_zoo.vision.get_model(name, classes=10, **kwargs)
    net.initialize()
    x = nd.zeros((2, 3, 32, 32))
    pure, args = _pure_forward(net, x)
    before = compiles.counters()["blocked_bias_grads"]
    jax.eval_shape(pure, *args)
    assert compiles.counters()["blocked_bias_grads"] - before == blocked
    # an inference trace counts nothing
    jax.eval_shape(_pure_forward(net, x, train=False)[0], *args)
    assert compiles.counters()["blocked_bias_grads"] - before == blocked


# -- (e) the trainer ----------------------------------------------------------
LR, WD, MOMENTUM = 0.05, 1e-4, 0.9
BIAS = np.array([0.5, -0.25, 1.0, 2.0], "float32")


def _small_trainer():
    net = nn.HybridSequential(prefix="")
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
            nn.BatchNorm(in_channels=4), nn.Activation("relu"),
            nn.GlobalAvgPool2D(), nn.Dense(3, in_units=4))
    net.initialize()
    net[0].bias.set_data(nd.array(BIAS))
    net.hybridize()
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": LR, "momentum": MOMENTUM, "wd": WD})
    x = np.random.RandomState(0).rand(8, 3, 6, 6).astype("float32")
    y = (np.arange(8) % 3).astype("float32")
    return net, trainer, x, y


def test_trainer_still_decays_the_blocked_bias_and_gives_it_momentum():
    lr, wd, momentum, bias = LR, WD, MOMENTUM, BIAS
    net, trainer, x, y = _small_trainer()
    weight = net[0].weight.data().asnumpy()
    counted = compiles.counters()["blocked_bias_grads"]
    trainer.step(x, y)
    trainer.flush()
    assert compiles.counters()["blocked_bias_grads"] - counted == 1
    # the gradient is exactly zero: mom = -lr * wd * b, b += mom
    mom = -np.float32(lr) * (np.float32(wd) * bias)
    after_one = net[0].bias.data().asnumpy()
    np.testing.assert_allclose(after_one, bias + mom, rtol=3e-7, atol=0)
    assert not np.array_equal(after_one, bias)
    assert not np.array_equal(net[0].weight.data().asnumpy(), weight)
    trainer.step(x, y)
    trainer.flush()
    mom = np.float32(momentum) * mom - np.float32(lr) * (np.float32(wd)
                                                         * after_one)
    np.testing.assert_allclose(net[0].bias.data().asnumpy(), after_one + mom,
                               rtol=3e-7, atol=0)


def test_the_doctor_says_how_many_bias_gradients_were_left_out(tmp_path):
    net, trainer, x, y = _small_trainer()
    telemetry.enable(directory=str(tmp_path), rank=0)
    try:
        trainer.step(x, y)
        trainer.flush()
        attr = telemetry.attribution()
        attr.flush_window()
        telemetry.dump_metrics(str(tmp_path / "metrics-worker0-1.json"),
                               extra={"attribution": attr.snapshot()})
    finally:
        telemetry.disable()
        telemetry.reset_attribution()
        trace.reset_spans()
    report = telemetry.doctor_report(str(tmp_path))
    blocked = report["ranks"]["worker0"]["compiles"]["blocked_bias_grads"]
    assert blocked >= 1
    assert ("%d convolution-bias gradient(s) left out of the traced programs"
            % blocked) in telemetry.render_doctor(report)


# -- (f) the parameters are the configuration ----------------------------------
@pytest.fixture(scope="module")
def saved_at_pr26():
    with open(os.path.join(DATA, "resnet_v1_params_at_pr26.json")) as f:
        return json.load(f)


def test_resnet50_v1_parameters_are_those_of_the_parent(saved_at_pr26):
    # the prefix a process's first ResNet v1 gets, as the file has it
    net = gluon.model_zoo.vision.get_model("resnet50_v1",
                                           prefix="resnetv10_")
    net.initialize()
    with autograd.pause():
        net(nd.zeros((1, 3, 32, 32)))
    params = net.collect_params()
    assert [[n, list(p.shape)] for n, p in params.items()] \
        == saved_at_pr26["resnet50_v1"]
    trained = sum(int(np.prod(p.shape)) for p in params.values()
                  if p.grad_req != "null")
    # the 25,557,032 of the paper's network and the 18,880 elements of the
    # zoo's 32 convolution biases
    assert trained == 25_575_912
    biases = [n for n in params if "conv" in n and n.endswith("_bias")]
    assert len(biases) == 32
    assert all(params[n].grad_req == "write" for n in biases)


def test_params_file_saved_by_the_parent_loads(saved_at_pr26):
    tiny = saved_at_pr26["tiny"]
    net = ResNetV1(BottleneckV1, tiny["layers"], tiny["channels"],
                   classes=tiny["classes"], thumbnail=True)
    path = os.path.join(DATA, "bottleneck_v1_tiny_saved_by_pr26.params")
    net.load_parameters(path)
    assert list(net._collect_params_with_prefix()) == tiny["names"]
    saved = nd.load(path)
    for name, p in net._collect_params_with_prefix().items():
        assert np.array_equal(p.data().asnumpy(), saved[name].asnumpy())
    x = nd.array(np.linspace(-1, 1, 2 * 3 * 8 * 8, dtype="float32")
                 .reshape(2, 3, 8, 8))
    with autograd.pause():
        out = net(x).asnumpy()
    np.testing.assert_allclose(out, np.array(tiny["inference_output"]),
                               rtol=1e-6)
