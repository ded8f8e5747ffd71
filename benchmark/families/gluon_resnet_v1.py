"""ResNet v1 (He et al., arXiv:1512.03385) as the Gluon model zoo builds it.

Three things live here, for every configuration whose ``family`` is
``gluon_resnet_v1``:

* ``build`` — the system under test, through the program's public entry
  points (``vision.get_resnet`` -> ``net.cast`` -> ``Parameter.set_data``
  -> ``DataParallelTrainer.step``), spelled as ``chip_smoke.py`` spells it.
* ``flops_per_item`` — the benchmark's own count of the arithmetic a
  trained image needs: 2 FLOPs per multiply-add, training = 3 x forward,
  convolutions and the dense layer only.
* ``reference_readings`` — the plain reference: the same network, loss,
  gradients, SGD-momentum update and BatchNorm statistics in float32
  ``jax.numpy``/``lax`` at matmul precision ``highest``.  It imports
  nothing of ``mxnet_tpu`` and is handed nothing the program made: the
  weights come from ``make_weights`` (this file, from the seed), which
  ``build`` also loads into the program.

Departures of the zoo's ResNet v1 from the paper, which the reference
follows because they are what the configuration names: the 1x1
convolutions of a bottleneck carry a bias (the 3x3 and the shortcut do
not), and a bottleneck strides in its first 1x1.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import seeds

# depth -> (block, blocks per stage, channels of stem and stages): Table 1
SPEC = {
    18: ("basic", (2, 2, 2, 2), (64, 64, 128, 256, 512)),
    34: ("basic", (3, 4, 6, 3), (64, 64, 128, 256, 512)),
    50: ("bottleneck", (3, 4, 6, 3), (64, 256, 512, 1024, 2048)),
    101: ("bottleneck", (3, 4, 23, 3), (64, 256, 512, 1024, 2048)),
    152: ("bottleneck", (3, 8, 36, 3), (64, 256, 512, 1024, 2048)),
}
BN_EPS = 1e-5
BN_MOMENTUM = 0.9
FP8_MAX = 448.0            # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# the network as a list of layers, in the order the zoo creates parameters
# ---------------------------------------------------------------------------
def _block_convs(block, channels, in_channels, stride, downsample):
    """(name, out, kernel, in, stride, bias) of one residual block's
    convolutions, body first and shortcut last, as the zoo orders them."""
    if block == "bottleneck":
        mid = channels // 4
        convs = [("conv0", mid, 1, in_channels, stride, True),
                 ("conv1", mid, 3, mid, 1, False),
                 ("conv2", channels, 1, mid, 1, True)]
    else:
        convs = [("conv0", channels, 3, in_channels, stride, False),
                 ("conv1", channels, 3, channels, 1, False)]
    if downsample:
        convs.append(("down", channels, 1, in_channels, stride, False))
    return convs


def architecture(config):
    """The stem, the residual blocks and the head of ``config`` as plain
    data: what ``leaves``, ``forward`` and ``flops_per_item`` all walk."""
    block, counts, channels = SPEC[int(config["depth"])]
    if block != config["block"]:
        raise ValueError("depth %s is built of %s blocks, the configuration "
                         "says %s" % (config["depth"], block, config["block"]))
    blocks = []
    for si, count in enumerate(counts):
        for bi in range(count):
            in_ch = channels[si] if bi == 0 else channels[si + 1]
            stride = 2 if (bi == 0 and si > 0) else 1
            down = bi == 0 and channels[si + 1] != channels[si]
            blocks.append({
                "name": "stage%d.block%d" % (si + 1, bi),
                "convs": _block_convs(block, channels[si + 1], in_ch, stride,
                                      down)})
    return {"stem": ("conv", channels[0], 7, 3, 2, False),
            "blocks": blocks, "features": channels[-1]}


def leaves(config, size):
    """[(name, kind, shape)] of every parameter and BatchNorm statistic, in
    the order ``net.collect_params()`` lists them.  Kinds: ``weight`` and
    ``bias`` (held in the configuration's dtype), ``gamma``/``beta``
    (float32, trained), ``running_mean``/``running_var`` (float32, not)."""
    arch = architecture(config)
    out = []

    def conv(prefix, spec):
        name, o, k, i, _, bias = spec
        out.append(("%s.%s.weight" % (prefix, name), "weight", (o, k, k, i)))
        if bias:
            out.append(("%s.%s.bias" % (prefix, name), "bias", (o,)))
        for kind in ("gamma", "beta", "running_mean", "running_var"):
            out.append(("%s.%s.bn.%s" % (prefix, name, kind), kind, (o,)))

    conv("stem", arch["stem"])
    for blk in arch["blocks"]:
        for spec in blk["convs"]:
            conv(blk["name"], spec)
    classes = int(size["classes"])
    out.append(("dense.weight", "weight", (classes, arch["features"])))
    out.append(("dense.bias", "bias", (classes,)))
    return out


TRAINED = ("weight", "bias", "gamma", "beta")
STATS = ("running_mean", "running_var")


def make_weights(config, size, seed):
    """name -> array, every leaf from ``seed`` in ONE jitted call on the
    default device, in the type it is trained in.  Convolution and dense
    weights are Xavier-uniform (``mx.init.Xavier()``: magnitude 3 over the
    mean of fan-in and fan-out), biases and shifts 0, scales and running
    variances 1 — what ``net.initialize(mx.init.Xavier())`` draws."""
    spec = leaves(config, size)
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def draw(key):
        out = {}
        for idx, (name, kind, shape) in enumerate(spec):
            if kind == "weight":
                receptive = int(np.prod(shape[1:-1])) if len(shape) == 4 else 1
                fan_out = shape[0] * receptive
                fan_in = shape[-1] * receptive
                scale = float(np.sqrt(3.0 / ((fan_in + fan_out) / 2.0)))
                out[name] = jax.random.uniform(
                    jax.random.fold_in(key, idx), shape, jnp.float32,
                    -scale, scale).astype(dtype)
            elif kind == "bias":
                out[name] = jnp.zeros(shape, dtype)
            elif kind in ("gamma", "running_var"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return draw(seeds.key(seed, stream=0))


# ---------------------------------------------------------------------------
# the benchmark's own count of the arithmetic
# ---------------------------------------------------------------------------
def forward_macs_per_item(config, size):
    """Multiply-adds of one image's forward pass: convolutions and the dense
    layer (BatchNorm, ReLU, pooling, additions and the loss are not
    counted)."""
    arch = architecture(config)
    side = int(size["side"])

    def out_side(s, k, stride):
        return (s + 2 * (k // 2) - k) // stride + 1

    macs = 0
    _, o, k, i, stride, _ = arch["stem"]
    side = out_side(side, k, stride)
    macs += side * side * o * k * k * i
    side = out_side(side, 3, 2)                       # 3x3/2 max pool
    for blk in arch["blocks"]:
        s_in = side
        for name, o, k, i, stride, _ in blk["convs"]:
            # the shortcut reads the block's input, the body its last output
            s_out = out_side(side if name == "down" else s_in, k, stride)
            macs += s_out * s_out * o * k * k * i
            if name != "down":
                s_in = s_out
        side = s_in
    macs += arch["features"] * int(size["classes"])
    return macs


def flops_per_item(config, size):
    """FLOPs one trained image needs: 2 per multiply-add, and the backward
    pass twice the forward's (a gradient for the input and one for the
    weight of every layer)."""
    return 3 * 2 * forward_macs_per_item(config, size)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _round_fp8(x):
    """``x`` rounded to float8_e4m3fn under one scale for the tensor."""
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _both_ways(round_fn):
    """``round_fn`` applied to a value on the way forward and to its
    cotangent on the way back: what holding a tensor and its gradient in a
    lower precision does."""
    @jax.custom_vjp
    def held(x):
        return round_fn(x)

    held.defvjp(lambda x: (round_fn(x), None),
                lambda _, g: (round_fn(g),))
    return held


# how a variant holds the operands and the result of every convolution and
# of the dense layer; everything else stays float32 in all of them
HOLD = {"float32": lambda a: a,
        "bfloat16": _both_ways(_round_bf16),
        "fp8": _both_ways(_round_fp8)}


def _conv(x, w, stride, hold):
    k = w.shape[1]
    return hold(lax.conv_general_dilated(
        hold(x), hold(w), (stride, stride),
        [(k // 2, k // 2), (k // 2, k // 2)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        precision=lax.Precision.HIGHEST))


def _bn(x, params, prefix, stats_out):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    stats_out[prefix + ".running_mean"] = mean
    stats_out[prefix + ".running_var"] = var
    return ((x - mean) * lax.rsqrt(var + BN_EPS) * params[prefix + ".gamma"]
            + params[prefix + ".beta"])


def _conv_bn(x, params, prefix, spec, hold, stats_out):
    name, _, _, _, stride, bias = spec
    base = "%s.%s" % (prefix, name)
    y = _conv(x, params[base + ".weight"], stride, hold)
    if bias:
        y = y + params[base + ".bias"]
    return _bn(y, params, base + ".bn", stats_out)


def _block(x, params, blk, hold):
    """One residual block; returns (out, batch statistics of its BNs)."""
    stats = {}
    body = [c for c in blk["convs"] if c[0] != "down"]
    shortcut = [c for c in blk["convs"] if c[0] == "down"]
    y = x
    for spec in body:
        y = _conv_bn(y, params, blk["name"], spec, hold, stats)
        if spec is not body[-1]:
            y = jax.nn.relu(y)
    if shortcut:
        x = _conv_bn(x, params, blk["name"], shortcut[0], hold, stats)
    return jax.nn.relu(x + y), stats


def forward(arch, params, x, hold):
    """Logits and the batch mean/variance every BatchNorm saw, training
    mode.  Each residual block is rematerialised in the backward pass so
    that the float32 activations of a full batch fit beside the chip's
    weights."""
    stats = {}
    x = jax.nn.relu(_conv_bn(x, params, "stem", arch["stem"], hold, stats))
    x = lax.reduce_window(x, -np.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for blk in arch["blocks"]:
        x, blk_stats = jax.checkpoint(
            functools.partial(_block, blk=blk, hold=hold))(x, params)
        stats.update(blk_stats)
    x = jnp.mean(x, axis=(1, 2))
    logits = hold(jnp.dot(hold(x), hold(params["dense.weight"]).T,
                          precision=lax.Precision.HIGHEST))
    logits = logits + params["dense.bias"]
    return logits, stats


def _loss(arch, params, x, y, hold):
    logits, stats = forward(arch, params, x, hold)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked), stats


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def reference_step_fn(config, variant="float32"):
    """``_reference_step_fn`` of the same configuration, traced and
    compiled once a process."""
    return _reference_step_fn(json.dumps(config, sort_keys=True), variant)


@functools.lru_cache(maxsize=None)
def _reference_step_fn(config_json, variant):
    """The jitted reference step ``(params, stats, momentum, x, y) ->
    (loss, gradients, params, stats, momentum)``.

    ``variant`` ``"float32"`` is the reference.  ``"fp8"`` is the control:
    the same step with the operands and the result of every convolution and
    of the dense layer, and their gradients, held in float8_e4m3fn, the
    precision below the bfloat16 the configuration trains in.
    ``"bfloat16"`` holds them in bfloat16: a second, independent spelling
    of what the configuration states, to set the program's readings
    against."""
    config = json.loads(config_json)
    arch = architecture(config)
    opt = config["optimizer"]
    lr, wd, mu = float(opt["learning_rate"]), float(opt["wd"]), \
        float(opt["momentum"])
    hold = HOLD[variant]

    @jax.jit
    def step(params, stats, momentum, x, y):
        (loss, batch_stats), grads = jax.value_and_grad(
            functools.partial(_loss, arch, hold=hold),
            has_aux=True)(params, x.astype(jnp.float32), y)
        new_mom = {k: mu * momentum[k] - lr * (grads[k] + wd * params[k])
                   for k in params}
        new_params = {k: params[k] + new_mom[k] for k in params}
        new_stats = {k: BN_MOMENTUM * stats[k]
                     + (1.0 - BN_MOMENTUM) * batch_stats[k] for k in stats}
        return loss, grads, new_params, new_stats, new_mom

    return step


def reference_readings(config, size, seed, batches, variant="float32",
                       fault=None):
    """Drive the reference through ``len(batches)`` steps from the seed's
    weights and return the readings ``correctness.compare`` takes.

    ``fault`` plants one of the faults a training cell can have in the
    reference, for the control tool and the tests: ``"half_batch"`` leaves
    out the second half of every batch and takes the mean over the rest;
    ``"state_unchanged"`` returns the state it was given."""
    spec = leaves(config, size)
    weights = make_weights(config, size, seed)
    params0 = {n: weights[n].astype(jnp.float32) for n, k, _ in spec
               if k in TRAINED}
    stats0 = {n: weights[n] for n, k, _ in spec if k in STATS}
    del weights
    step = reference_step_fn(config, variant)
    params, stats = params0, stats0
    momentum = {k: jnp.zeros_like(v) for k, v in params0.items()}
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for x, y in batches:
            if fault == "half_batch":
                half = x.shape[0] // 2
                x, y = x[:half], y[:half]
            loss, grads, new_params, new_stats, new_mom = step(
                params, stats, momentum, x, y)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = jax.device_get(_leaf_norms(grads))
            del grads
            if fault != "state_unchanged":
                params, stats, momentum = new_params, new_stats, new_mom
    update = {k: params[k] - params0[k] for k in params0}
    moved = {k: stats[k] - stats0[k] for k in stats0}
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "update_norms": {k: float(v) for k, v in
                             jax.device_get(_leaf_norms(update)).items()},
            "stats_norms": {k: float(v) for k, v in
                            jax.device_get(_leaf_norms(moved)).items()}}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class Program:
    """The zoo network under ``DataParallelTrainer``: what the window
    drives.  ``step``/``flush`` are the trainer's own; ``snapshot`` copies
    the training state to the host under the reference's leaf names."""

    def __init__(self, config, size, mesh, seed):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.parallel import DataParallelTrainer

        mx.random.seed(int(seed) & 0x7FFFFFFF)
        self._config, self._size = config, size
        self._spec = leaves(config, size)
        net = vision.get_resnet(1, int(config["depth"]),
                                layout=config["layout"],
                                classes=int(size["classes"]))
        net.cast(config["dtype"])
        # the seed's weights go in the way a checkpoint's would: no
        # initializer runs and no shape is left for an eager pass to find
        weights = make_weights(config, size, seed)
        params = list(net.collect_params().items())
        if len(params) != len(self._spec):
            raise RuntimeError("the zoo network has %d parameters, the "
                               "reference %d" % (len(params), len(self._spec)))
        self._zoo_name = {}
        for (zoo_name, p), (name, kind, shape) in zip(params, self._spec):
            if not zoo_name.endswith(kind):
                raise RuntimeError("parameter order differs: %s is not a %s "
                                   "(%s)" % (zoo_name, kind, name))
            p.set_data(NDArray(weights[name]))
            if tuple(p.shape) != tuple(shape):
                raise RuntimeError("%s: shape %s, the reference has %s"
                                   % (zoo_name, p.shape, shape))
            self._zoo_name[name] = zoo_name
        self.weights0 = {n: np.asarray(weights[n].astype(jnp.float32))
                         for n, k, _ in self._spec if k in TRAINED}
        self.stats0 = {n: np.asarray(weights[n])
                       for n, k, _ in self._spec if k in STATS}
        opt = dict(config["optimizer"])
        self._net = net
        self.trainer = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), opt.pop("name"), opt,
            mesh=mesh)

    def step(self, data, label):
        """One training step; the loss as a lazy device scalar."""
        return self.trainer.step(data, label)._data

    def flush(self):
        self.trainer.flush()

    def snapshot(self):
        """Host copies, by reference leaf name, of the float32 value of
        every trained leaf (the master where the weight is held lower),
        its momentum, and the BatchNorm statistics.  Reads the optimizer's
        state as the trainer groups it: a group is one leaf, or several
        raveled and concatenated in the group's order; a lower-precision
        group's state is ``(master, momentum)``."""
        self.flush()
        trainer = self.trainer
        params, _ = trainer.device_arrays()
        by_zoo = {}
        for names, state in zip(trainer._groups, trainer._states_raw):
            low = jnp.dtype(params[names[0]].dtype) != jnp.float32
            master, mom = state if low else (None, state)
            mom = np.asarray(mom).ravel()
            master = None if master is None else np.asarray(master).ravel()
            off = 0
            for zoo_name in names:
                n = int(np.prod(params[zoo_name].shape))
                value = (master[off:off + n] if low
                         else np.asarray(params[zoo_name]).ravel())
                by_zoo[zoo_name] = (value, mom[off:off + n])
                off += n
        snap = {"weights": {}, "momentum": {}, "stats": {}}
        for name, kind, shape in self._spec:
            zoo_name = self._zoo_name[name]
            if kind in STATS:
                snap["stats"][name] = np.asarray(params[zoo_name])
            else:
                value, mom = by_zoo[zoo_name]
                snap["weights"][name] = value.reshape(shape)
                snap["momentum"][name] = mom.reshape(shape)
        return snap

    def readings(self, losses, after_first, after_last):
        """The program's side of the comparison, from the losses of the
        checked steps and the snapshots after the first and the last of
        them.  The first gradient as the optimizer got it follows from the
        momentum after one step: m1 = -lr (g + wd w0)."""
        opt = self._config["optimizer"]
        lr, wd = float(opt["learning_rate"]), float(opt["wd"])

        def norm(a):
            return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))

        grad = {k: norm(-after_first["momentum"][k] / lr - wd * w0)
                for k, w0 in self.weights0.items()}
        update = {k: norm(after_last["weights"][k] - w0)
                  for k, w0 in self.weights0.items()}
        moved = {k: norm(after_last["stats"][k] - s0)
                 for k, s0 in self.stats0.items()}
        return {"losses": [float(v) for v in losses], "grad_norms": grad,
                "update_norms": update, "stats_norms": moved}

    def close(self):
        """Drop the training state so that the reference has the chip."""
        self.flush()
        self.trainer = None
        self._net = None


def build(config, size, mesh, seed):
    return Program(config, size, mesh, seed)
