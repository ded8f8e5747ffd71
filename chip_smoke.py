#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once — ResNet-50 v1, 224x224, NHWC, bf16 with f32
masters, bs=256 per chip, SGD+momentum, ``DataParallelTrainer.step`` fed by
``ImageRecordIter(device_tail=True)`` — through the entry points a user
calls, in ONE process (a chip belongs to one process at a time), on a TPU:

    python chip_smoke.py              # one chip, or all four of a host

Phases, each fatal (an uncaught exception ends the run non-zero, and
neither closing line is printed): ``device``, ``train``, ``fed``,
``infer``, ``kernels``, ``delta_rule`` and — when four devices are
visible — ``four_chips``.  Every phase prints one JSON line naming the
device JAX reports and the jax/jaxlib/libtpu versions; a ``summary`` line
lists the phases that passed, and the last line of stdout is
``{"ok": true, "device": {...}}``.  The compile seconds and step
milliseconds in those lines are information, not metrics.

Without a TPU it exits non-zero, names the platform it found on stderr and
prints nothing on stdout.  ``--rehearsal`` is the one exception, and it has
to be asked for: the same phases at a tiny size on whatever backend JAX has
(Pallas interpreted off-TPU), to debug the script itself; every line then
says ``"rehearsal": true`` and the last line carries no ``ok``.

Weights and data are made from seeds; nothing is read from the network;
what it writes (a synthetic ``.rec``, the native decoder it builds) stays
under the checkout.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def _version(dist):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


class Smoke:
    """One run: the device it found, the sizes it uses, the lines it
    prints."""

    def __init__(self, rehearsal):
        import jax

        self.rehearsal = rehearsal
        devices = jax.devices()
        self.devices = devices
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        self.versions = {d: _version(d) for d in ("jax", "jaxlib", "libtpu")}
        self.on_tpu = devices[0].platform == "tpu"
        # the real sizes, and the rehearsal's (same code, small enough for
        # a CPU and the Pallas interpreter)
        if rehearsal:
            self.batch, self.side, self.classes = 8, 32, 10
            self.lr = 0.005            # a batch of 8 diverges at 0.05
            self.flat_sizes = (300_000, 53_120)
            self.ln_shapes = ((512, 256), (100, 256))
            self.attn_shapes = ((2, 256, 128), (2, 200, 64))
        else:
            self.batch, self.side, self.classes = 256, 224, 1000
            self.lr = 0.05
            self.flat_sizes = (25_557_032, 53_120)
            self.ln_shapes = ((8192, 2048), (1000, 2048))
            self.attn_shapes = ((16, 2048, 128), (4, 1000, 64))
        self.passed = []

    def emit(self, phase, seconds, **info):
        line = {"phase": phase, "passed": True,
                "seconds": round(seconds, 1)}
        line.update(info)
        line["device"] = self.device
        line["versions"] = self.versions
        if self.rehearsal:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)
        self.passed.append(phase)

    def run(self, phase, fn):
        t0 = time.monotonic()
        info = fn()
        self.emit(phase, time.monotonic() - t0, **(info or {}))


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------
def build_net(smoke, seed):
    """ResNet-50 v1 NHWC bf16 (BN statistics stay f32), seeded."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    net = vision.resnet50_v1(layout="NHWC", classes=smoke.classes)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    return net


def build_trainer(smoke, net, mesh):
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DataParallelTrainer

    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": smoke.lr, "momentum": 0.9, "wd": 1e-4,
         "multi_precision": True}, mesh=mesh)


def fixed_batch(smoke, n, seed):
    """One seeded batch in the geometry the fed phase's iterator yields
    (bf16 NHWC data, f32 labels), so both phases share one compiled step."""
    import numpy as np

    import mxnet_tpu as mx

    rng = np.random.RandomState(seed)
    x = rng.rand(n, smoke.side, smoke.side, 3).astype(np.float32)
    y = rng.randint(0, smoke.classes, n).astype(np.float32)
    return mx.nd.array(x).astype("bfloat16"), mx.nd.array(y)


def train_steps(trainer, x, y, steps):
    """(compile_s, step_ms, losses): the first step compiles; every loss
    is read back, which waits for that step."""
    t0 = time.monotonic()
    losses = [float(trainer.step(x, y).asscalar())]
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(steps):
        losses.append(float(trainer.step(x, y).asscalar()))
    step_ms = (time.monotonic() - t0) / steps * 1e3
    check(all(math.isfinite(v) for v in losses),
          "non-finite loss: %r" % (losses,))
    check(losses[-1] < losses[0],
          "loss did not fall on one fixed batch: %r" % (losses,))
    return compile_s, step_ms, losses


def device_sets(arrays):
    return [frozenset(a.devices()) for a in arrays]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_train(smoke, state):
    """Compile + >= 5 steps on one fixed seeded batch on a one-device mesh;
    the training state lives on that device; the fused optimizer the TPU
    default claims was compiled by Mosaic."""
    from mxnet_tpu.ops import fused_optimizer
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh((1,), ("data",), smoke.devices[:1])
    net = build_net(smoke, seed=0)
    trainer = build_trainer(smoke, net, mesh)
    x, y = fixed_batch(smoke, smoke.batch, seed=1)
    compile_s, step_ms, losses = train_steps(trainer, x, y, steps=6)

    params, opt_leaves = trainer.device_arrays()
    home = frozenset(smoke.devices[:1])
    strays = [n for n, a in params.items() if frozenset(a.devices()) != home]
    check(not strays, "parameters off %s: %s" % (smoke.devices[0], strays[:5]))
    check(all(s == home for s in device_sets(opt_leaves)),
          "optimizer-state leaves off %s" % (smoke.devices[0],))

    # what the default claims, and what the step was lowered to
    # (the fused update takes the f32 parameters — here the BatchNorm
    # scales and shifts; the bf16 weights keep the unfused spelling)
    text = trainer.lower_step(x, y).as_text()
    mosaic_calls = text.count("tpu_custom_call")
    f32_trainable = sum(
        1 for p in net.collect_params().values()
        if p.grad_req != "null" and str(p.dtype) == "float32")
    check(f32_trainable > 0, "no f32 trainable parameter to update fused")
    if smoke.on_tpu:
        check(fused_optimizer.fused_update_enabled(),
              "fused optimizer update is not on by default on a TPU")
        check(mosaic_calls >= 1,
              "lowered step has no tpu_custom_call: the fused optimizer "
              "update of the %d f32 parameters was not compiled by Mosaic"
              % f32_trainable)
    else:
        check(not fused_optimizer.fused_update_enabled()
              and mosaic_calls == 0,
              "off-TPU default should be the unfused update")
    state.update(trainer=trainer, net=net)
    return {"compile_s": round(compile_s, 1), "step_ms": round(step_ms, 1),
            "loss_first": losses[0], "loss_last": losses[-1],
            "params": len(params), "optimizer_leaves": len(opt_leaves),
            "f32_trainable_params": f32_trainable,
            "tpu_custom_calls_in_step": mosaic_calls,
            "compile_cache": state["cache_dir"]}


def _open_files(pid):
    """Targets of process ``pid``'s open file descriptors (Linux /proc; a
    TPU host is one)."""
    fd_dir = "/proc/%s/fd" % pid
    for fd in os.listdir(fd_dir):
        try:
            yield os.readlink(os.path.join(fd_dir, fd))
        except FileNotFoundError:      # closed between listdir and readlink
            continue


def _holds_accelerator(pid, device_files):
    """How process ``pid`` touched the accelerator, or None: it has libtpu
    mapped, or holds one of ``device_files`` (the accelerator device files
    this process holds)."""
    with open("/proc/%d/maps" % pid) as f:
        if "libtpu" in f.read():
            return "libtpu mapped"
    held = device_files.intersection(_open_files(pid))
    return "holds %s" % sorted(held) if held else None


def phase_fed(smoke, state):
    """>= 2 full batches from a synthetic .rec through the multi-process
    decode pipeline and the fused uint8 device tail into the SAME trainer.
    The decoder is the native one, built here from native/mxtpu_io.cc; no
    worker died; no worker process touched the accelerator."""
    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.test_utils import synthetic_image_rec

    check(_native.available(),
          "native decoder missing: native/mxtpu_io.cc did not build or "
          "load (the compiler's message is in the log above)")
    trainer = state["trainer"]
    full_batches = 3
    os.makedirs(OUT_DIR, exist_ok=True)
    rec_path, idx_path = synthetic_image_rec(
        OUT_DIR, full_batches * smoke.batch, size=smoke.side,
        classes=smoke.classes, seed=2)
    workers = max(2, min(4, (os.cpu_count() or 2) // 2))
    fed = mx.io.ImageRecordIter(
        path_imgrec=rec_path, path_imgidx=idx_path,
        data_shape=(3, smoke.side, smoke.side), batch_size=smoke.batch,
        shuffle=True, seed=3, dtype="bfloat16", layout="NHWC",
        device_tail=True, std_r=255.0, std_g=255.0, std_b=255.0,
        preprocess_threads=workers, prefetch_buffer=2)
    pipeline = fed.base
    try:
        pids = pipeline.worker_pids()
        check(len(pids) == workers,
              "asked for %d decode workers, have %d" % (workers, len(pids)))
        losses, step_s = [], []
        for batch in fed:
            t0 = time.monotonic()
            losses.append(float(
                trainer.step(batch.data[0], batch.label[0]).asscalar()))
            step_s.append(time.monotonic() - t0)
        check(len(losses) == full_batches,
              "fed %d batches of %d" % (len(losses), full_batches))
        check(all(math.isfinite(v) for v in losses),
              "non-finite fed loss: %r" % (losses,))
        respawns = pipeline.stats.snapshot()["respawns"]
        check(respawns == 0, "%d decode worker respawn(s)" % respawns)
        # the workers, and the forkserver they were forked from
        device_files = {t for t in _open_files("self")
                        if t.startswith(("/dev/accel", "/dev/vfio"))}
        with open("/proc/%d/stat" % pids[0]) as f:
            forkserver = int(f.read().rsplit(")", 1)[1].split()[1])
        watched = list(pids)
        if forkserver != os.getpid():
            watched.append(forkserver)
        for pid in watched:
            held = _holds_accelerator(pid, device_files)
            check(held is None, "process %d of the decode pool touched the "
                                "accelerator: %s" % (pid, held))
    finally:
        pipeline.close()
    return {"batches": len(losses), "workers": workers,
            "first_step_s": round(step_s[0], 2),
            "last_step_s": round(step_s[-1], 2), "respawns": respawns,
            "parent_accelerator_files": sorted(device_files),
            "loss_last": losses[-1]}


def phase_infer(smoke, state):
    """Hybridized bf16 forward at the training batch size: finite logits of
    the expected shape; on a batch of 8 they agree with the same network,
    same weights, run in f32 on the CPU backend.

    The network is the train phase's, un-stepped: every parameter and
    BatchNorm statistic re-drawn from a seed, which is the net
    ``__graft_entry__.entry()`` builds minus its eager shape-resolving
    pass (108.6 s for an un-initialised ResNet-50 at bs=256 on a v5e,
    CHANGES.md PR 21).  Left as the train phase stepped it — nine steps
    on noise — its BatchNorm running statistics sit far from its batch
    statistics and a few channels carry logits of ~2e5.  An un-stepped
    net in inference mode normalises nothing (running mean 0, variance
    1, no shifts, no biases), so its activations shrink layer by layer
    and the logits are small (``logits_scale``); every layer is
    positively homogeneous, so the error relative to the logits' scale
    is the scale-free quantity, and that is what is bounded."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    del state["trainer"]         # its optimizer state is not needed now
    net = state.pop("net")
    mx.random.seed(4)
    net.initialize(mx.init.Xavier(), force_reinit=True)
    net.hybridize()
    x, _ = fixed_batch(smoke, smoke.batch, seed=5)
    t0 = time.monotonic()
    out = net(x).asnumpy()
    first_s = time.monotonic() - t0
    check(out.shape == (smoke.batch, smoke.classes),
          "logits shape %r" % (out.shape,))
    check(np.isfinite(out.astype(np.float32)).all(), "non-finite logits")

    x8 = x[:8]
    got = net(x8).asnumpy().astype(np.float32)

    # reference: identical (bf16-representable) weights, f32 arithmetic,
    # CPU backend — through the same Gluon entry points, under mx.cpu()
    cpu0 = jax.devices("cpu")[0]
    with mx.cpu(), jax.default_device(cpu0):
        ref_net = vision.resnet50_v1(layout="NHWC", classes=smoke.classes)
        ref_net.initialize(mx.init.Xavier())
        x8_ref = mx.nd.array(x8.astype("float32").asnumpy())
        with mx.autograd.pause():
            ref_net(x8_ref)                # resolve deferred shapes
        pairs = list(zip(ref_net.collect_params().values(),
                         net.collect_params().values()))
        check(all(r.shape == p.shape for r, p in pairs),
              "reference net's parameters do not line up")
        for r, p in pairs:
            r.set_data(mx.nd.array(p.data().astype("float32").asnumpy()))
        ref_out = ref_net(x8_ref)
        check(frozenset(ref_out._data.devices()) == {cpu0},
              "the f32 reference did not run on the CPU backend")
        ref = ref_out.asnumpy()

    # Tolerance: both nets hold the same weights, so what differs is the
    # arithmetic — bf16 activations (8 mantissa bits: 2^-8 = 0.4% per
    # rounding) through 53 convolutions and 16 residual sums, against f32
    # on the host.  Independent roundings grow as ~sqrt(depth) x 0.4% ~ 3%
    # of the logits' scale at worst.  3% of max|logit| still fails on a
    # wrong layout, a dropped layer or a mis-cast, which move logits by
    # their own magnitude.
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    check(err <= 0.03 * scale,
          "bf16 TPU logits differ from f32 CPU logits by %.4g "
          "(scale %.4g, allowed 3%%)" % (err, scale))
    return {"first_call_s": round(first_s, 1), "logits_scale": scale,
            "max_abs_err_vs_cpu_f32": err,
            "rel_err": err / scale if scale else 0.0}


def phase_kernels(smoke):
    """Every Pallas kernel that is on by default on a TPU, called with
    ``interpret=False`` spelled out (``True`` only in a rehearsal off-TPU),
    at one production-sized aligned shape and one awkward one, against its
    jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import fused_optimizer as fo
    from mxnet_tpu.ops import pallas_kernels as pk

    # False on the chip — main() runs nothing without one; True only in a
    # rehearsal off-TPU, where Mosaic does not exist
    interpret = False if smoke.on_tpu else True
    rng = np.random.RandomState(6)
    verdicts = {}

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def lowered_by_mosaic(jitted, *args):
        if not smoke.on_tpu:
            return
        check("tpu_custom_call" in jitted.lower(*args).as_text(),
              "kernel did not lower to a Mosaic tpu_custom_call")

    def randn(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    # -- fused optimizer: plain, loss-scaled (inv_scale), skipped (ok=0).
    # f32 elementwise, same expression order as the reference: 1e-5 abs
    # covers fused-multiply-add contraction differences on O(1) values.
    lr, wd, mom = jnp.float32(0.05), 1e-4, 0.9
    b1, b2, eps = 0.9, 0.999, 1e-8
    for p in smoke.flat_sizes:
        w, g = randn(p), randn(p)
        m, v = randn(p) * 0.1, jnp.abs(randn(p)) * 0.1
        for inv, ok in ((1.0, 1.0), (1.0 / 1024, 1.0), (1.0 / 1024, 0.0)):
            tag = "p=%d inv_scale=%g ok=%g" % (p, inv, ok)
            sgdm = jax.jit(lambda w, g, m, inv=inv, ok=ok:
                           fo.fused_sgd_momentum(
                               w, g, m, lr, momentum=mom, wd=wd,
                               inv_scale=jnp.float32(inv),
                               ok=jnp.float32(ok), interpret=interpret))
            lowered_by_mosaic(sgdm, w, g, m)
            nw, nm = sgdm(w, g, m)
            rm = mom * m - lr * wd * w - lr * (g * inv)
            rw, rm = (w + rm, rm) if ok else (w, m)
            e = max(err(nw, rw), err(nm, rm))
            check(e <= 1e-5, "fused_sgd_momentum %s: err %g" % (tag, e))
            verdicts["fused_sgd_momentum " + tag] = e

            adam = jax.jit(lambda w, g, m, v, inv=inv, ok=ok:
                           fo.fused_adam(
                               w, g, m, v, lr, beta1=b1, beta2=b2,
                               epsilon=eps, wd=wd,
                               inv_scale=jnp.float32(inv),
                               ok=jnp.float32(ok), interpret=interpret))
            lowered_by_mosaic(adam, w, g, m, v)
            nw, nm, nv = adam(w, g, m, v)
            gg = g * inv + wd * w
            rm = b1 * m + (1 - b1) * gg
            rv = b2 * v + (1 - b2) * gg * gg
            rw = w - lr * rm / (jnp.sqrt(rv) + eps)
            if not ok:
                rw, rm, rv = w, m, v
            e = max(err(nw, rw), err(nm, rm), err(nv, rv))
            check(e <= 1e-5, "fused_adam %s: err %g" % (tag, e))
            verdicts["fused_adam " + tag] = e

    # -- fused layernorm (f32, d % 128 == 0 is what the default turns on);
    # a row-mean over d terms: 1e-4 abs on O(1) outputs
    for rows, d in smoke.ln_shapes:
        x, sc, b = randn(rows, d), jnp.abs(randn(d)) + 0.5, randn(d)
        ln = jax.jit(lambda x, sc, b: fo._ln_fwd_impl(x, sc, b, 1e-5,
                                                      interpret))
        lowered_by_mosaic(ln, x, sc, b)
        xc = x - x.mean(-1, keepdims=True)
        ref = xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True)
                                 + 1e-5) * sc + b
        e = err(ln(x, sc, b), ref)
        check(e <= 1e-4, "fused layernorm %dx%d: err %g" % (rows, d, e))
        verdicts["fused_layer_norm %dx%d" % (rows, d)] = e

    # -- flash attention forward + both backward kernels, causal, against
    # softmax(QK^T)V on the same (bf16- or f32-valued) inputs computed in
    # f32 at precision=HIGHEST, i.e. true f32 products in the reference.
    # The kernels' dots carry no precision argument, and Mosaic's default
    # rounds f32 operands to bf16 for the MXU: against this reference a
    # v5e measured 1.5e-2 for f32 inputs and 1.6e-2 for bf16 (CHANGES.md
    # PR 21).  So the f32 rows show that the f32 kernels compile and are
    # bf16-accurate; they do NOT show f32 arithmetic (ROADMAP Speed 8).
    # One bound for both: 2^-8 relative per product on O(1) outputs and
    # gradients -> 4e-2 abs.
    for dtype in (jnp.bfloat16, jnp.float32):
        for bh, t, d in smoke.attn_shapes:
            tag = "%s BH=%d T=%d D=%d" % (jnp.dtype(dtype).name, bh, t, d)
            q, k, v, do = (randn(bh, t, d).astype(dtype) for _ in range(4))
            scale = d ** -0.5

            def ref_fn(q, k, v):
                return pk._attention_reference(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True, scale)

            fwd = jax.jit(lambda q, k, v: pk.flash_forward_with_lse(
                q, k, v, True, scale, interpret=interpret))
            lowered_by_mosaic(fwd, q, k, v)
            out, lse = fwd(q, k, v)
            with jax.default_matmul_precision("highest"):
                ref, vjp = jax.vjp(ref_fn, q, k, v)
                rdq, rdk, rdv = vjp(do.astype(jnp.float32))
            delta = pk.flash_delta(out, do)
            dq_fn = jax.jit(lambda *a: pk.flash_dq(
                *a, True, scale, interpret=interpret))
            dkv_fn = jax.jit(lambda *a: pk.flash_dkv(
                *a, True, scale, interpret=interpret))
            lowered_by_mosaic(dq_fn, q, k, v, do, lse, delta)
            lowered_by_mosaic(dkv_fn, q, k, v, do, lse, delta)
            dq = dq_fn(q, k, v, do, lse, delta)
            dk, dv = dkv_fn(q, k, v, do, lse, delta)
            errs = {"out": err(out, ref), "dq": err(dq, rdq),
                    "dk": err(dk, rdk), "dv": err(dv, rdv)}
            check(max(errs.values()) <= 4e-2,
                  "flash attention %s: %r" % (tag, errs))
            verdicts["flash_attention fwd+dq+dkv " + tag] = max(
                errs.values())

    return {"kernels": len(verdicts), "interpret": interpret,
            "max_abs_err": verdicts}


def phase_delta_rule(smoke):
    """The delta-rule kernel pair of ``ops/kda_kernels.py`` under Mosaic at
    the ``Ling-3.0-flash`` cell's tile (32 heads of 128 columns, chunks of
    64; 1,024 tokens), bfloat16 operands, against the token-by-token
    ``kda.kda_recurrence`` in float32 at precision HIGHEST, values and all
    five gradients: with seeded keys; with keys that all but coincide at
    ``beta`` 0.9 and hardly any decay (what a few steps of training make of
    a layer, and where the first ``Ling-3.0-flash`` tree read NaN on the
    chip and on no CPU: PERF.md section 6, PR 38); and with the gate at its
    bound of -5 at every token.  Bounds, as shares of the reference's
    largest entry: 4% (bfloat16 operands through a chunk's triangular
    system; a v5e read 0.6% and 0.8%), and 20% for the aligned keys, whose
    differences bfloat16 rounds to a tenth (a v5e read 7.8%; the nilpotent
    product read 1e3 to 1e21 there).  The gate's gradient is a sum of
    ``k x dk`` terms that all but cancels, so its error is held to their
    size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import kda_kernels
    from mxnet_tpu.transformer import kda

    heads, tokens, width = (2, 128, 128) if smoke.rehearsal \
        else (32, 1024, 128)
    check(kda_kernels.tiles(64, heads, width, jnp.bfloat16),
          "kda_kernels.tiles refuses the cell's tile")
    shape = (1, tokens, heads, width)
    by_all = (0, 1, 2, 3, 4)

    def inputs(seed, noise=None, g=None, beta=None):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        base = 0.0 if noise is None else \
            jax.random.normal(ks[5], (1, 1, heads, width)) / noise
        q = kda._l2_normed(base + jax.random.normal(ks[0], shape)) \
            * width ** -0.5
        k = kda._l2_normed(base + jax.random.normal(ks[1], shape))
        v = jax.random.normal(ks[2], shape)
        gate = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], shape) - 2) \
            if g is None else jnp.full(shape, g, jnp.float32)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])) \
            if beta is None else jnp.full(shape[:3], beta, jnp.float32)
        return q, k, v, gate, beta

    def scored(fn, weight):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
            argnums=by_all))

    verdicts = {}
    cases = (("seeded keys", 4e-2, {}),
             ("aligned keys", 2e-1, dict(noise=0.1, g=-0.001, beta=0.9)),
             ("gate at its bound", 4e-2, dict(g=-5.0)))
    for seed, (tag, bound, kw) in enumerate(cases):
        args = inputs(40 + seed, **kw)
        low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
        weight = jax.random.normal(jax.random.PRNGKey(9), shape)
        kernels = scored(kda_kernels.kda_scan, weight)
        if smoke.on_tpu:
            check(kernels.lower(*low).as_text().count("tpu_custom_call")
                  >= 2, "the kernels did not lower to Mosaic calls")
        out = jax.jit(kda_kernels.kda_scan)(*low)
        _, got = kernels(*low)
        with jax.default_matmul_precision("highest"):
            ref = kda.kda_recurrence(*args)
            _, want = scored(kda.kda_recurrence, weight)(*args)
        peak = lambda a: float(jnp.max(jnp.abs(a.astype(jnp.float32))))
        scales = [peak(w) for w in want]
        scales[3] += scales[1] * peak(args[1])   # dg: sums of k x dk
        errs = {"out": peak(out.astype(jnp.float32) - ref) / peak(ref)}
        for name, a, b, scale in zip(("dq", "dk", "dv", "dg", "dbeta"),
                                     got, want, scales):
            errs[name] = peak(a.astype(jnp.float32) - b) / scale
        check(all(np.isfinite(e) and e <= bound for e in errs.values()),
              "delta-rule kernels, %s: %r" % (tag, errs))
        verdicts[tag] = errs
    return {"heads": heads, "tokens": tokens, "interpret": not smoke.on_tpu,
            "rel_err": verdicts}


def four_data_parallel(smoke, four):
    """The train phase on ``make_mesh((4,), ("data",))`` at global batch
    4 x per-chip: batch split over four distinct devices, training state
    replicated on all four, loss finite and falling."""
    import jax

    from mxnet_tpu.parallel import make_mesh

    all_four = frozenset(four)
    mesh = make_mesh((4,), ("data",), four)
    trainer = build_trainer(smoke, build_net(smoke, seed=7), mesh)
    x, y = fixed_batch(smoke, 4 * smoke.batch, seed=8)
    compile_s, step_ms, losses = train_steps(trainer, x, y, steps=5)
    # step() got a default-device array, as mx.nd.array makes them, and
    # re-placed it with this sharding
    placed = jax.device_put(x._data, trainer.batch_sharding)
    shard_devices = [s.device for s in placed.addressable_shards]
    check(len(shard_devices) == 4 and frozenset(shard_devices) == all_four,
          "batch shards sit on %r, not on four distinct devices"
          % (shard_devices,))
    check(all(s.data.shape[0] == smoke.batch
              for s in placed.addressable_shards),
          "batch is not split evenly over the data axis")
    params, opt_leaves = trainer.device_arrays()
    strays = [n for n, a in params.items()
              if frozenset(a.devices()) != all_four]
    check(not strays, "parameters not replicated on all four: %s"
          % strays[:5])
    check(all(s == all_four for s in device_sets(opt_leaves)),
          "optimizer state not on all four devices")
    mosaic_calls = trainer.lower_step(x, y).as_text().count(
        "tpu_custom_call")
    check(mosaic_calls >= 1 or not smoke.on_tpu,
          "four-device step lost the fused optimizer's tpu_custom_call")
    return {"compile_s": round(compile_s, 1), "step_ms": round(step_ms, 1),
            "loss_first": losses[0], "loss_last": losses[-1],
            "tpu_custom_calls_in_step": mosaic_calls}


def four_zero1_mlp(smoke, four):
    """ZeRO-1 on the replicated tier (a small MLP, the elastic tests'
    fixture): the shard-local fused optimizer kernel runs inside the
    reduce-scatter / all-gather ``shard_map``; optimizer state is 1/4 per
    device."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh((4,), ("data",), four), zero=1)
    rng = np.random.RandomState(11)
    x = mx.nd.array(rng.randn(32, 8).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 4, 32).astype(np.float32))
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(4)]
    check(all(math.isfinite(v) for v in losses) and losses[-1] < losses[0],
          "zero=1 MLP losses %r" % (losses,))
    _, opt_leaves = trainer.device_arrays()
    for leaf in opt_leaves:
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        check(frozenset(leaf.devices()) == frozenset(four)
              and shard_rows == {leaf.shape[0] // 4},
              "zero=1 optimizer state is not sharded four ways: %r"
              % (leaf.sharding,))
    return {"loss_first": losses[0], "loss_last": losses[-1]}


def four_transformer(smoke, four, plan, zero, n_layers, batch):
    """One step of the transformer mesh tier under ``plan`` at the fixture
    sizes of tests/test_transformer.py and tests/test_pipeline.py."""
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import DataParallelTrainer
    from mxnet_tpu.transformer import TransformerLM, TransformerLMConfig

    all_four = frozenset(four)
    cfg = TransformerLMConfig(vocab_size=32, d_model=16, n_heads=4,
                              n_layers=n_layers, d_ff=32, seq_len=16)
    mx.random.seed(0)
    trainer = DataParallelTrainer(
        TransformerLM(cfg), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh_plan=plan, zero=zero)
    tokens = np.random.RandomState(9).randint(
        0, cfg.vocab_size, size=(batch, cfg.seq_len)).astype(np.int32)
    t0 = time.monotonic()
    loss = float(trainer.step(
        NDArray(jnp.asarray(tokens)),
        NDArray(jnp.asarray(np.roll(tokens, -1, axis=1)))).asscalar())
    check(math.isfinite(loss), "%r: loss %r" % (plan, loss))
    check(frozenset(trainer.mesh.devices.flat) == all_four,
          "%r: mesh spans %r" % (plan, trainer.mesh.devices))
    params, opt_leaves = trainer.device_arrays()
    for what, arrays in (("parameters", list(params.values())),
                         ("optimizer state", opt_leaves)):
        spread = frozenset().union(*device_sets(arrays))
        check(spread == all_four, "%r: %s sit on devices %r only"
              % (plan, what, sorted(d.id for d in spread)))
    return {"step_s": round(time.monotonic() - t0, 1), "loss": loss}


def phase_four_chips(smoke):
    """The first four devices as one mesh, driven by this one process: the
    data-parallel ResNet-50 step (gradient psum), ZeRO-1 with the fused
    kernel (reduce-scatter / all-gather), the dp x tp + ring-attention dry
    run (ring ppermute), and one step each of the transformer tier at
    data=2 x model=2 with ZeRO-1 and at pipeline=2 x data=2 (1F1B
    ppermute hops).  The question is only whether each compiles and
    places work on all four."""
    from mxnet_tpu.parallel import MeshPlan

    import __graft_entry__

    four = smoke.devices[:4]
    info = {"data_parallel": four_data_parallel(smoke, four),
            "zero1_fused_mlp": four_zero1_mlp(smoke, four)}
    t0 = time.monotonic()
    __graft_entry__.dryrun_multichip(4)
    info["dryrun_multichip_s"] = round(time.monotonic() - t0, 1)
    info["data2_model2_zero1"] = four_transformer(
        smoke, four, MeshPlan(data=2, model=2), zero=1, n_layers=1, batch=4)
    info["pipeline2_data2"] = four_transformer(
        smoke, four, MeshPlan(pipeline=2, data=2), zero=0, n_layers=4,
        batch=8)
    return info


# ---------------------------------------------------------------------------
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on whatever backend JAX has (no chip needed); "
             "every line says rehearsal and no ok is printed")
    args = parser.parse_args(argv)

    # the program first: in a directory that holds only this script the
    # import fails here, before anything is printed
    sys.path.insert(0, HERE)
    import mxnet_tpu as mx

    smoke = Smoke(args.rehearsal)
    if not smoke.on_tpu and not args.rehearsal:
        sys.exit("chip_smoke: no TPU — jax.devices() found platform %r "
                 "(%d x %s); nothing was run"
                 % (smoke.device["platform"], smoke.device["count"],
                    smoke.device["kind"]))
    state = {"cache_dir": mx.base.use_compilation_cache()}
    smoke.emit("device", 0.0)
    smoke.run("train", lambda: phase_train(smoke, state))
    smoke.run("fed", lambda: phase_fed(smoke, state))
    smoke.run("infer", lambda: phase_infer(smoke, state))
    smoke.run("kernels", lambda: phase_kernels(smoke))
    smoke.run("delta_rule", lambda: phase_delta_rule(smoke))
    if smoke.device["count"] >= 4:
        smoke.run("four_chips", lambda: phase_four_chips(smoke))
    else:
        print(json.dumps({
            "phase": "four_chips", "skipped": True,
            "reason": "%d device(s) visible" % smoke.device["count"],
            "device": smoke.device, "versions": smoke.versions,
            **({"rehearsal": True} if args.rehearsal else {})}), flush=True)

    print(json.dumps({
        "phase": "summary", "phases": smoke.passed, "device": smoke.device,
        "versions": smoke.versions,
        **({"rehearsal": True} if args.rehearsal else {})}), flush=True)
    # the last line: the verdict and the device, nothing else; a rehearsal
    # is not a verdict and prints no ok
    last = {"rehearsal": True} if args.rehearsal else {"ok": True}
    print(json.dumps({**last, "device": smoke.device}), flush=True)


if __name__ == "__main__":
    main()
