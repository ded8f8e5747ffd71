"""Host-only run-ahead overlap micro-bench: ``python -m
mxnet_tpu.engine_bench``.

Measures what the async dispatch engine buys: wall time of a *stepped*
training loop (feed → step → per-step ``float(loss)`` fetch, fully
serialized — the pre-engine ``DataParallelTrainer`` behaviour) against
the *bulk* loop (``PrefetchToDeviceIter`` ships batch k+1 on a thread
while step k executes, ``engine.bulk(depth)`` keeps the dispatch queue
full, the loss accumulates device-resident and is fetched once).

Run as a ``JAX_PLATFORMS=cpu`` subprocess by bench.py BEFORE backend
acquisition (the PR-2/PR-4 pattern), so ``train_loop_overlap_ratio``
stays live when the TPU is down.  The host feed latency is simulated
with a calibrated sleep equal to the measured device step time — the
stand-in for the multi-process shm pipeline, whose decode cost is paid
in worker *processes*, not on this thread (io/pipeline.py).  With feed
≈ step, a perfectly overlapped loop approaches 2× the stepped one; the
CI gate asserts ≥ 1.3×.

Prints one JSON line; bench.py merges it into the round record.
"""
from __future__ import annotations

import json
import os
import time


class _SlowFeedIter:
    """Host iterator with a fixed per-batch latency (decode stand-in)."""

    def __init__(self, inner, delay_s):
        self.inner = inner
        self.delay_s = delay_s
        self.batch_size = inner.batch_size

    @property
    def provide_data(self):
        return self.inner.provide_data

    @property
    def provide_label(self):
        return self.inner.provide_label

    def reset(self):
        self.inner.reset()

    def next(self):
        batch = self.inner.next()  # raises StopIteration at epoch end
        time.sleep(self.delay_s)
        return batch

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.io import NDArrayIter, PrefetchToDeviceIter
    from mxnet_tpu.parallel import DataParallelTrainer

    steps = int(os.environ.get("MXTPU_OVERLAP_STEPS", "24"))
    depth = int(os.environ.get("MXTPU_OVERLAP_DEPTH", "4"))
    batch = int(os.environ.get("MXTPU_OVERLAP_BATCH", "128"))
    # big enough that the device step dwarfs the fixed per-step python
    # dispatch cost (~5ms on the 1-core CI host, GIL-held, un-overlappable)
    # — the regime every real model is in
    hidden = int(os.environ.get("MXTPU_OVERLAP_HIDDEN", "1024"))
    feat = 784

    rng = np.random.RandomState(0)
    X = rng.rand(steps * batch, feat).astype(np.float32)
    y = (np.arange(steps * batch) % 10).astype(np.float32)

    def build_trainer():
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden, activation="relu"),
                nn.Dense(10))
        net.initialize(mx.init.Xavier())
        return DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05})

    # -- calibrate: compile the step and measure its synchronous latency
    tr = build_trainer()
    xb = mx.nd.array(X[:batch])
    yb = mx.nd.array(y[:batch])
    tr.step(xb, yb).wait_to_read()  # compile
    t0 = time.perf_counter()
    calib_iters = 8
    for _ in range(calib_iters):
        loss = tr.step(xb, yb)
        loss.wait_to_read()
    step_s = (time.perf_counter() - t0) / calib_iters
    # feed == step: the balanced-pipeline regime where serialization
    # costs the most (2x) and overlap pays the most.  The sleep is
    # GIL-free, so it overlaps with the XLA compute threads even on the
    # 1-core CI host — exactly like the real shm pipeline, whose decode
    # burns worker-process CPUs, not this thread's.
    feed_s = step_s * float(os.environ.get("MXTPU_OVERLAP_FEED_MULT",
                                           "1.0"))

    def make_iter():
        return _SlowFeedIter(NDArrayIter(X, y, batch,
                                         last_batch_handle="discard"),
                             feed_s)

    # -- stepped: the pre-engine loop — feed, step, fetch, every batch.
    # The per-step fetch is the deliberate baseline under test, not a
    # recommendation.
    tr = build_trainer()
    tr.step(xb, yb).wait_to_read()  # compile outside the timed window
    it = make_iter()
    t0 = time.perf_counter()
    n_stepped = 0
    for b in it:
        loss = tr.step(b.data[0], b.label[0])
        float(loss.asscalar())  # mxlint: disable=SRC001,SRC004
        n_stepped += 1
    stepped_s = time.perf_counter() - t0

    # -- bulk: prefetch thread + run-ahead window + lazy loss accumulation
    tr = build_trainer()
    tr.step(xb, yb).wait_to_read()  # compile outside the timed window
    pf = PrefetchToDeviceIter(make_iter(), sharding=tr.batch_sharding,
                              depth=2)
    tot = None
    t0 = time.perf_counter()
    n_bulk = 0
    with engine.bulk(depth):
        for b in pf:
            loss = tr.step(b.data[0], b.label[0])
            tot = loss if tot is None else tot + loss
            n_bulk += 1
    float(tot.asscalar())  # the window's one fetch
    bulk_s = time.perf_counter() - t0

    snap = tr.dispatch_stats.snapshot()
    out = {
        "train_loop_overlap_ratio": round(stepped_s / bulk_s, 3),
        "dispatch_depth": depth,
        "overlap_step_ms": round(step_s * 1000, 3),
        "overlap_feed_ms": round(feed_s * 1000, 3),
        "overlap_stepped_steps_per_sec": round(n_stepped / stepped_s, 2),
        "overlap_bulk_steps_per_sec": round(n_bulk / bulk_s, 2),
        "overlap_inflight_max": snap["inflight_max"],
        "overlap_dispatch_stall_s": snap["dispatch_stall_s"],
        "overlap_prefetch_slots_max": pf.live_slots_max,
        "overlap_prefetch_hbm_bound_bytes": pf.hbm_bound_bytes(),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
