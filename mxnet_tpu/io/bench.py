"""Host-only input-pipeline micro-bench: ``python -m mxnet_tpu.io.bench``.

Measures what the host can FEED, with no accelerator in the loop (run as a
``JAX_PLATFORMS=cpu`` subprocess by bench.py, the PR-2 serving pattern —
the number stays live even when the TPU backend is down).

``fed`` here means: decode + augment + transfer fenced on the (cpu)
device + the fused normalization tail applied, per batch, measured over a
steady-state epoch (workers up, jits warm — construction/compile cost is
paid in a warm-up epoch, as in steady training).  Three variants:

- legacy: the in-process float path — host-side mean/std normalize,
  float32 NCHW batches (what the port did before the pipeline PR);
- new: the multi-process shared-memory pipeline shipping raw uint8 NHWC
  with the device-side fused tail (``device_tail=True``);
- a worker-scaling curve for the new pipeline (0 = in-process), from
  which the headline ``pipeline_fed_imgs_per_sec`` takes the best
  config on this host (reported in ``pipeline_best_workers``).

Prints one JSON line; bench.py merges it into the round record.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time


def _timed_epoch(make_iter, consume):
    """Steady-state epoch rate: epoch 1 warms (workers, prefetch, jit
    compiles), epoch 2 is timed."""
    it = make_iter()
    n_img = 0
    for b in it:
        consume(b)
    it.reset()
    t0 = time.perf_counter()
    for b in it:
        consume(b)
        n_img += b.data[0].shape[0]
    dt = time.perf_counter() - t0
    # prefer the decode pipeline's stats (worker pool utilization) over
    # the DeviceFeedIter wrapper's own feed-thread stats
    if hasattr(getattr(it, "base", None), "stats"):
        stats = it.base.stats.snapshot()
    elif hasattr(it, "stats"):
        stats = it.stats.snapshot()
    else:
        stats = None
    close = getattr(it, "close", None) or getattr(
        getattr(it, "base", None), "close", None)
    if close:
        close()
    return n_img / dt, stats


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _native, recordio
    from mxnet_tpu.test_utils import synthetic_image_rec

    n = int(os.environ.get("MXTPU_PIPE_BENCH_N", "768"))
    batch = int(os.environ.get("MXTPU_PIPE_BENCH_BATCH", "128"))
    size = int(os.environ.get("MXTPU_PIPE_BENCH_SIZE", "224"))
    workers_curve = [int(w) for w in os.environ.get(
        "MXTPU_PIPE_BENCH_WORKERS", "0,1,2").split(",")]
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_pipe_bench_")
    rec, idx = synthetic_image_rec(tmpdir, n, size)
    out = {"pipeline_host_cores": os.cpu_count(),
           "pipeline_batch": batch, "pipeline_n_records": n}
    try:
        # raw native decode rate: the host's physical ceiling
        if _native.available():
            r = recordio.MXIndexedRecordIO(idx, rec, "r")
            bufs = [recordio.unpack(r.read_idx(i))[1] for i in range(n)]
            r.close()
            t0 = time.perf_counter()
            _native.decode_batch(bufs, size, size, 3)
            out["pipeline_decode_imgs_per_sec"] = round(
                n / (time.perf_counter() - t0), 2)
            del bufs

        mean = dict(mean_r=123.68, mean_g=116.28, mean_b=103.53,
                    std_r=58.395, std_g=57.12, std_b=57.375)
        # the consumer: one tiny jitted reduction per batch, fenced — a
        # stand-in for "the device accepted this batch" that costs the
        # same for every variant
        consumed = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

        def consume(b):
            consumed(b.data[0]._data).block_until_ready()

        # legacy: in-process float path, host normalize, NCHW float32
        def legacy():
            return mx.io.DeviceFeedIter(mx.io.ImageRecordIter(
                path_imgrec=rec, path_imgidx=idx, batch_size=batch,
                data_shape=(3, size, size), shuffle=False, **mean))
        rate, _ = _timed_epoch(legacy, consume)
        out["pipeline_fed_legacy_imgs_per_sec"] = round(rate, 2)

        # new: uint8 NHWC + fused device tail, over the worker curve
        scaling = {}
        best, best_w, best_stats = 0.0, 0, None
        for w in workers_curve:
            def new_pipe(w=w):
                return mx.io.ImageRecordIter(
                    path_imgrec=rec, path_imgidx=idx, batch_size=batch,
                    data_shape=(3, size, size), shuffle=False,
                    layout="NHWC", device_tail=True, seed=0,
                    preprocess_threads=w, prefetch_buffer=2, **mean)
            rate, stats = _timed_epoch(new_pipe, consume)
            scaling[str(w)] = round(rate, 2)
            if rate > best:
                best, best_w, best_stats = rate, w, stats
        out["pipeline_worker_scaling"] = scaling
        out["pipeline_fed_imgs_per_sec"] = round(best, 2)
        out["pipeline_best_workers"] = best_w
        if out.get("pipeline_fed_legacy_imgs_per_sec"):
            out["pipeline_speedup_vs_legacy"] = round(
                best / out["pipeline_fed_legacy_imgs_per_sec"], 2)
        if best_stats:
            out["pipeline_stall_pct"] = best_stats["stall_pct"]
            out["pipeline_worker_utilization"] = \
                best_stats["worker_utilization"]
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
