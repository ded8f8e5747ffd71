#!/usr/bin/env python
"""Collective-bandwidth measurement (reference: tools/bandwidth/ — measures
kvstore push/pull throughput).  Here: psum / all_gather / ppermute over the
device mesh, the primitives every layer of the stack rides on."""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec


def bench(fn, x, iters=10):
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=float, default=64)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    devs = np.array(jax.devices())
    n = len(devs)
    mesh = Mesh(devs, ("x",))
    elems = int(args.size_mb * 1e6 / 4)
    elems = (elems // (n * 128)) * n * 128
    x = jnp.ones((elems,), jnp.float32)
    nbytes = elems * 4
    print("%d devices (%s), buffer %.1f MB" % (n, jax.default_backend(),
                                               nbytes / 1e6))

    spec = PartitionSpec("x")
    psum = jax.jit(shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                             in_specs=spec, out_specs=spec))
    t = bench(psum, x, args.iters)
    # ring allreduce moves 2*(n-1)/n of the buffer per chip
    algo_bytes = 2 * (n - 1) / n * nbytes
    print("psum        %8.2f ms   %8.2f GB/s (algo)" %
          (t * 1e3, algo_bytes / t / 1e9))

    ag = jax.jit(shard_map(lambda v: jax.lax.all_gather(v, "x"), mesh=mesh,
                           in_specs=spec, out_specs=PartitionSpec("x", None)))
    t = bench(ag, x, args.iters)
    print("all_gather  %8.2f ms   %8.2f GB/s (algo)" %
          (t * 1e3, (n - 1) / n * nbytes / t / 1e9))

    perm = [(i, (i + 1) % n) for i in range(n)]
    pp = jax.jit(shard_map(lambda v: jax.lax.ppermute(v, "x", perm),
                           mesh=mesh, in_specs=spec, out_specs=spec))
    t = bench(pp, x, args.iters)
    print("ppermute    %8.2f ms   %8.2f GB/s" %
          (t * 1e3, nbytes / n / t / 1e9))


if __name__ == "__main__":
    main()
