"""A/B harness: Pallas conv3x3_epilogue vs XLA's conv lowering at the
ResNet-50 residual-block shapes, int8 and bf16.

The per-layer winners decide the lowering in
ops/quantization.quantized_conv (int8) and the fused-epilogue experiments
in docs/perf_resnet50_tpu.md (bf16) — reference precedent:
src/operator/quantization/quantized_conv.cu exists precisely because the
generic float path lost to implicit-GEMM int8 on the same shapes.

Usage: python tools/conv_ab.py [--batch 256] [--iters 20]
One JSON line per (stage, dtype, impl) as it goes.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

# ResNet-50 bottleneck 3x3 stages: (H, W, C) with Cin == Cout
STAGES = [(56, 56, 64), (28, 28, 128), (14, 14, 256), (7, 7, 512)]


def _time(fn, *args, iters=20):
    """Steady-state per-call time, fenced with ``block_until_ready``."""
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--dtypes", nargs="*", default=["int8", "bf16"])
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops.pallas_kernels import conv3x3_epilogue

    N = args.batch
    rng = np.random.RandomState(0)

    for (H, W, C) in STAGES:
        if "int8" in args.dtypes:
            x = jnp.asarray(rng.randint(-127, 128, (N, H, W, C)), jnp.int8)
            w = jnp.asarray(rng.randint(-16, 16, (3, 3, C, C)), jnp.int8)
            scale = jnp.asarray(rng.rand(C) * 0.01 + 1e-3, jnp.float32)
            shift = jnp.asarray(rng.randn(C), jnp.float32)

            @jax.jit
            def xla_int8(x, w, scale, shift):
                dn = lax.conv_dimension_numbers(
                    x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
                acc = lax.conv_general_dilated(
                    x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
                    preferred_element_type=jnp.int32)
                real = jnp.maximum(
                    acc.astype(jnp.float32) * scale + shift, 0.0)
                return jnp.clip(jnp.round(real), -127, 127).astype(jnp.int8)

            pallas_int8 = jax.jit(functools.partial(
                conv3x3_epilogue, relu=True))
            for name, fn in (("xla", xla_int8), ("pallas", pallas_int8)):
                try:
                    dt = _time(fn, x, w, scale, shift, iters=args.iters)
                    rec = {"stage": [H, W, C], "dtype": "int8", "impl": name,
                           "ms": round(dt * 1e3, 3),
                           "img_per_s": round(N / dt, 1)}
                except Exception as e:
                    rec = {"stage": [H, W, C], "dtype": "int8", "impl": name,
                           "error": str(e)[:200]}
                print(json.dumps(rec), flush=True)

        if "bf16" in args.dtypes:
            x = jnp.asarray(rng.randn(N, H, W, C), jnp.bfloat16)
            w = jnp.asarray(rng.randn(3, 3, C, C) * 0.05, jnp.bfloat16)
            scale = jnp.asarray(rng.rand(C) + 0.5, jnp.float32)
            shift = jnp.asarray(rng.randn(C), jnp.float32)

            @jax.jit
            def xla_bf16(x, w, scale, shift):
                dn = lax.conv_dimension_numbers(
                    x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
                acc = lax.conv_general_dilated(
                    x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
                    preferred_element_type=jnp.float32)
                return jnp.maximum(acc * scale + shift, 0.0) \
                    .astype(jnp.bfloat16)

            pallas_bf16 = jax.jit(functools.partial(
                conv3x3_epilogue, relu=True))
            for name, fn in (("xla", xla_bf16), ("pallas", pallas_bf16)):
                try:
                    dt = _time(fn, x, w, scale, shift, iters=args.iters)
                    rec = {"stage": [H, W, C], "dtype": "bf16", "impl": name,
                           "ms": round(dt * 1e3, 3),
                           "img_per_s": round(N / dt, 1)}
                except Exception as e:
                    rec = {"stage": [H, W, C], "dtype": "bf16", "impl": name,
                           "error": str(e)[:200]}
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
