#!/usr/bin/env python
"""Serve trained models over HTTP: multi-model fleet, SLO tiers, dynamic
batching, graceful degradation.

The deployment CLI the reference never shipped (its story stopped at
``HybridBlock.export``): load one or many Module checkpoints, stand them
behind fixed padded batch buckets (AOT-compiled at load so steady-state
traffic never recompiles), pack them against the modeled-HBM cap, coalesce
concurrent requests deadline-aware, answer on ``/predict`` with per-model
``/readyz``, ``/livez`` and ``/stats`` beside it, and drain gracefully on
SIGTERM/SIGINT.  See docs/serving.md.

    # single model (PR-2 form, still supported)
    python tools/serve.py --prefix model --epoch 3 --data-shape 64 \
        --buckets 1,4,16,64 --port 8080

    # a fleet: fp32 primary + int8 quantized variant as its
    # degraded-mode target (overflow the primary sheds reroutes there)
    python tools/serve.py --data-shape 3,224,224 \
        --model resnet=ckpt/resnet@3 \
        --model resnet_int8=ckpt/resnet@3:int8 \
        --fallback resnet=resnet_int8 --hbm-cap $((8 << 30))

    # an autoregressive decode model (paged KV cache, continuous
    # batching) from a resilience checkpoint directory, beside the
    # fixed-shape fleet
    python tools/serve.py --decode lm=ckpt/lm_decode@200 --port 8080

    curl -s -X POST localhost:8080/predict \
        -d '{"data": [[0.1, ...]], "model": "resnet", "tier": "silver",
             "deadline_ms": 50}'
    curl -s -X POST localhost:8080/decode \
        -d '{"prompt": [5, 12, 3], "model": "lm", "max_new_tokens": 16,
             "tier": "gold"}'
    curl -s localhost:8080/readyz; curl -s localhost:8080/stats
"""
from __future__ import annotations

import argparse
import os
import signal
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="multi-model SLO-tiered inference fleet "
                    "(mxnet_tpu.serving)")
    p.add_argument("--prefix", help="checkpoint prefix (Module."
                                    "save_checkpoint) — single-model form")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--demo", action="store_true",
                   help="serve a randomly initialized demo MLP instead of "
                        "a checkpoint")
    p.add_argument("--calib", default=None, metavar="PATH",
                   help="calibration set for :int8 models — a .npy "
                        "array of real example rows; routes "
                        "quantization through the PTQ pipeline "
                        "(serving.quantize) with the scales digest in "
                        "provenance.  Without it :int8 falls back to "
                        "the legacy synthetic-data naive path "
                        "(deprecated).")
    p.add_argument("--decode-kv-dtype", default=None,
                   choices=("f32", "int8"),
                   help="KV-cache dtype for --decode models (default: "
                        "the checkpoint's kv_dtype, else f32); int8 "
                        "stores quantized codes + per-page scales and "
                        "halves-plus the admission page bytes")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=PREFIX[@EPOCH][:int8]",
                   help="register a fleet model from a checkpoint; the "
                        ":int8 suffix quantizes it at load (naive "
                        "calibration over synthetic data — the cheap "
                        "degraded-mode variant).  Repeatable.")
    p.add_argument("--decode", action="append", default=[],
                   metavar="NAME=DIR[@STEP]",
                   help="register an autoregressive decode model from a "
                        "resilience checkpoint directory (payload: "
                        "transformer-LM config + MeshProgram params, the "
                        "format examples/serving/decode_demo.py saves); "
                        "@STEP picks a step, default the newest loadable "
                        "one.  Served on POST /decode.  Repeatable.")
    p.add_argument("--decode-slots", type=int, default=4,
                   help="decode batch width per --decode model — the "
                        "continuous-batching bound (one compile)")
    p.add_argument("--fallback", action="append", default=[],
                   metavar="NAME=VARIANT",
                   help="degraded mode: overflow NAME sheds (or refuses "
                        "with an open breaker) reroutes to VARIANT. "
                        "Repeatable.")
    p.add_argument("--canary", action="append", default=[],
                   metavar="NAME=PREFIX[@EPOCH]",
                   help="arm a deterministic canary traffic split on "
                        "fleet model NAME: the checkpoint at PREFIX[@"
                        "EPOCH] is loaded as NAME__canary and receives "
                        "the seeded hash slice of NAME's requests at "
                        "--canary-fraction.  Repeatable (one per model).")
    p.add_argument("--canary-fraction", type=float, default=0.05,
                   help="fraction of request-id hash space routed to "
                        "each --canary variant (a single pinned stage; "
                        "ramped schedules belong to tools/promote.py)")
    p.add_argument("--canary-seed", type=int, default=0,
                   help="hash seed for the canary traffic split")
    p.add_argument("--hbm-cap", type=int, default=None,
                   help="fleet modeled-HBM packing cap in bytes (SRV004; "
                        "default: MXTPU_SERVING_HBM_CAP, 0 disables)")
    p.add_argument("--data-name", default="data")
    p.add_argument("--data-shape", default=None,
                   help="per-example input shape, e.g. '64' or '3,224,224' "
                        "(required with --prefix/--model)")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--buckets", default="1,4,16,64",
                   help="padded batch buckets compiled at load")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--max-batch", type=int, default=None,
                   help="max requests coalesced per device call "
                        "(default: the largest bucket)")
    p.add_argument("--batch-timeout-ms", type=float, default=2.0,
                   help="how long the batcher waits to fill a batch after "
                        "the first request arrives")
    p.add_argument("--max-queue", type=int, default=256,
                   help="per-model admission queue depth; beyond it "
                        "requests get 429 (or evict a lower tier)")
    p.add_argument("--max-body-bytes", type=int, default=16 << 20,
                   help="largest POST body the handler will buffer; "
                        "beyond it requests get 413")
    p.add_argument("--stall-threshold-s", type=float, default=30.0,
                   help="a model whose in-flight batch exceeds this is "
                        "reported unready on /readyz")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip AOT bucket compilation (first requests pay "
                        "the compile)")
    p.add_argument("--verbose", action="store_true")
    return p.parse_args(argv)


def _shape(text):
    return tuple(int(d) for d in str(text).split(",") if d.strip())


def parse_model_spec(spec):
    """``NAME=PREFIX[@EPOCH][:int8]`` -> (name, prefix, epoch, int8)."""
    name, sep, rest = str(spec).partition("=")
    if not sep or not name or not rest:
        raise SystemExit("bad --model spec %r "
                         "(want NAME=PREFIX[@EPOCH][:int8])" % (spec,))
    int8 = rest.endswith(":int8")
    if int8:
        rest = rest[: -len(":int8")]
    prefix, sep, ep = rest.partition("@")
    try:
        epoch = int(ep) if sep else 0
    except ValueError:
        raise SystemExit("bad epoch in --model spec %r" % (spec,))
    if not prefix:
        raise SystemExit("empty checkpoint prefix in --model spec %r"
                         % (spec,))
    return name, prefix, epoch, int8


def _load_calib(path):
    """``--calib`` loader: a ``.npy`` array (or first array of a
    ``.npz``) of real example rows for PTQ activation calibration."""
    if path is None:
        return None
    import numpy as np
    data = np.load(path)
    if hasattr(data, "files"):    # npz: take the first array
        data = data[data.files[0]]
    arr = np.asarray(data, np.float32)
    if arr.ndim < 2 or arr.shape[0] < 1:
        raise SystemExit("--calib %r must hold a (n, ...) example array "
                         "with n >= 1, got shape %r" % (path, arr.shape))
    return arr


def parse_decode_spec(spec):
    """``NAME=DIR[@STEP]`` -> (name, directory, step or None)."""
    name, sep, rest = str(spec).partition("=")
    if not sep or not name or not rest:
        raise SystemExit("bad --decode spec %r (want NAME=DIR[@STEP])"
                         % (spec,))
    directory, sep, st = rest.partition("@")
    try:
        step = int(st) if sep else None
    except ValueError:
        raise SystemExit("bad step in --decode spec %r" % (spec,))
    if not directory:
        raise SystemExit("empty checkpoint dir in --decode spec %r"
                         % (spec,))
    return name, directory, step


def _load_decode_runner(directory, step, slots, warmup=True,
                        kv_dtype=None):
    """Build a :class:`DecodeRunner` from a resilience checkpoint whose
    payload carries ``{"kind": "transformer_lm_decode", "config":
    cfg.describe(), "params": {name: array}, "page_size": N}`` — the
    format ``examples/serving/decode_demo.py`` saves.  Provenance (the
    digest /healthz surfaces) rides along from the checkpoint record."""
    from mxnet_tpu.resilience.checkpoint import (list_checkpoints,
                                                 load_checkpoint,
                                                 provenance)
    from mxnet_tpu.serving.decode import DecodeRunner
    from mxnet_tpu.transformer import TransformerLMConfig
    from mxnet_tpu.transformer.decode import DecodeProgram

    entries = dict(list_checkpoints(directory))
    if not entries:
        raise SystemExit("no checkpoints under %r" % (directory,))
    if step is None:
        step = max(entries)
    if step not in entries:
        raise SystemExit("no step-%d checkpoint under %r (have %s)"
                         % (step, directory, sorted(entries)))
    rec = load_checkpoint(entries[step])
    payload = rec["payload"]
    if not isinstance(payload, dict) or \
            payload.get("kind") != "transformer_lm_decode":
        raise SystemExit(
            "checkpoint %r is not a transformer_lm_decode payload "
            "(got kind=%r)" % (entries[step],
                               payload.get("kind")
                               if isinstance(payload, dict) else None))
    cfg = TransformerLMConfig(**payload["config"])
    prog = DecodeProgram(cfg, page_size=int(payload.get("page_size", 8)),
                         kv_dtype=kv_dtype or payload.get("kv_dtype"))
    return DecodeRunner(prog, payload["params"], slots=slots,
                        warmup=warmup, provenance=provenance(rec))


def _load_module(prefix, epoch, data_name, example_shape, buckets,
                 int8=False, calib=None):
    """Load a Module checkpoint bound for bucketed inference.  With
    ``int8`` + ``calib`` (a real example array from ``--calib``), the
    quantization routes through the PTQ pipeline — activation ranges
    measured over the real set, the scales digest returned for
    provenance.  ``int8`` WITHOUT a calibration set keeps the legacy
    naive-over-synthetic numerics but is deprecated: synthetic ranges
    bound nothing about production activations.  Returns
    ``(module, quant_report_or_None)``."""
    import numpy as np

    import mxnet_tpu as mx

    sym, arg, aux = mx.model.load_checkpoint(prefix, epoch)
    max_b = max(buckets)
    report = None
    if int8:
        from mxnet_tpu.serving.quantize import ptq_quantize_module
        if calib is not None:
            calib = np.asarray(calib, np.float32)
            n = (len(calib) // max_b) * max_b or len(calib)
            calib_it = mx.io.NDArrayIter(
                calib[:n], np.zeros(len(calib[:n]), np.float32),
                min(max_b, n))
            sym, arg, aux, report = ptq_quantize_module(
                sym, arg, aux, calib_it, data_names=(data_name,),
                num_calib_examples=n)
        else:
            import warnings
            warnings.warn(
                ":int8 without --calib quantizes against SYNTHETIC "
                "activation ranges — pass --calib with real example "
                "rows to route through the PTQ pipeline",
                DeprecationWarning, stacklevel=2)
            calib_batch = min(max_b, 32)
            rng = np.random.RandomState(0)
            calib_it = mx.io.NDArrayIter(
                rng.rand(calib_batch, *example_shape).astype(np.float32),
                np.zeros(calib_batch, np.float32), calib_batch)
            sym, arg, aux = mx.contrib.quantization.quantize_model(
                sym, arg, aux, data_names=(data_name,),
                calib_data=calib_it, num_calib_examples=calib_batch,
                calib_mode="naive")
    # label slots (…_label by convention) are bound with a batch-matched
    # dummy feed; everything else non-data is a parameter
    label_names = [n for n in sym.list_arguments() if n.endswith("_label")]
    mod = mx.mod.Module(sym, data_names=(data_name,),
                        label_names=label_names)
    mod.bind(
        data_shapes=[(data_name, (max_b,) + tuple(example_shape))],
        label_shapes=[(n, (max_b,)) for n in label_names] or None,
        for_training=False)
    mod.set_params(arg, aux)
    return mod, report


def build_module_runner(args):
    from mxnet_tpu.serving import ModelRunner

    if not args.data_shape:
        raise SystemExit("--data-shape is required with --prefix")
    example_shape = _shape(args.data_shape)
    buckets = _shape(args.buckets)
    mod, _ = _load_module(args.prefix, args.epoch, args.data_name,
                          example_shape, buckets)
    return ModelRunner(mod, buckets=buckets, dtype=args.dtype,
                       warmup=not args.no_warmup)


def build_demo_runner(args):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.serving import ModelRunner

    feat = _shape(args.data_shape) if args.data_shape else (32,)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"))
    net.add(gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return ModelRunner(net, buckets=_shape(args.buckets),
                       example_shape=feat, dtype=args.dtype,
                       warmup=not args.no_warmup)


def build_fleet(args):
    """Fleet form: every ``--model`` becomes a registered runner (int8
    variants quantized at load), ``--fallback`` wires degraded-mode
    routes, and registration enforces the modeled-HBM packing cap
    (SRV004) before any traffic arrives."""
    from mxnet_tpu.serving import ModelFleet, ModelRunner

    if args.model and not args.data_shape:
        raise SystemExit("--data-shape is required with --model")
    example_shape = _shape(args.data_shape) if args.data_shape else None
    buckets = _shape(args.buckets)
    fallbacks = {}
    for spec in args.fallback:
        name, sep, variant = str(spec).partition("=")
        if not sep or not name or not variant:
            raise SystemExit("bad --fallback spec %r (want NAME=VARIANT)"
                             % (spec,))
        fallbacks[name] = variant
    fleet = ModelFleet(hbm_cap_bytes=args.hbm_cap,
                       stall_threshold_s=args.stall_threshold_s,
                       batch_timeout_ms=args.batch_timeout_ms,
                       max_queue=args.max_queue)
    names = []
    calib = _load_calib(args.calib)
    for spec in args.model:
        name, prefix, epoch, int8 = parse_model_spec(spec)
        mod, report = _load_module(prefix, epoch, args.data_name,
                                   example_shape, buckets, int8=int8,
                                   calib=calib)
        runner = ModelRunner(
            mod, buckets=buckets, dtype=args.dtype,
            warmup=not args.no_warmup,
            provenance={"quant_digest": report["digest"],
                        "quant": report["kind"]} if report else None)
        fleet.register(name, runner, fallback=fallbacks.get(name),
                       max_batch=args.max_batch)
        names.append(name)
    unknown = {v for v in fallbacks.values() if v not in names}
    missing = {k for k in fallbacks if k not in names}
    if unknown or missing:
        raise SystemExit("--fallback names unregistered models: %s"
                         % sorted(unknown | missing))
    # canary variants ride the same --model parsing (NAME=PREFIX[@EPOCH],
    # :int8 allowed): each loads as NAME__canary and splits NAME's
    # traffic by the seeded request-id hash — legacy flags untouched
    for spec in args.canary:
        name, prefix, epoch, int8 = parse_model_spec(spec)
        if name not in names:
            raise SystemExit("--canary names unregistered model %r "
                             "(give --model %s=... too)" % (name, name))
        mod, report = _load_module(prefix, epoch, args.data_name,
                                   example_shape, buckets, int8=int8,
                                   calib=calib)
        runner = ModelRunner(
            mod, buckets=buckets, dtype=args.dtype,
            warmup=not args.no_warmup,
            provenance={"quant_digest": report["digest"],
                        "quant": report["kind"]} if report else None)
        canary_name = name + "__canary"
        fleet.register(canary_name, runner, max_batch=args.max_batch)
        fleet.set_canary(name, canary_name,
                         schedule=(args.canary_fraction,),
                         seed=args.canary_seed)
    # decode models: the autoregressive tier beside the fixed-shape
    # ones — same SRV004 packing ledger (priced by pages), routed on
    # POST /decode, never a fallback target (live page tables pin one
    # runner's cache pool)
    for spec in args.decode:
        name, directory, step = parse_decode_spec(spec)
        if name in names:
            raise SystemExit("--decode name %r collides with a --model "
                             "registration" % name)
        runner = _load_decode_runner(directory, step, args.decode_slots,
                                     warmup=not args.no_warmup,
                                     kv_dtype=args.decode_kv_dtype)
        fleet.register_decode(name, runner, max_queue=args.max_queue)
        names.append(name)
    return fleet


def main(argv=None):
    args = parse_args(argv)
    if not args.demo and not args.prefix and not args.model \
            and not args.decode:
        raise SystemExit("give --model/--decode specs (a fleet), "
                         "--prefix (a checkpoint) or --demo")

    from mxnet_tpu.base import use_compilation_cache
    from mxnet_tpu.serving import Server
    use_compilation_cache()
    if args.model or args.decode:
        target = build_fleet(args)
        summary = "fleet %s" % target.models()
    else:
        target = build_demo_runner(args) if args.demo \
            else build_module_runner(args)
        summary = repr(target)
    server = Server(target, host=args.host, port=args.port,
                    max_batch=args.max_batch,
                    batch_timeout_ms=args.batch_timeout_ms,
                    max_queue=args.max_queue,
                    max_body_bytes=args.max_body_bytes,
                    verbose=args.verbose)
    host, port = server.address
    print("serving %s on http://%s:%d  (buckets=%s, ready=%s)"
          % (summary, host, port, args.buckets, server.ready),
          flush=True)

    def _graceful(signum, frame):
        print("draining (%s)..." % signal.Signals(signum).name, flush=True)
        server.drain()
        print("drained; bye", flush=True)
        sys.exit(0)

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    server.serve_forever()


if __name__ == "__main__":
    main()
