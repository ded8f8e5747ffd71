#!/usr/bin/env python
"""Bench-lineage regression gate: the BENCH_r*.json history, gated.

Every bench round the driver archives a ``BENCH_r<N>.json`` record
(``{"n", "cmd", "rc", "tail", "parsed"}``); until now that lineage was
an unread archive.  This tool makes it a gate:

- **well-formedness**: every file must be a JSON object with the record
  keys; ``parsed`` is either null (a round that died before emitting —
  BENCH_r03/r04) or the bench's tail-line record.  A malformed file
  exits 1.
- **regression gate**: for each gated metric, the *newest live* value is
  compared against the *best prior live* value with a declared
  tolerance.  "Live" honors the bench's own staleness protocol
  (``bench.py``): a key listed in ``stale_keys`` — or the primary
  ``value`` under ``stale: true`` — is a carry-forward, not a
  measurement, and neither sets the bar nor gets gated.  A regression
  beyond tolerance exits 2 and names the metric.

Gated metrics (direction, tolerance)::

    value (resnet50 img/s/chip)        higher, 10% relative
    pipeline_fed_imgs_per_sec          higher, 10% relative
    pipeline_iter_imgs_per_sec         higher, 10% relative
    serving_reqs_per_sec               higher, 10% relative
    serving_fleet_reqs_per_sec         higher, 10% relative
    train_loop_overlap_ratio           higher, 10% relative
    int8_infer_imgs_per_sec            higher, 10% relative
    bf16_infer_imgs_per_sec            higher, 10% relative
    checkpoint_overhead_pct            lower, +2.0 absolute slack
    modeled_zero1_hbm_drop_pct         higher, 2% relative (modeled:
                                       deterministic, so near-zero slack)
    modeled_ring_attn_collective_bytes lower, 2% relative (growing ring
                                       traffic is the regression)
    simulator_accuracy_pct             higher, 10% relative (fleet-sim
                                       fidelity vs the real host bench)
    promotion_decision_ms              lower, +25 abs slack (decision
                                       tick on a noisy 1-core host)
    capacity_replicas_for_1m_dau       lower, 10% relative (pinned
                                       deterministic capacity answer)
    zero1_modeled_hbm_drop_pct         higher, 2% relative (runtime-tape
                                       ZeRO-1 memory win; deterministic)
    reshard_restore_ms                 lower, +150 abs slack (resize-on-
                                       resume restore, noisy 1-core host)
    supervisor_failover_steps_lost     lower, zero slack (checkpoint-
                                       every-step failover must lose 0)
    tp_modeled_model_axis_bytes        lower, 2% relative (modeled
                                       tensor-parallel wire bytes; up
                                       is the regression)
    seqpar_tokens_per_sec_host         higher, 10% relative (2x2x2 mesh
                                       train loop on the virtual host
                                       mesh)
    tp_numerics_ok                     higher, zero slack (mesh losses
                                       must equal the replicated
                                       baseline: 1.0 or regression)
    pp_modeled_bubble_frac             lower, 2% relative (modeled 1F1B
                                       bubble (K-1)/(K-1+M); up is the
                                       regression)
    pp_modeled_pipe_axis_bytes         lower, 2% relative (modeled
                                       stage-boundary wire bytes)
    pp_tokens_per_sec_host             higher, 10% relative (pipe=2 x
                                       model=2 x data=2 train loop on
                                       the virtual host mesh)
    pp_numerics_ok                     higher, zero slack (pipelined
                                       losses must equal the replicated
                                       baseline: 1.0 or regression)
    fused_optimizer_speedup_host       higher, 10% relative (measured
                                       unfused vs fused update on the
                                       1-core host, >= 1.2x expected)
    modeled_fusion_bytes_saved_pct     higher, 2% relative (modeled:
                                       deterministic fusion win of the
                                       optimizer chain)
    fusion_numerics_ok                 higher, zero slack (fused must
                                       equal unfused Optimizer.update:
                                       1.0 or regression)
    codegen_generated_speedup_host     higher, 10% relative (measured
                                       op-at-a-time unfused chain vs
                                       the mxgen generated kernel)
    codegen_modeled_bytes_saved_pct    higher, 2% relative (modeled:
                                       deterministic byte win of the
                                       shipped generated chains)
    codegen_numerics_ok                higher, zero slack (generated
                                       kernel must equal the tape
                                       reference: 1.0 or regression)
    decode_tokens_per_sec_host         higher, 10% relative (continuous
                                       batching through the paged KV
                                       cache on the 1-core host)
    decode_numerics_ok                 higher, zero slack (cached decode
                                       must equal the no-cache full-
                                       forward reference: 1.0 or
                                       regression)
    decode_recompiles                  lower, zero slack (steady-state
                                       decode traffic must never grow
                                       the jit cache)
    fused_loss_scaled_speedup_host     higher, 10% relative (measured
                                       unscale+clip+update chain vs the
                                       one-pass fused kernel)
    bf16_modeled_hbm_ratio             lower, +0.02 abs slack (modeled
                                       bf16/f32 peak-HBM ratio from the
                                       budget builder)
    bf16_convergence_delta             lower, +0.005 abs slack (bf16 vs
                                       f32 loss-trajectory gap)
    int8_kv_decode_tokens_per_sec_host higher, 10% relative (greedy
                                       decode over the int8 KV cache)
    precision_numerics_ok              higher, zero slack (fused/skip/
                                       int8-token contracts)
    decode_pages_leaked                lower, zero slack (every retired
                                       sequence returns its KV pages)

A metric with fewer than two live occurrences has no prior bar and
passes vacuously (the r01–r05 lineage: ``value`` is live in r01+r02,
the pipeline keys only in r02, everything in r05 is a carry-forward).

Usage::

    python tools/bench_compare.py --check BENCH_r0*.json
    python tools/bench_compare.py --json BENCH_r0*.json NEW_RECORD.json

Stdlib-only (CI and postmortem hosts need no jax); importable — tests
call :func:`compare` directly.  Exit codes: 0 ok, 1 malformed, 2
regression.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

# metric -> (direction, tolerance).  "higher": newest >= best * (1 - tol)
# (relative).  "lower_abs": newest <= best + tol (absolute slack — the
# overhead percentages live near zero, where relative tolerance is
# meaningless).  "lower_rel": newest <= best * (1 + tol) (relative, for
# byte counts where down is good and zero is unreachable).
GATES = {
    "value": ("higher", 0.10),
    "pipeline_fed_imgs_per_sec": ("higher", 0.10),
    "pipeline_iter_imgs_per_sec": ("higher", 0.10),
    "serving_reqs_per_sec": ("higher", 0.10),
    "serving_fleet_reqs_per_sec": ("higher", 0.10),
    "train_loop_overlap_ratio": ("higher", 0.10),
    "int8_infer_imgs_per_sec": ("higher", 0.10),
    "bf16_infer_imgs_per_sec": ("higher", 0.10),
    "checkpoint_overhead_pct": ("lower_abs", 2.0),
    # modeled (hardware-free) numbers from the static_cost stage: fully
    # deterministic, so the slack is only there for intentional
    # regenerations a PR ships alongside (r06 onward — no prior bar in
    # the r01-r05 lineage, so they gate vacuously until then)
    "modeled_zero1_hbm_drop_pct": ("higher", 0.02),
    "modeled_ring_attn_collective_bytes": ("lower_rel", 0.02),
    # mlops stage (r06 onward): simulator fidelity must not rot (the
    # documented tolerance is error <= 15%, i.e. accuracy >= 85 — the
    # gate holds the best achieved level within 10%); the decision tick
    # is timing on a noisy 1-core host, so absolute slack; the capacity
    # answer is a pinned deterministic computation — more replicas for
    # the same pinned scenario is a policy/model regression (10% rel
    # covers intentional scenario retunes shipped with their PR)
    "simulator_accuracy_pct": ("higher", 0.10),
    "promotion_decision_ms": ("lower_abs", 25.0),
    "capacity_replicas_for_1m_dau": ("lower_rel", 0.10),
    # elastic stage (r06 onward): the RUNTIME-tape ZeRO-1 memory win is
    # deterministic (2% covers intentional model retunes shipped with
    # their PR); the resize-restore path is wall time on a noisy 1-core
    # host (absolute slack); steps lost at checkpoint-every-step cadence
    # is a pure policy computation — any loss is a regression, zero
    # slack
    "zero1_modeled_hbm_drop_pct": ("higher", 0.02),
    "reshard_restore_ms": ("lower_abs", 150.0),
    "supervisor_failover_steps_lost": ("lower_abs", 0.0),
    # transformer mesh-tier stage (r06 onward): the fixture's modeled
    # tensor-parallel wire bytes are deterministic (growing model-axis
    # traffic is the regression; 2% covers intentional geometry retunes
    # shipped with their PR); tokens/sec is wall time on the noisy
    # 1-core host (10% rel); the mesh-vs-replicated loss parity is a
    # hard contract — any drop from 1.0 is a numerics regression, zero
    # slack
    "tp_modeled_model_axis_bytes": ("lower_rel", 0.02),
    "seqpar_tokens_per_sec_host": ("higher", 0.10),
    "tp_numerics_ok": ("higher", 0.0),
    # pipeline-parallel stage: the modeled 1F1B bubble fraction and
    # pipe-axis wire bytes are deterministic (2% covers intentional
    # schedule-geometry retunes shipped with their PR); tokens/sec is
    # wall time on the noisy 1-core host (10% rel); the pipelined-vs-
    # replicated loss parity is a hard contract — any drop from 1.0 is
    # a numerics regression, zero slack
    "pp_modeled_bubble_frac": ("lower_rel", 0.02),
    "pp_modeled_pipe_axis_bytes": ("lower_rel", 0.02),
    "pp_tokens_per_sec_host": ("higher", 0.10),
    "pp_numerics_ok": ("higher", 0.0),
    # fusion stage (r06 onward): the measured fused-vs-unfused optimizer
    # update speedup on the 1-core host (10% rel — wall time on a noisy
    # host); the modeled bytes-saved of the optimizer chain is
    # deterministic (2% covers intentional geometry retunes shipped
    # with their PR); fused-vs-unfused numerics is a hard contract —
    # any drop from 1.0 is a kernel regression, zero slack
    "fused_optimizer_speedup_host": ("higher", 0.10),
    "modeled_fusion_bytes_saved_pct": ("higher", 0.02),
    "fusion_numerics_ok": ("higher", 0.0),
    # codegen stage (r09 onward): the measured unfused-chain vs
    # generated-kernel speedup on the 1-core host (10% rel — wall time
    # on a noisy host); the mxgen lowering's modeled bytes-saved is
    # deterministic (2% covers intentional chain retunes shipped with
    # their PR, in lockstep with the codegen_chains budget rows); the
    # generated-vs-tape-reference numerics contract is hard — any drop
    # from 1.0 is a mislowering, zero slack
    "codegen_generated_speedup_host": ("higher", 0.10),
    "codegen_modeled_bytes_saved_pct": ("higher", 0.02),
    "codegen_numerics_ok": ("higher", 0.0),
    # decode stage (r07 onward): continuous-batching token throughput is
    # wall time on the noisy 1-core host (10% rel); the cached-vs-full-
    # forward numerics contract and the zero-recompile/zero-page-leak
    # contracts are hard — any drop from 1.0 / rise from 0 is a serving
    # regression, zero slack
    "decode_tokens_per_sec_host": ("higher", 0.10),
    "decode_numerics_ok": ("higher", 0.0),
    "decode_recompiles": ("lower_abs", 0.0),
    "decode_pages_leaked": ("lower_abs", 0.0),
    # precision stage (r08 onward): the fused loss-scaled update
    # speedup and int8-KV decode throughput are wall time on the noisy
    # 1-core host (10% rel); the modeled bf16/f32 peak-HBM ratio is
    # deterministic (absolute slack covers intentional geometry retunes
    # shipped with their PR); the bf16-vs-f32 convergence delta and the
    # fused/skip/int8-token numerics contract are hard — a growing
    # trajectory gap or any drop from 1.0 is a precision regression
    "fused_loss_scaled_speedup_host": ("higher", 0.10),
    "bf16_modeled_hbm_ratio": ("lower_abs", 0.02),
    "bf16_convergence_delta": ("lower_abs", 0.005),
    "int8_kv_decode_tokens_per_sec_host": ("higher", 0.10),
    "precision_numerics_ok": ("higher", 0.0),
}

_RECORD_KEYS = ("n", "cmd", "rc", "parsed")
_ROUND_RE = re.compile(r"BENCH_r0*(\d+)", re.I)


class MalformedRecord(ValueError):
    """A lineage file that is not a bench record."""


def load_record(path):
    """Load + validate one BENCH_r*.json -> (round_number, record).
    Raises :class:`MalformedRecord` on anything that is not a bench
    record (unparseable JSON, wrong shape, non-dict non-null parsed)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except OSError as e:
        raise MalformedRecord("%s: unreadable (%s)" % (path, e))
    except ValueError as e:
        raise MalformedRecord("%s: not JSON (%s)" % (path, e))
    if not isinstance(rec, dict):
        raise MalformedRecord("%s: top level is %s, not an object"
                              % (path, type(rec).__name__))
    missing = [k for k in _RECORD_KEYS if k not in rec]
    if missing:
        raise MalformedRecord("%s: missing record key(s) %s"
                              % (path, ", ".join(missing)))
    parsed = rec["parsed"]
    if parsed is not None and not isinstance(parsed, dict):
        raise MalformedRecord("%s: parsed is %s, not an object/null"
                              % (path, type(parsed).__name__))
    m = _ROUND_RE.search(os.path.basename(path))
    rnd = int(m.group(1)) if m else int(rec.get("n") or 0)
    return rnd, rec


def live_values(parsed, gates=None):
    """The gated metrics measured LIVE in one round's record — the
    bench's staleness protocol applied: ``stale_keys`` entries (and the
    primary ``value`` under ``stale: true``) are carry-forwards."""
    gates = gates or GATES
    if not isinstance(parsed, dict):
        return {}
    stale_keys = set(parsed.get("stale_keys") or [])
    out = {}
    for key in gates:
        if key not in parsed or key in stale_keys:
            continue
        if key == "value" and parsed.get("stale"):
            continue
        v = parsed[key]
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[key] = float(v)
    return out


def compare(paths, gates=None, tolerance_scale=1.0):
    """Gate the lineage.  Returns a report dict:

    ``rounds``: [(round, file, live-metric dict)] ascending;
    ``gates``: per metric — newest live value/round, best prior live
    value/round, the allowed bar, and the verdict;
    ``regressions``: gated metrics whose newest live value fell past
    tolerance;
    ``malformed``: [(file, error)] (well-formedness failures).
    """
    gates = gates or GATES
    rounds, malformed = [], []
    for path in paths:
        try:
            rnd, rec = load_record(path)
        except MalformedRecord as e:
            malformed.append((path, str(e)))
            continue
        rounds.append((rnd, os.path.basename(path),
                       live_values(rec["parsed"], gates)))
    rounds.sort(key=lambda r: r[0])
    report = {"rounds": [(r, f, vals) for r, f, vals in rounds],
              "gates": {}, "regressions": [], "malformed": malformed}
    for key, (direction, tol) in sorted(gates.items()):
        tol = tol * float(tolerance_scale)
        history = [(rnd, fname, vals[key]) for rnd, fname, vals in rounds
                   if key in vals]
        if not history:
            continue
        newest_rnd, newest_file, newest = history[-1]
        prior = history[:-1]
        entry = {"newest": newest, "newest_round": newest_rnd,
                 "direction": direction, "tolerance": tol,
                 "live_rounds": [r for r, _, _ in history]}
        if not prior:
            entry["verdict"] = "no-prior"
            report["gates"][key] = entry
            continue
        if direction == "higher":
            best_rnd, _, best = max(prior, key=lambda h: h[2])
            allowed = best * (1.0 - tol)
            ok = newest >= allowed
        elif direction == "lower_rel":
            best_rnd, _, best = min(prior, key=lambda h: h[2])
            allowed = best * (1.0 + tol)
            ok = newest <= allowed
        else:  # lower_abs
            best_rnd, _, best = min(prior, key=lambda h: h[2])
            allowed = best + tol
            ok = newest <= allowed
        entry.update(best_prior=best, best_prior_round=best_rnd,
                     allowed=round(allowed, 6),
                     verdict="ok" if ok else "regression")
        report["gates"][key] = entry
        if not ok:
            report["regressions"].append(key)
    return report


def render(report):
    lines = []
    for path, err in report["malformed"]:
        lines.append("MALFORMED %s" % err)
    for key, g in sorted(report["gates"].items()):
        if g["verdict"] == "no-prior":
            lines.append("  ----    %-32s %12.4g (r%02d) — first live "
                         "value, no prior bar"
                         % (key, g["newest"], g["newest_round"]))
            continue
        tag = "  OK  " if g["verdict"] == "ok" else "REGRESSION"
        cmp_ch = ">=" if g["direction"] == "higher" else "<="
        lines.append("%s  %-32s %12.4g (r%02d) %s %.4g "
                     "(best prior %.4g @ r%02d, tol %s)"
                     % (tag, key, g["newest"], g["newest_round"], cmp_ch,
                        g["allowed"], g["best_prior"],
                        g["best_prior_round"],
                        ("%.0f%%" % (100 * g["tolerance"])
                         if g["direction"] in ("higher", "lower_rel")
                         else "+%.2g abs" % g["tolerance"])))
    if report["regressions"]:
        lines.append("REGRESSION in: %s"
                     % ", ".join(sorted(report["regressions"])))
    elif not report["malformed"]:
        lines.append("bench lineage ok (%d round(s), %d gated metric(s) "
                     "with live values)"
                     % (len(report["rounds"]), len(report["gates"])))
    return "\n".join(lines) + "\n"


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="gate bench metrics against the best prior live "
                    "value in the BENCH_r*.json lineage")
    parser.add_argument("files", nargs="+",
                        help="BENCH_r*.json records, any order")
    parser.add_argument("--check", action="store_true",
                        help="explicit CI spelling (validation + gates "
                             "run either way)")
    parser.add_argument("--tolerance-scale", type=float, default=1.0,
                        help="scale every gate's tolerance (e.g. 2.0 "
                             "doubles the slack)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable report")
    args = parser.parse_args(argv)
    report = compare(args.files, tolerance_scale=args.tolerance_scale)
    if args.as_json:
        json.dump(report, sys.stdout, indent=1, default=str)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(render(report))
    if report["malformed"]:
        return 1
    if report["regressions"]:
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
