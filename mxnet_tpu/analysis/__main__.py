"""CLI: ``python -m mxnet_tpu.analysis [target] [options]``.

Targets:
  ``--self-check``        registry lint over the live registry (CI tier-1)
                          + docs sync + cost-pass determinism
  ``--coverage``          regenerate tests/OP_COVERAGE.md from the registry
                          + test map; fails if any op has zero coverage
  ``--cost``              static cost/memory analysis (hardware-free):
                          over a symbol target, over ``--model`` budget
                          models, or — with ``--budget FILE`` — the
                          STATIC_BUDGETS.json CI gate (COST001/COST002)
                          including each trainer model's DST lint
  ``--race``              mxrace concurrency lint (docs/concurrency.md):
                          over a ``script.py`` target, or — bare — the
                          whole-repo sweep of the threaded host tiers
                          plus the lock-order/hierarchy sync; adds the
                          ``race`` section to ``--json`` (schema 5)
  ``script.py``           AST source lint for trace-time traps
  ``symbol.json``         graph lint a saved Symbol (``Symbol.save``)

Options:
  ``--json``              machine-readable output (schema in docs/analysis.md;
                          ``schema_version`` 2 added cost/dist sections,
                          3 adds the ``--shard`` shard section)
  ``--shard``             with --cost: mxshard sharding propagation —
                          collective schedules (explicit + inferred),
                          forced reshards, the ZeRO-1 memory proof
  ``--strict``            exit 1 on warnings (default for --self-check)
  ``--disable R1,R2``     mute rules globally
  ``--shapes "data=(1,3,224,224),label=(1,)"``
                          argument shapes for the graph pass (enables the
                          large-constant trace check) and the cost pass
  ``--serving``           with a symbol target: also run the SRV rules
                          (recompile-free bucket serving; --shapes feeds
                          the batch-polymorphism probe)
  ``--hbm-cap BYTES``     with --serving: SRV003 cap on per-bucket
                          modeled peak HBM
  ``--model M1,M2``       with --cost: budget models to analyze
                          (default: every non-heavy registered model)
  ``--codegen``           with --cost: print the mxgen lowered plan per
                          shipped fusion chain (generated kernel name,
                          byte contract, emitted Pallas body); adds the
                          ``codegen`` section to ``--json`` (schema 6)
  ``--budget FILE``       with --cost: gate modeled metrics against the
                          checked-in budgets (exit 2 on COST001/DST001)
"""
from __future__ import annotations

import argparse
import ast
import sys


def _parse_shapes(text):
    if not text:
        return None
    out = {}
    # "name=(1,2),other=(3,)" — split on commas not inside parens
    depth, start, parts = 0, 0, []
    for i, ch in enumerate(text):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(text[start:i])
            start = i + 1
    parts.append(text[start:])
    for part in parts:
        if not part.strip():
            continue
        name, _, val = part.partition("=")
        out[name.strip()] = tuple(ast.literal_eval(val.strip()))
    return out


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.analysis",
        description="mxlint: static graph/registry linter for mxnet_tpu")
    p.add_argument("target", nargs="?",
                   help="a .py script (source lint) or .json symbol "
                        "(graph lint)")
    p.add_argument("--self-check", action="store_true",
                   help="registry lint over the live registry")
    p.add_argument("--coverage", action="store_true",
                   help="regenerate tests/OP_COVERAGE.md and fail on "
                        "uncovered ops")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--strict", action="store_true",
                   help="exit 1 on warnings too")
    p.add_argument("--disable", default="",
                   help="comma-separated rule ids to mute")
    p.add_argument("--shapes", default="",
                   help="arg shapes for graph lint, e.g. "
                        "\"data=(1,3,224,224)\"")
    p.add_argument("--no-consts", action="store_true",
                   help="skip the trace-based large-constant check")
    p.add_argument("--serving", action="store_true",
                   help="with a .json symbol target: also run the SRV "
                        "serving rules (recompile-free bucket execution; "
                        "needs --shapes for the batch-polymorphism probe)")
    p.add_argument("--cost", action="store_true",
                   help="static cost/memory analysis: of the symbol "
                        "target, of --model budget models, or the "
                        "--budget gate")
    p.add_argument("--budget", default="",
                   help="with --cost: STATIC_BUDGETS.json path to gate "
                        "modeled metrics against (COST001 on regression)")
    p.add_argument("--model", default="",
                   help="with --cost: comma-separated budget-model names "
                        "(see analysis/budget_models.py)")
    p.add_argument("--shard", action="store_true",
                   help="with --cost: run the mxshard sharding-"
                        "propagation pass — collective schedules, "
                        "reshards and the ZeRO-1 memory proof for the "
                        "shard-aware budget models; adds the 'shard' "
                        "section to --json (schema_version 3)")
    p.add_argument("--fusion", action="store_true",
                   help="with --cost: run the mxfuse fusion-candidate "
                        "pass — fusable chains ranked by modeled "
                        "bytes-saved-if-fused over the budget models' "
                        "unfused spellings (docs/fusion.md); adds the "
                        "'fusion' section to --json (schema_version 4)")
    p.add_argument("--codegen", action="store_true",
                   help="with --cost: print the mxgen lowered plan per "
                        "shipped fusion chain — generated kernel name, "
                        "provable-lowering status, byte contract and the "
                        "emitted Pallas body (docs/fusion.md \"Generated "
                        "kernels\"); adds the 'codegen' section to "
                        "--json (schema_version 6)")
    p.add_argument("--race", action="store_true",
                   help="mxrace concurrency lint: of a .py target, or "
                        "(bare) the whole-repo sweep over the threaded "
                        "host tiers — lock-guard inference, lock-order/"
                        "hierarchy sync, blocking-under-lock, thread "
                        "lifecycle, callback discipline "
                        "(docs/concurrency.md); adds the 'race' section "
                        "to --json (schema_version 5)")
    p.add_argument("--hbm-cap", type=int, default=0, dest="hbm_cap",
                   help="with --serving: flag buckets whose modeled peak "
                        "HBM exceeds this many bytes (SRV003)")
    args = p.parse_args(argv)

    from . import (self_check, lint_file, lint_symbol, lint_serving,
                   generate_coverage_md, render_text, render_json,
                   exit_code)
    disable = tuple(r.strip() for r in args.disable.split(",") if r.strip())

    if args.coverage:
        rows, uncovered = generate_coverage_md()
        n = len(rows)
        print("OP_COVERAGE.md: %d ops, %d uncovered" % (n, len(uncovered)))
        for name in uncovered:
            print("  NOT COVERED: %s" % name)
        return 1 if uncovered else 0

    if args.self_check:
        findings = self_check(disable=disable)
        print(render_json(findings) if args.as_json
              else render_text(findings, title="mxlint --self-check"))
        # the shipped registry must be clean: warnings fail too
        return exit_code(findings, strict=True)

    if args.race:
        from .race_lint import (lint_race_file, lint_threaded_sources,
                                race_summary)
        if args.target:
            findings = lint_race_file(args.target, disable=disable)
            title = "mxrace %s" % args.target
            print(render_json(findings) if args.as_json
                  else render_text(findings, title=title))
            return exit_code(findings, strict=args.strict)
        findings = lint_threaded_sources(disable=disable)
        if args.as_json:
            print(render_json(findings, race=race_summary()))
        else:
            print(render_text(findings, title="mxrace sweep"))
            summary = race_summary()
            print("mxrace: %d files, %d locks, %d guarded attrs, "
                  "%d lock-order edges (%d pinned)"
                  % (summary["n_files"], len(summary["locks"]),
                     len(summary["guards"]), len(summary["edges"]),
                     len(summary["hierarchy"])))
        return exit_code(findings, strict=args.strict)

    if args.cost and not (args.target and args.target.endswith(".json")):
        return _run_cost(args, disable)

    if not args.target:
        p.error("give a target script/symbol, --self-check, --coverage, "
                "or --cost")

    if args.target.endswith(".json"):
        from ..symbol import load
        sym = load(args.target)
        shapes = _parse_shapes(args.shapes)
        findings = lint_symbol(sym, shapes=shapes, disable=disable,
                               check_consts=not args.no_consts)
        if args.serving:
            findings += lint_serving(sym, data_shapes=shapes,
                                     disable=disable,
                                     hbm_cap_bytes=args.hbm_cap or None)
        cost = None
        if args.cost:
            from .cost import analyze_symbol
            report = analyze_symbol(sym, shapes=shapes)
            if report is not None:
                cost = {args.target: report}
        title = "mxlint graph %s" % args.target
        if args.as_json:
            print(render_json(findings, cost=cost))
        else:
            print(render_text(findings, title=title))
            if cost:
                for name, rep in sorted(cost.items()):
                    print(rep.render(title="mxcost %s" % name))
        return exit_code(findings, strict=args.strict)

    findings = lint_file(args.target, disable=disable)
    title = "mxlint source %s" % args.target
    print(render_json(findings) if args.as_json
          else render_text(findings, title=title))
    return exit_code(findings, strict=args.strict)


def _run_cost(args, disable):
    """--cost over budget models / the --budget CI gate."""
    import os

    # hardware-free by contract: the budget numbers are defined on the
    # CPU backend, so when the caller did not pick one the static pass
    # asks for the CPU by name and never takes a chip.  Explicit
    # JAX_PLATFORMS wins.
    if not os.environ.get("JAX_PLATFORMS"):
        import jax
        jax.config.update("jax_platforms", "cpu")

    from . import render_json, render_text, exit_code, filter_findings
    from .budget_models import (BUDGET_MODELS, build_model,
                                build_fusion_report, check_budgets)
    from .dist_lint import dist_summary
    from .shard_prop import shard_summary

    cost, shards, findings = {}, {}, []
    if args.budget:
        findings, reports, shards = check_budgets(args.budget)
        findings = filter_findings(findings, disable)
        cost = reports
        title = "mxcost --budget %s" % args.budget
    else:
        names = [m.strip() for m in args.model.split(",") if m.strip()] \
            or [m for m in sorted(BUDGET_MODELS)
                if m != "resnet50_train_step"]
        for name in names:
            report, dst, shard = build_model(name)
            cost[name] = report
            if shard is not None:
                shards[name] = shard
            findings += filter_findings(dst, disable)
        title = "mxcost %s" % ",".join(names)
    fusion = {}
    if args.fusion:
        for name in sorted(cost):
            frep = build_fusion_report(name)
            if frep is not None:
                fusion[name] = frep
    codegen = None
    if args.codegen:
        from .codegen import codegen_plans
        codegen = codegen_plans()
    axis_sizes = {}
    for rep in cost.values():
        axis_sizes.update(rep.axis_sizes)
    if args.as_json:
        print(render_json(
            findings, cost=cost,
            dist=dist_summary(findings, axis_sizes=axis_sizes),
            shard=shard_summary(shards, findings)
            if (args.shard and shards) else None,
            fusion=fusion if (args.fusion and fusion) else None,
            codegen=codegen))
    else:
        print(render_text(findings, title=title))
        for name, rep in sorted(cost.items()):
            print(rep.render(title="mxcost %s" % name))
        if args.shard:
            for name, rep in sorted(shards.items()):
                print(rep.render(title="mxshard %s" % name))
        if args.fusion:
            for name, rep in sorted(fusion.items()):
                print(rep.render(title="mxfuse %s" % name))
        if codegen is not None:
            from .codegen import render_codegen
            print(render_codegen(codegen))
    return exit_code(findings, strict=args.strict)


if __name__ == "__main__":
    sys.exit(main())
