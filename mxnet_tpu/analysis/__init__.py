"""mxnet_tpu.analysis — "mxlint", static graph/registry analysis.

The reference stack proves graph attributes with dedicated nnvm passes
(``src/executor/infer_graph_attr_pass.cc``); the JAX reproduction had no
analogue, so a malformed op registration or a recompile-forcing pattern
only failed deep inside ``jax.jit``.  This package closes that gap with
three cooperating passes:

- **registry lint** (:mod:`.registry_lint`): per-op metadata vs. the real
  fn signature — slot counts/order, scalar/optional/aux/mutates indices,
  ``num_outputs`` totality, alias shadowing, docstrings, test coverage;
- **graph lint** (:mod:`.graph_lint`): whole-Symbol checks — dead
  outputs, gradient-cutting ops on loss paths, aux misuse, float64
  promotion, static reshapes, oversized baked-in constants;
- **source lint** (:mod:`.source_lint`): AST heuristics over driver
  scripts for trace-time scalar captures and shape-dependent branching.

Entry points: ``python -m mxnet_tpu.analysis`` (CLI), ``Symbol.lint()``,
``Module.lint()`` and ``Executor.simple_bind(..., lint=True)``.
"""
from __future__ import annotations

from .findings import (Finding, RULES, ERROR, WARNING, INFO,
                       filter_findings, suppressed_rules)
from .registry_lint import lint_registry, unique_ops
from .graph_lint import lint_graph, LOSS_OPS, LARGE_CONST_BYTES
from .source_lint import lint_source, lint_file
from .serving_lint import (lint_serving, lint_fleet_hbm,
                           lint_deadline_propagation)
from .mlops_lint import (lint_wallclock_reads, lint_promotion_sources,
                         lint_supervisor_sources)
from .telemetry_lint import (lint_chaos_sites, probe_sites_used,
                             lint_attribution_phases,
                             attribution_phases_used,
                             attribution_phase_decls)
from .coverage import load_test_map, generate_coverage_md
from .report import (render_text, render_json, exit_code, worst_severity,
                     SCHEMA_VERSION)
from .cost import (CostReport, analyze_jaxpr, analyze_fn, analyze_symbol,
                   XLA_FLOP_RTOL, ring_bytes_per_axis, unpriced_findings,
                   KERNEL_COSTS, declare_kernel_cost)
from .fusion import (FusionReport, FusionChain, analyze_tape_fusion,
                     fusion_from_jaxpr, fusion_from_fn,
                     fusion_for_symbol, lint_kernel_costs,
                     FUSION_HINT_MIN_PCT)
from .codegen import (LoweredKernel, lower_chain, LOWERABLE,
                      lint_generated_kernels, codegen_plans,
                      render_codegen, equivalence_check_host,
                      shipped_lowered, shipped_chain_rows,
                      autotune_block_rows, AUTOTUNE_LADDER,
                      AUTOTUNE_SEED)
from .dist_lint import lint_dist_step, lint_trainer, dist_summary
from .race_lint import (lint_race_source, lint_race_file,
                        lint_threaded_sources, lock_order_findings,
                        parse_hierarchy, race_summary, threaded_targets)
from .shard_prop import (MeshSpec, ShardSpec, ShardReport, propagate,
                         collective_schedule, lint_sharded_step,
                         lint_ring_schedule, lint_global_sharding,
                         shard_summary)

__all__ = [
    "Finding", "RULES", "ERROR", "WARNING", "INFO",
    "lint_registry", "lint_graph", "lint_source", "lint_file",
    "lint_symbol", "lint_serving", "lint_fleet_hbm",
    "lint_deadline_propagation", "lint_serving_sources",
    "lint_decode_sources", "lint_decode_trace_constants",
    "lint_wallclock_reads", "lint_promotion_sources",
    "lint_supervisor_sources",
    "lint_rule_docs", "self_check",
    "lint_shipped_loops", "lint_worker_loops",
    "lint_chaos_sites", "probe_sites_used", "lint_attribution_phases",
    "attribution_phases_used", "attribution_phase_decls",
    "load_test_map",
    "generate_coverage_md",
    "render_text", "render_json", "exit_code", "worst_severity",
    "filter_findings", "suppressed_rules", "unique_ops",
    "LOSS_OPS", "LARGE_CONST_BYTES",
    "CostReport", "analyze_jaxpr", "analyze_fn", "analyze_symbol",
    "XLA_FLOP_RTOL", "SCHEMA_VERSION", "ring_bytes_per_axis",
    "unpriced_findings",
    "lint_dist_step", "lint_trainer", "dist_summary", "cost_self_check",
    "MeshSpec", "ShardSpec", "ShardReport", "propagate",
    "collective_schedule", "lint_sharded_step", "lint_ring_schedule",
    "lint_global_sharding", "shard_summary", "shard_self_check",
    "lint_parallel_sources",
    "FusionReport", "FusionChain", "analyze_tape_fusion",
    "fusion_from_jaxpr", "fusion_from_fn", "fusion_for_symbol",
    "lint_kernel_costs", "FUSION_HINT_MIN_PCT", "KERNEL_COSTS",
    "declare_kernel_cost",
    "LoweredKernel", "lower_chain", "LOWERABLE",
    "lint_generated_kernels", "codegen_plans", "render_codegen",
    "equivalence_check_host", "shipped_lowered", "shipped_chain_rows",
    "autotune_block_rows", "AUTOTUNE_LADDER", "AUTOTUNE_SEED",
    "lint_race_source", "lint_race_file", "lint_threaded_sources",
    "lock_order_findings", "parse_hierarchy", "race_summary",
    "threaded_targets",
]


def lint_symbol(symbol, shapes=None, type_dict=None, disable=(),
                check_consts=True):
    """Graph-lint a Symbol (the ``Symbol.lint()`` implementation)."""
    return lint_graph(symbol, shapes=shapes, type_dict=type_dict,
                      disable=disable, check_consts=check_consts)


def self_check(disable=(), with_coverage=True, with_cost=True,
               with_examples=True, with_workers=True, with_serving=True,
               with_telemetry=True, with_shard=True, with_mlops=True,
               with_race=True, with_codegen=True):
    """Registry lint over the live registry, the rule-table docs sync
    check, the cost-pass determinism check, the SRC004 sweep over the
    shipped training loops, the SRC005 sweep over the shipped worker
    loops, the SRV004 deadline-propagation sweep over the shipped
    serving request paths, the SRV005 wall-clock sweep over the
    promotion/capacity decision path (``mlops/`` + the decision CLIs),
    the telemetry sweeps — TEL001 chaos-probe sites and TEL002
    attribution phases + context hints — the mxshard sweeps: the golden
    sharded-step fixtures must lint clean and deterministically
    (``shard_self_check``) and the shipped ring/Ulysses attention paths
    must pass the mixed-axis DST rules (``lint_parallel_sources``) —
    and the declared-cost sweep over the shipped Pallas kernels
    (``lint_kernel_costs``, COST005/COST006) and the mxgen sweep over
    the generated kernels (``lint_generated_kernels``, GEN001/GEN002:
    every shipped chain lowers provably and every registered generated
    kernel passed its auto-equivalence check) — plus the mxrace
    concurrency
    sweep over every threaded host module (``lint_threaded_sources``:
    RACE001-RACE005, the lock-order/hierarchy sync against
    ``docs/concurrency.md``, and race-report determinism) — what CI
    runs.

    Returns the findings list; clean means the shipped registry is sound
    (every severity counts: ``--self-check`` exits non-zero on warnings).
    """
    coverage_map = load_test_map() if with_coverage else None
    findings = lint_registry(coverage_map=coverage_map, disable=disable)
    findings += lint_rule_docs(disable=disable)
    if with_cost:
        findings += cost_self_check(disable=disable)
    if with_examples:
        findings += lint_shipped_loops(disable=disable)
    if with_workers:
        findings += lint_worker_loops(disable=disable)
    if with_serving:
        findings += lint_serving_sources(disable=disable)
        findings += lint_decode_sources(disable=disable)
    if with_mlops:
        findings += lint_promotion_sources(disable=disable)
        findings += lint_supervisor_sources(disable=disable)
    if with_telemetry:
        findings += lint_chaos_sites(disable=disable)
        findings += lint_attribution_phases(disable=disable)
    if with_shard:
        findings += shard_self_check(disable=disable)
        findings += lint_parallel_sources(disable=disable)
    if with_race:
        findings += lint_threaded_sources(disable=disable)
    if with_codegen:
        # the mxgen sweep (GEN001/GEN002): every shipped chain lowers
        # inside the provable set and every registered generated kernel
        # carries a passing auto-equivalence check
        findings += lint_generated_kernels(disable=disable)
    if with_cost:
        # the declared-cost sweep (COST005 + the COST006 registry diff
        # for exec'd mxgen kernels): every shipped pallas_call must
        # price itself — an un-annotated kernel fails CI here.  Runs
        # AFTER the codegen sweep so the generated registry is built
        findings += lint_kernel_costs(disable=disable)
    return findings


def lint_serving_sources(disable=()):
    """SRV004 (deadline-propagation half) over every shipped serving
    request path: the serving package itself, the serve CLI and the
    serving examples.  A shipped path that binds ``deadline_ms`` but
    drops it before the Batcher breaks admission control for anyone
    copying it.  (The packing half of SRV004 runs at every
    ``ModelFleet.register`` — it needs live modeled costs, not source.)
    Skipped silently outside a repo checkout."""
    import glob
    import os

    pkg = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(pkg)          # mxnet_tpu/
    repo = os.path.dirname(root)
    targets = sorted(glob.glob(os.path.join(root, "serving", "*.py")))
    if os.path.isfile(os.path.join(repo, "tools", "serve.py")):
        targets.append(os.path.join(repo, "tools", "serve.py"))
    if os.path.isdir(os.path.join(repo, "examples", "serving")):
        targets += sorted(glob.glob(os.path.join(
            repo, "examples", "serving", "*.py")))
    findings = []
    for path in targets:
        try:
            findings += lint_deadline_propagation(os.path.normpath(path))
        except OSError:
            continue
    return filter_findings(findings, disable)


def lint_decode_sources(disable=()):
    """SRV006 over the shipped decode tier: the serving package (the
    DecodeRunner/DecodeBatcher host paths) plus the traced phase
    spellings in ``mxnet_tpu/transformer/decode.py``.  A decode path
    that bakes sequence length or batch position into a trace constant
    recompiles per request geometry — the exact contract the
    prefill/decode split exists to keep.  Skipped silently outside a
    repo checkout."""
    import glob
    import os

    from .serving_lint import lint_decode_trace_constants

    pkg = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(pkg)          # mxnet_tpu/
    targets = sorted(glob.glob(os.path.join(root, "serving", "*.py")))
    tdec = os.path.join(root, "transformer", "decode.py")
    if os.path.isfile(tdec):
        targets.append(tdec)
    findings = []
    for path in targets:
        try:
            findings += lint_decode_trace_constants(os.path.normpath(path))
        except OSError:
            continue
    return filter_findings(findings, disable)


def lint_shipped_loops(disable=()):
    """SRC004 over every ``examples/`` script and the in-repo fit loops
    (``module/base_module.py``, ``parallel/trainer.py``,
    ``monitor.py``): the training loops this repo ships must not block
    the host once per dispatched step — the engine's run-ahead window would collapse to 1 for anyone
    copying them.  Only SRC004 is kept (the other source rules are
    advisory for user scripts; examples demonstrate plenty of idioms
    they would flag).  Skipped silently outside a repo checkout."""
    import glob
    import os

    pkg = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(pkg))
    examples = os.path.join(repo, "examples")
    if not os.path.isdir(examples):
        return []
    targets = sorted(glob.glob(os.path.join(examples, "**", "*.py"),
                               recursive=True))
    targets += [os.path.join(pkg, os.pardir, "module", "base_module.py"),
                os.path.join(pkg, os.pardir, "parallel", "trainer.py"),
                # the legacy Monitor used to block per batch; its lazy
                # toc-boundary drain keeps it in the sweep, not a hole
                os.path.join(pkg, os.pardir, "monitor.py")]
    findings = []
    for path in targets:
        try:
            found = lint_file(os.path.normpath(path))
        except (OSError, ValueError):
            continue
        findings += [f for f in found if f.rule_id == "SRC004"]
    return filter_findings(findings, disable)


def lint_worker_loops(disable=()):
    """SRC005 over every shipped concurrency surface: the pipeline's
    worker processes, the PS server/client loops, the serving batcher,
    the resilience heartbeat/watchdog threads, the run-ahead engine, the
    data loader, the launcher and all examples.  A worker loop this repo
    ships must never block unboundedly on a peer that can die.  Skipped
    silently outside a repo checkout."""
    import glob
    import os

    pkg = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(pkg)          # mxnet_tpu/
    repo = os.path.dirname(root)
    targets = sorted(
        glob.glob(os.path.join(root, "io", "*.py"))
        + glob.glob(os.path.join(root, "serving", "*.py"))
        + glob.glob(os.path.join(root, "resilience", "*.py"))
        + glob.glob(os.path.join(root, "gluon", "data", "*.py")))
    targets += [os.path.join(root, "engine.py"),
                os.path.join(root, "kvstore.py"),
                os.path.join(root, "kvstore_ps.py"),
                os.path.join(root, "kvstore_server.py"),
                os.path.join(root, "parallel", "trainer.py")]
    if os.path.isdir(os.path.join(repo, "tools")):
        targets += sorted(glob.glob(os.path.join(repo, "tools", "*.py")))
    if os.path.isdir(os.path.join(repo, "examples")):
        targets += sorted(glob.glob(os.path.join(repo, "examples", "**",
                                                 "*.py"), recursive=True))
    findings = []
    for path in targets:
        try:
            found = lint_file(os.path.normpath(path))
        except (OSError, ValueError):
            continue
        findings += [f for f in found if f.rule_id == "SRC005"]
    return filter_findings(findings, disable)


def cost_self_check(disable=()):
    """COST003: the cost pass must be deterministic — two analyses of
    the same fixture program (an MLP forward + a collective step) must
    produce byte-identical reports, or STATIC_BUDGETS.json gating would
    flap in CI."""
    import jax.numpy as jnp
    from jax import lax

    def fixture(w1, w2, x):
        h = jnp.maximum(x @ w1, 0.0)
        g = lax.pmean(h @ w2, "data")
        return jnp.exp(g).sum()

    args = (jnp.zeros((16, 32)), jnp.zeros((32, 8)), jnp.zeros((4, 16)))
    reports = [analyze_fn(fixture, *args, axis_env=[("data", 8)],
                          donate_argnums=(0,), host_argnums=(2,))
               .as_dict() for _ in range(2)]
    findings = []
    if reports[0] != reports[1]:
        diff = sorted(k for k in reports[0]
                      if reports[0][k] != reports[1].get(k))
        findings.append(Finding(
            "COST003", "cost_self_check",
            "two runs of the cost pass over the same program disagree "
            "on %s — the budget gate would flap" % (diff,)))
    return filter_findings(findings, disable)


def shard_self_check(disable=()):
    """mxshard sweep for ``--self-check``: the three canonical sharded
    patterns (docs/analysis.md "Sharding propagation") must lint clean
    under the mixed-axis DST rules, and the propagation must be
    deterministic — the golden fixtures are miniatures (the full
    budgeted geometries run in the budget gate / tests)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import shard_prop as sp
    from .shard_fixtures import tp_matmul_program

    findings = []
    k = 4
    mesh = sp.MeshSpec({"data": k})

    # mini ZeRO-1: reduce-scatter / shard-update / all-gather round trip
    def mini_zero1(w, m_sh, x):
        loss, g = jax.value_and_grad(
            lambda w: ((x @ w) ** 2).mean())(w)
        g_sh = lax.psum_scatter(g.ravel(), "data", scatter_dimension=0,
                                tiled=True) / k
        idx = lax.axis_index("data")
        n = w.size // k
        w_sh = lax.dynamic_slice(w.ravel(), (idx * n,), (n,))
        new_m = 0.9 * m_sh + g_sh
        new_flat = lax.all_gather(w_sh - 0.1 * new_m, "data", tiled=True)
        return lax.pmean(loss, "data"), new_flat.reshape(w.shape), new_m

    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    m = jax.ShapeDtypeStruct((16 * 8 // k,), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    closed = jax.make_jaxpr(mini_zero1, axis_env=[("data", k)])(w, m, x)
    findings += sp.lint_sharded_step(
        closed, mesh, data_axes=("data",), varying_invars=[2],
        shard_dims={1: {0: ("data",)}}, param_outvars=[1],
        param_names=["w"], subject="shard_self_check.zero1")

    # mini tensor-parallel matmul: exactly one inferred psum over model
    fn, args, specs = tp_matmul_program(batch=8, d_in=8, d_mid=16,
                                        d_out=4)
    tmesh = sp.MeshSpec({"data": 4, "model": 2})
    tclosed = jax.make_jaxpr(fn)(*args)
    reports = [sp.propagate(tclosed, tmesh, specs).as_dict()
               for _ in range(2)]
    if reports[0] != reports[1]:
        findings.append(Finding(
            "COST003", "shard_self_check",
            "two runs of the shard propagation over the same program "
            "disagree — the shard section of the budget gate would "
            "flap"))
    inferred = [ev for ev in reports[0]["schedule"]
                if ev["inferred"] and "model" in ev["axes"]]
    if not inferred:
        findings.append(Finding(
            "COST003", "shard_self_check",
            "the tensor-parallel matmul fixture no longer infers its "
            "partial-sum psum over the model axis — the propagation "
            "lost the GSPMD contraction rule"))
    for ev in reports[0]["reshards"]:
        findings.append(Finding(
            "DST010", "shard_self_check",
            "the clean tensor-parallel fixture reports a forced "
            "reshard (%r) — propagation regression" % (ev,)))

    # mini ring: a scanned full-ring ppermute must satisfy DST009
    def mini_ring(x):
        perm = [(i, (i + 1) % k) for i in range(k)]
        def hop(c, _):
            return lax.ppermute(c, "seq", perm), ()
        out, _ = lax.scan(hop, x, jnp.arange(k))
        return out

    rclosed = jax.make_jaxpr(mini_ring, axis_env=[("seq", k)])(
        jax.ShapeDtypeStruct((8, 8), jnp.float32))
    findings += sp.lint_ring_schedule(rclosed, "seq", k,
                                      subject="shard_self_check.ring")
    return filter_findings(findings, disable)


def lint_parallel_sources(disable=()):
    """The mixed-axis shard passes over the shipped sequence-parallel
    attention paths (``parallel/ring_attention.py``): ring attention
    forward+backward must prove its ppermute ring (DST009) and stay
    clean under lint_sharded_step; the Ulysses all_to_all path must
    lint clean too.  Miniature geometry — the pinned budget model
    (``ring_attention_fwd``) covers the full one."""
    import jax

    from . import shard_prop as sp
    from .shard_fixtures import ring_attention_program

    k = 4
    mesh = sp.MeshSpec({"sequence": k})
    findings = []
    for tag, with_grad in (("fwd", False), ("fwd+bwd", True)):
        fn, args = ring_attention_program(
            k=k, batch=1, t_global=32, heads=4, head_dim=8,
            causal=True, with_grad=with_grad)
        closed = jax.make_jaxpr(fn, axis_env=[("sequence", k)])(*args)
        subject = "parallel/ring_attention.py:%s" % tag
        findings += sp.lint_ring_schedule(closed, "sequence", k,
                                          subject=subject)
        findings += sp.lint_sharded_step(
            closed, mesh, data_axes=("sequence",),
            varying_invars=[0, 1, 2],
            shard_dims={i: {1: ("sequence",)} for i in range(3)},
            param_outvars=[], subject=subject)

    from .shard_fixtures import ulysses_attention_program
    for tag, with_grad in (("ulysses", False),
                           ("ulysses fwd+bwd", True)):
        fn, args = ulysses_attention_program(
            k=k, batch=1, t_global=32, heads=4, head_dim=8,
            causal=True, with_grad=with_grad)
        uclosed = jax.make_jaxpr(
            fn, axis_env=[("sequence", k)])(*args)
        findings += sp.lint_sharded_step(
            uclosed, mesh, data_axes=("sequence",),
            varying_invars=[0, 1, 2],
            shard_dims={i: {1: ("sequence",)} for i in range(3)},
            param_outvars=[],
            subject="parallel/ring_attention.py:%s" % tag)
    return filter_findings(findings, disable)


def lint_rule_docs(disable=()):
    """DOC001: every rule in RULES must have a row in the docs/analysis.md
    rule table — new rules (e.g. a source-pass addition) land in the docs
    in the same PR, enforced by ``--self-check``.  Skipped silently when
    the repo docs are not present (installed package)."""
    import os
    import re

    docs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "docs", "analysis.md")
    if not os.path.isfile(docs):
        return []
    with open(docs) as f:
        documented = set(re.findall(r"^\|\s*([A-Z]{3,4}\d{3})\s*\|",
                                    f.read(), re.M))
    findings = [Finding("DOC001", rule,
                        "rule %s is registered but has no row in "
                        "docs/analysis.md" % rule)
                for rule in sorted(RULES)
                if rule not in documented and rule != "DOC001"]
    return filter_findings(findings, disable)
