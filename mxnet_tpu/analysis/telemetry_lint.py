"""TEL rules: the chaos fault model and the telemetry trace must agree.

The chaos harness (``resilience/chaos.py``) and the telemetry layer meet
at probe sites: every ``chaos.maybe_inject("site", ...)`` call is both a
fault-injection point and — when a fault fires — a telemetry instant
event + flight-ring record.  Three ways that contract silently drifts,
all caught here as TEL001 (error, wired into ``--self-check`` per the
DOC001 discipline):

- a probe site *used* somewhere in ``mxnet_tpu/`` that is not registered
  in ``chaos.SITES`` (an undocumented fault point: schedules can target
  it but no one knows it exists, and the docs table lies by omission);
- a site *registered* in ``chaos.SITES`` but never probed in the code
  (the fault model advertises a failure mode that can no longer be
  injected — usually a refactor moved the call);
- a registered site missing from the ``docs/observability.md`` probe
  table, or ``chaos.maybe_inject`` no longer stamping fired faults
  through ``telemetry.fault_event`` (the emission point every site's
  "must emit a telemetry instant event" guarantee routes through).

TEL002 applies the same discipline to the performance doctor's
*attribution phases* (``telemetry/attribution.py``): the ``PHASES``
tuple, the ``HINTS`` map the doctor prints from, the
``docs/observability.md`` phase table and the ``add_phase`` call sites
in the shipped sources must all name the same set — a phase measured
but undocumented, documented but unmeasured, or missing its doctor hint
is the attribution layer lying about its own coverage.

Pure AST over the shipped sources — no imports of the probed modules.
"""
from __future__ import annotations

import ast
import glob
import os
import re

from .findings import Finding, filter_findings

__all__ = ["lint_chaos_sites", "probe_sites_used", "SITE_DOC",
           "lint_attribution_phases", "attribution_phases_used",
           "attribution_phase_decls", "context_hint_decls"]

# the documentation the probe table must live in (TEL001's third leg);
# the TEL002 phase table lives in the same doc
SITE_DOC = os.path.join("docs", "observability.md")


def _pkg_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def probe_sites_used(root=None):
    """Scan ``mxnet_tpu/**/*.py`` (plus the shipped drivers,
    ``tools/*.py``) for ``maybe_inject(<literal>, ...)``
    calls.  Returns ``(sites, dynamic)``: ``sites`` maps each literal
    site name to its ``file:line`` use sites; ``dynamic`` lists calls
    whose site argument is not a string literal (unverifiable — those
    are findings too: a computed site name can never be checked against
    the registered fault model)."""
    root = root or _pkg_root()
    repo = os.path.dirname(root)
    sites, dynamic = {}, []
    targets = sorted(glob.glob(os.path.join(root, "**", "*.py"),
                               recursive=True))
    # probe sites also live in the shipped drivers outside the package
    # (tools/train_elastic.py's train.step): same fault model, same sweep
    targets += sorted(glob.glob(os.path.join(repo, "tools", "*.py")))
    for path in targets:
        rel = os.path.relpath(path, os.path.dirname(root))
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else \
                getattr(fn, "id", None)
            if name != "maybe_inject" or not node.args:
                continue
            where = "%s:%d" % (rel, node.lineno)
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                sites.setdefault(arg.value, []).append(where)
            else:
                dynamic.append(where)
    return sites, dynamic


def _documented_sites(repo):
    """Site names appearing in the docs probe table (a row whose first
    cell is the backticked site name).  None when the doc is absent
    (installed package — the doc legs are skipped silently, the code
    legs still run)."""
    path = os.path.join(repo, SITE_DOC)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        text = f.read()
    return set(re.findall(r"^\|\s*`([a-z_.]+)`", text, re.M))


def _maybe_inject_emits_fault_event(root):
    """chaos.maybe_inject must route fired faults through
    ``telemetry.fault_event`` — the single emission point that makes
    "every probe site emits a telemetry instant event" true by
    construction.  Verified structurally (AST), so deleting the call
    fails ``--self-check`` instead of silently blinding the trace."""
    path = os.path.join(root, "resilience", "chaos.py")
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return False
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and \
                node.name == "maybe_inject":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    fn = sub.func
                    name = fn.attr if isinstance(fn, ast.Attribute) else \
                        getattr(fn, "id", None)
                    if name == "fault_event":
                        return True
    return False


def lint_chaos_sites(disable=(), root=None):
    """The TEL001 sweep (see module docstring).  Returns Finding
    records; empty means fault model, code and docs agree."""
    from ..resilience.chaos import SITES
    root = root or _pkg_root()
    repo = os.path.dirname(root)
    used, dynamic = probe_sites_used(root)
    findings = []
    for site in sorted(set(used) - set(SITES)):
        findings.append(Finding(
            "TEL001", site,
            "chaos probe site %r is used at %s but not registered in "
            "chaos.SITES — an unregistered fault point is invisible to "
            "the fault model and the docs"
            % (site, ", ".join(used[site]))))
    for site in sorted(set(SITES) - set(used)):
        findings.append(Finding(
            "TEL001", site,
            "chaos.SITES registers %r but no maybe_inject call probes "
            "it anywhere in mxnet_tpu/ — the fault model advertises an "
            "injectable failure that no longer exists" % (site,)))
    for where in dynamic:
        findings.append(Finding(
            "TEL001", where,
            "maybe_inject called with a non-literal site name — the "
            "site cannot be checked against the registered fault model"))
    documented = _documented_sites(repo)
    if documented is not None:
        for site in sorted(set(SITES) - documented):
            findings.append(Finding(
                "TEL001", site,
                "chaos probe site %r has no row in the %s probe table "
                "(keep the fault model and the docs in sync)"
                % (site, SITE_DOC)))
    if not _maybe_inject_emits_fault_event(root):
        findings.append(Finding(
            "TEL001", "chaos.maybe_inject",
            "chaos.maybe_inject no longer stamps fired faults through "
            "telemetry.fault_event — injected faults would leave no "
            "instant event or flight-ring record behind"))
    return filter_findings(findings, disable)


# ---------------------------------------------------------------------------
# TEL002: attribution phase names — code, hint map and docs in lockstep
# ---------------------------------------------------------------------------
def attribution_phase_decls(root=None, attribution_path=None):
    """Parse ``telemetry/attribution.py`` (AST, no import) for the
    declared ``PHASES`` tuple and the ``HINTS`` map's literal keys.
    Returns ``(phases, hint_keys)`` as ordered lists; non-literal
    entries come back as None placeholders so the lint can flag them."""
    root = root or _pkg_root()
    path = attribution_path or os.path.join(root, "telemetry",
                                            "attribution.py")
    phases, hint_keys = [], []
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return phases, hint_keys
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        name = getattr(target, "id", None)
        if name == "PHASES" and isinstance(node.value, (ast.Tuple,
                                                        ast.List)):
            for elt in node.value.elts:
                phases.append(elt.value if isinstance(elt, ast.Constant)
                              and isinstance(elt.value, str) else None)
        elif name == "HINTS" and isinstance(node.value, ast.Dict):
            for key in node.value.keys:
                hint_keys.append(key.value if isinstance(key, ast.Constant)
                                 and isinstance(key.value, str) else None)
    return phases, hint_keys


def context_hint_decls(root=None, attribution_path=None):
    """Parse ``telemetry/attribution.py`` (AST, no import) for the
    ``CONTEXT_HINTS`` map's literal ``(phase, tag)`` keys.  Non-literal
    keys come back as None placeholders so the lint can flag them."""
    root = root or _pkg_root()
    path = attribution_path or os.path.join(root, "telemetry",
                                            "attribution.py")
    pairs = []
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return pairs
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        if getattr(node.targets[0], "id", None) != "CONTEXT_HINTS":
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        for key in node.value.keys:
            if isinstance(key, ast.Tuple) and len(key.elts) == 2 and \
                    all(isinstance(e, ast.Constant)
                        and isinstance(e.value, str) for e in key.elts):
                pairs.append((key.elts[0].value, key.elts[1].value))
            else:
                pairs.append(None)
    return pairs


def _documented_context_hints(repo, doc_path=None):
    """(phase, tag) rows of the docs context-hint table: the table whose
    header row starts ``| phase | context``.  None when the doc is
    absent (installed package — doc legs skipped)."""
    path = doc_path or os.path.join(repo, SITE_DOC)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        lines = f.read().splitlines()
    pairs = set()
    in_table = False
    for line in lines:
        if re.match(r"^\|\s*phase\s*\|\s*context", line):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                in_table = False
                continue
            m = re.match(r"^\|\s*`([a-z0-9_]+)`\s*\|\s*`([a-z0-9_]+)`",
                         line)
            if m:
                pairs.add((m.group(1), m.group(2)))
    return pairs


def attribution_phases_used(root=None):
    """Scan the shipped sources (``mxnet_tpu/**``, ``bench.py``,
    ``tools/*.py``) for ``add_phase(<literal>, ...)`` calls — the
    attribution instrumentation points.  Returns ``(names, dynamic)``
    exactly like :func:`probe_sites_used`."""
    root = root or _pkg_root()
    repo = os.path.dirname(root)
    names, dynamic = {}, []
    targets = sorted(glob.glob(os.path.join(root, "**", "*.py"),
                               recursive=True))
    if os.path.isfile(os.path.join(repo, "bench.py")):
        targets.append(os.path.join(repo, "bench.py"))
    targets += sorted(glob.glob(os.path.join(repo, "tools", "*.py")))
    for path in targets:
        rel = os.path.relpath(path, os.path.dirname(root))
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else \
                getattr(fn, "id", None)
            if name != "add_phase" or not node.args:
                continue
            where = "%s:%d" % (rel, node.lineno)
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                names.setdefault(arg.value, []).append(where)
            else:
                dynamic.append(where)
    return names, dynamic


def _documented_phases(repo, doc_path=None):
    """Phase names in the docs phase table: the table whose header row's
    first cell is ``phase``, rows with a backticked first cell.  None
    when the doc is absent (installed package — doc legs skipped)."""
    path = doc_path or os.path.join(repo, SITE_DOC)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        lines = f.read().splitlines()
    phases = set()
    in_table = False
    for line in lines:
        if re.match(r"^\|\s*phase\s*\|", line):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                in_table = False
                continue
            m = re.match(r"^\|\s*`([a-z0-9_]+)`", line)
            if m:
                phases.add(m.group(1))
    return phases


def lint_attribution_phases(disable=(), root=None, attribution_path=None,
                            doc_path=None):
    """The TEL002 sweep: ``PHASES`` (attribution.py), the ``HINTS``
    doctor map, the docs phase table and the shipped ``add_phase`` call
    sites must agree both ways.  Returns Finding records; empty means
    the attribution layer, the doctor and the docs tell one story."""
    root = root or _pkg_root()
    repo = os.path.dirname(root)
    phases_raw, hints_raw = attribution_phase_decls(
        root, attribution_path=attribution_path)
    findings = []
    if not phases_raw:
        findings.append(Finding(
            "TEL002", "PHASES",
            "telemetry/attribution.py no longer declares a literal "
            "PHASES tuple — the attribution phase set cannot be "
            "verified against the docs or the doctor's hint map"))
        return filter_findings(findings, disable)
    if None in phases_raw or None in hints_raw:
        findings.append(Finding(
            "TEL002", "PHASES",
            "PHASES/HINTS contain non-literal entries — computed phase "
            "names can never be checked against the docs table"))
    phases = {p for p in phases_raw if p}
    hints = {h for h in hints_raw if h}
    used, dynamic = attribution_phases_used(root)
    for name in sorted(set(used) - phases):
        findings.append(Finding(
            "TEL002", name,
            "add_phase(%r) at %s but the phase is not declared in "
            "attribution.PHASES — measured time would be rejected at "
            "runtime and is invisible to the doctor/docs"
            % (name, ", ".join(used[name]))))
    for name in sorted(phases - set(used)):
        findings.append(Finding(
            "TEL002", name,
            "attribution phase %r is declared in PHASES but no "
            "add_phase call measures it anywhere in the shipped "
            "sources — the doctor advertises a decomposition slot that "
            "is always zero" % (name,)))
    for where in dynamic:
        findings.append(Finding(
            "TEL002", where,
            "add_phase called with a non-literal phase name — the phase "
            "cannot be checked against PHASES/docs"))
    for name in sorted(phases - hints):
        findings.append(Finding(
            "TEL002", name,
            "phase %r has no entry in the doctor's HINTS map — a rank "
            "bottlenecked there would get no actionable knob" % (name,)))
    for name in sorted(hints - phases):
        findings.append(Finding(
            "TEL002", name,
            "HINTS names phase %r which is not in PHASES — a stale "
            "doctor hint for a phase that no longer exists" % (name,)))
    documented = _documented_phases(repo, doc_path=doc_path)
    if documented is not None:
        for name in sorted(phases - documented):
            findings.append(Finding(
                "TEL002", name,
                "attribution phase %r has no row in the %s phase table "
                "(keep the decomposition and the docs in sync)"
                % (name, SITE_DOC)))
        for name in sorted(documented - phases):
            findings.append(Finding(
                "TEL002", name,
                "the %s phase table documents %r but attribution.PHASES "
                "does not declare it — the docs promise a phase the "
                "doctor cannot produce" % (SITE_DOC, name)))
    # CONTEXT_HINTS legs: every (phase, tag) specialization must refine
    # a declared phase and have its row in the docs context-hint table
    # (both ways — a stale doc row promises a hint the doctor cannot
    # print)
    ctx_raw = context_hint_decls(root, attribution_path=attribution_path)
    if None in ctx_raw:
        findings.append(Finding(
            "TEL002", "CONTEXT_HINTS",
            "CONTEXT_HINTS contains non-literal (phase, tag) keys — "
            "computed context hints can never be checked against "
            "PHASES or the docs"))
    ctx = {p for p in ctx_raw if p is not None}
    for phase, tag in sorted(ctx):
        if phase not in phases:
            findings.append(Finding(
                "TEL002", "%s:%s" % (phase, tag),
                "CONTEXT_HINTS specializes phase %r (tag %r) which is "
                "not in PHASES — a stale hint for a phase that no "
                "longer exists" % (phase, tag)))
    doc_ctx = _documented_context_hints(repo, doc_path=doc_path)
    if doc_ctx is not None:
        for phase, tag in sorted(ctx - doc_ctx):
            findings.append(Finding(
                "TEL002", "%s:%s" % (phase, tag),
                "context hint (%r, %r) has no row in the %s "
                "context-hint table (keep the doctor's specialized "
                "hints and the docs in sync)" % (phase, tag, SITE_DOC)))
        for phase, tag in sorted(doc_ctx - ctx):
            findings.append(Finding(
                "TEL002", "%s:%s" % (phase, tag),
                "the %s context-hint table documents (%r, %r) but "
                "attribution.CONTEXT_HINTS does not declare it — the "
                "docs promise a hint the doctor cannot print"
                % (SITE_DOC, phase, tag)))
    return filter_findings(findings, disable)
