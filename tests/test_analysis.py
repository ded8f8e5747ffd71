"""mxnet_tpu.analysis (mxlint) — registry, graph and source passes.

Every rule_id fires at least once on a crafted fixture and stays silent
on a clean op/graph; the self-check CLI (what CI runs) passes on the
shipped registry.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym

pytestmark = pytest.mark.analysis
from mxnet_tpu.analysis import (lint_graph, lint_registry, lint_source,
                                render_json, render_text, exit_code)
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import registry
from mxnet_tpu.symbol.symbol import Symbol, _sym_invoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules(findings):
    return {f.rule_id for f in findings}


# ---------------------------------------------------------------------------
# registry pass — against an isolated fake registry (the real one must stay
# clean, which test_self_check_cli proves)
# ---------------------------------------------------------------------------
class FakeReg:
    def __init__(self):
        self._ops = {}
        self._shadows = []

    def add(self, op, *names):
        for n in (op.name,) + names:
            self._ops[n] = op
        return op

    def list_ops(self):
        return sorted(self._ops)

    def get(self, name):
        return self._ops[name]

    def shadowed(self):
        return list(self._shadows)


def _good_fn(data, weight, alpha=1.0):
    """A well-formed fixture op."""
    return data * weight * alpha


def test_clean_op_is_silent():
    reg = FakeReg()
    reg.add(registry.Op("good", _good_fn, arg_names=["data", "weight"],
                        scalar_args=("alpha",)))
    assert lint_registry(registry=reg) == []


def test_reg001_missing_tensor_slot():
    reg = FakeReg()
    reg.add(registry.Op("bad", lambda data: data,
                        arg_names=["data", "weight"],
                        doc="fn has one positional param, two slots."))
    assert rules(lint_registry(registry=reg)) == {"REG001"}


def test_reg001_variadic_without_star_args():
    reg = FakeReg()
    reg.add(registry.Op("badvar", lambda data: data, arg_names=["args"],
                        doc="variadic registration over a unary fn."))
    assert "REG001" in rules(lint_registry(registry=reg))


def test_reg002_slot_order_swap():
    reg = FakeReg()
    reg.add(registry.Op("swapped", lambda weight, data: data @ weight,
                        arg_names=["data", "weight"],
                        doc="slots transposed vs fn params."))
    assert rules(lint_registry(registry=reg)) == {"REG002"}


def test_reg003_unknown_scalar_arg():
    reg = FakeReg()
    reg.add(registry.Op("badscalar", lambda data: data,
                        scalar_args=("alpha",),
                        doc="alpha is not a parameter of fn."))
    assert rules(lint_registry(registry=reg)) == {"REG003"}


def test_reg004_unknown_optional_arg():
    reg = FakeReg()
    reg.add(registry.Op("badopt", lambda data, bias=None: data,
                        arg_names=["data", "bias"],
                        optional_args=("nonexistent",),
                        doc="optional names no slot."))
    assert rules(lint_registry(registry=reg)) == {"REG004"}


def test_reg005_aux_index_gap():
    reg = FakeReg()
    reg.add(registry.Op("badaux",
                        lambda data, gamma, mean=None, var=None: data,
                        arg_names=["data", "gamma"],
                        aux={3: "mean", 4: "var"},   # should start at 2
                        doc="aux range leaves a hole at index 2."))
    assert rules(lint_registry(registry=reg)) == {"REG005"}


def test_reg006_mutates_out_of_range():
    reg = FakeReg()
    reg.add(registry.Op("badmut", lambda w, g: (w, w - g),
                        arg_names=["weight", "grad"], mutates={5: 1},
                        doc="mutated input index 5 does not exist."))
    assert rules(lint_registry(registry=reg)) == {"REG006"}


def test_reg007_num_outputs_not_total():
    reg = FakeReg()
    reg.add(registry.Op("badnout", lambda data: data,
                        num_outputs=lambda p: p["k"],   # KeyError on {}
                        doc="num_outputs requires an undefaulted param."))
    assert rules(lint_registry(registry=reg)) == {"REG007"}


def test_reg008_alias_shadow():
    reg = FakeReg()
    a = reg.add(registry.Op("first", lambda data: data, doc="original."))
    reg.add(registry.Op("second", lambda data: -data, doc="usurper."))
    reg._shadows.append(("first", "first", "second"))
    assert "REG008" in rules(lint_registry(registry=reg))


def test_register_records_shadows():
    before = list(registry.shadowed())
    ops_before = dict(registry._OPS)
    try:
        registry.register("_lintfix_shadow_victim",
                          doc="victim.")(lambda data: data)
        registry.register("_lintfix_other",
                          aliases=("_lintfix_shadow_victim",),
                          doc="shadows the victim via alias.")(
                              lambda data: -data)
        new = [s for s in registry.shadowed() if s not in before]
        assert ("_lintfix_shadow_victim", "_lintfix_shadow_victim",
                "_lintfix_other") in new
    finally:
        registry._OPS.clear()
        registry._OPS.update(ops_before)
        registry._SHADOWS[:] = before


def test_reg009_missing_docstring_and_suppression():
    reg = FakeReg()
    reg.add(registry.Op("nodoc", lambda data: data))
    assert rules(lint_registry(registry=reg)) == {"REG009"}

    def suppressed_fn(data):
        # mxlint: disable=REG009
        return data

    reg2 = FakeReg()
    reg2.add(registry.Op("nodoc2", suppressed_fn))
    assert lint_registry(registry=reg2) == []


def test_reg010_zero_coverage():
    reg = FakeReg()
    reg.add(registry.Op("uncovered", lambda data: data, doc="fixture."))
    assert rules(lint_registry(registry=reg, coverage_map={})) == {"REG010"}
    # an alias entry in the map covers the canonical name too
    reg.add(reg.get("uncovered"), "uncovered_alias")
    assert lint_registry(
        registry=reg,
        coverage_map={"uncovered_alias": "somewhere"}) == []


def test_reg011_introspection_fallback():
    class Weird:
        __signature__ = "not-a-signature"

        def __call__(self, data):
            return data

    reg = FakeReg()
    reg.add(registry.Op("weird", Weird(), doc="uninspectable callable."))
    assert "REG011" in rules(lint_registry(registry=reg))


def test_fn_params_robust_to_partial():
    def base(data, other, alpha=1.0, beta=2.0):
        """Partial-registered fixture."""
        return data + other * alpha * beta

    op = registry.Op("partial_op", functools.partial(base, beta=3.0),
                     arg_names=["data", "other"], scalar_args=("alpha",))
    assert op.fn_params == ["data", "other", "alpha"]
    assert not op.fn_params_fallback
    reg = FakeReg()
    reg.add(op)
    assert lint_registry(registry=reg) == []


# ---------------------------------------------------------------------------
# graph pass
# ---------------------------------------------------------------------------
def test_grf001_dead_output():
    data = sym.var("data")
    parts = sym.SliceChannel(data, num_outputs=3, name="dead_split")
    findings = lint_graph(parts[0], check_consts=False)
    assert [f.rule_id for f in findings] == ["GRF001", "GRF001"]
    # consuming every output silences the rule
    s = parts[0] + parts[1] + parts[2]
    assert lint_graph(s, check_consts=False) == []


def test_grf002_nondiff_on_grad_path():
    data = sym.var("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="g2_fc")
    cut = sym.argmax(fc, axis=1, name="g2_argmax")
    loss = sym.MakeLoss(cut, name="g2_loss")
    findings = lint_graph(loss, check_consts=False)
    assert rules(findings) == {"GRF002"}
    assert findings[0].subject == "g2_argmax"
    # no loss head -> predict-only graph, rule stays quiet
    assert lint_graph(cut, check_consts=False) == []
    # differentiable path to the loss head is fine
    assert lint_graph(sym.MakeLoss(fc, name="g2_ok"),
                      check_consts=False) == []


def test_grf003_aux_read_outside_train():
    data = sym.var("data")
    bn = sym.BatchNorm(data, name="g3_bn")
    aux_nodes = [n for n in bn._nodes() if n.op is None and n._is_aux]
    assert aux_nodes
    leaked = bn + Symbol([(aux_nodes[0], 0)])
    findings = lint_graph(leaked, check_consts=False)
    assert rules(findings) == {"GRF003"}
    assert lint_graph(bn, check_consts=False) == []


def test_grf004_float64_promotion():
    a = sym.var("a", dtype="float64")
    b = sym.var("b")
    findings = lint_graph(a * b, check_consts=False)
    assert rules(findings) == {"GRF004"}
    # all-f32 graph is silent
    assert lint_graph(sym.var("x") * sym.var("y"), check_consts=False) == []
    # explicit f64 Cast from f32 is flagged too
    assert rules(lint_graph(sym.Cast(sym.var("z"), dtype="float64"),
                            check_consts=False)) == {"GRF004"}


def test_grf005_static_reshape():
    data = sym.var("data")
    bad = sym.Reshape(data, shape=(32, 100), name="g5_bad")
    assert rules(lint_graph(bad, check_consts=False)) == {"GRF005"}
    ok = sym.Reshape(data, shape=(0, -1), name="g5_ok")
    assert lint_graph(ok, check_consts=False) == []


def test_grf005_node_level_suppression():
    data = sym.var("data")
    bad = sym.Reshape(data, shape=(32, 100), name="g5_muted")
    bad._set_attr(__mxlint_disable__="GRF005")
    assert lint_graph(bad, check_consts=False) == []


def test_grf006_large_baked_constant():
    big = np.ones((512, 600), np.float32)   # ~1.2 MiB
    ops_before = dict(registry._OPS)
    try:
        registry.register("_lintfix_bigconst",
                          doc="adds a >1MiB closure constant.")(
                              lambda data: data + jnp.asarray(big).sum())
        s = _sym_invoke(registry.get("_lintfix_bigconst"),
                        "_lintfix_bigconst", (sym.var("data"),), {})
        findings = lint_graph(s, shapes={"data": (4, 8)})
        assert rules(findings) == {"GRF006"}
        assert "MiB" in findings[0].message
    finally:
        registry._OPS.clear()
        registry._OPS.update(ops_before)


# ---------------------------------------------------------------------------
# source pass
# ---------------------------------------------------------------------------
def test_src001_scalar_capture():
    src = "loss = net.forward(batch)\nval = loss.item()\n"
    findings = lint_source(src, filename="train.py")
    assert rules(findings) == {"SRC001"}
    assert findings[0].subject == "train.py:2"
    # float() over an array expression is the same trap
    assert rules(lint_source("x = float(net(y))\n")) == {"SRC001"}


def test_src002_shape_branch():
    src = "if x.shape[0] > 16:\n    y = f(x)\nwhile x.size > 1:\n    x = g(x)\n"
    findings = lint_source(src)
    assert [f.rule_id for f in findings] == ["SRC002", "SRC002"]


def test_src_inline_suppression_and_clean():
    src = "v = loss.item()  # mxlint: disable=SRC001\n"
    assert lint_source(src) == []
    clean = "y = net(x)\nz = y + 1\n"
    assert lint_source(clean) == []


def test_src003_host_normalize_variants():
    """Host-side mean/std normalization is flagged with the fused
    device-tail suggestion (PR 3)."""
    # the spelled-out idiom
    assert rules(lint_source("x = (img - rgb_mean) / rgb_std\n")) == \
        {"SRC003"}
    # normalize helpers
    assert rules(lint_source("y = mx.image.color_normalize(img, m, s)\n")) \
        == {"SRC003"}
    assert rules(lint_source("aug = ColorNormalizeAug(mean, std)\n")) == \
        {"SRC003"}
    # iterator factories given mean/std without the device tail
    src = "it = mx.io.ImageRecordIter(path_imgrec=p, mean_r=123.0)\n"
    findings = lint_source(src)
    assert rules(findings) == {"SRC003"}
    assert "device_tail" in findings[0].message


def test_src003_clean_cases():
    # device_tail=True is exactly the fix — no finding
    ok = "it = ImageRecordIter(path_imgrec=p, mean_r=1.0, " \
         "device_tail=True)\n"
    assert lint_source(ok) == []
    # unrelated subtraction/division
    assert lint_source("z = (a - b) / c\n") == []
    # suppression works
    assert lint_source("x = (v - mean) / std  "
                       "# mxlint: disable=SRC003\n") == []


def test_src004_per_step_sync_in_training_loop():
    """A blocking host fetch at step frequency (same innermost loop as the
    dispatch) collapses the engine's run-ahead window — flagged."""
    src = ("for batch in it:\n"
           "    loss = trainer.step(batch.data, batch.label)\n"
           "    tot += float(loss.asscalar())\n")
    got = rules(lint_source(src))
    assert "SRC004" in got
    # np.asarray of a produced value in the step loop is the same trap
    src2 = ("for b in it:\n"
            "    mod.forward_backward(b)\n"
            "    mod.update()\n"
            "    hist.append(np.asarray(mod.get_outputs()[0]))\n")
    assert "SRC004" in rules(lint_source(src2))


def test_src004_clean_cases():
    # epoch-boundary fetch: the sync's innermost loop (epoch) does not
    # itself dispatch steps — the batch loop does
    epoch = ("for epoch in range(10):\n"
             "    tot = None\n"
             "    for b in it:\n"
             "        loss = trainer.step(b.data, b.label)\n"
             "        tot = loss if tot is None else tot + loss\n"
             "    print(float(tot.asscalar()))\n")
    assert "SRC004" not in rules(lint_source(epoch))
    # periodic flush guard (`if step % k == 0`) is the documented fix
    guarded = ("for step, b in enumerate(it):\n"
               "    loss = trainer.step(b.data, b.label)\n"
               "    if step % 50 == 0:\n"
               "        print(float(loss.asscalar()))\n")
    assert "SRC004" not in rules(lint_source(guarded))
    # a sync in a non-training loop (no step dispatch) is not SRC004
    evalloop = ("for b in it:\n"
                "    preds.append(net(b).asnumpy())\n")
    assert "SRC004" not in rules(lint_source(evalloop))
    # inline suppression
    sup = ("for b in it:\n"
           "    trainer.step(b.data, b.label)\n"
           "    v = loss.asscalar()  # mxlint: disable=SRC001,SRC004\n")
    assert rules(lint_source(sup)) == set()


def test_src004_shipped_loops_clean():
    """The --self-check sweep: every examples/ script and the in-repo fit
    loops are SRC004-clean (the loops this repo tells users to copy must
    not per-step sync)."""
    from mxnet_tpu.analysis import lint_shipped_loops
    assert lint_shipped_loops() == []


def test_doc001_rule_table_in_sync():
    """Every registered rule has a docs/analysis.md row (and the check is
    part of --self-check, so a new rule cannot land undocumented)."""
    from mxnet_tpu.analysis import lint_rule_docs
    assert lint_rule_docs() == []


# ---------------------------------------------------------------------------
# hooks: Symbol.lint / Module.lint / simple_bind(lint=True)
# ---------------------------------------------------------------------------
def _mlp():
    data = sym.var("data")
    h = sym.FullyConnected(data, num_hidden=8, name="lint_fc1")
    a = sym.Activation(h, act_type="relu", name="lint_relu")
    out = sym.FullyConnected(a, num_hidden=4, name="lint_fc2")
    return sym.SoftmaxOutput(out, name="lint_softmax")


def test_clean_graph_is_silent_end_to_end():
    net = _mlp()
    assert net.lint(shapes={"data": (2, 16)}) == []


def test_module_lint_uses_bound_shapes():
    mod = mx.module.Module(_mlp(), data_names=("data",),
                           label_names=("lint_softmax_label",))
    findings = mod.lint()          # unbound: shape-dependent rules skip
    assert findings == []
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("lint_softmax_label", (2,))])
    assert mod.lint() == []


def test_simple_bind_lint_raises_on_error():
    data = sym.var("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="sb_fc")
    loss = sym.MakeLoss(sym.argmax(fc, axis=1, name="sb_argmax"),
                        name="sb_loss")
    with pytest.raises(MXNetError, match="GRF002"):
        loss.simple_bind(mx.cpu(), lint=True, data=(2, 8))
    # without lint the (broken) graph still binds as before
    ex = loss.simple_bind(mx.cpu(), data=(2, 8))
    assert ex is not None


def test_simple_bind_lint_warns_on_warning():
    data = sym.var("data")
    r = sym.Reshape(data, shape=(2, 16), name="sb_reshape")
    with pytest.warns(UserWarning, match="GRF005"):
        ex = r.simple_bind(mx.cpu(), lint=True, data=(2, 4, 4))
    assert ex.forward()[0].shape == (2, 16)


# ---------------------------------------------------------------------------
# reporters + CLI (satellite: CI tier-1 self-check)
# ---------------------------------------------------------------------------
def test_reporters_and_exit_codes():
    reg = FakeReg()
    reg.add(registry.Op("nodoc", lambda data: data))
    findings = lint_registry(registry=reg)
    text = render_text(findings)
    assert "REG009" in text and "nodoc" in text
    payload = json.loads(render_json(findings))
    assert payload["version"] == 1
    assert payload["findings"][0]["rule"] == "REG009"
    assert payload["counts"] == {"warning": 1}
    assert exit_code(findings, strict=False) == 0
    assert exit_code(findings, strict=True) == 1
    assert exit_code([], strict=True) == 0


def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "mxnet_tpu.analysis"]
                          + list(args), capture_output=True, text=True,
                          cwd=REPO, env=env, timeout=300)


def test_self_check_cli_clean_on_shipped_registry():
    """CI gate: new op registrations that break a registry invariant (or
    land without docs/coverage) fail here before anything executes."""
    proc = _run_cli("--self-check")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_source_lint_json(tmp_path):
    script = tmp_path / "bad_train.py"
    script.write_text("for b in loader:\n"
                      "    v = model(b).item()\n"
                      "    if b.shape[0] < 8:\n"
                      "        break\n")
    proc = _run_cli(str(script), "--json", "--strict")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    got = {f["rule"] for f in payload["findings"]}
    assert got == {"SRC001", "SRC002"}


# ---------------------------------------------------------------------------
# cost pass (mxcost): golden per-op models, liveness, transfer,
# collectives, XLA cross-validation, determinism
# ---------------------------------------------------------------------------
import jax
from jax import lax

from mxnet_tpu.analysis import cost as mxcost


def _xla_flops(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    d = c[0] if isinstance(c, list) else c
    return float(d.get("flops", 0.0)), float(d.get("transcendentals", 0.0))


def test_cost_dot_general_golden():
    r = mxcost.analyze_fn(lambda a, b: a @ b,
                          jnp.zeros((64, 128)), jnp.zeros((128, 256)))
    assert r.flops == 2 * 64 * 128 * 256
    assert r.per_primitive["dot_general"]["count"] == 1
    # batched matmul counts the batch dims too
    rb = mxcost.analyze_fn(jnp.matmul, jnp.zeros((4, 8, 16)),
                           jnp.zeros((4, 16, 32)))
    assert rb.flops == 2 * 4 * 8 * 16 * 32


def test_cost_conv_golden():
    x = jnp.zeros((8, 32, 32, 16))
    w = jnp.zeros((3, 3, 16, 32))

    def conv(a, b):
        return lax.conv_general_dilated(
            a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    r = mxcost.analyze_fn(conv, x, w)
    assert r.flops == 2 * 8 * 32 * 32 * 32 * 9 * 16


def test_cost_reduce_golden():
    r = mxcost.analyze_fn(lambda x: x.sum(axis=1), jnp.zeros((64, 1000)))
    assert r.flops == 64 * 1000 - 64
    rmax = mxcost.analyze_fn(lambda x: x.max(), jnp.zeros((128,)))
    assert rmax.flops == 127


def test_cost_elementwise_and_transcendental():
    r = mxcost.analyze_fn(lambda x: x + x, jnp.zeros((64, 1000)))
    assert r.flops == 64000 and r.transcendentals == 0
    re_ = mxcost.analyze_fn(jnp.exp, jnp.zeros((64, 1000)))
    assert re_.flops == 0 and re_.transcendentals == 64000


def test_cost_reshape_and_movement_are_free():
    r = mxcost.analyze_fn(lambda x: x.reshape(-1).T, jnp.zeros((16, 32)))
    assert r.flops == 0 and r.transcendentals == 0
    # but the bytes moved are counted
    assert r.bytes_read >= 16 * 32 * 4


def test_cost_collective_bytes_per_axis():
    n = 1 << 20
    r = mxcost.analyze_fn(lambda x: lax.psum(x, "data"),
                          jnp.zeros((n,), jnp.float32),
                          axis_env=[("data", 8)])
    # ring all-reduce: 2*(K-1)/K * payload
    assert r.collective_bytes_per_axis == {
        "data": int(2 * 7 * (n * 4) // 8)}
    # all_gather moves the OUTPUT around the ring: (K-1)/K x (K x input)
    rg = mxcost.analyze_fn(lambda x: lax.all_gather(x, "data"),
                           jnp.zeros((n,), jnp.float32),
                           axis_env=[("data", 8)])
    assert rg.collective_bytes_per_axis == {"data": int(7 * (n * 4))}
    # reduce_scatter moves the input: (K-1)/K x input
    rs = mxcost.analyze_fn(
        lambda x: lax.psum_scatter(x, "data", scatter_dimension=0,
                                   tiled=True),
        jnp.zeros((n,), jnp.float32), axis_env=[("data", 8)])
    assert rs.collective_bytes_per_axis == {"data": int(7 * (n * 4) // 8)}
    # grouped psum: ONE ring over the combined group (K = 8 x 4),
    # attributed per axis proportionally to (size - 1); the per-axis
    # sum equals the group total exactly
    gp = mxcost.analyze_fn(lambda x: lax.psum(x, ("data", "model")),
                           jnp.zeros((n,), jnp.float32),
                           axis_env=[("data", 8), ("model", 4)])
    total = int(2 * 31 * (n * 4) // 32)
    assert sum(gp.collective_bytes_per_axis.values()) == total
    assert set(gp.collective_bytes_per_axis) == {"data", "model"}
    assert gp.collective_bytes_per_axis["data"] == total - total * 3 // 10
    # ppermute prices one hop of the payload
    pp = mxcost.analyze_fn(
        lambda x: lax.ppermute(x, "data",
                               [(i, (i + 1) % 8) for i in range(8)]),
        jnp.zeros((n,), jnp.float32), axis_env=[("data", 8)])
    assert pp.collective_bytes_per_axis == {"data": n * 4}
    # axis of size 1 moves nothing
    r1 = mxcost.analyze_fn(lambda x: lax.psum(x, "data"),
                           jnp.zeros((n,)), axis_env=[("data", 1)])
    assert r1.collective_bytes == 0


def test_cost_transfer_classification():
    w = jnp.zeros((256, 256))
    x = jnp.zeros((8, 256))
    r = mxcost.analyze_fn(lambda w, x: x @ w, w, x, host_argnums=(1,))
    # only x is host-fed; the output (8,256) f32 is fetched
    assert r.transfer_h2d_bytes == 8 * 256 * 4
    assert r.transfer_d2h_bytes == 8 * 256 * 4
    assert r.input_bytes == (256 * 256 + 8 * 256) * 4


def test_cost_peak_hbm_liveness_and_donation():
    # chain: big intermediate dies after use; peak = inputs + biggest
    # simultaneous pair
    def f(x):
        a = x * 2.0        # 4 MiB live with x
        b = a.sum(axis=1)  # a dies after this
        return b

    x = jnp.zeros((1024, 1024))
    nb = 1024 * 1024 * 4
    r = mxcost.analyze_fn(f, x)
    # non-donated input resident + intermediate a + the (1024,) output
    assert r.peak_hbm_bytes == nb + nb + 1024 * 4
    # donating x does not change the peak here (x is live when a is
    # written) but a donated input must not outlive its last use:
    def g(x):
        a = x * 2.0
        b = a * 3.0        # x already dead if donated
        return b.sum()

    rd = mxcost.analyze_fn(g, x, donate_argnums=(0,))
    rn = mxcost.analyze_fn(g, x)
    assert rd.peak_hbm_bytes < rn.peak_hbm_bytes


def test_cost_nested_jit_is_inlined():
    inner = jax.jit(lambda a, b: a @ b)
    r = mxcost.analyze_fn(lambda a, b: inner(a, b) + 1.0,
                          jnp.zeros((32, 32)), jnp.zeros((32, 32)))
    assert r.per_primitive["dot_general"]["flops"] == 2 * 32 * 32 * 32


def test_cost_xla_cross_validation():
    """Modeled flops vs XLA's own post-compile cost_analysis() on CPU,
    within the documented XLA_FLOP_RTOL for the golden ops."""
    x = jnp.zeros((8, 32, 32, 16))
    w = jnp.zeros((3, 3, 16, 32))
    cases = [
        ("dot", lambda a, b: a @ b,
         (jnp.zeros((64, 128)), jnp.zeros((128, 256)))),
        ("conv", lambda a, b: lax.conv_general_dilated(
            a, b, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")), (x, w)),
        ("reduce", lambda a: a.sum(axis=1), (jnp.zeros((64, 1000)),)),
        ("add", lambda a: a + a, (jnp.zeros((64, 1000)),)),
        ("exp", jnp.exp, (jnp.zeros((64, 1000)),)),
    ]
    for name, fn, args in cases:
        modeled = mxcost.analyze_fn(fn, *args)
        xla_f, xla_t = _xla_flops(fn, *args)
        if xla_f:
            err = abs(modeled.flops - xla_f) / xla_f
            assert err <= mxcost.XLA_FLOP_RTOL, (name, modeled.flops,
                                                 xla_f, err)
        if xla_t:
            err = abs(modeled.transcendentals - xla_t) / xla_t
            assert err <= mxcost.XLA_FLOP_RTOL, (name, err)


def test_cost_determinism_and_self_check():
    from mxnet_tpu.analysis import cost_self_check
    a = mxcost.analyze_fn(lambda x: jnp.exp(x @ x.T).sum(),
                          jnp.zeros((32, 32))).as_dict()
    b = mxcost.analyze_fn(lambda x: jnp.exp(x @ x.T).sum(),
                          jnp.zeros((32, 32))).as_dict()
    assert a == b
    assert cost_self_check() == []


def test_cost_report_dict_shape():
    r = mxcost.analyze_fn(lambda a, b: a @ b, jnp.zeros((4, 8)),
                          jnp.zeros((8, 2)))
    d = r.as_dict()
    for key in ("flops", "transcendentals", "bytes_read", "bytes_written",
                "transfer_bytes", "collective_bytes_per_axis",
                "peak_hbm_bytes", "per_primitive", "n_eqns"):
        assert key in d
    assert "mxcost" in r.render()


# ---------------------------------------------------------------------------
# DST distributed-step rules
# ---------------------------------------------------------------------------
from mxnet_tpu.analysis import dist_lint


def _step_jaxpr(fn, *avals, axis=8):
    return jax.make_jaxpr(fn, axis_env=[("data", axis)])(*avals)


def test_dst001_missing_grad_reduction():
    """A step that applies raw per-replica grads leaves the new weights
    replica-varying."""
    w = jnp.zeros((16, 4))
    x = jnp.zeros((8, 16))

    def bad_step(w, x):
        g = jax.grad(lambda w: (x @ w).sum())(w)
        return w - 0.1 * g          # no pmean: replicas diverge

    closed = _step_jaxpr(bad_step, w, x)
    findings = dist_lint.lint_dist_step(
        closed, "data", varying_invars=[1], param_outvars=[0],
        param_names=["w"], axis_size=8)
    assert rules(findings) == {"DST001"}
    assert findings[0].subject == "w"

    def good_step(w, x):
        g = jax.grad(lambda w: (x @ w).sum())(w)
        return w - 0.1 * lax.pmean(g, "data")

    closed = _step_jaxpr(good_step, w, x)
    assert dist_lint.lint_dist_step(
        closed, "data", varying_invars=[1], param_outvars=[0],
        param_names=["w"], axis_size=8) == []


def test_dst002_duplicate_reduction():
    def dup_step(w, x):
        g = jax.grad(lambda w: (x @ w).sum())(w)
        g = lax.psum(g, "data")
        return w - lax.psum(g, "data")   # second psum: scales by K

    closed = _step_jaxpr(dup_step, jnp.zeros((16, 4)), jnp.zeros((8, 16)))
    findings = dist_lint.lint_dist_step(
        closed, "data", varying_invars=[1], param_outvars=[0],
        param_names=["w"], axis_size=8)
    assert rules(findings) == {"DST002"}


def test_dst004_subf32_collective_is_error():
    """Tightened DST004 (docs/precision.md): reducing bf16 over the
    data axis is an ERROR — cast-to-f32-then-reduce is the CORRECT
    mixed-precision spelling and traces clean."""
    # the broken spelling: bf16 on the wire
    closed = _step_jaxpr(lambda g: lax.psum(g, "data"),
                         jnp.zeros((1024,), jnp.bfloat16))
    findings = dist_lint.lint_dist_step(
        closed, "data", varying_invars=[0], param_outvars=[],
        axis_size=8)
    assert rules(findings) == {"DST004"}
    assert findings[0].severity == "error"
    assert "bfloat16" in findings[0].message

    # reduce-in-bf16-widen-after is the SAME wire bug
    closed_rs = _step_jaxpr(
        lambda g: lax.psum_scatter(g, "data", scatter_dimension=0,
                                   tiled=True).astype(jnp.float32),
        jnp.zeros((1024,), jnp.bfloat16))
    findings_rs = dist_lint.lint_dist_step(
        closed_rs, "data", varying_invars=[0], param_outvars=[],
        axis_size=8)
    assert "DST004" in rules(findings_rs)
    assert any(f.severity == "error" for f in findings_rs
               if f.rule_id == "DST004")

    # the correct spelling: widen BEFORE the collective — clean
    closed2 = _step_jaxpr(lambda g: lax.psum(g.astype(jnp.float32),
                                             "data"),
                          jnp.zeros((1024,), jnp.bfloat16))
    assert dist_lint.lint_dist_step(
        closed2, "data", varying_invars=[0], param_outvars=[],
        axis_size=8) == []

    # the retained widen flavor: an ALREADY-f32 operand widened to f64
    # right before the wire stays a warning (x64 scoped: jax silently
    # maps float64 to float32 otherwise)
    with jax.enable_x64(True):
        closed3 = _step_jaxpr(lambda g: lax.psum(g.astype(jnp.float64),
                                                 "data"),
                              jnp.zeros((1024,), jnp.float32))
    findings3 = dist_lint.lint_dist_step(
        closed3, "data", varying_invars=[0], param_outvars=[],
        axis_size=8)
    assert rules(findings3) == {"DST004"}
    assert findings3[0].severity == "warning"
    assert "float32->float64" in findings3[0].message


def test_dst005_baked_step_constant():
    lr = np.float32(0.1)        # python-side value baked into the trace

    def step(w, x):
        g = lax.pmean(jax.grad(lambda w: (x @ w).sum())(w), "data")
        return w - jnp.asarray(np.full((16, 4), lr)) * g

    closed = _step_jaxpr(step, jnp.zeros((16, 4)), jnp.zeros((8, 16)))
    assert closed.consts, "fixture should bake a constant"
    findings = dist_lint.lint_dist_step(
        closed, "data", varying_invars=[1], param_outvars=[0],
        param_names=["w"], axis_size=8)
    assert rules(findings) == {"DST005"}


def _make_trainer(**kwargs):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"))
    net.add(nn.Dense(10))
    net.initialize(mx.init.Xavier())
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, **kwargs)


def test_trainer_lint_clean():
    tr = _make_trainer()
    assert tr.lint(data_shape=(64, 16), label_shape=(64,)) == []
    # and the cost report of the same step is populated
    rep = tr.cost_report(data_shape=(64, 16), label_shape=(64,))
    assert rep.flops > 0 and rep.collective_bytes > 0
    assert rep.transfer_h2d_bytes == 64 * 16 * 4 + 64 * 4


def test_trainer_lint_catches_removed_grad_psum(monkeypatch):
    """The acceptance bug class: the gradient reduction deleted from
    DataParallelTrainer — every trainable param raises DST001."""
    from mxnet_tpu.parallel import step
    monkeypatch.setattr(step, "reduce_grads", lambda grads, axis: grads)
    tr = _make_trainer()
    findings = tr.lint(data_shape=(64, 16), label_shape=(64,))
    assert "DST001" in rules(findings)
    subjects = {f.subject for f in findings if f.rule_id == "DST001"}
    # all four MLP params (2x weight, 2x bias) desync, and the loss is
    # no longer the global mean either
    assert len(subjects) >= 4


def test_dst003_param_sharded_over_data_axis():
    from jax.sharding import PartitionSpec
    # shard only the 8-divisible params over the data axis so setup's
    # device_put succeeds and the *lint* is what reports the bug
    tr = _make_trainer(param_spec_fn=lambda name, shape:
                       PartitionSpec("data")
                       if int(shape[0]) % 8 == 0 else PartitionSpec())
    findings = tr.lint(data_shape=(64, 16), label_shape=(64,))
    assert "DST003" in rules(findings)
    msgs = " ".join(f.message for f in findings
                    if f.rule_id == "DST003")
    assert "data" in msgs


def test_dst003_batch_not_divisible():
    tr = _make_trainer()
    findings = tr.lint(data_shape=(30, 16), label_shape=(30,),
                       declared_axis_size=8)
    assert any(f.rule_id == "DST003" and f.subject == "data"
               for f in findings)


# ---------------------------------------------------------------------------
# budget gate: STATIC_BUDGETS.json + tools/update_budgets.py
# ---------------------------------------------------------------------------
def test_budget_gate_cli():
    """CI gate: the checked-in budgets pass on the seed models."""
    proc = _run_cli("--cost", "--budget",
                    os.path.join(REPO, "STATIC_BUDGETS.json"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_budget_gate_fails_on_flop_regression(tmp_path):
    """A budget whose flops entry is >10% below the modeled value is
    exactly what a flop-doubling PR produces: COST001, exit 2."""
    with open(os.path.join(REPO, "STATIC_BUDGETS.json")) as f:
        budget = json.load(f)
    budget["models"]["mlp_train_step"]["flops"] = int(
        budget["models"]["mlp_train_step"]["flops"] / 1.5)
    bad = tmp_path / "budgets.json"
    bad.write_text(json.dumps(budget))
    proc = _run_cli("--cost", "--budget", str(bad))
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "COST001" in proc.stdout

    # and a stale (too-high) budget is a COST002 warning: rc 0 plain,
    # rc 1 under --strict
    budget["models"]["mlp_train_step"]["flops"] = int(
        budget["models"]["mlp_train_step"]["flops"] * 4)
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(budget))
    proc = _run_cli("--cost", "--budget", str(stale))
    assert proc.returncode == 0 and "COST002" in proc.stdout
    proc = _run_cli("--cost", "--budget", str(stale), "--strict")
    assert proc.returncode == 1


def test_budget_gate_unknown_model(tmp_path):
    bad = tmp_path / "budgets.json"
    bad.write_text(json.dumps({
        "tolerance_pct": 10,
        "models": {"no_such_model": {"flops": 1}}}))
    proc = _run_cli("--cost", "--budget", str(bad))
    assert proc.returncode == 2
    assert "COST001" in proc.stdout and "no_such_model" in proc.stdout


def test_update_budgets_check_mode(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tool = os.path.join(REPO, "tools", "update_budgets.py")
    proc = subprocess.run(
        [sys.executable, tool, "--check"], capture_output=True,
        text=True, cwd=REPO, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # regenerating into a scratch path writes a loadable, gate-clean file
    out = tmp_path / "budgets.json"
    proc = subprocess.run(
        [sys.executable, tool, "--path", str(out)], capture_output=True,
        text=True, cwd=REPO, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    written = json.loads(out.read_text())
    assert written["models"] and written["tolerance_pct"] == 10.0
    proc = subprocess.run(
        [sys.executable, tool, "--check", "--path", str(out)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cost_json_schema_version():
    proc = _run_cli("--cost", "--json", "--model", "mlp_infer")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["schema_version"] == 6    # 6: the codegen section
    assert payload["version"] == 1
    assert "mlp_infer" in payload["cost"]
    assert payload["cost"]["mlp_infer"]["flops"] > 0
    assert payload["dist"]["rules"][0] == "DST001"


# ---------------------------------------------------------------------------
# cost hooks: Symbol / Module / serving ModelRunner
# ---------------------------------------------------------------------------
def test_symbol_and_module_cost_report():
    net = _mlp()
    rep = net.cost_report(shapes={"data": (2, 16)})
    assert rep is not None and rep.flops > 0
    # FC1 dominates: 2*2*16*8 + FC2 2*2*8*4
    assert rep.per_primitive["dot_general"]["flops"] == \
        2 * 2 * 16 * 8 + 2 * 2 * 8 * 4
    # host-fed = the names shapes were given for
    assert rep.transfer_h2d_bytes == 2 * 16 * 4

    mod = mx.module.Module(_mlp(), data_names=("data",),
                           label_names=("lint_softmax_label",))
    assert mod.cost_report() is None          # unbound: no shapes
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("lint_softmax_label", (2,))])
    mrep = mod.cost_report()
    assert mrep is not None and mrep.flops == rep.flops


def test_serving_modeled_cost_and_srv003():
    import mxnet_tpu.serving as serving
    data = sym.var("data")
    h = sym.FullyConnected(data, num_hidden=16, name="srv_fc1")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Activation(h, act_type="relu"),
                           num_hidden=3, name="srv_fc2"),
        name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))], for_training=False)
    mod.init_params(mx.init.Xavier())
    runner = serving.ModelRunner(mod, buckets=(1, 4), example_shape=(8,))
    cost = runner.modeled_cost()
    assert set(cost) == {1, 4}
    for b, row in cost.items():
        assert row["flops"] > 0 and row["peak_hbm_bytes"] > 0
    # flops scale with the bucket's batch
    assert cost[4]["flops"] > cost[1]["flops"]
    # SRV003: a cap below the modeled HBM flags at load
    with pytest.warns(UserWarning, match="SRV003"):
        serving.ModelRunner(mod, buckets=(1, 4), example_shape=(8,),
                            hbm_cap_bytes=16, warmup=False)
    # a generous cap stays silent (no SRV003 in any warning)
    import warnings as _warnings
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        serving.ModelRunner(mod, buckets=(1, 4), example_shape=(8,),
                            hbm_cap_bytes=1 << 30, warmup=False)
    assert not any("SRV003" in str(w.message) for w in caught)


def test_srv004_fleet_hbm_packing():
    from mxnet_tpu.analysis import lint_fleet_hbm
    # under cap / no cap: clean
    assert lint_fleet_hbm({"a": 600 << 20, "b": 300 << 20}, 1 << 30) == []
    assert lint_fleet_hbm({"a": 600 << 20, "b": 600 << 20}, 0) == []
    # over cap: one SRV004 error carrying the per-model modeled numbers
    found = lint_fleet_hbm({"a": 600 << 20, "b": 500 << 20, "c": None},
                           1 << 30)
    assert [f.rule_id for f in found] == ["SRV004"]
    assert found[0].severity == "error"
    msg = found[0].message
    assert "a=600.0 MiB" in msg and "b=500.0 MiB" in msg
    assert "1100.0 MiB" in msg and "1024.0 MiB" in msg
    assert "c" in msg        # unmodelable runners are named, not counted


def test_srv004_deadline_propagation():
    from mxnet_tpu.analysis import lint_deadline_propagation
    bad = (
        "def handler(payload):\n"
        "    deadline_ms = payload.get('deadline_ms')\n"
        "    return fleet.submit(payload['x'], tier='gold')\n")
    found = lint_deadline_propagation(source=bad)
    assert [f.rule_id for f in found] == ["SRV004"]
    assert "handler" in found[0].message
    # propagating the kwarg (or an opaque **kwargs splat) is clean, and
    # functions that never bind deadline_ms are out of scope
    good = bad.replace("tier='gold'", "tier='gold', deadline_ms=deadline_ms")
    splat = bad.replace("tier='gold'", "**kw")
    unbound = "def f(x):\n    return fleet.submit(x)\n"
    infer_bad = bad.replace(".submit", ".infer")
    assert lint_deadline_propagation(source=good) == []
    assert lint_deadline_propagation(source=splat) == []
    assert lint_deadline_propagation(source=unbound) == []
    assert [f.rule_id for f in lint_deadline_propagation(
        source=infer_bad)] == ["SRV004"]


def test_srv004_shipped_serving_sources_clean():
    """The --self-check sweep: every shipped serving request path
    (mxnet_tpu/serving/, tools/serve.py, examples/serving/) propagates
    deadline_ms to its submit/infer sinks."""
    from mxnet_tpu.analysis import lint_serving_sources
    assert lint_serving_sources() == []


def test_srv004_fleet_registration_refused_end_to_end():
    """ModelFleet.register is the enforcement point: the refusal error
    carries the rendered SRV004 finding."""
    import mxnet_tpu.serving as serving
    data = sym.var("data")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=3, name="sf4_fc"),
        name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2,))], for_training=False)
    mod.init_params(mx.init.Xavier())
    runner = serving.ModelRunner(mod, buckets=(1, 2), example_shape=(8,))
    hbm = runner.modeled_peak_hbm()
    assert hbm and hbm > 0
    fleet = serving.ModelFleet(hbm_cap_bytes=hbm)      # exactly one fits
    fleet.register("one", runner)
    with pytest.raises(MXNetError, match="SRV004"):
        fleet.register("two", runner, hbm_bytes=1)
    fleet.drain()


def test_srv005_wallclock_reads_flagged_and_suppressed():
    """SRV005: wall-clock calls in promotion/capacity decision code are
    errors; an inline justified disable (the measurement escape hatch)
    and non-clock receivers are clean."""
    from mxnet_tpu.analysis import lint_wallclock_reads
    bad = (
        "import time, datetime\n"
        "def decide(metrics):\n"
        "    t0 = time.monotonic()\n"
        "    stamp = datetime.datetime.now()\n"
        "    time.sleep(1.0)\n"
        "    return t0, stamp\n")
    found = lint_wallclock_reads(source=bad)
    assert [f.rule_id for f in found] == ["SRV005"] * 3
    assert all(f.severity == "error" for f in found)
    assert "time.monotonic" in found[0].message
    # the justified-measurement escape hatch: inline disable per line
    ok = bad.replace(
        "time.monotonic()",
        "time.monotonic()  # mxlint: disable=SRV005 - measuring")
    assert len(lint_wallclock_reads(source=ok)) == 2
    # an arbitrary object's .now()/.sleep() is not a clock read
    clean = ("def decide(sched):\n"
             "    return sched.now() + queue.sleep(3)\n")
    assert lint_wallclock_reads(source=clean) == []


def test_srv005_shipped_mlops_sources_clean():
    """The --self-check sweep: mxnet_tpu/mlops/ plus the decision CLIs
    (tools/promote.py, tools/capacity.py) carry no unjustified
    wall-clock reads — promotion reruns stay byte-identical."""
    from mxnet_tpu.analysis import lint_promotion_sources
    assert lint_promotion_sources() == []


def test_srv005_sweep_catches_injected_clock(tmp_path):
    """End-to-end through the sweep plumbing: a wall-clock read written
    into a fake mlops/ tree is found by the same path --self-check
    runs."""
    from mxnet_tpu.analysis.mlops_lint import lint_promotion_sources
    root = tmp_path / "mxnet_tpu"
    (root / "mlops").mkdir(parents=True)
    (root / "mlops" / "promote.py").write_text(
        "import time\n"
        "def evaluate():\n"
        "    if time.time() % 60 < 30:\n"
        "        return 'promote'\n")
    found = lint_promotion_sources(root=str(root))
    assert [f.rule_id for f in found] == ["SRV005"]
    assert "promote.py:3" in found[0].subject


def test_serving_stats_expose_modeled_cost():
    from mxnet_tpu.serving.stats import ServingStats  # noqa: F401  (sanity)
    import mxnet_tpu.serving as serving
    data = sym.var("data")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=3, name="ss_fc"),
        name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2,))], for_training=False)
    mod.init_params(mx.init.Xavier())
    runner = serving.ModelRunner(mod, buckets=(1, 2), example_shape=(8,))
    server = serving.Server(runner, port=0)
    host, port = server.start()
    try:
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=10)
        conn.request("GET", "/stats")
        resp = json.loads(conn.getresponse().read())
        assert set(resp["modeled_cost"]) == {"1", "2"}
        assert resp["modeled_cost"]["2"]["flops"] > 0
    finally:
        server.drain(timeout=10)


# ---------------------------------------------------------------------------
# TEL001: chaos probe sites vs the registered fault model (ISSUE 9)
# ---------------------------------------------------------------------------
def test_tel001_shipped_sites_clean():
    """Every probe site used in the shipped sources is registered in
    chaos.SITES, every registered site is probed somewhere, the docs
    table covers them all, and maybe_inject still stamps fired faults
    through telemetry.fault_event."""
    from mxnet_tpu.analysis import lint_chaos_sites
    assert lint_chaos_sites() == []


def test_tel001_detects_drift(tmp_path):
    """A probe site used-but-unregistered, a registered-but-unused
    fault model entry, and a non-literal site name all fire TEL001."""
    from mxnet_tpu.analysis import lint_chaos_sites
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "from resilience import chaos\n"
        "def f(name):\n"
        "    chaos.maybe_inject('totally.unregistered')\n"
        "    chaos.maybe_inject(name)\n")
    findings = lint_chaos_sites(root=str(pkg))
    subjects = {f.subject for f in findings}
    rules = {f.rule_id for f in findings}
    assert rules == {"TEL001"}
    # used but unregistered
    assert "totally.unregistered" in subjects
    # non-literal site argument
    assert any(s.endswith("mod.py:4") for s in subjects)
    # every registered site is "unused" under this synthetic root
    from mxnet_tpu.resilience.chaos import SITES
    assert set(SITES) <= subjects
    # the synthetic root has no chaos.py: the emission check fires too
    assert "chaos.maybe_inject" in subjects


def test_tel001_probe_site_scan_matches_fault_model():
    """probe_sites_used finds every shipped maybe_inject literal —
    including the drivers outside the package (tools/train_elastic.py
    train.step)."""
    from mxnet_tpu.analysis import probe_sites_used
    from mxnet_tpu.resilience.chaos import SITES
    used, dynamic = probe_sites_used()
    assert not dynamic
    assert set(used) == set(SITES)
    assert any(w.startswith("tools/train_elastic.py:")
               for w in used["train.step"])


# ---------------------------------------------------------------------------
# TEL002: attribution phase names vs docs table vs doctor hint map (ISSUE 10)
# ---------------------------------------------------------------------------
def test_tel002_shipped_phases_clean():
    """Every add_phase literal in the shipped sources is declared in
    attribution.PHASES, every declared phase is measured somewhere, the
    HINTS map and the docs/observability.md phase table cover exactly
    that set — both ways."""
    from mxnet_tpu.analysis import lint_attribution_phases
    assert lint_attribution_phases() == []


def test_tel002_phase_scan_matches_declaration():
    """attribution_phases_used finds every shipped add_phase literal;
    the declared PHASES/HINTS parse out of attribution.py by AST."""
    from mxnet_tpu.analysis import (attribution_phase_decls,
                                    attribution_phases_used)
    from mxnet_tpu.telemetry.attribution import HINTS, PHASES
    phases, hints = attribution_phase_decls()
    assert phases == list(PHASES)
    assert set(hints) == set(HINTS)
    used, dynamic = attribution_phases_used()
    assert not dynamic
    assert set(used) == set(PHASES)
    # the trainer is the instrumentation spine: every phase has at least
    # one call site in parallel/trainer.py
    for phase in PHASES:
        assert any("trainer.py" in w for w in used[phase]), (phase, used)


def test_tel002_detects_drift(tmp_path):
    """An undeclared phase measured in code, a declared-but-unmeasured
    phase, a HINTS/PHASES mismatch, a docs-table mismatch and a
    non-literal phase name all fire TEL002 (error)."""
    from mxnet_tpu.analysis import lint_attribution_phases
    from mxnet_tpu.analysis.findings import RULES, ERROR
    assert RULES["TEL002"][0] == ERROR
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "telemetry" / "attribution.py").write_text(
        "PHASES = ('never_measured', 'documented_less')\n"
        "HINTS = {'never_measured': 'hint', 'ghost_phase': 'stale'}\n")
    (pkg / "mod.py").write_text(
        "def f(attr, name):\n"
        "    attr.add_phase('undeclared_phase', 0.1)\n"
        "    attr.add_phase(name, 0.2)\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "| phase | measured where | doctor hint names |\n"
        "|---|---|---|\n"
        "| `never_measured` | somewhere | knob |\n"
        "| `only_in_docs` | nowhere | knob |\n")
    findings = lint_attribution_phases(root=str(pkg))
    assert {f.rule_id for f in findings} == {"TEL002"}
    subjects = {f.subject for f in findings}
    assert "undeclared_phase" in subjects       # measured, not declared
    assert "documented_less" in subjects        # declared, never measured
    assert "ghost_phase" in subjects            # stale HINTS key
    assert "only_in_docs" in subjects           # docs row with no phase
    assert any(s.endswith("mod.py:3") for s in subjects)  # non-literal
    # a PHASES tuple that is no longer a literal is itself a finding
    (pkg / "telemetry" / "attribution.py").write_text(
        "PHASES = tuple(x for x in ['a'])\n")
    findings = lint_attribution_phases(root=str(pkg))
    assert any(f.subject == "PHASES" for f in findings)


def test_tel002_in_self_check(tmp_path):
    """TEL002 drift fails `--self-check` end to end: tamper with the
    phase table in a copied docs file and sweep against it."""
    from mxnet_tpu.analysis import lint_attribution_phases
    import mxnet_tpu.analysis.telemetry_lint as tl
    import os
    doc = tmp_path / "observability.md"
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(tl.__file__))), os.pardir, "docs",
            "observability.md")) as f:
        text = f.read()
    doc.write_text(text.replace("| `input_wait` |", "| `renamed_wait` |"))
    findings = lint_attribution_phases(doc_path=str(doc))
    subjects = {f.subject for f in findings}
    assert "input_wait" in subjects      # phase lost its docs row
    assert "renamed_wait" in subjects    # docs row without a phase
