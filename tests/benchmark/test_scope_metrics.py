"""The per-layer metrics of ISSUE 34, on the CPU: the language-model
step's scopes (``scope_reduce.scope_ms``), what no scope holds, the scan's
kernel pair and its share of its roofline; the breakdown's names; and the
two repairs to what they stand on (an empty ``stats_norms``, the kernel
found by the instruction's own name).  Nothing here is a measurement.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
PLANE = "/device:TPU:0"
STEP = "jit(replica_step)/"
BWD = STEP + "transpose(jvp(l%d))/l%d/checkpoint/"
RERUN = BWD + "rematted_computation/"
FWD_KERNEL = "mamba_mixer/ssm_scan/jit(_forward)/_ssd_scan_fwd_kernel/" \
    "pallas_call"
BWD_KERNEL = "mamba_mixer/ssm_scan/jit(_backward)/_ssd_scan_bwd_kernel/" \
    "pallas_call"
CALL = "%%%s = (bf16[1,4096,4096], f32[16,4096,128]) custom-call(bf16[1,128" \
    ",4096] %%get-tuple-element.7), custom_call_target=\"tpu_custom_call\""


def _load(name, folder=""):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def sr():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import scope_reduce
    return scope_reduce


@pytest.fixture(scope="module")
def tr():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import trace_reduce
    return trace_reduce


@pytest.fixture(scope="module")
def granite():
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return (_load("granite_hybrid", "families"), config,
            {k: config[k] for k in config["rehearsal_size"]}, peaks)


def _lm_ops(fwd_ns=40, bwd_ns=80):
    """One device's operations over two steps of a language model's step,
    in ns: a Mamba layer ``l0`` and an attention layer ``l5``, forward,
    re-run and backward, with the scan's kernels ``fwd_ns`` and ``bwd_ns``
    long.  ``fusion.2`` reads the forward kernel's result and names it."""
    rows = [
        ("%fusion.1 = bf16[8] fusion(...)", 100,
         STEP + "jvp(l0)/mamba_mixer/ssm_in_proj/dot_general"),
        (CALL % "_ssd_scan_fwd_kernel.3", fwd_ns,
         STEP + "jvp(l0)/" + FWD_KERNEL),
        ("%fusion.2 = bf16[8] fusion(bf16[8] %_ssd_scan_fwd_kernel.3)", 10,
         STEP + "jvp(l0)/mamba_mixer/ssm_scan/mul"),
        ("%fusion.3 = bf16[8] fusion(...)", 100,
         STEP + "jvp(l0)/gated_mlp/dot_general"),
        ("%fusion.4 = bf16[8] fusion(...)", 50,
         STEP + "jvp(l5)/attention/bqkge,bske->bkgqs/dot_general"),
        ("%fusion.5 = f32[] fusion(...)", 30,
         STEP + "jvp(lm_head_loss)/btd,vd->btv/dot_general"),
        ("%fusion.6 = bf16[8] fusion(...)", 50,
         RERUN % (5, 5) + "attention/checkpoint/add"),
        (CALL % "_ssd_scan_bwd_kernel.9", bwd_ns, BWD % (0, 0) + BWD_KERNEL),
        (CALL % "_ssd_scan_fwd_kernel.4", fwd_ns,
         RERUN % (0, 0) + FWD_KERNEL),
        ("%fusion.7 = bf16[8] fusion(...)", 200,
         BWD % (0, 0) + "gated_mlp/dot_general"),
        ("%fusion.8 = bf16[8] fusion(...)", 10, STEP + "jvp(embed)/gather"),
        ("%fusion.9 = f32[8] fusion(...)", 10,
         STEP + "optimizer_update/sub"),
        ("%copy.12 = bf16[8] copy(...)", 30, ""),
        ("%convert.1 = bf16[8] convert(...)", 10,
         "jit(convert_element_type)/convert_element_type"),
    ]
    ops, at = [], 0
    for name, ns, scope in rows:
        ops.append((name, at, at + ns, scope))
        at += ns
    return ops


def _run(sr, monkeypatch, ops, **more):
    """A run as ``run.py`` hands it to the readers, its trace ``ops``."""
    reduced = {"devices": {PLANE: ops}, "modules": {PLANE: []}, "spans": []}
    monkeypatch.setattr(sr, "of_run", lambda run: reduced)
    trace = {"devices": {PLANE: [op[:3] for op in ops]}, "steps": []}
    return dict({"trace": trace, "traced_steps": 2, "peaks": None,
                 "family": None, "config": {}, "size": {}}, **more)


class Family:
    """A family whose scan takes 20 ns forward (its bytes bound it) and
    40 ns backward (its operations do) at the peaks below."""
    PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}

    @staticmethod
    def ssd_scan_forward_work(config, size):
        return 10e3, 20

    @staticmethod
    def ssd_scan_backward_work(config, size):
        return 40e3, 10


# ns over the two steps of ``_lm_ops()``; kernels 40 + 80 + 40 of 160
READINGS = {"ssm_scan_device_ms": (40 + 10 + 80 + 40) / 2 / 1e6,
            "mamba_device_ms": (100 + 40 + 10 + 80 + 40) / 2 / 1e6,
            "attention_device_ms": (50 + 50) / 2 / 1e6,
            "mlp_device_ms": (100 + 200) / 2 / 1e6,
            "head_loss_device_ms": 30 / 2 / 1e6,
            "unscoped_device_ms": (30 + 10) / 2 / 1e6,
            "ssd_scan_kernel_us": (40 + 80 + 40) / 2 / 1e3,
            "ssd_scan_roofline": 100.0 * (20 + 40 + 20) / (40 + 80 + 40)}


@pytest.mark.parametrize("name", list(READINGS))
def test_the_eight_on_a_hand_made_step(sr, monkeypatch, name):
    run = _run(sr, monkeypatch, _lm_ops(), peaks=Family.PEAKS, family=Family)
    assert _load(name, "layer_metrics").read(run) == pytest.approx(
        READINGS[name])


def test_the_scopes_and_what_is_outside_them_add_up_to_the_busy_time(
        sr, monkeypatch):
    run = _run(sr, monkeypatch, _lm_ops())
    read = {name: _load(name, "layer_metrics").read(run)
            for name in READINGS if name.endswith("_device_ms")}
    whole = sum(v for k, v in read.items() if k != "ssm_scan_device_ms") \
        + sr.scope_ms(run, ("embed", "optimizer_update"))
    assert whole == pytest.approx(_load(
        "step_device_ms", "layer_metrics").read(run))
    assert sr.scope_ms(run, ("no_such_scope",)) is None


@pytest.mark.parametrize("name", list(READINGS))
def test_the_eight_say_nothing_on_the_empty_run(name):
    run = {"trace": None, "traced_steps": 0, "attribution": None,
           "memory_peak_bytes": 0, "peaks": None, "family": None,
           "window": {"steps": 0, "items": 0, "seconds": 0.0, "chips": 1},
           "config": {}, "size": {}}
    assert _load(name, "layer_metrics").read(run) is None
    # a rehearsal on the CPU: a trace, but no device plane in it
    run.update(trace={"devices": {}, "steps": [("bench_step", 0, 1)]},
               traced_steps=40, peaks=Family.PEAKS, family=Family)
    assert _load(name, "layer_metrics").read(run) is None


def test_a_convolutional_step_reads_unscoped_and_none_of_the_other_seven(
        sr, monkeypatch):
    net = "jit(pure_step)/transpose(jvp(net))/stage1/conv2d0/"
    ops = [("%fusion.1 = bf16[8] fusion(...)", 0, 100,
            net + "conv_general_dilated"),
           ("%_fused_sgd_mom_kernel.1 = f32[8] custom-call(...)", 100, 110,
            "jit(pure_step)/optimizer_update/_fused_sgd_mom_kernel/"
            "pallas_call"),
           ("%copy.1 = bf16[8] copy(...)", 110, 130, "")]
    run = _run(sr, monkeypatch, ops, peaks=Family.PEAKS,
               family=_load("gluon_resnet_v1", "families"))
    read = {name: _load(name, "layer_metrics").read(run)
            for name in READINGS}
    assert read.pop("unscoped_device_ms") == pytest.approx(20 / 2 / 1e6)
    assert set(read.values()) == {None}
    # nothing scoped at all (a commit before the scopes): as silent as
    # ``scoped_device_share``, whose complement it is
    run = _run(sr, monkeypatch, [op[:3] + ("",) for op in ops])
    assert _load("unscoped_device_ms", "layer_metrics").read(run) is None
    assert _load("scoped_device_share", "layer_metrics").read(run) is None


# -- the scan's kernels and their roofline ----------------------------------
def test_the_scans_work_is_counted_from_shapes_alone(granite):
    """One chip's 4,096 tokens: 64 heads of 64, state 128, chunks of 256.
    The multiply-adds are the scan's term of ``forward_macs_per_token``;
    the bytes every operand and result once."""
    family, config, size, peaks = granite
    tokens, n, inner, heads, chunk = 4096, 128, 64 * 64, 64, 256
    macs = tokens * ((n + inner) * (chunk + 1) / 2 + 2 * inner * n)
    small = (2 * n + inner) * 2 + 2 * heads * 4      # C, B, x; dt, cum
    states = tokens // chunk * inner * n * 4
    forward = family.ssd_scan_forward_work(config, size)
    backward = family.ssd_scan_backward_work(config, size)
    assert forward == (2 * macs, tokens * (small + inner * 2) + states)
    assert backward == (4 * macs,
                        tokens * (2 * small + inner * 2) + states)
    assert forward == (13036421120.0, 104857600)
    assert backward == (26072842240.0, 142606336)
    # the same term as the whole count holds: one Mamba layer's count less
    # its projections, its feed-forward and the head
    d, f, v = config["hidden_size"], config["shared_intermediate_size"], \
        config["vocab_size"]
    one = dict(size, num_hidden_layers=1)
    assert config["layer_types"][0] == "mamba"
    rest = d * v + d * (2 * inner + 2 * n + heads) + inner * d + 3 * d * f
    assert (family.forward_macs_per_token(config, one) - rest) * tokens \
        == macs
    # bytes bound the forward run on a v5e
    assert forward[1] / peaks["hbm_bytes_per_s"] > \
        forward[0] / peaks["bf16_flops_per_s"]


@pytest.mark.parametrize("slower,share", [(1, 100.0), (2, 50.0), (4, 25.0)])
def test_the_roofline_share_of_kernels_that_take_their_roofline_time(
        sr, monkeypatch, granite, slower, share):
    """A trace in which each kernel run takes exactly ``slower`` times the
    least a v5e could: the share reads 100 / ``slower`` and never above
    100 for a kernel that is no faster than the chip."""
    family, config, size, peaks = granite
    least_ns = [1e9 * max(flops / peaks["bf16_flops_per_s"],
                          moved / peaks["hbm_bytes_per_s"])
                for flops, moved in (family.ssd_scan_forward_work(config, size),
                                     family.ssd_scan_backward_work(config,
                                                                   size))]
    assert [round(ns) for ns in least_ns] == [128031, 174123]
    run = _run(sr, monkeypatch,
               _lm_ops(slower * least_ns[0], slower * least_ns[1]),
               peaks=peaks, family=family, config=config, size=size)
    assert _load("ssd_scan_roofline", "layer_metrics").read(run) == \
        pytest.approx(share)
    assert _load("ssd_scan_kernel_us", "layer_metrics").read(run) == \
        pytest.approx(slower * (2 * least_ns[0] + least_ns[1]) / 2 / 1e3)


def test_a_kernel_is_found_by_the_instructions_own_name(tr):
    ops = [op[:3] for op in _lm_ops()]
    run = {"trace": {"devices": {PLANE: ops, "/device:TPU:1": ops}},
           "traced_steps": 2}
    kernels = ("_ssd_scan_fwd_kernel", "_ssd_scan_bwd_kernel")
    per_chip = tr.kernel_runs(run, kernels)
    assert per_chip == [[(kernels[0], 40), (kernels[1], 80),
                         (kernels[0], 40)]] * 2
    assert tr.kernel_runs(run, ("_fused_sgd_mom_kernel",)) is None
    assert tr.own_name(ops[2][0]) == "fusion.2"     # it only reads one
    run["trace"]["devices"]["/device:TPU:1"] = ops[:1]   # a chip without
    assert tr.kernel_runs(run, kernels) is None
    assert tr.kernel_runs({"trace": None}, kernels) is None


# -- the breakdown ------------------------------------------------------------
def test_top_ops_names_an_operation_by_where_it_is_from(sr, tr):
    """Innermost two program scopes and the phase before the instruction's
    own name; ``checkpoint`` and ``rematted_computation`` are jax's, not
    the program's.  Summed by the whole name, the ten longest."""
    ops = _lm_ops()
    named = tr.top_ops(ops + [(n, s + 1000, e + 1000, scope)
                              for n, s, e, scope in ops[:1]])
    assert named[:4] == [
        ["mamba_mixer/ssm_in_proj fwd fusion.1", 200 / 1e9],    # 2 x 100
        ["l0/gated_mlp bwd fusion.7", 200 / 1e9],
        ["l0/gated_mlp fwd fusion.3", 100 / 1e9],
        ["ssm_scan/_ssd_scan_bwd_kernel bwd _ssd_scan_bwd_kernel.9",
         80 / 1e9]]
    assert len(named) == 10
    names = [name for name, _ in tr.top_ops(ops, 14)]
    assert "l5/attention bwd fusion.6" in names
    assert "ssm_scan/_ssd_scan_fwd_kernel bwd _ssd_scan_fwd_kernel.4" in names
    assert "optimizer_update upd fusion.9" in names
    assert "(unscoped) other copy.12" in names
    assert "(unscoped) other convert.1" in names
    assert sr.where_from(STEP + "jvp(lm_head_loss)/btd,vd->btv/dot_general") \
        == "lm_head_loss/btd,vd->btv fwd"
    # without op_names (``trace_reduce.load``'s operations): as before
    assert tr.top_ops([op[:3] for op in ops], 2) == [
        ["fusion.7", 200 / 1e9], ["fusion.1", 100 / 1e9]]


# -- the comparison takes a family without running statistics ---------------
def test_compare_takes_an_empty_stats_norms():
    correctness = _load("correctness")
    leaves = {"a": 1.0, "b": 2.0, "c": 4.0}
    reference = {"losses": [9.4, 9.3], "grad_norms": dict(leaves),
                 "update_norms": dict(leaves), "stats_norms": {}}
    program = {"losses": [9.4, 9.3], "grad_norms": dict(leaves, b=2.1),
               "update_norms": dict(leaves), "stats_norms": {}}
    numbers = correctness.compare(program, reference)
    assert "stats_norm_gap" not in numbers
    assert numbers["grad_norm_gap"] == (pytest.approx(0.05), "b")
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 0.1, "update_norm_gap": 0.1}
    assert set(correctness.compare(program, reference, limits)) == set(limits)
    correct, rows = correctness.verdict(
        correctness.compare(program, reference, limits), limits)
    assert correct and [r["name"] for r in rows] == list(limits)
    # a limit on what was not handed in has not been met
    limits["stats_norm_gap"] = 0.1
    correct, rows = correctness.verdict(
        correctness.compare(program, reference, limits), limits)
    assert not correct
    assert rows[-1] == {"name": "stats_norm_gap", "value": 1e30,
                        "limit": 0.1, "at": "nothing to compare"}
    # with statistics handed in, as before
    reference["stats_norms"] = program["stats_norms"] = {"m": 3.0}
    assert correctness.compare(program, reference)["stats_norm_gap"] == (
        0.0, "m")
