"""tools/bench_compare.py: a BENCH_r*.json lineage as a regression
gate (tier-1, ISSUE 10 satellite).

Contract points: a five-record lineage in the shape the driver
archives (two live rounds, two dead rounds with ``parsed: null``, one
carry-forward) passes, its carried-forward keys setting no bar; a
synthetically injected regression in a copied BENCH file exits nonzero
and names the metric; a malformed record fails fast; the gate math
(direction, relative vs absolute tolerance, no-prior vacuous pass) is
pinned at the function level.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_TOOL = os.path.join(_ROOT, "tools", "bench_compare.py")


def _load_tool():
    spec = importlib.util.spec_from_file_location("_bench_compare", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bc = _load_tool()

_R02 = {"metric": "resnet50_train_imgs_per_sec_per_chip", "value": 2391.37,
        "unit": "img/s/chip", "vs_baseline": 8.011,
        "pipeline_iter_imgs_per_sec": 701.12,
        "pipeline_fed_imgs_per_sec": 126.93, "pipeline_host_cores": 1,
        "int8_infer_imgs_per_sec": 1546.47}


def _record(n, parsed, rc=0):
    return {"n": n, "cmd": "bench", "rc": rc, "tail": "", "parsed": parsed}


@pytest.fixture
def lineage(tmp_path):
    """r01/r02 live, r03 timed out, r04 died, r05 re-emits r02 marked
    stale — the shapes the staleness protocol exists for."""
    records = [
        _record(1, {"metric": _R02["metric"], "value": 1254.31,
                    "unit": "img/s/chip", "vs_baseline": 4.202}),
        _record(2, _R02),
        _record(3, None, rc=124),
        _record(4, None, rc=1),
        _record(5, dict(_R02, stale=True, stale_from_round=2, stale_keys=[
            "int8_infer_imgs_per_sec", "pipeline_fed_imgs_per_sec",
            "pipeline_host_cores", "pipeline_iter_imgs_per_sec"])),
    ]
    files = []
    for rec in records:
        path = tmp_path / ("BENCH_r%02d.json" % rec["n"])
        path.write_text(json.dumps(rec))
        files.append(str(path))
    return files


def test_lineage_passes_check(lineage):
    out = subprocess.run(
        [sys.executable, _TOOL, "--check"] + lineage,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "bench lineage ok" in out.stdout


def test_staleness_protocol_sets_no_bar(lineage):
    """r05 re-emits r02's numbers as carry-forwards (stale/stale_keys);
    they must count as neither newest-live nor best-prior."""
    report = bc.compare(lineage)
    gates = report["gates"]
    # pipeline_fed was live ONLY in r02 (r05's copy is stale) -> no bar
    assert gates["pipeline_fed_imgs_per_sec"]["verdict"] == "no-prior"
    assert gates["pipeline_fed_imgs_per_sec"]["live_rounds"] == [2]
    # the primary metric was live in r01 and r02, r02 improved
    assert gates["value"]["verdict"] == "ok"
    assert gates["value"]["live_rounds"] == [1, 2]
    assert report["regressions"] == [] and report["malformed"] == []


def test_injected_regression_detected(lineage, tmp_path):
    """The acceptance criterion: copy a BENCH file, regress one gated
    metric -> exit nonzero, metric named."""
    rec = _record(6, dict(_R02, pipeline_fed_imgs_per_sec=50.0))  # was 126.93
    r06 = tmp_path / "BENCH_r06.json"
    r06.write_text(json.dumps(rec))
    files = lineage + [str(r06)]
    out = subprocess.run([sys.executable, _TOOL] + files,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout
    assert "REGRESSION" in out.stdout
    assert "pipeline_fed_imgs_per_sec" in out.stdout
    # an improvement (or within-tolerance dip) stays green
    rec["parsed"]["pipeline_fed_imgs_per_sec"] = 120.0  # -5.5% < 10% tol
    r06.write_text(json.dumps(rec))
    out = subprocess.run([sys.executable, _TOOL] + files,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout


def test_malformed_record_fails_fast(tmp_path):
    bad = tmp_path / "BENCH_r09.json"
    bad.write_text("{torn mid-write")
    out = subprocess.run([sys.executable, _TOOL, str(bad)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "MALFORMED" in out.stdout
    # structurally wrong (missing record keys) is malformed too
    bad.write_text(json.dumps({"unexpected": 1}))
    out = subprocess.run([sys.executable, _TOOL, str(bad)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout
    with pytest.raises(bc.MalformedRecord):
        bc.load_record(str(bad))


def test_fusion_keys_gated(tmp_path):
    """The r06 fusion-stage keys gate like any other: a slower fused
    update, a thinner modeled win, or a numerics drop all regress; the
    zero-slack numerics gate bites on ANY drop from 1.0."""
    def rec(n, parsed):
        return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": parsed}
    a = tmp_path / "BENCH_r06.json"
    b = tmp_path / "BENCH_r07.json"
    base = {"fused_optimizer_speedup_host": 2.2,
            "modeled_fusion_bytes_saved_pct": 70.6,
            "fusion_numerics_ok": 1.0}
    a.write_text(json.dumps(rec(6, base)))
    b.write_text(json.dumps(rec(7, dict(base))))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == []
    # speedup collapse past 10% regresses
    b.write_text(json.dumps(rec(7, dict(base,
                                        fused_optimizer_speedup_host=1.5))))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == ["fused_optimizer_speedup_host"]
    # modeled bytes-saved is near-deterministic: 2% rel
    b.write_text(json.dumps(rec(
        7, dict(base, modeled_fusion_bytes_saved_pct=60.0))))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == ["modeled_fusion_bytes_saved_pct"]
    # numerics: zero slack — any drop from 1.0 regresses
    b.write_text(json.dumps(rec(7, dict(base, fusion_numerics_ok=0.0))))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == ["fusion_numerics_ok"]


def test_precision_keys_gated(tmp_path):
    """The r08 precision-stage keys gate like any other: a slower
    fused loss-scaled update, a fatter modeled bf16/f32 HBM ratio, a
    widening bf16 convergence gap, slower int8-KV decode, or a
    numerics drop all regress — the abs-slack gates bite past their
    documented slack, the zero-slack one on ANY drop from 1.0."""
    def rec(n, parsed):
        return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": parsed}
    a = tmp_path / "BENCH_r08.json"
    b = tmp_path / "BENCH_r09.json"
    base = {"fused_loss_scaled_speedup_host": 2.5,
            "bf16_modeled_hbm_ratio": 0.66,
            "bf16_convergence_delta": 0.006,
            "int8_kv_decode_tokens_per_sec_host": 2200.0,
            "precision_numerics_ok": 1.0}
    a.write_text(json.dumps(rec(8, base)))
    b.write_text(json.dumps(rec(9, dict(base))))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == []
    # fused loss-scaled speedup collapse past 10% regresses
    b.write_text(json.dumps(rec(
        9, dict(base, fused_loss_scaled_speedup_host=1.8))))
    assert bc.compare([str(a), str(b)])["regressions"] == [
        "fused_loss_scaled_speedup_host"]
    # modeled HBM ratio creeping up past the 0.02 abs slack regresses
    # (the f32 masters leaking out of the shard looks exactly like this)
    b.write_text(json.dumps(rec(
        9, dict(base, bf16_modeled_hbm_ratio=0.75))))
    assert bc.compare([str(a), str(b)])["regressions"] == [
        "bf16_modeled_hbm_ratio"]
    # a widening bf16-vs-f32 trajectory gap past +0.005 regresses
    b.write_text(json.dumps(rec(
        9, dict(base, bf16_convergence_delta=0.05))))
    assert bc.compare([str(a), str(b)])["regressions"] == [
        "bf16_convergence_delta"]
    # int8-KV decode throughput collapse past 10% regresses
    b.write_text(json.dumps(rec(
        9, dict(base, int8_kv_decode_tokens_per_sec_host=1500.0))))
    assert bc.compare([str(a), str(b)])["regressions"] == [
        "int8_kv_decode_tokens_per_sec_host"]
    # numerics: zero slack — any drop from 1.0 regresses
    b.write_text(json.dumps(rec(
        9, dict(base, precision_numerics_ok=0.0))))
    assert bc.compare([str(a), str(b)])["regressions"] == [
        "precision_numerics_ok"]


def test_gate_math_directions(tmp_path):
    """lower_abs gates (overhead pcts near zero) use absolute slack;
    higher gates use relative tolerance."""
    def rec(n, parsed):
        return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": parsed}
    a = tmp_path / "BENCH_r01.json"
    b = tmp_path / "BENCH_r02.json"
    a.write_text(json.dumps(rec(1, {"checkpoint_overhead_pct": 0.5,
                                    "serving_reqs_per_sec": 100.0})))
    # overhead 0.5 -> 2.0 is within +2.0 abs slack; reqs/s -15% is not
    b.write_text(json.dumps(rec(2, {"checkpoint_overhead_pct": 2.0,
                                    "serving_reqs_per_sec": 85.0})))
    report = bc.compare([str(a), str(b)])
    assert report["gates"]["checkpoint_overhead_pct"]["verdict"] == "ok"
    assert report["gates"]["serving_reqs_per_sec"]["verdict"] == \
        "regression"
    assert report["regressions"] == ["serving_reqs_per_sec"]
    # overhead past the absolute slack regresses
    b.write_text(json.dumps(rec(2, {"checkpoint_overhead_pct": 3.5,
                                    "serving_reqs_per_sec": 100.0})))
    report = bc.compare([str(a), str(b)])
    assert report["regressions"] == ["checkpoint_overhead_pct"]
    # --tolerance-scale widens every gate
    report = bc.compare([str(a), str(b)], tolerance_scale=2.0)
    assert report["regressions"] == []
