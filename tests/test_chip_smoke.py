"""The chip bring-up's host-checkable contracts (docs: README "Testing"):
no TPU -> ``chip_smoke.py`` fails and says what it found; the compile cache
is placed from outside; an accelerator context does not resolve to the
host unless the CPU was asked for; one compile-or-interpret decision."""
import importlib.util
import os
import re
import subprocess
import sys

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import context
from mxnet_tpu.ops import fused_optimizer, generated_kernels, pallas_kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode not in (0, 2, 3)    # 2/3 are the chip tool's own
    assert out.stdout == ""
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr


def test_compilation_cache_is_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert mx.base.use_compilation_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert mx.base.use_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # one site sets it, whatever the entry point
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored_dirs = {line.strip().rstrip("/") for line in f
                        if line.strip().endswith("/")} | {".git"}
    sites = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in ignored_dirs]
        for name in files:
            if name.endswith(".py") and not name.startswith("test_"):
                with open(os.path.join(root, name)) as f:
                    if re.search(r'update\(\s*"jax_compilation_cache_dir"',
                                 f.read()):
                        sites.append(os.path.relpath(
                            os.path.join(root, name), REPO))
    assert sites == [os.path.join("mxnet_tpu", "base.py")]


def test_accelerator_context_does_not_fall_back_to_host(monkeypatch):
    # under the test pin (JAX_PLATFORMS=cpu) the CPU stands in
    assert mx.tpu(0).jax_device().platform == "cpu"
    monkeypatch.setattr(context, "_ACCEL_CACHE", None)
    monkeypatch.setattr(context, "_cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        mx.tpu(0).jax_device()


def test_one_compile_or_interpret_decision():
    assert fused_optimizer.resolve_interpret is pallas_kernels.resolve_interpret
    assert generated_kernels.resolve_interpret is \
        pallas_kernels.resolve_interpret
    assert pallas_kernels.resolve_interpret(True) is True
    assert pallas_kernels.resolve_interpret(False) is False
    assert pallas_kernels.resolve_interpret(None) is True      # CPU here
    ops = os.path.join(REPO, "mxnet_tpu", "ops")
    for name in sorted(os.listdir(ops)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(ops, name)) as f:
            src = f.read()
        # only the resolver asks for the backend, no call site hard-codes
        # the flag, and every module with a pallas_call goes through it
        assert ("default_backend()" in src) == (name == "pallas_kernels.py")
        assert not re.search(r"interpret\s*=\s*(True|False|not )", src), name
        if "pallas_call(" in src:
            assert "resolve_interpret(" in src, name


def test_bench_exit_code_needs_a_live_tpu_measurement():
    spec = importlib.util.spec_from_file_location(
        "_bench", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rec = bench._Record(60)
    rec.stage("host_stage", 1, lambda: {"some_host_key": 1.0})
    assert rec.exit_code() == 1           # a host stage alone is not success
    rec.tpu_live = True
    assert rec.exit_code() == 0

    def boom():
        raise RuntimeError("stage broke")
    rec.stage("broken", 1, boom)
    assert rec.result["broken_error"] == "stage broke"
    assert rec.exit_code() == 1           # a failed stage fails the run
