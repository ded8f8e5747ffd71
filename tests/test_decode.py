"""mxnet_tpu.transformer.decode: the KV-cached autoregressive program
(ISSUE 17).  Contract points:

(a) a paged-cache greedy decode matches the sequential no-cache
    full-forward reference EXACTLY (the cache changes latency, never
    tokens), eos semantics included;
(b) prefill bucket padding is exact — the same prompt through different
    length buckets yields bitwise-identical next-token logits
    (causality makes the padded tail invisible to real positions);
(c) the phases are analyzable as-spelled: ``make_jaxpr(axis_env=...)``
    over the tensor-parallel plan traces ``decode_replica`` with the
    expected cache scatters and model-axis collectives;
(d) the recompile contract: after the AOT warmup ladder, steady-state
    mixed-length traffic grows the jit cache by ZERO entries;
(e) the DECODE_WRITE_KV mutation seam (skipping the cache write — the
    classic stale-KV bug) fails the STATIC_BUDGETS gate rc=2 from a
    subprocess with the divergence named.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.mesh import MeshPlan
from mxnet_tpu.serving.decode import DecodeRunner, PagePool
from mxnet_tpu.transformer import TransformerLMConfig
from mxnet_tpu.transformer.decode import DecodeProgram

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CFG = dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
           seq_len=32)


def _runner(slots=2, buckets=(8, 16, 32), warmup=True, page_size=8):
    prog = DecodeProgram(TransformerLMConfig(**CFG), page_size=page_size)
    return DecodeRunner(prog, prog.program.init_params(0), slots=slots,
                        prefill_buckets=buckets, warmup=warmup)


@pytest.fixture(scope="module")
def runner():
    return _runner()


# -- (a) exact numerics ------------------------------------------------------
def test_cached_generate_matches_reference_exact(runner):
    rng = np.random.RandomState(0)
    for n in (1, 3, 7, 8, 9, 15, 20):
        prompt = rng.randint(1, CFG["vocab_size"], size=n).astype(np.int32)
        cached = runner.generate(prompt, 6)
        ref = runner.reference_decode(prompt, 6)
        assert np.array_equal(cached, ref), \
            "paged decode diverged at prompt len %d: %r vs %r" \
            % (n, cached, ref)
    assert runner.pool.pages_in_use == 0


def test_eos_stops_generation(runner):
    prompt = np.arange(1, 6, dtype=np.int32)
    free_run = runner.reference_decode(prompt, 8)
    eos = int(free_run[-1])                       # guaranteed to appear
    stop = int(np.argmax(free_run == eos)) + 1    # ... first, here
    cached = runner.generate(prompt, 8, eos_token=eos)
    ref = runner.reference_decode(prompt, 8, eos_token=eos)
    assert np.array_equal(cached, ref)
    assert cached[-1] == eos and len(cached) == stop
    assert np.array_equal(cached, free_run[:stop])


# -- (b) bucket-padding equivalence ------------------------------------------
def test_prefill_padding_equivalence():
    """Same prompt, three different bucket ladders: the same logits (the
    causal mask makes the padded tail invisible).

    Buckets 8 and 16 are bitwise equal.  Bucket 32 is equal to a few f32
    ulps only: XLA's CPU backend picks its dot kernel — and with it the
    accumulation order over the contraction — from the operand shapes
    (jax 0.9.0: the ``bqhd,bkhd->bhqk`` scores of the same 5 rows differ
    in the last bits between T=16 and T=32), so it lands ~1e-6 from
    buckets 8/16 on logits of magnitude ~1.7.  A padded position leaking
    through the mask would move them by O(0.1), five orders above this
    tolerance."""
    prompt = np.array([3, 9, 1, 27, 14], np.int32)
    outs = []
    for bucket in (8, 16, 32):
        r = _runner(buckets=(bucket,), warmup=False)
        outs.append(r.prefill(prompt, np.zeros(0, np.int32)))
    assert np.array_equal(outs[0], outs[1])
    np.testing.assert_allclose(outs[2], outs[0], rtol=1e-5, atol=1e-5)


# -- geometry validation -----------------------------------------------------
def test_decode_program_rejects_bad_geometry():
    cfg = TransformerLMConfig(**CFG)
    with pytest.raises(ValueError):   # batch is the host's concern
        DecodeProgram(cfg, plan=MeshPlan(data=2))
    with pytest.raises(ValueError):   # page_size must divide seq_len
        DecodeProgram(cfg, page_size=5)
    with pytest.raises(MXNetError):   # buckets must be page multiples
        _runner(buckets=(6,), warmup=False)
    with pytest.raises(MXNetError):   # page 0 is scratch: >= 2 pages
        PagePool(1, 8, 1024)


# -- (c) the analysis surface ------------------------------------------------
@pytest.mark.analysis
def test_tp_decode_replica_traces_with_expected_structure():
    """The SAME ``decode_replica`` spelling the runtime jits feeds
    ``make_jaxpr(axis_env=...)``: 2 cache scatters per layer (K and V)
    and the model-axis collectives (row-parallel psum + the vocab
    all-gather) are visible in the traced program."""
    import jax

    plan = MeshPlan(data=1, model=2)
    prog = DecodeProgram(TransformerLMConfig(**CFG), plan=plan,
                         page_size=8)
    avals = prog.decode_avals(n_pages=9, slots=2)
    closed = jax.make_jaxpr(prog.decode_replica,
                            axis_env=plan.axis_env())(*avals)
    # collectives can sit inside nested sub-jaxprs — walk them all
    def prims(jaxpr):
        for e in jaxpr.eqns:
            yield e.primitive.name
            for v in e.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    for p in prims(sub):
                        yield p
    names = list(prims(closed.jaxpr))
    scatters = sum(1 for p in names if "scatter" in p)
    assert scatters >= 2 * prog.cfg.n_layers, \
        "want >= %d cache scatters, traced %d" \
        % (2 * prog.cfg.n_layers, scatters)
    assert any("psum" in p for p in names), sorted(set(names))
    assert any("all_gather" in p for p in names), sorted(set(names))
    # logits replicate the full vocab on every rank
    assert closed.out_avals[0].shape == (2, CFG["vocab_size"])


# -- (d) the recompile contract ----------------------------------------------
def test_zero_steady_state_recompiles(runner):
    assert runner.warmed_up
    warm = runner.jit_cache_keys()
    assert len(warm) == len(runner.buckets) + 1   # ladder + ONE decode
    rng = np.random.RandomState(1)
    for n in (2, 5, 8, 13, 21, 30 - 2):
        prompt = rng.randint(1, CFG["vocab_size"], size=n).astype(np.int32)
        runner.generate(prompt, 2)
    assert runner.jit_cache_keys() == warm, \
        "steady-state decode recompiled: %r" % (
            runner.jit_cache_keys() - warm)
    assert runner.recompiles_since_warmup() == 0


# -- (e) the mutation seam kills the budget gate -----------------------------
@pytest.mark.analysis
def test_decode_step_budget_gate_passes():
    """The shipped decode row holds: ``--cost --budget --model
    decode_step`` (static trace + the runtime numerics companion) is
    green in-process."""
    from mxnet_tpu.analysis.__main__ import main
    rc = main(["--cost", "--budget",
               os.path.join(REPO, "STATIC_BUDGETS.json"),
               "--model", "decode_step"])
    assert rc == 0


@pytest.mark.analysis
def test_decode_write_kv_seam_fails_budget_gate_rc2(tmp_path):
    """Headline mutation kill: skipping the cache write (the stale-KV
    bug — every step attends over a cache missing its own token) fails
    the STATIC_BUDGETS gate rc=2 from a subprocess, with BOTH halves
    named: the static scatter count and the runtime cached-vs-reference
    divergence."""
    script = tmp_path / "mutate.py"
    script.write_text(
        "import os, sys\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from mxnet_tpu.transformer import decode\n"
        "decode.DECODE_WRITE_KV = False\n"
        "from mxnet_tpu.analysis.__main__ import main\n"
        "sys.exit(main(['--cost', '--budget', %r, "
        "'--model', 'decode_step']))\n"
        % os.path.join(REPO, "STATIC_BUDGETS.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=600)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "COST001" in proc.stdout
    assert "decode_step" in proc.stdout
    assert "scatter" in proc.stdout or "diverged" in proc.stdout
