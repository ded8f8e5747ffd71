"""The Mamba-2 chunked scan as a Pallas kernel pair
(``ops/ssd_kernels.py``): the same map as the recurrence, the quadratic form
and ``ssd_chunked``'s einsums, in values and in the gradients of every
input; which shapes take it; what the traced gradient holds; the kernel
under a ``data`` mesh; its counter; its declared costs; and that Mosaic
takes both kernels at the widths of ``granite-4.0-h-micro``.

On the CPU the kernels run in the Pallas interpreter
(``pallas_kernels.resolve_interpret``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import ssd_kernels
from mxnet_tpu.transformer import ssm

# a small scan that tiles, and the sizes of the benchmark's cell
CHUNK, HEADS, HEAD_DIM, STATE = 128, 2, 64, 128
CELL = dict(chunk=256, heads=64, head_dim=64, state=128)
INPUTS = (0, 1, 2, 3, 4)                         # x, dt, A_log, B, C


def _scan_inputs(t, dtype, b=2, h=HEADS, p=HEAD_DIM, n=STATE):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    return (jax.random.normal(ks[0], (b, t, h, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0),
            jnp.log(jnp.arange(1, h + 1.0)),
            (0.3 * jax.random.normal(ks[2], (b, t, n))).astype(dtype),
            (0.3 * jax.random.normal(ks[3], (b, t, n))).astype(dtype),
            jax.random.normal(ks[4], (b, t, h, p)))


def _scored(fn, weight):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
        argnums=INPUTS)


def _close(got, wanted):
    """``tests/test_hybrid_lm.py``'s tolerance of the three spellings, the
    absolute part by the gradient's largest entry: ``A_log``'s is a sum
    over every token and column of a head, tens of thousands of terms here
    that cancel to its size (the spellings differ by 3e-4 of it among
    themselves, the quadratic form the furthest out)."""
    np.testing.assert_allclose(
        got, wanted, rtol=2e-4,
        atol=2e-4 * max(1.0, float(np.abs(wanted).max())))


def _einsums(monkeypatch):
    """``ssd_chunked`` spells every shape with its einsums."""
    monkeypatch.setattr(ssd_kernels, "tiles", lambda *a: False)


def _equations(jaxpr, into=lambda eqn: True):
    """Every equation of a jaxpr and of the jaxprs its equations hold,
    those of an equation ``into`` refuses left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if into(eqn):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub, into)


def _is_kernel(eqn):
    return eqn.primitive.name == "pallas_call"


# -- (a) the kernel path is the recurrence and the quadratic form ------------
@pytest.mark.parametrize("t", [256, 320])
def test_kernel_path_is_the_recurrence_and_the_quadratic_form(t):
    """float32, values and the gradients of x, dt, A_log, B and C, for a
    length that is and one that is not a multiple of the chunk."""
    *args, weight = _scan_inputs(t, jnp.float32)
    assert ssd_kernels.tiles(CHUNK, HEADS, HEAD_DIM, STATE, jnp.float32)
    chunked = lambda *a: ssm.ssd_chunked(*a, CHUNK)
    kernels = sum(map(_is_kernel, _equations(
        jax.make_jaxpr(_scored(chunked, weight))(*args).jaxpr)))
    assert kernels == 2                          # forward and backward
    value, grads = _scored(chunked, weight)(*args)
    with jax.default_matmul_precision("highest"):
        for other in (ssm.ssd_recurrence, ssm.ssd_quadratic):
            want, want_grads = _scored(other, weight)(*args)
            np.testing.assert_allclose(chunked(*args), other(*args),
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(value, want, rtol=2e-4)
            for got, wanted in zip(grads, want_grads):
                _close(got, wanted)


# -- (b) bfloat16 against the einsum spelling --------------------------------
@pytest.mark.parametrize("t", [256, 320])
def test_kernel_path_in_bfloat16_is_the_einsum_spelling(t, monkeypatch):
    """Values and gradients to bfloat16's rounding: the two spellings round
    at the same places but sum in another order."""
    *args, weight = _scan_inputs(t, jnp.bfloat16)
    chunked = lambda *a: ssm.ssd_chunked(*a, CHUNK)
    y = chunked(*args)
    value, grads = _scored(chunked, weight)(*args)
    _einsums(monkeypatch)
    want_y = chunked(*args)
    want, want_grads = _scored(chunked, weight)(*args)
    assert y.dtype == want_y.dtype == jnp.bfloat16

    def gap(got, wanted):
        got, wanted = (np.asarray(v, np.float32) for v in (got, wanted))
        return np.linalg.norm(got - wanted) / np.linalg.norm(wanted)

    assert gap(y, want_y) < 4e-3                 # half a unit in the last
    np.testing.assert_allclose(value, want, rtol=2e-3)
    for got, wanted in zip(grads, want_grads):
        assert got.dtype == wanted.dtype
        assert gap(got, wanted) < 1e-2


# -- (c) what the traced gradient holds --------------------------------------
def _gradient_jaxpr(t, chunk, dtype, **sizes):
    *args, weight = _scan_inputs(t, dtype, **sizes)
    return jax.make_jaxpr(_scored(
        lambda *a: ssm.ssd_chunked(*a, chunk), weight))(*args).jaxpr


def _chunk_squares(jaxpr, chunk, into):
    """Arrays of (..., chunk, chunk) among the equations' results."""
    return [v.aval for eqn in _equations(jaxpr, into)
            if not _is_kernel(eqn) for v in eqn.outvars
            if getattr(v.aval, "shape", ())[-2:] == (chunk, chunk)]


def test_no_chunk_square_reaches_the_program_where_the_kernel_runs(
        monkeypatch):
    """Chunks of 256 (of no other size in the scan), of any dtype."""
    jaxpr = _gradient_jaxpr(512, 256, jnp.bfloat16)
    assert sum(map(_is_kernel, _equations(jaxpr))) == 2
    outside = lambda eqn: not _is_kernel(eqn)
    assert _chunk_squares(jaxpr, 256, outside) == []
    # the einsum spelling of the same scan has them: the test can see one
    _einsums(monkeypatch)
    squares = _chunk_squares(_gradient_jaxpr(512, 256, jnp.bfloat16), 256,
                             outside)
    assert any(a.dtype == jnp.float32 for a in squares)


def test_the_rehearsal_size_holds_no_kernel():
    """chunk 16, heads of 16, state 8: ``ssd_chunked``'s einsums."""
    jaxpr = _gradient_jaxpr(32, 16, jnp.float32, h=4, p=16, n=8)
    assert not any(map(_is_kernel, _equations(jaxpr)))
    assert _chunk_squares(jaxpr, 16, lambda eqn: True)


# -- (d) the rule -------------------------------------------------------------
@pytest.mark.parametrize("sizes,dtype,takes", [
    (CELL, jnp.bfloat16, True),                  # granite-4.0-h-micro.tokens
    (CELL, jnp.float32, True),
    (dict(chunk=16, heads=4, head_dim=16, state=8), jnp.float32, False),
    (dict(chunk=8, heads=4, head_dim=16, state=8), jnp.float32, False),
    (dict(CELL, chunk=192), jnp.bfloat16, False),
    (dict(CELL, state=64), jnp.bfloat16, False),
    (dict(CELL, heads=3), jnp.bfloat16, False),  # two heads to a 128-row tile
    (dict(CELL, heads=3, head_dim=128), jnp.bfloat16, True),
    (dict(CELL, heads=12), jnp.bfloat16, False),  # eight heads a grid step
    (dict(CELL, head_dim=96), jnp.bfloat16, False),
    (CELL, jnp.float16, False),
])
def test_which_shapes_take_the_kernel(sizes, dtype, takes):
    from mxnet_tpu.transformer import HybridLMConfig
    assert ssd_kernels.tiles(dtype=dtype, **sizes) is takes
    cfg = HybridLMConfig(ssm_chunk=sizes["chunk"], ssm_heads=sizes["heads"],
                         ssm_head_dim=sizes["head_dim"],
                         ssm_state=sizes["state"])
    assert ssm.scan_kernel_tiles(cfg, dtype) is takes


@pytest.mark.parametrize("heads,step", [
    (64, 8), (16, 8), (8, 8), (2, 2), (6, 6)])
def test_heads_of_a_grid_step(heads, step):
    assert ssd_kernels.heads_per_step(heads) == step


def test_heads_in_pairs_and_heads_of_a_whole_tile_agree():
    """The two layouts of a step's rows (two heads of 64 to a 128-row tile,
    a head of 128 a tile of its own) against the quadratic form, two steps
    of heads a chunk."""
    for h, p in ((16, 64), (16, 128)):
        *args, weight = _scan_inputs(128, jnp.float32, b=1, h=h, p=p)
        assert h // ssd_kernels.heads_per_step(h) == 2
        value, grads = _scored(lambda *a: ssm.ssd_chunked(*a, CHUNK),
                               weight)(*args)
        with jax.default_matmul_precision("highest"):
            want, want_grads = _scored(ssm.ssd_quadratic, weight)(*args)
        np.testing.assert_allclose(value, want, rtol=2e-4)
        for got, wanted in zip(grads, want_grads):
            _close(got, wanted)


# -- (e) under a data mesh ----------------------------------------------------
def _tiling_config(**sizes):
    from mxnet_tpu.transformer import HybridLMConfig
    return HybridLMConfig(
        layer_types=("mamba", "attention", "mamba"), d_model=32, d_ff=64,
        ssm_heads=HEADS, ssm_head_dim=HEAD_DIM, ssm_state=STATE,
        ssm_chunk=CHUNK, seq_len=CHUNK, **sizes)


def test_kernel_under_a_data_axis_of_two_matches_one_device():
    """The mesh step is one ``shard_map`` program: the kernels' results
    carry their inputs' varying axes, and two replicas of half the batch
    take the step one replica of the whole batch takes."""
    from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    cfg = _tiling_config()
    ids = np.random.RandomState(0).randint(
        0, 64, (2, CHUNK + 1)).astype(np.int32)
    losses = {}
    for data in (1, 2):
        before = compiles.counters()["ssm_kernel_layers"]
        trainer = DataParallelTrainer(
            HybridLM(cfg), None, "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh_plan=MeshPlan(data=data))
        losses[data] = [float(trainer.step(ids[:, :-1], ids[:, 1:]).asnumpy())
                        for _ in range(2)]
        assert compiles.counters()["ssm_kernel_layers"] > before
    np.testing.assert_allclose(losses[2], losses[1], rtol=1e-5)
    assert losses[1][1] < losses[1][0]


# -- (f) the counter and the doctor's line ------------------------------------
@pytest.mark.parametrize("chunk,kernel_layers", [(CHUNK, 2), (64, 0)])
def test_kernel_layers_counter_and_the_doctors_line(chunk, kernel_layers):
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    cfg = _tiling_config()
    cfg.ssm_chunk = chunk
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    params = program.init_params()
    before = compiles.counters()
    x = jnp.zeros((1, CHUNK), jnp.int32)
    jaxpr = jax.make_jaxpr(program.loss_replica)(
        tuple(params[n] for n in program.param_names), x, x, None).jaxpr
    after = compiles.counters()
    traced = {k: after[k] - before[k] for k in after}
    assert traced["ssm_layers"] == 2
    assert traced["ssm_kernel_layers"] == kernel_layers
    assert sum(map(_is_kernel, _equations(jaxpr))) == kernel_layers
    traced["ssm_chunks_per_seq"] = after["ssm_chunks_per_seq"]
    text = telemetry.render_doctor({
        "directory": "d", "ranks": {"worker0": {"compiles": traced}},
        "stragglers": [], "events": dict.fromkeys(
            ("straggler", "anomaly", "queue_growth", "fault"), ())})
    assert ("2 Mamba-2 layer(s) in the traced programs, the scan in %d "
            "chunk(s) a sequence, as a Pallas kernel pair in %d of them"
            % (CHUNK // chunk, kernel_layers)) in text


def test_the_cells_memory_decision_stays_keep():
    """With the scan's ``L x L`` arrays out of the reckoned live set the
    cell keeps its products as before, and twice the tokens still do not
    fit."""
    import json
    import os
    from mxnet_tpu.transformer import HybridLMConfig
    from mxnet_tpu.transformer.hybrid import (_layer_live_bytes,
                                              keeps_products)
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "granite-4.0-h-micro.json")
    with open(path) as f:
        config = json.load(f)
    cfg = HybridLMConfig.from_hf(config, seq_len=config["seq_len"])
    bf16 = jnp.bfloat16
    assert ssm.scan_kernel_tiles(cfg, bf16)
    squares = 4 * 4 * 16 * 64 * 256 * 256       # four float32 (c, h, L, L)
    cfg.ssm_chunk = 64                           # 64 chunks of 64: einsums
    einsums = _layer_live_bytes(cfg, "mamba", 1, 4096, bf16)
    cfg.ssm_chunk = 256
    assert einsums - _layer_live_bytes(cfg, "mamba", 1, 4096, bf16) \
        == squares // 4
    assert keeps_products(cfg, 772.2e6, 1, 4096, bf16, 16.9e9)
    assert not keeps_products(cfg, 772.2e6, 2, 4096, bf16, 16.9e9)


# -- the declared costs --------------------------------------------------------
def test_declared_costs_of_both_kernels():
    """One Mamba layer of the cell: the products the kernels run and one
    pass over operands and results; the lint finds nothing undeclared."""
    from mxnet_tpu.analysis import lint_kernel_costs
    from mxnet_tpu.analysis.cost import KERNEL_COSTS, kernel_name_of
    assert lint_kernel_costs() == []
    b, c, size, h, p, n = 1, 16, 256, 64, 64, 128
    shapes = (jax.ShapeDtypeStruct((b, c, size, n), jnp.bfloat16),) * 2 + (
        jax.ShapeDtypeStruct((b, c, size, h, p), jnp.bfloat16),) + (
        jax.ShapeDtypeStruct((b, c, size, h), jnp.float32),) * 2
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(ssd_kernels.ssd_scan, *a)[1](
        jnp.ones((b, c, size, h, p), jnp.bfloat16)))(*shapes).jaxpr
    costs = {kernel_name_of(eqn): KERNEL_COSTS[kernel_name_of(eqn)](eqn)
             for eqn in _equations(jaxpr) if _is_kernel(eqn)}
    fwd, bwd = (costs["_ssd_scan_%s_kernel" % k] for k in ("fwd", "bwd"))
    tokens, columns = b * c * size, h * p
    within = 2 * tokens * size * (n + columns)   # C B^T and M xdt
    state = 2 * tokens * n * columns             # (L, p) with (p, n)
    assert fwd["flops"] == within + 2 * state == 17_448_304_640
    assert bwd["flops"] == 3 * within + 5 * state
    assert fwd["transcendentals"] == bwd["transcendentals"] \
        == tokens * h * (size + 3)
    operands = tokens * (2 * n * 2 + columns * 2 + 3 * h * 4)
    states = c * columns * n * 4                 # what each chunk was handed
    assert fwd["bytes_read"] == operands
    assert fwd["bytes_written"] == tokens * columns * 2 + states
    assert bwd["bytes_read"] == operands + states + tokens * columns * 2
    assert bwd["bytes_written"] == tokens * (columns * 2 + 2 * h * 4
                                             + 2 * n * 2)


# -- Mosaic takes the kernels at the cell's widths -----------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_chips_compiler_takes_both_kernels_at_the_cells_widths(
        one_chip, monkeypatch, request, dtype):
    """One layer's scan of ``granite-4.0-h-micro`` (1 x 4,096 tokens, 64
    heads of 64, state 128, chunks of 256) compiled for a v5e that is
    described, not attached: forward and gradient hold the two kernels and
    no float32 array of (..., 256, 256)."""
    import re
    monkeypatch.setattr(ssd_kernels, "resolve_interpret", lambda *a: False)
    # the kernels' wrappers are ``jit``s: a trace of these shapes made for
    # the interpreter must not answer for the compiler, nor this one later
    forget = lambda: [fn.clear_cache() for fn in (ssd_kernels._forward,
                                                  ssd_kernels._backward)]
    forget()
    request.addfinalizer(forget)
    b, t, h, p, n, size = 1, 4096, 64, 64, 128, 256

    def sds(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)

    args = (sds((b, t, h, p), dtype), sds((b, t, h), jnp.float32),
            sds((h,), jnp.float32), sds((b, t, n), dtype),
            sds((b, t, n), dtype))
    loss = lambda *a: ssm.ssd_chunked(*a, size).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=INPUTS)) \
        .lower(*args).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert not re.findall(r"f32\[[0-9,]*256,256\]", text)


# the flash kernels of ``ops/pallas_kernels.py`` are compiled here and not in
# ``tests/test_flash_hybrid.py``: one file describes the chip, so one worker
# of a test run loads its compiler
@pytest.mark.parametrize("b,t,heads,kv_heads,e_qk,e_v", [
    (2, 8192, 32, 32, 192, 128),                 # JoyAI-LLM-Flash.tokens
    (1, 4096, 32, 8, 64, 64),                    # granite-4.0-h-micro.tokens
], ids=["JoyAI-LLM-Flash", "granite-4.0-h-micro"])
def test_the_chips_compiler_takes_the_flash_kernels_at_the_cells_widths(
        one_chip, monkeypatch, request, b, t, heads, kv_heads, e_qk, e_v):
    """One layer's causal attention of each language-model cell, under the
    layer's checkpoint and its policy, compiled for a v5e that is described,
    not attached: value and gradient hold the forward kernel once (the
    re-run reads the kept ``o`` and ``lse``) and each backward kernel once,
    and no float32 or bfloat16 array of a block of query rows by the keys."""
    import re
    from mxnet_tpu.ops import pallas_kernels
    from mxnet_tpu.transformer import hybrid
    monkeypatch.setattr(pallas_kernels, "resolve_interpret",
                        lambda *a: False)
    forget = lambda: [fn.clear_cache() for fn in (
        pallas_kernels.flash_forward, pallas_kernels.flash_backward)]
    forget()
    request.addfinalizer(forget)
    bf16 = jnp.bfloat16
    assert pallas_kernels.flash_tiles(t, heads, kv_heads, e_qk, e_v, bf16)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, bf16, sharding=one_chip)

    layer = jax.checkpoint(
        lambda q, k, v: hybrid.causal_gqa_attention(
            q * 2, k * 2, v * 2, e_qk ** -0.5, 512),
        policy=jax.checkpoint_policies.save_only_these_names(
            hybrid.ATTENTION_OUT))
    loss = lambda *a: layer(*a).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds(b, t, heads, e_qk), sds(b, t, kv_heads, e_qk),
        sds(b, t, kv_heads, e_v)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    for kernel in ("_fa_kernel", "_fa_dq_kernel", "_fa_dkv_kernel"):
        assert sum("/%s/pallas_call" % kernel in c for c in calls) == 1
    assert len(calls) == 3
    assert not re.findall(
        r"(?:f32|bf16)\[[0-9,]*(?:512|1024|%d),%d\]" % (t, t), text)


# the delta-rule kernels of ``ops/kda_kernels.py``, for the same reason here
# and not in ``tests/test_kda_kernel.py``
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_chips_compiler_takes_the_delta_rule_kernels_at_the_cells_shape(
        one_chip, monkeypatch, request, dtype):
    """One layer's scan of ``Ling-3.0-flash`` (1 x 16,384 tokens, 32 heads of
    128 key and value columns, chunks of 64) under the layer's checkpoint,
    compiled for a v5e that is described, not attached: value and gradient
    hold the forward kernel twice (the step and the layer's re-run) and the
    backward kernel once, no loop, and no float32 array of a chunk's square
    or of a block of chunks."""
    import re
    from mxnet_tpu.ops import kda_kernels
    from mxnet_tpu.transformer import kda
    monkeypatch.setattr(kda_kernels, "resolve_interpret", lambda *a: False)
    forget = lambda: [fn.clear_cache() for fn in (kda_kernels._forward,
                                                  kda_kernels._backward)]
    forget()
    request.addfinalizer(forget)
    b, t, h, e = 1, 16384, 32, 128
    assert kda_kernels.tiles(64, h, e, dtype)

    def sds(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)

    args = (sds((b, t, h, e), dtype),) * 3 + (
        sds((b, t, h, e), jnp.float32), sds((b, t, h), jnp.float32))
    layer = jax.checkpoint(lambda q, k, v, g, beta: kda_kernels.kda_scan(
        q * 2, k, v, g * 0.5, beta))
    loss = lambda *a: layer(*a).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))) \
        .lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sum("/_kda_scan_fwd_kernel/pallas_call" in c for c in calls) == 2
    assert sum("/_kda_scan_bwd_kernel/pallas_call" in c for c in calls) == 1
    assert len(calls) == 3
    assert " while(" not in text
    assert not re.findall(r"f32\[[0-9,]*,64,64\]", text)
    assert kda.SCAN_BLOCK_CHUNKS == 32
    assert not re.findall(r"f32\[[0-9,]*32,64,[0-9,]*\]", text)
