"""Every operation the program's code puts into a step carries a program
scope (ISSUE 40), on the CPU: the compiled step of each benchmark family
at its rehearsal size, read through ``lower_step`` and
``tools/unscoped_inventory.py``'s parser, has no instruction of the entry
or a control-flow computation whose ``op_name`` names the program's code
and no scope but jax's own; the casts at the forward boundary sit under
``compute_cast`` in every tier.  Nothing here is a measurement.
"""
from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan, make_mesh
from mxnet_tpu.transformer import HybridLM, HybridLMConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELLS = ["granite-4.0-h-micro.tokens", "JoyAI-LLM-Flash.tokens",
         "Ling-3.0-flash.tokens", "resnet18_v1.synthetic"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def inventory():
    return _load(os.path.join(REPO, "tools", "unscoped_inventory.py"),
                 "unscoped_inventory")


@pytest.fixture(scope="module")
def harness():
    """``benchmark/run.py``, through which a cell is found by name."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return _load(os.path.join(BENCH, "run.py"), "benchmark_run_scopes")


def _compiled_step(harness, cell):
    """The optimized HLO of the cell's step at its rehearsal size, built
    through its family as ``run.py`` builds it, after one step."""
    entry, config, traffic, _, _ = harness.resolve(
        harness.load_json(REPO, "BENCHMARK.json"), cell)
    size = harness.cell_size(config, True)
    family = harness.load_module("families", config["family"])
    kind = harness.load_module("traffic_kinds", traffic["kind"])
    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    feed = kind.batches(config, size, mesh, 40, traffic)
    program = family.build(config, size, mesh, 40)
    program.step(*feed[0])
    program.flush()
    text = program.trainer.lower_step(*feed[0]).compile().as_text()
    program.close()
    return text


@pytest.mark.parametrize("cell", CELLS)
def test_every_program_instruction_of_the_step_has_a_scope(harness,
                                                           inventory, cell):
    module = inventory.parse_hlo(_compiled_step(harness, cell))
    assert module["entry"] in inventory.control_computations(module)
    named = [f["op_name"] for c in module["computations"].values()
             for f in c.values() if inventory.names_program(f["op_name"])]
    assert len(named) > 50                 # the parser reads the metadata
    assert inventory.unnamed_program_instructions(module) == []


def test_the_guard_sees_an_instruction_without_a_scope(inventory):
    """The rule bites: a program of two operations, one of them under a
    scope, shows the other; jax's own ``checkpoint`` is no scope."""
    def program(x):
        with jax.named_scope("inside"):
            y = jnp.sin(x)
        return jax.checkpoint(jnp.exp)(y) * 3.0

    x = jnp.ones((8, 128), jnp.float32)
    text = jax.jit(program).lower(x).compile().as_text()
    found = inventory.unnamed_program_instructions(inventory.parse_hlo(text))
    assert found and all("inside" not in op for _, _, op in found)
    assert set(inventory.program_scopes(
        "jit(f)/transpose(jvp(l3))/l3/checkpoint/rematted_computation/"
        "kda_mixer/dot_general")) == {"l3", "kda_mixer"}
    assert inventory.program_scopes(
        "jit(f)/transpose(jvp(checkpoint))/rematted_computation/mul") == []


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, jax_core.ClosedJaxpr):
                    yield from _walk(sub.jaxpr)
                elif isinstance(sub, jax_core.Jaxpr):
                    yield from _walk(sub)


def _casts(fn, *args):
    """[(name stack, from, to)] of the step's casts under ``compute_cast``."""
    closed = jax.make_jaxpr(fn)(*args)
    return [(str(e.source_info.name_stack), e.invars[0].aval,
             e.outvars[0].aval) for e in _walk(closed.jaxpr)
            if e.primitive.name == "convert_element_type"
            and "compute_cast" in str(e.source_info.name_stack)]


def _gluon(zero):
    mx.random.seed(5)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"))
    net.add(gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((2,), ("data",), jax.devices()[:2])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh, zero=zero,
        dtype="bf16")
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(8, 16).astype(np.float32))
    y = mx.nd.array((np.arange(8) % 10).astype(np.float32))
    trainer.step(x, y)
    trainer.flush()
    sh = trainer.batch_sharding
    return trainer, trainer._put_batch(x, sh), trainer._put_batch(y, sh)


def _tier(name):
    """(casts under ``compute_cast``, the f32 masters' shapes the forward
    pass casts, the batch's shape) of one tier's step."""
    key = jax.random.PRNGKey(0)
    if name == "mesh":
        cfg = HybridLMConfig(seq_len=16)      # a Mamba-2 and an attention
        trainer = DataParallelTrainer(
            HybridLM(cfg), None, "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh_plan=MeshPlan(data=1), dtype="bfloat16")
        ids = np.random.RandomState(1).randint(0, 64, (2, 17))
        x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
        trainer.step(x, y)
        trainer.flush()
        params, states = trainer.device_arrays()
        names = trainer._mesh_param_names
        casts = _casts(trainer._mesh_step_fn,
                       tuple(params[n] for n in names), tuple(states),
                       trainer._put_batch(x, trainer.batch_sharding),
                       trainer._put_batch(y, trainer.batch_sharding), key,
                       *trainer._step_scalars(0.05))
        return casts, [params[n].shape for n in names], None
    trainer, x, y = _gluon(zero=int(name == "zero1"))
    if name == "zero1":
        # the masters live in the shard: the forward pass casts the batch
        casts = _casts(trainer._zero_grad_fn, *trainer._live_vals(), x, y,
                       key, trainer._ls_scale)
        return casts, [], x.shape
    casts = _casts(trainer._step_fn, *trainer._step_args(
        *trainer._live_vals(), x, y, key, *trainer._step_scalars(0.1)))
    return casts, [v.shape for v in trainer._live_vals()[0]], x.shape


@pytest.mark.parametrize("tier", ["mesh", "replicated", "zero1"])
def test_compute_cast_holds_the_casts_at_the_forward_boundary(tier):
    """Each float32 master the forward pass reads is cast to bfloat16 under
    ``compute_cast`` (``jvp(compute_cast)``), its gradient comes back through
    the cast's transpose under the same scope, and the batch's cast is
    there too where the batch is floating."""
    casts, masters, batch = _tier(tier)
    f32, bf16 = jnp.dtype("float32"), jnp.dtype("bfloat16")
    forward = [a.shape for s, a, b in casts if s.startswith("jvp(")
               and (a.dtype, b.dtype) == (f32, bf16)]
    backward = [b.shape for s, a, b in casts if s.startswith("transpose(")
                and (a.dtype, b.dtype) == (bf16, f32)]
    assert sorted(forward) == sorted(masters)
    assert sorted(backward) == sorted(masters)
    if batch is not None:
        # one replica's rows of it where the tier runs under shard_map
        assert any(s == "compute_cast" and a.shape[1:] == batch[1:]
                   for s, a, _ in casts)


def test_the_inventory_names_an_unscoped_operation_by_its_neighbours(
        inventory):
    """``inventory`` over a trace made by hand from a compiled program's own
    instructions, as a TPU's trace names them: the operation between two
    scopes is billed with its time, its kind and the scopes on either side;
    a scoped one is left out; one the step's HLO does not hold belongs to
    another program."""
    def program(w, x):
        with jax.named_scope("before"):
            y = jnp.tanh(x @ w)
        z = jnp.sort(y, axis=0)
        with jax.named_scope("after"):
            return jnp.sum(jnp.exp(z) * y)

    w = jnp.ones((128, 128), jnp.float32)
    hlo = jax.jit(program).lower(w, w).compile().as_text()
    module = inventory.parse_hlo(hlo)
    entry = module["computations"][module["entry"]]
    ops, at = [], 0
    for name, fields in entry.items():
        if fields["opcode"] in ("parameter", "constant", "tuple",
                                "get-tuple-element", "bitcast"):
            continue
        ops.append(("%%%s = f32[128,128] %s()" % (name, fields["opcode"]),
                    at, at + 1000, fields["op_name"]))
        at += 1000
    ops.append(("%fusion.99999 = f32[] fusion()", at, at + 500,
                "jit(convert_element_type)/convert_element_type"))
    modules = [("jit_program(1)", 0, at), ("jit_convert(2)", at, at + 500)]
    rows = inventory.inventory(ops, modules, hlo, 2)
    sort = [r for r in rows if r["op_name"].endswith("/sort")]
    assert sort and sort[0]["kind"] == "program"
    assert sort[0]["producer"].startswith("before")
    assert sort[0]["consumer"].startswith("after")
    assert sort[0]["ms"] == pytest.approx(1000 / 2 / 1e6)
    assert all(not inventory.program_scopes(r["op_name"]) for r in rows)
    other = [r for r in rows if r["op"] == "fusion.99999"]
    assert other and other[0]["kind"] == "other program"
    table = inventory.summary(rows)
    assert sum(t[3] for t in table) == pytest.approx(
        sum(r["ms"] for r in rows))
