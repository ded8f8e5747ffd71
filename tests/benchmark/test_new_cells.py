"""The cells that joined the benchmark after its first two, on the CPU at
rehearsal size: ``granite-4.0-h-micro.tokens`` (family ``granite_hybrid``,
traffic kind ``device_resident_tokens``).  The same proofs
``test_benchmark_harness.py`` makes of the first two: the count of the
arithmetic, a sound rehearsal comes out correct, the lower-precision control
and each planted fault do not; and what the manifest reports in the new
cell.  Nothing here is a measurement.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
GRANITE = "granite-4.0-h-micro"
CATALOG_KEYS = {"hidden_size": 2048, "shared_intermediate_size": 8192,
                "num_attention_heads": 32, "num_key_value_heads": 8,
                "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
                "mamba_d_conv": 4, "mamba_chunk_size": 256, "mamba_expand": 2,
                "attention_multiplier": 0.015625, "embedding_multiplier": 12,
                "residual_multiplier": 0.22, "logits_scaling": 8,
                "rms_norm_eps": 1e-5, "max_position_embeddings": 131072}
# limits for the rehearsal size only: widths of 32 read closer to the
# float32 reference than the cell's own (limits/<workload>.json)
REHEARSAL_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.2,
                    "grad_norm_gap_median": 0.02, "update_norm_gap": 0.2,
                    "update_norm_gap_median": 0.02, "stats_norm_gap": 0.2}


def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def harness():
    return _load(os.path.join(BENCH, "run.py"), "benchmark_run")


@pytest.fixture(scope="module")
def family(harness):
    return harness.load_module("families", "granite_hybrid")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", GRANITE + ".json")) as f:
        return json.load(f)


@pytest.fixture()
def keep_jax_config():
    """``run.main`` turns the persistent compilation cache on for its
    process; put the settings back for the tests that follow."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    import mxnet_tpu as mx
    mx.telemetry.disable()


# -- the configuration is the published one, cut as it says ------------------
def test_configuration_holds_the_published_widths(config, family):
    for key, value in CATALOG_KEYS.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert len(config["layer_types"]) == 40
    size = {k: config[k] for k in config["rehearsal_size"]}
    table = family.layer_table(family.sized(config, size))
    assert table == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    spec = family.leaves(config, size)
    count = sum(math.prod(s) for _, _, s in spec)
    assert 772.1e6 < count < 772.3e6                 # ISSUE 28's 772.2M
    assert len(spec) == 9 * 12 + 8 + 2


@pytest.mark.parametrize("name,low,high", [
    # 2 x 772.2M in matrix products, the scan's and attention's own on top
    (GRANITE, 0.789e9, 0.800e9),
])
def test_flops_per_item(harness, name, low, high):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    family = harness.load_module("families", config["family"])
    size = {k: config[k] for k in config["rehearsal_size"]}
    macs = family.forward_macs_per_token(config, size)
    assert low <= macs <= high
    assert family.flops_per_item(config, size) == 6 * macs * 4096
    # an independent count: XLA's own, of the program's forward pass at
    # the rehearsal size (one chunk and one block of query rows as long as
    # the sequence there, which XLA counts in full and the family by the
    # causal half; it adds the norms and gates)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    small = dict(config["rehearsal_size"], batch_per_chip=1)
    small["mamba_chunk_size"] = small["seq_len"]
    cfg = family.sized(config, small)
    program = HybridLM(HybridLMConfig.from_hf(
        cfg, seq_len=small["seq_len"])).mesh_program(MeshPlan(data=1))
    vals = tuple(jax.ShapeDtypeStruct(program.global_shape(n), jnp.float32)
                 for n in program.param_names)
    x = jax.ShapeDtypeStruct((1, small["seq_len"]), jnp.int32)
    cost = jax.jit(lambda v, x: program.loss_replica(v, x, x, None)).lower(
        vals, x).compile().cost_analysis()
    counted = 2 * family.forward_macs_per_token(config, small) \
        * small["seq_len"]
    assert 1.0 * counted <= cost["flops"] <= 1.6 * counted


# -- the reference's control and faults are not correct ----------------------
def _feed(harness, config, size, seed, steps):
    import jax
    from mxnet_tpu.parallel import make_mesh
    kind = harness.load_module("traffic_kinds", "device_resident_tokens")
    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    return mesh, kind.batches(config, size, mesh, seed,
                              {"distinct_batches": steps})


def test_control_and_faults_read_not_correct(harness, family, config):
    """Against the float32 reference at the rehearsal size, under the
    rehearsal's limits: the reference itself reads nought, the float8
    control and each planted fault pass at least one limit."""
    correctness = _load(os.path.join(BENCH, "correctness.py"), "correctness")
    size = dict(config["rehearsal_size"])
    seed = 2 ** 31 + 12345
    _, feed = _feed(harness, config, size, seed, 3)
    reference = family.reference_readings(config, size, seed, feed)
    again = family.reference_readings(config, size, seed, feed)
    assert correctness.verdict(correctness.compare(again, reference),
                               REHEARSAL_LIMITS)[0]
    assert set(reference["stats_norms"]) == set(reference["grad_norms"])
    for variant, fault in (("fp8", None), ("float32", "half_batch"),
                           ("float32", "state_unchanged")):
        control = family.reference_readings(config, size, seed, feed,
                                            variant=variant, fault=fault)
        correct, rows = correctness.verdict(
            correctness.compare(control, reference), REHEARSAL_LIMITS)
        assert not correct, (variant, fault, rows)
    # one row of tokens: the fault leaves out the second half of the tokens
    one = dict(size, batch_per_chip=1)
    _, feed = _feed(harness, config, one, seed, 1)
    whole = family.reference_readings(config, one, seed, feed)
    half = family.reference_readings(config, one, seed, feed,
                                     fault="half_batch")
    assert not correctness.verdict(correctness.compare(half, whole),
                                   REHEARSAL_LIMITS)[0]


# -- a run of the new cell, sound and with the timed path broken -------------
def _rehearse(harness, monkeypatch, capsys, workload, limits, seconds="0.2"):
    import correctness
    monkeypatch.setattr(correctness, "load_limits",
                        lambda workload: dict(limits))
    capsys.readouterr()
    assert harness.main(["--workload", workload, "--seed", str(2 ** 31 + 77),
                         "--seconds", seconds, "--trace", "0",
                         "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_rehearsal_is_correct(harness, monkeypatch, capsys,
                                      keep_jax_config):
    line = _rehearse(harness, monkeypatch, capsys, GRANITE + ".tokens",
                     REHEARSAL_LIMITS)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    assert [r["name"] for r in line["compared"]] == list(REHEARSAL_LIMITS)
    assert set(line["metrics"]) == {"train_throughput", "step_ms_p95",
                                    "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(harness, monkeypatch, capsys,
                                      keep_jax_config, fault):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import DataParallelTrainer
    real_step = DataParallelTrainer.step

    def state_unchanged(self, data, label):
        if not self._ready:
            return real_step(self, data, label)     # the first builds it
        kept = {n: jnp.copy(v) for n, v in self._mesh_params.items()}
        states = jax.tree_util.tree_map(jnp.copy, self._mesh_state_leaves)
        loss = real_step(self, data, label)
        self.flush()
        self._mesh_params, self._mesh_state_leaves = kept, states
        return loss

    def half_batch(self, data, label):
        half = data.shape[0] // 2
        return real_step(self, data[:half], label[:half])

    monkeypatch.setattr(DataParallelTrainer, "step",
                        {"state_unchanged": state_unchanged,
                         "half_batch": half_batch}[fault])
    line = _rehearse(harness, monkeypatch, capsys, GRANITE + ".tokens",
                     REHEARSAL_LIMITS)
    assert line["correct"] is False
    assert any(r["value"] > r["limit"] for r in line["compared"])


# -- what the manifest reports in the new cell --------------------------------
def test_manifest_appends_the_granite_cell_and_moves_nothing(harness):
    """The manifest starts with what was accepted (``manifest_rule.py``:
    names, fields and relative order; what is new comes after), and in that
    table the Granite configuration and cell follow the two ResNets', and
    the cell is listed by the accepted metrics whose readers find something
    in a language model's step."""
    rule = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "manifest_rule.py"), "manifest_rule")
    manifest, accepted = rule.load_manifest(), rule.load_accepted()
    assert rule.departures(manifest, accepted) == []
    cell = GRANITE + ".tokens"
    old = ["resnet50_v1.synthetic", "resnet18_v1.synthetic"]
    assert [w["name"] for w in accepted["workloads"]] == old + [cell]
    assert [c["name"] for c in accepted["configs"]][2] == GRANITE
    silent = {"conv_device_ms", "fused_update_us"}
    for m in accepted["per_layer"][:16]:
        assert m["workloads"] == old + ([] if m["name"] in silent else [cell])
    names = {m["name"] for m in harness.resolve(manifest, cell)[4]}
    assert "step_mfu" in names and "scoped_device_share" in names
    assert not silent & names
