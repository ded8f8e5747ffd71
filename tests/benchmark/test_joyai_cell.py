"""The cell ``JoyAI-LLM-Flash.tokens`` (family ``mla_moe``, traffic kind
``device_resident_tokens``), on the CPU at rehearsal size: the proofs
``test_new_cells.py`` makes of Granite's.  The configuration holds the
published widths and is cut as it says; the count of the arithmetic; a sound
rehearsal comes out correct, the lower-precision control and each planted
fault do not; the manifest takes the appended entries.  Nothing here is a
measurement.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
JOYAI = "JoyAI-LLM-Flash"
CELL = JOYAI + ".tokens"
GRANITE_CELL = "granite-4.0-h-micro.tokens"
# the catalog row's ``config``, key for key (architectures.jsonl, org
# jdopensource); the three in REDUCED are this chip's share
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16160}
NEW_METRICS = {"experts_device_ms": "ms", "moe_routing_device_ms": "ms",
               "latent_proj_device_ms": "ms", "mtp_device_ms": "ms",
               "moe_grouped_rows": "count"}
# accepted metrics that read the Mamba-2 scan, which this model has not
SILENT = {"ssm_scan_device_ms", "mamba_device_ms", "ssd_scan_kernel_us",
          "ssd_scan_roofline", "conv_device_ms", "fused_update_us"}
# limits for the rehearsal size only: at widths of 32 a choice of experts
# that flips between bfloat16 and float32 moves a 32-element leaf by a
# fifth (the program reads 0.02-0.16, 0.002-0.006, 0.08-0.13, 0.006-0.008,
# 0.13-0.20 there; the float8 control 0.43-1.0, 0.035-0.063, 0.24-0.52,
# 0.028-0.046, 0.31-0.32); the cell's own are limits/<workload>.json
REHEARSAL_LIMITS = {"loss_gap": 1.5e-3, "grad_norm_gap": 0.3,
                    "grad_norm_gap_median": 0.02, "update_norm_gap": 0.22,
                    "update_norm_gap_median": 0.018, "stats_norm_gap": 0.28}


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
    return harness.load_module("families", "mla_moe")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", JOYAI + ".json")) as f:
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
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_value(config, key):
    if key in REDUCED:
        assert config[key] == REDUCED[key]
        assert config["published"][key] == PUBLISHED[key]
        assert key in config["reduced"]
    else:
        assert config[key] == PUBLISHED[key]


def test_configuration_states_its_cut(config, family):
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert config["expert_shard"] == {"index": 0, "of": 16}
    assert config["n_routed_experts"] * config["expert_shard"]["of"] == 256
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (config["seq_len"], config["batch_per_chip"], config["dtype"],
            config["recompute"], config["items"]) == (
        8192, 2, "bfloat16", "layer", "seq")
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.05,
                                   "momentum": 0.9, "wd": 0.0001}
    assert config["mtp_loss_weight"] == 0.3
    assert set(config["assumed"]) >= {
        "optimizer", "mtp_loss_weight", "mtp_input",
        "e_score_correction_bias", "initialisation"}
    assert "16 chips share each layer's experts" in config["deployment"]
    # every size the run reads has a rehearsal value, and no width is cut
    # in the run itself
    size = {k: config[k] for k in config["rehearsal_size"]}
    assert family.layer_table(family.sized(config, size)) == \
        ["gated_mlp"] + ["sparse_experts"] * 4
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == JOYAI)
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]


def test_flops_per_item(family, config):
    """ISSUE 35's arithmetic: an expert layer 75.9M multiply-adds a token,
    the dense layer 112.3M, the module 84.3M, the two heads 66.2M: 566M a
    token; and XLA's own count of the program's forward pass at the
    rehearsal size."""
    size = {k: config[k] for k in config["rehearsal_size"]}
    macs = family.forward_macs_per_token(config, size)
    assert 565e6 <= macs <= 568e6
    assert family.flops_per_item(config, size) == 6 * macs * 8192
    def only(**kw):
        return family.forward_macs_per_token(
            dict(config, **kw),
            dict(size, **{k: v for k, v in kw.items() if k in size}))

    head = 2048 * 16160
    dense = only(num_hidden_layers=1, num_nextn_predict_layers=0) - head
    expert = only(num_hidden_layers=2, num_nextn_predict_layers=0) \
        - dense - head
    module = only(num_hidden_layers=1) - dense - 2 * head
    assert dense == pytest.approx(112.3e6, rel=2e-3)
    assert expert == pytest.approx(75.9e6, rel=2e-3)
    assert module == pytest.approx(84.3e6, rel=2e-3)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    # one block of query rows as long as the sequence, which XLA counts in
    # full and the family by the causal half; on the CPU the grouped product
    # is every held expert over every row of the buffer, which XLA counts
    # and the family does not; XLA adds norms, gates and rotary turns
    small = dict(config["rehearsal_size"], batch_per_chip=1)
    small["attention_block"] = small["seq_len"]
    cfg = family.sized(config, small)
    keys, sizes = family.program_keys(cfg)
    program = HybridLM(HybridLMConfig.from_hf(keys, **sizes)).mesh_program(
        MeshPlan(data=1))
    vals = tuple(jax.ShapeDtypeStruct(program.global_shape(n), jnp.float32)
                 for n in program.param_names)
    x = jax.ShapeDtypeStruct((1, small["seq_len"]), jnp.int32)
    cost = jax.jit(lambda v, x: program.loss_replica(v, x, x, None)).lower(
        vals, x).compile().cost_analysis()
    counted = 2 * family.forward_macs_per_token(config, small) \
        * small["seq_len"]
    assert 1.0 * counted <= cost["flops"] <= 2.0 * counted


# -- the reference's control and faults are not correct ----------------------
def _feed(harness, config, size, seed, steps):
    import jax
    from mxnet_tpu.parallel import make_mesh
    kind = harness.load_module("traffic_kinds", "device_resident_tokens")
    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    return kind.batches(config, size, mesh, seed, {"distinct_batches": steps})


def test_control_and_faults_read_not_correct(harness, family, config):
    """Against the float32 reference at the rehearsal size, under the
    rehearsal's limits: the reference itself reads nought, the float8
    control and each planted fault pass at least one limit."""
    correctness = _load(os.path.join(BENCH, "correctness.py"), "correctness")
    size = dict(config["rehearsal_size"])
    seed = 2 ** 31 + 35035
    feed = _feed(harness, config, size, seed, 3)
    reference = family.reference_readings(config, size, seed, feed)
    again = family.reference_readings(config, size, seed, feed)
    assert correctness.verdict(correctness.compare(again, reference),
                               REHEARSAL_LIMITS)[0]
    assert set(reference["stats_norms"]) == set(reference["grad_norms"])
    # three steps twice over, one program: the state starts where the step
    # hands it back (replicated over the batches' mesh), so the step's
    # second call does not compile it again (57 s and 71 MiB of the
    # machine's compile cache at the cell's size: PERF.md, PR 35)
    step = family._reference_step_fn(
        json.dumps(family.sized(config, size), sort_keys=True), "float32",
        True)
    assert step._cache_size() == 1
    for variant, fault in (("fp8", None), ("float32", "half_batch"),
                           ("float32", "state_unchanged")):
        control = family.reference_readings(config, size, seed, feed,
                                            variant=variant, fault=fault)
        correct, rows = correctness.verdict(
            correctness.compare(control, reference), REHEARSAL_LIMITS)
        assert not correct, (variant, fault, rows)


# -- a run of the new cell, sound and with the timed path broken -------------
def _rehearse(harness, monkeypatch, capsys, limits, trace="0"):
    import correctness
    monkeypatch.setattr(correctness, "load_limits",
                        lambda workload: dict(limits))
    capsys.readouterr()
    assert harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 35),
                         "--seconds", "0.2", "--trace", trace,
                         "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_rehearsal_is_correct(harness, monkeypatch, capsys,
                                      keep_jax_config):
    line = _rehearse(harness, monkeypatch, capsys, REHEARSAL_LIMITS)
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
    line = _rehearse(harness, monkeypatch, capsys, REHEARSAL_LIMITS)
    assert line["correct"] is False
    assert any(r["value"] > r["limit"] for r in line["compared"])


# -- what the manifest reports in the new cell --------------------------------
@pytest.fixture(scope="module")
def rule():
    return _load(os.path.join(HERE, "manifest_rule.py"), "manifest_rule")


def test_the_manifest_appends_and_moves_nothing(rule, harness):
    """The manifest still starts with what was accepted; the configuration,
    the cell and the five metrics come after; the cell's name follows
    Granite's on the list of every accepted metric whose reader finds
    something in this model's step, and on no other."""
    manifest, accepted = rule.load_manifest(), rule.load_accepted()
    assert rule.departures(manifest, accepted) == []
    names = lambda key: [e["name"] for e in manifest[key]]   # noqa: E731
    assert names("configs").index(JOYAI) >= len(accepted["configs"])
    assert names("workloads").index(CELL) >= len(accepted["workloads"])
    entry = next(c for c in manifest["configs"] if c["name"] == JOYAI)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % JOYAI
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        JOYAI, "tokens", 1)
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    for old in accepted["per_layer"]:
        now = next(m for m in manifest["per_layer"]
                   if m["name"] == old["name"])
        listed = GRANITE_CELL in old["workloads"] \
            and old["name"] not in SILENT
        assert now["workloads"] == old["workloads"] + (
            [CELL] if listed else []), old["name"]
    new = manifest["per_layer"][len(accepted["per_layer"]):]
    assert {m["name"]: m["unit"] for m in new} == NEW_METRICS
    for m in new:
        assert m["workloads"] == [CELL] and m["better"] == "lower"
        assert (m["layer"], m["moves"]) == ("compiled step",
                                            "train_throughput")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    reported = {m["name"] for m in harness.resolve(manifest, CELL)[4]}
    assert "step_mfu" in reported and not SILENT & reported
    assert set(NEW_METRICS) <= reported
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) | set(limits["not_compared"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_gap_median",
        "update_norm_gap", "update_norm_gap_median", "stats_norm_gap"}


def test_the_new_readers_read_a_recorded_step(harness):
    """Each scope's reader over hand-made operations of one traced step;
    the counter's reader where the program keeps no such counter."""
    scope_reduce = _load(os.path.join(BENCH, "scope_reduce.py"),
                         "scope_reduce")
    ops = {}
    for name, scopes in (
            ("experts_device_ms", ("sparse_experts",)),
            ("moe_routing_device_ms", ("moe_router", "moe_dispatch",
                                       "moe_combine")),
            ("latent_proj_device_ms", ("mla_q_proj", "mla_kv_proj",
                                       "mla_rope", "mla_out_proj")),
            ("mtp_device_ms", ("mtp_module",))):
        reader = harness.load_module("layer_metrics", name)
        assert reader.SCOPES == scopes
        assert reader.read({"trace": None}) is None
        ops[name] = reader
    counter = harness.load_module("layer_metrics", "moe_grouped_rows")
    assert counter.read({"trace": None, "attribution": None}) is None
    assert scope_reduce.named_scopes(
        "jit(step)/jit(main)/l2/checkpoint/sparse_experts/moe_dispatch/"
        "gather")[-2:] == ["sparse_experts", "moe_dispatch"]
