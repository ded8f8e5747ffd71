"""The cell ``Ling-3.0-flash.tokens`` (family ``kda_mla_moe``, traffic kind
``device_resident_tokens``), on the CPU at rehearsal size: the proofs
``test_joyai_cell.py`` makes of JoyAI's.  The configuration holds the
published values key for key and is cut as it says; the counts of the
arithmetic; a sound rehearsal comes out correct, the lower-precision control
and each planted fault do not; the manifest holds the appended entries, by
name.  Nothing here is a measurement.
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
LING = "Ling-3.0-flash"
CELL = LING + ".tokens"
JOYAI_CELL = "JoyAI-LLM-Flash.tokens"
# the catalog row's ``config``, key for key (architectures.jsonl, org
# inclusionAI); the five in REDUCED are this chip's share
PUBLISHED = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 512, "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 8, "vocab_size": 19648,
           "num_nextn_predict_layers": 0}
# the five readings ISSUE 38 fixes, each with its other reading beside it
READINGS = ("rotary_in_linear_attention", "use_qk_norm", "group_norm_size",
            "gated_attention_proj_granularity_type", "no_kda_lora")
NEW_METRICS = {"linear_attn_device_ms": ("ms", "lower", ("kda_mixer",)),
               "kda_scan_device_ms": ("ms", "lower", ("kda_scan",)),
               "kda_scan_roofline": ("%", "higher", ("kda_scan",))}
# the accepted metrics whose readers find something in this model's step,
# and those that read what it has not (a prediction module, the Mamba-2
# scan, convolutions, the fused update)
LISTED = {"dispatch_ms", "step_mfu", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "fwd_device_ms", "bwd_device_ms",
          "update_device_ms", "scoped_device_share", "host_wait_ms",
          "programs_per_step", "window_compiles", "setup_trace_s",
          "setup_load_s", "attention_device_ms", "mlp_device_ms",
          "head_loss_device_ms", "unscoped_device_ms", "experts_device_ms",
          "moe_routing_device_ms", "latent_proj_device_ms",
          "moe_grouped_rows", "grouped_dot_kernel_us",
          "flash_attn_kernel_us", "flash_attn_roofline"}
SILENT = {"mtp_device_ms", "ssm_scan_device_ms", "mamba_device_ms",
          "ssd_scan_kernel_us", "ssd_scan_roofline", "conv_device_ms",
          "fused_update_us"}
# limits for the rehearsal size only: at widths of 32 and 96 tokens the
# program reads 0.05-0.14, 0.011-0.016, 0.24-0.40, 0.018-0.031, 0.35-0.47
# (a four-element A_log is the worst leaf) and the float8 control 0.24-0.6,
# 0.045-0.07, 0.37-0.9, 0.054-0.08, 0.89-1.0; the cell's own are
# limits/<workload>.json
REHEARSAL_LIMITS = {"loss_gap": 3e-3, "grad_norm_gap": 0.2,
                    "grad_norm_gap_median": 0.035, "update_norm_gap": 0.55,
                    "update_norm_gap_median": 0.045, "stats_norm_gap": 0.7}


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
    return harness.load_module("families", "kda_mla_moe")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", LING + ".json")) as f:
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
    assert config["expert_shard"] == {"index": 0, "of": 64}
    assert config["num_experts"] * config["expert_shard"]["of"] == 512
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (config["seq_len"], config["batch_per_chip"], config["dtype"],
            config["recompute"], config["items"], config["kda_chunk"]) == (
        16384, 1, "bfloat16", "layer", "seq", 64)
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.05,
                                   "momentum": 0.9, "wd": 0.0001}
    assert set(config["assumed"]) >= set(READINGS) | {
        "optimizer", "e_score_correction_bias", "initialisation", "seq_len",
        "batch_per_chip", "kda_chunk", "prediction_module", "group_step"}
    for reading in READINGS:
        assert "The other reading" in config["assumed"][reading], reading
    assert "64 chips share each layer's experts" in config["deployment"]
    assert "1/64" in config["deployment"]
    # the table written out is the one the keys give: one whole period
    # after one leading dense layer, the published ratio
    size = {k: config[k] for k in config["rehearsal_size"]}
    table = family.layer_table(family.sized(config, size))
    assert [m for m, _ in table] == config["layer_types"] == \
        ["linear_attention"] * 5 + ["latent_attention", "linear_attention"]
    assert [f for _, f in table] == ["gated_mlp"] + ["sparse_experts"] * 6
    held = config["num_hidden_layers"]
    assert not any(config["expert_swiglu_limit_list"][:held])
    assert not any(config["share_expert_swiglu_limit_list"][:held])
    assert config["mtp_loss_scaling_factor"] == 0
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == LING)
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]


def test_the_rehearsal_size_cuts_widths_only_for_the_cpu(config, family):
    """Every key of ``rehearsal_size`` is a key of the configuration (the
    harness reads the run's sizes so), and the small table still has both
    mixers and both feed-forwards."""
    small = config["rehearsal_size"]
    assert set(small) <= set(config)
    table = family.layer_table(family.sized(config, small))
    assert table == [("linear_attention", "gated_mlp"),
                     ("latent_attention", "sparse_experts"),
                     ("linear_attention", "sparse_experts")]
    assert small["seq_len"] % small["kda_chunk"]      # a padded last chunk


def test_flops_per_item(family, config):
    """ISSUE 38's arithmetic: a KDA mixer 54.2M multiply-adds a token (the
    recurrence 1.57M of it), the latent layer 32.0M + 83.9M of causal scores
    and values at 16k, the dense feed-forward 47.2M, an expert layer's 7.95M,
    the head 50.3M: 586M a token, 57.6 TFLOP a step; and XLA's own count of
    the program's forward pass at the rehearsal size."""
    size = {k: config[k] for k in config["rehearsal_size"]}
    macs = family.forward_macs_per_token(config, size)
    assert 584e6 <= macs <= 588e6
    assert family.flops_per_item(config, size) == 6 * macs * 16384
    assert 57.3e12 <= family.flops_per_item(config, size) <= 57.9e12

    def only(layers, group=6, dense=1):
        sized = dict(size, num_hidden_layers=layers, layer_group_size=group)
        return family.forward_macs_per_token(
            dict(config, first_k_dense_replace=dense), sized)

    head = 2560 * 19648
    kda_dense = only(1) - head
    kda_expert = only(2) - only(1)
    latent_expert = only(2, group=2) - only(1)
    assert kda_dense == pytest.approx(54.16e6 + 47.19e6, rel=2e-3)
    assert kda_expert == pytest.approx(54.16e6 + 7.95e6, rel=2e-3)
    assert latent_expert == pytest.approx(31.97e6 + 83.89e6 + 7.95e6,
                                          rel=2e-3)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    # one block of query rows as long as the sequence, which XLA counts in
    # full and the family by the causal half; the chunked scan does more
    # than the recurrence's 3 E^2; on the CPU the grouped product is every
    # held expert over every row of the buffer; XLA adds norms, gates,
    # convolutions and rotary turns
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
    assert 1.0 * counted <= cost["flops"] <= 3.0 * counted


def test_the_scans_and_attentions_work_from_shapes(family, config):
    """``kda_scan_work``: six layers x 16,384 tokens x 32 heads of 3 x 128^2
    multiply-adds forward and twice that backward; q, k, v, o in bfloat16,
    the gate a channel and beta a head in float32: memory-bound both ways on
    a v5e (16.8 ms a step).  ``flash_attention_work``: the one latent
    layer."""
    size = {k: config[k] for k in config["rehearsal_size"]}
    (fwd_flops, fwd_bytes), (bwd_flops, bwd_bytes) = family.kda_scan_work(
        config, size)
    rows = 6 * 16384 * 32
    assert fwd_flops == rows * 2 * 3 * 128 * 128 and bwd_flops == 2 * fwd_flops
    assert fwd_bytes == rows * (3 * 256 + 512 + 4 + 256)
    assert bwd_bytes == rows * (2 * (3 * 256 + 512 + 4) + 256)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    least = sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
                for f, b in ((fwd_flops, fwd_bytes), (bwd_flops, bwd_bytes)))
    assert least == pytest.approx(0.0168, rel=0.02)
    assert fwd_bytes / peaks["hbm_bytes_per_s"] \
        > fwd_flops / peaks["bf16_flops_per_s"]
    (a_flops, _), (b_flops, _) = family.flash_attention_work(config, size)
    pairs = 16384 * 16385 // 2 * 32
    assert a_flops == 2 * (192 + 128) * pairs
    assert b_flops == 2 * (3 * 192 + 2 * 128) * pairs
    half = dict(size, seq_len=8192)
    assert family.kda_scan_work(config, half)[0][0] * 2 == fwd_flops


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
    control and each planted fault pass at least one limit; the reference's
    step compiles once."""
    correctness = _load(os.path.join(BENCH, "correctness.py"), "correctness")
    size = dict(config["rehearsal_size"])
    seed = 2 ** 31 + 38038
    feed = _feed(harness, config, size, seed, 3)
    reference = family.reference_readings(config, size, seed, feed)
    again = family.reference_readings(config, size, seed, feed)
    assert correctness.verdict(correctness.compare(again, reference),
                               REHEARSAL_LIMITS)[0]
    assert set(reference["stats_norms"]) == set(reference["grad_norms"])
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
    assert harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 38),
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


def test_a_traced_rehearsal_reads_no_device_metric(harness, monkeypatch, capsys,
                                               keep_jax_config):
    """A CPU has no device plane: the three new readers say nothing there
    (nor the counters', which a rehearsal does not count as measured); the
    host's speak."""
    line = _rehearse(harness, monkeypatch, capsys, REHEARSAL_LIMITS, "1")
    assert line["correct"] is True
    assert not set(NEW_METRICS) & set(line["metrics"])
    assert "dispatch_ms" in line["metrics"]


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
def test_the_manifest_holds_the_cells_entries_by_name(rule, harness,
                                                      manifest):
    """The manifest still starts with what was accepted; the configuration
    and the cell come after JoyAI's; the cell's name follows JoyAI's cell's
    somewhere on the list of every accepted metric whose reader finds
    something in this model's step, and is on none of those that read what
    it has not; the three new metrics come after every accepted one.  Of
    entries this file does not name nothing is held."""
    accepted = rule.load_accepted()
    assert rule.departures(manifest, accepted) == []
    names = lambda key: [e["name"] for e in manifest[key]]   # noqa: E731
    assert names("configs").index(LING) > names("configs").index(
        "JoyAI-LLM-Flash")
    assert names("workloads").index(CELL) > names("workloads").index(
        JOYAI_CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == LING)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % LING
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        LING, "tokens", 1)
    assert len(cell["why"]) <= 200 and "1/64" in cell["why"]
    assert "16,384" in cell["why"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in SILENT:
        assert CELL not in per_layer[name]["workloads"], name
    for name in LISTED:
        cells = per_layer[name]["workloads"]
        assert CELL in cells, name
        if JOYAI_CELL in cells:
            assert cells.index(CELL) > cells.index(JOYAI_CELL), name
    after = max(names("per_layer").index(m["name"])
                for m in accepted["per_layer"])
    places = [names("per_layer").index(name) for name in NEW_METRICS]
    assert places == sorted(places) and places[0] > after
    for name, (unit, better, _) in NEW_METRICS.items():
        m = per_layer[name]
        assert m["workloads"][:1] == [CELL]
        assert (m["unit"], m["better"], m["source"]) == (unit, better,
                                                         "device_trace")
        assert (m["layer"], m["moves"]) == ("compiled step",
                                            "train_throughput")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    reported = {m["name"] for m in harness.resolve(manifest, CELL)[4]}
    assert not SILENT & reported
    assert set(NEW_METRICS) | LISTED <= reported
    # the share of the whole step's peak stands beside the scan's share
    assert per_layer["step_mfu"]["moves"] == \
        per_layer["kda_scan_roofline"]["moves"]
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) | set(limits["not_compared"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_gap_median",
        "update_norm_gap", "update_norm_gap_median", "stats_norm_gap"}
    assert len(limits["limits"]) >= 4 and "where" in limits["set_from"]


def test_the_new_readers_read_a_recorded_step(harness, family, config):
    """Each scope's reader says nothing without a trace; the share needs the
    family's count and the device's peaks too; the scopes are found in an
    operation's name as the trace has it."""
    scope_reduce = _load(os.path.join(BENCH, "scope_reduce.py"),
                         "scope_reduce")
    for name, (_, _, scopes) in NEW_METRICS.items():
        reader = harness.load_module("layer_metrics", name)
        assert reader.SCOPES == scopes
        assert reader.read({"trace": None}) is None
    share = harness.load_module("layer_metrics", "kda_scan_roofline")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    size = {k: config[k] for k in config["rehearsal_size"]}
    run = {"trace": None, "peaks": peaks, "family": family, "config": config,
           "size": size, "traced_steps": 40}
    assert share.read(run) is None                  # no trace
    assert share.read(dict(run, peaks=None)) is None
    assert share.read(dict(run, family=object())) is None
    assert scope_reduce.named_scopes(
        "jit(step)/jit(main)/l2/checkpoint/kda_mixer/kda_scan/while/body/"
        "checkpoint/dot_general")[:4] == ["l2", "checkpoint", "kda_mixer",
                                          "kda_scan"]
    assert "moe_group_choice" in scope_reduce.named_scopes(
        "jit(step)/l1/checkpoint/sparse_experts/moe_router/moe_group_choice/"
        "top_k")
