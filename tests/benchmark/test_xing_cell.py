"""The cell ``Xing4.0-29B-A4B.tokens`` (family ``mhc_mla_moe``, traffic kind
``device_resident_tokens``), on the CPU at rehearsal size: the proofs
``test_ling_cell.py`` makes of Ling's.  The configuration holds the
published values key for key (compared with the dict below, not with a file
outside the repository) and is cut as it says; the counts of the
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
XING = "Xing4.0-29B-A4B"
CELL = XING + ".tokens"
LING_CELL = "Ling-3.0-flash.tokens"
# the published config.json, key for key; the five in REDUCED are this
# chip's share
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}
# the readings the keys leave open, (a)-(g), each with the other beside it
READINGS = ("streams_start", "streams_end", "stream_norm", "hc_eps",
            "sinkhorn_order", "h_res_clamp", "hc_init")
NEW_METRICS = {"hyper_conn_device_ms": ("ms", "lower"),
               "hyper_conn_roofline": ("%", "higher")}
# the accepted metrics whose readers find something in this model's step,
# and those that read what it has not (a prediction module, the Mamba-2
# scan, linear attention, convolutions, the fused update)
LISTED = {"dispatch_ms", "step_mfu", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "fwd_device_ms", "bwd_device_ms",
          "update_device_ms", "scoped_device_share", "host_wait_ms",
          "programs_per_step", "window_compiles", "setup_trace_s",
          "setup_load_s", "attention_device_ms", "mlp_device_ms",
          "head_loss_device_ms", "unscoped_device_ms", "experts_device_ms",
          "moe_routing_device_ms", "latent_proj_device_ms",
          "moe_grouped_rows", "flash_attn_kernel_us", "flash_attn_roofline",
          "grouped_dot_kernel_us", "compute_cast_device_ms"}
SILENT = {"mtp_device_ms", "ssm_scan_device_ms", "mamba_device_ms",
          "ssd_scan_kernel_us", "ssd_scan_roofline", "conv_device_ms",
          "fused_update_us", "linear_attn_device_ms", "kda_scan_device_ms",
          "kda_scan_roofline"}
# limits for the rehearsal size only: at widths of 32 and 64 tokens in
# bfloat16 the program reads, over four seeds, 0.020-0.088 (worst first
# gradient), 0.0029-0.0107 (its median leaf), 0.05-0.18 (worst update),
# 0.0074-0.0245 (its median) and 0.19-0.29 (momentum), a norm's weight the
# worst leaf; the float8 control, two seeds, 0.50-0.63, 0.047-0.053,
# 0.31-0.36, 0.044-0.064 and 0.25-0.51; the cell's own limits are
# limits/<workload>.json
REHEARSAL_LIMITS = {"loss_gap": 2e-3, "grad_norm_gap": 0.25,
                    "grad_norm_gap_median": 0.02, "update_norm_gap": 0.6,
                    "update_norm_gap_median": 0.025, "stats_norm_gap": 0.7}


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
    return harness.load_module("families", "mhc_mla_moe")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", XING + ".json")) as f:
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
    assert config["source"] == ("https://huggingface.co/XingChen-AGI/"
                                "Xing4.0-29B-A4B/blob/main/config.json")
    assert config["family"] == "mhc_mla_moe"
    assert config["expert_shard"] == {"index": 0, "of": 8}
    assert config["n_routed_experts"] * config["expert_shard"]["of"] == 64
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (config["seq_len"], config["batch_per_chip"], config["dtype"],
            config["recompute"], config["items"]) == (
        4096, 2, "bfloat16", "layer", "seq")
    assert config["seq_len"] == \
        PUBLISHED["rope_scaling"]["original_max_position_embeddings"]
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.05,
                                   "momentum": 0.9, "wd": 0.0001}
    assert set(config["assumed"]) >= set(READINGS) | {
        "rope_interleave", "optimizer", "initialisation", "mtp", "yarn",
        "e_score_correction_bias", "seq_len", "batch_per_chip"}
    for reading in READINGS:
        assert "The other reading" in config["assumed"][reading], reading
    assert "8 chips share each layer's experts" in config["deployment"]
    assert "1/8" in config["deployment"]
    assert "759,346,446" in config["deployment"]
    size = {k: config[k] for k in config["rehearsal_size"]}
    assert family.layer_table(family.sized(config, size)) == \
        ["gated_mlp"] + ["sparse_experts"] * 4
    assert family.parameters(config, size) == 759_346_446


def test_the_rehearsal_size_cuts_widths_only_for_the_cpu(config, family):
    """Every key of ``rehearsal_size`` is a key of the configuration (the
    harness reads the run's sizes so); the small table still has both
    feed-forwards, four streams, YaRN and 4 of 8 experts."""
    small = config["rehearsal_size"]
    assert set(small) <= set(config)
    cfg = family.sized(config, small)
    assert family.layer_table(cfg) == ["gated_mlp", "sparse_experts",
                                       "sparse_experts"]
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"]) == (4, 3)
    assert cfg["n_routed_experts"] * cfg["expert_shard"]["of"] == 8
    assert max(v for k, v in small.items() if k != "vocab_size"
               and k.endswith(("_size", "_dim", "_rank"))) <= 64
    assert cfg["rope_scaling"]["type"] == "yarn"


def test_flops_per_item(family, config):
    """The arithmetic: 471.7M multiply-adds a token of the layers and
    the head (mla_moe's count: latent attention 28.4M and 21.0M of causal
    scores and values at 4k, the dense feed-forward 99.1M, an expert
    layer's 16.7M, the head 58.7M) and 3.44M of the ten products with
    ``phi``: 475.2M a token, 23.36 TFLOP a step; and XLA's own count of the
    program's forward pass at the rehearsal size."""
    size = {k: config[k] for k in config["rehearsal_size"]}
    macs = family.forward_macs_per_token(config, size)
    assert macs == pytest.approx(475.16e6, rel=1e-4)
    phi = 10 * 4 * 3584 * 24
    assert macs - phi == pytest.approx(471.72e6, rel=1e-4)
    assert family.flops_per_item(config, size) == 6 * macs * 4096
    assert 2 * family.flops_per_item(config, size) == pytest.approx(
        23.355e12, rel=1e-3)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    # one block of query rows as long as the sequence, which XLA counts in
    # full and the family by the causal half; on the CPU the grouped
    # product is every held expert over every row of the buffer; XLA adds
    # norms, the mixes, Sinkhorn and rotary turns
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


def test_the_hyper_connections_work_from_shapes(family, config):
    """``hyper_connection_work``: ten sublayers x 8,192 tokens of a 4 x
    3,584 stream, (3n + 2) C elements forward and (6n + 3) C backward in
    bfloat16 (8.22 and 15.85 GB), with the expansion and the merge (0.59 GB
    each way); the product with ``phi``, the mixes and Sinkhorn's
    operations: memory-bound both ways on a v5e, 30.8 ms a step.
    ``flash_attention_work``: the five layers at 4k."""
    size = {k: config[k] for k in config["rehearsal_size"]}
    (fwd_flops, fwd_bytes), (bwd_flops, bwd_bytes) = \
        family.hyper_connection_work(config, size)
    tokens, c, n = 8192, 3584, 4
    ends = tokens * 2 * (n + 1) * c * 2
    assert fwd_bytes - ends == 10 * tokens * (3 * n + 2) * c * 2 \
        == 8_220_835_840
    assert bwd_bytes - ends == 10 * tokens * (6 * n + 3) * c * 2
    assert ends == 587_202_560
    per_token = 2 * n * c * 24 + 2 * 24 * c + 16 * (1 + 4 * 20)
    assert fwd_flops == 10 * tokens * per_token and bwd_flops == 2 * fwd_flops
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    least = sum(max(f / peaks["bf16_flops_per_s"],
                    b / peaks["hbm_bytes_per_s"])
                for f, b in ((fwd_flops, fwd_bytes), (bwd_flops, bwd_bytes)))
    assert least == pytest.approx(0.0308, rel=0.01)
    assert fwd_bytes / peaks["hbm_bytes_per_s"] \
        > fwd_flops / peaks["bf16_flops_per_s"]
    (a_flops, _), (b_flops, _) = family.flash_attention_work(config, size)
    pairs = 4096 * 4097 // 2 * 32
    assert a_flops == 5 * 2 * 2 * (192 + 128) * pairs
    assert b_flops == 5 * 2 * 2 * (3 * 192 + 2 * 128) * pairs


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
    seed = 2 ** 31 + 42042
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
    assert harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 42),
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


def test_a_traced_rehearsal_reads_no_device_metric(harness, monkeypatch,
                                                   capsys, keep_jax_config):
    """A CPU has no device plane: the two new readers say nothing there
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
    and the cell come after Ling's; the cell's name follows Ling's cell's
    somewhere on the list of every accepted metric whose reader finds
    something in this model's step, and is on none of those that read what
    it has not; the two new metrics come after every accepted one and name
    this cell first.  Of entries this file does not name nothing is
    held."""
    accepted = rule.load_accepted()
    assert rule.departures(manifest, accepted) == []
    names = lambda key: [e["name"] for e in manifest[key]]   # noqa: E731
    assert names("configs").index(XING) > names("configs").index(
        "Ling-3.0-flash")
    assert names("workloads").index(CELL) > names("workloads").index(
        LING_CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == XING)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % XING
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        XING, "tokens", 1)
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    assert "4,096" in cell["why"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in SILENT:
        assert CELL not in per_layer[name]["workloads"], name
    for name in LISTED:
        cells = per_layer[name]["workloads"]
        assert CELL in cells, name
        if LING_CELL in cells:
            assert cells.index(CELL) > cells.index(LING_CELL), name
    after = max(names("per_layer").index(m["name"])
                for m in accepted["per_layer"])
    places = [names("per_layer").index(name) for name in NEW_METRICS]
    assert places == sorted(places) and places[0] > after
    for name, (unit, better) in NEW_METRICS.items():
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
    # the share of the whole step's peak stands beside the connections'
    assert per_layer["step_mfu"]["moves"] == \
        per_layer["hyper_conn_roofline"]["moves"]
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) | set(limits["not_compared"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_gap_median",
        "update_norm_gap", "update_norm_gap_median", "stats_norm_gap"}
    assert len(limits["limits"]) >= 4 and "where" in limits["set_from"]


def test_the_new_readers_read_a_recorded_step(harness, family, config):
    """The time's reader says nothing without a trace; the share needs the
    family's count and the device's peaks too; the scopes are found in an
    operation's name as the trace has it, beside the sublayer's own."""
    scope_reduce = _load(os.path.join(BENCH, "scope_reduce.py"),
                         "scope_reduce")
    for name in NEW_METRICS:
        reader = harness.load_module("layer_metrics", name)
        assert reader.SCOPES == ("hyper_connection",)
        assert reader.read({"trace": None}) is None
    share = harness.load_module("layer_metrics", "hyper_conn_roofline")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    size = {k: config[k] for k in config["rehearsal_size"]}
    run = {"trace": None, "peaks": peaks, "family": family, "config": config,
           "size": size, "traced_steps": 40}
    assert share.read(run) is None                  # no trace
    assert share.read(dict(run, peaks=None)) is None
    assert share.read(dict(run, family=object())) is None
    scopes = scope_reduce.named_scopes(
        "jit(step)/jit(main)/transpose(jvp(l3))/checkpoint/"
        "hyper_connection/hc_coeffs/while/body/div")
    assert scopes[:3] == ["l3", "checkpoint", "hyper_connection"]
    assert "hc_coeffs" in scopes
    assert "hyper_connection" not in scope_reduce.named_scopes(
        "jit(step)/jit(main)/l3/checkpoint/attention/mla_rope/mul")
