"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's approach of testing distributed semantics without a
cluster (SURVEY.md §4: launch.py --launcher local); here
xla_force_host_platform_device_count gives 8 virtual devices so sharding /
collective paths compile and execute single-process.
"""
import os

# MXTPU_TEST_TPU=1 lifts the CPU pin so @pytest.mark.tpu tests (e.g. the
# non-degenerate TPU-vs-CPU consistency pass) can reach a real chip:
#   MXTPU_TEST_TPU=1 python -m pytest tests/ -m tpu
_USE_TPU = os.environ.get("MXTPU_TEST_TPU") == "1"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

if not _USE_TPU:
    assert jax.default_backend() == "cpu" and jax.device_count() == 8, (
        "tests require the virtual 8-device CPU mesh; JAX came up as "
        f"{jax.default_backend()} with {jax.device_count()} devices")

import numpy as np
import pytest

# -- smoke tier -------------------------------------------------------------
# One (or two) fast representatives per subsystem, curated centrally so the
# tier's coverage is reviewable in one place.  `pytest -m smoke` must stay
# under 3 minutes on the 1-core bench host (VERDICT r4 item 8: the round
# driver runs it beside the bench so a slow full suite can never starve the
# perf capture again).  Tests can also self-mark with @pytest.mark.smoke.
SMOKE = {
    "test_autograd.py::test_basic_backward",
    "test_contrib.py::test_gluon_ctc_loss_blank_last",
    "test_contrib_proposal.py::test_sparse_embedding_forward",
    "test_contrib_py.py::test_text_vocabulary",
    "test_contrib_text.py::test_custom_embedding_loads_and_indexes",
    "test_custom_op.py::test_custom_sigmoid_forward_backward",
    "test_det_libsvm_io.py::test_basic_csr_batches",
    "test_dist.py::test_dist_sync_kvstore_two_processes",
    "test_exc_handling.py::test_shape_mismatch_raises",
    "test_exc_handling.py::test_state_intact_after_failure",
    "test_flash_backward.py::test_flash_grads_match_reference",
    "test_gluon.py::test_dense_shapes_and_forward",
    "test_gluon_model_zoo.py::test_unknown_name",
    "test_group2ctx.py::test_groups_land_different_shardings",
    "test_infer_shape.py::test_mlp_chain",
    "test_io.py::test_recordio_roundtrip",
    "test_io.py::test_indexed_recordio",
    "test_layout_bf16.py::test_conv_nhwc_matches_nchw",
    "test_linalg_cf_quant.py::test_linalg_potrf_potri",
    "test_losses_metrics_sched.py::test_l2_loss_vs_torch",
    "test_mesh_coverage.py::test_module_dp_matches_single_device",
    "test_model_store.py::test_plain_local_params_resolve",
    "test_module.py::test_module_predict_shapes",
    "test_ndarray.py::test_creation",
    "test_ndarray.py::test_arithmetic",
    "test_op_deep_nn.py::test_convolution_vs_torch",
    "test_operator.py::test_unary_family",
    "test_optimizer_ops.py::test_adam_update",
    "test_pallas_conv.py::test_padded_cout_slice",
    "test_parallel.py::test_data_parallel_training_decreases_loss",
    "test_quantization_int8.py::test_quantize_model_rewrites_conv_and_pooling",
    "test_registry_parity.py::test_registry_covers_reference_ops",
    "test_ring_attention.py::test_ring_matches_full",
    "test_rnn.py::test_rnn_op_vs_torch",
    "test_sparse_operator.py::test_cast_storage_csr",
    "test_symbol.py::test_infer_shape",
    "test_train.py::test_mlp_convergence",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    files_collected = set()
    for item in items:
        files_collected.add(item.fspath.basename)
        rel = "%s::%s" % (item.fspath.basename, item.name.split("[")[0])
        if rel in SMOKE:
            matched.add(rel)
            item.add_marker(pytest.mark.smoke)
    # a rename/deletion must not silently shrink the tier: any SMOKE entry
    # whose file WAS collected but whose test no longer exists is an
    # error.  Skipped when the invocation selects single tests by node-id
    # (pytest file.py::test_x) — partial collection would false-positive.
    if any("::" in str(a) for a in config.args):
        return
    ghosts = {s for s in SMOKE - matched
              if s.split("::")[0] in files_collected}
    if ghosts:
        raise pytest.UsageError(
            "smoke-tier entries match no collected test (renamed or "
            "deleted?): %s" % ", ".join(sorted(ghosts)))


@pytest.fixture(autouse=True)
def fixed_seed():
    """Fixed seeds per test (reference: tests/python/unittest/common.py with_seed)."""
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
