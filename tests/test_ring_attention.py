"""Ring attention + Ulysses sequence parallelism vs full-attention oracle
(new TPU-side capability; no reference analogue — SURVEY.md §5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from mxnet_tpu.parallel import (local_attention, ring_attention_sharded,
                                ulysses_attention_sharded)


@pytest.fixture(scope="module")
def mesh4():
    devs = np.array(jax.devices()[:4])
    return Mesh(devs, ("sp",))


def _qkv(B=2, T=32, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(mesh4, causal):
    q, k, v = _qkv()
    ref = local_attention(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh4, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(mesh4, causal):
    q, k, v = _qkv(seed=1)
    ref = local_attention(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, mesh4, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_multi_head_group_order(causal=True):
    """Regression: head2seq must restore the ORIGINAL head order when
    each rank holds more than one head (H/n > 1) — the historical
    concat_axis=3 spelling silently permuted heads."""
    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _qkv(B=2, T=16, H=4, D=8, seed=5)   # H/n = 2
    ref = local_attention(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_grads_match(mesh4):
    """The swap-back pair's custom VJPs (inverse reshards) make the
    Ulysses path trainable — grads must match full attention."""
    q, k, v = _qkv(B=1, T=16, H=4, D=4, seed=6)
    g_uly = jax.grad(
        lambda a, b, c: ulysses_attention_sharded(a, b, c, mesh4,
                                                  causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: local_attention(a, b, c, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_grads_match(mesh4):
    q, k, v = _qkv(B=1, T=16, H=2, D=4, seed=2)
    g_ring = jax.grad(
        lambda a, b, c: ring_attention_sharded(a, b, c, mesh4,
                                               causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: local_attention(a, b, c, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_under_jit(mesh4):
    q, k, v = _qkv(seed=3)
    fn = jax.jit(lambda a, b, c: ring_attention_sharded(a, b, c, mesh4))
    out = fn(q, k, v)
    ref = local_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_eight_devices():
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _qkv(T=64, seed=4)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
