"""Long-context attention: ring attention + Ulysses sequence parallelism.

The reference (2018-era MXNet) has no long-context story beyond bucketing
(SURVEY.md §5); these are the explicitly-new TPU-side capabilities the
rebuild adds as first-class citizens:

- **Ring attention** (Liu et al. 2023): the sequence axis is sharded over a
  mesh axis; K/V chunks rotate around the ring via ``lax.ppermute`` riding
  ICI while each hop's partial attention is folded in with an online
  (flash-style) softmax.  Peak memory is O(T/n) per chip and the K/V
  transfer overlaps the matmuls.
- **Ulysses / all-to-all sequence parallelism** (DeepSpeed-Ulysses): an
  ``all_to_all`` swaps sequence sharding for head sharding, full attention
  runs locally per head group, and a second all_to_all swaps back.  Cheaper
  collectives for moderate sequence lengths; requires heads % n == 0.

Both are pure jax functions usable inside ``shard_map`` (see
``ring_attention_sharded`` for the pre-wired entry point).

**Now trained with, not just shipped**: the ``mxnet_tpu.transformer``
mesh tier (docs/transformer.md) wires both paths into the real
``DataParallelTrainer(mesh_plan=...)`` step — ring (or Ulysses, when
the local head count divides the sequence axis) attention runs over the
``sequence`` mesh axis inside the jitted training program, composing
with tensor parallelism over ``model`` and ZeRO-1 over ``data``; the
``tp_transformer_train_step`` and ``ulysses_attention`` budget rows in
STATIC_BUDGETS.json pin the resulting collective schedules.

The collective schedule here is a *proven* artifact: the analysis
tier's mxshard passes (``docs/analysis.md`` "Sharding propagation")
trace these functions on a declared ``sequence`` axis and verify that
every scanned ``ppermute`` is a single full ring whose modeled bytes
match the closed-form formula (K hops x chunk — DST009), that no dead
or mixed-axis reduction sneaks in (DST006/DST008), and that the
priced total (6 rotating buffers x K x chunk for forward+backward) is
pinned in ``STATIC_BUDGETS.json`` as ``ring_attention_fwd``.  Both the
ring and Ulysses paths currently lint clean with zero inline disables
(``--self-check`` sweeps them via ``lint_parallel_sources``); anyone
changing a ``perm``, hop count or accumulator rotation below will hear
about it from CI before any hardware runs it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ring_attention", "ulysses_attention", "local_attention",
           "ring_attention_sharded", "ulysses_attention_sharded"]

_NEG_INF = -1e30


def local_attention(q, k, v, causal=False, scale=None, q_offset=0,
                    k_offset=0):
    """Plain attention on local chunks.  q: (B, Tq, H, D), k/v: (B, Tk, H, D).
    Offsets give the chunks' global positions for causal masking."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _pvary(x, axis_name):
    return lax.pcast(x, (axis_name,), to="varying")


def _to_bhtd(x):
    """(B, T, H, D) → (B*H, T, D) — the flash kernels' layout."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bhtd(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _hop_cases(src, idx, causal, diag_fn, full_fn, skip_fn):
    """Causal trichotomy per ring hop: the chunk is the diagonal (aligned
    causal mask), strictly earlier (full attention) or strictly later
    (contributes nothing).  Chunks are aligned so no offset math is needed
    inside the kernels."""
    if not causal:
        return full_fn()
    return lax.cond(
        src == idx, lambda _: diag_fn(),
        lambda _: lax.cond(src < idx, lambda __: full_fn(),
                           lambda __: skip_fn(), _), operand=None)


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    from ..ops import pallas_kernels as _pk

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    qf = _to_bhtd(q)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, hop):
        o, lse, k_cur, v_cur = carry                 # o (BH,Tl,D) f32, lse f32
        src = (idx - hop) % n

        def run(c):
            out, l = _pk.flash_forward_with_lse(qf, _to_bhtd(k_cur),
                                                _to_bhtd(v_cur), c, scale)
            return out.astype(jnp.float32), l

        o_h, lse_h = _hop_cases(
            src, idx, causal,
            diag_fn=lambda: run(True),
            full_fn=lambda: run(False),
            skip_fn=lambda: (jnp.zeros_like(o),
                             jnp.full_like(lse, _NEG_INF)))
        # combine normalized chunk outputs through their logsumexps
        lse_new = jnp.logaddexp(lse, lse_h)
        safe = jnp.where(lse_new <= _NEG_INF / 2, 0.0, lse_new)
        c_old = jnp.where(lse <= _NEG_INF / 2, 0.0, jnp.exp(lse - safe))
        c_hop = jnp.where(lse_h <= _NEG_INF / 2, 0.0, jnp.exp(lse_h - safe))
        o_new = o * c_old[..., None] + o_h * c_hop[..., None]
        # rotate K/V over ICI; the compiler overlaps the permute with the
        # next hop's kernels
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, lse_new, k_next, v_next), None

    o0 = _pvary(jnp.zeros((B * H, Tl, D), jnp.float32), axis_name)
    lse0 = _pvary(jnp.full((B * H, Tl), _NEG_INF, jnp.float32), axis_name)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return _from_bhtd(o.astype(q.dtype), B, H), lse


def _ring_bwd_impl(q, k, v, o_f, lse, do, axis_name, causal, scale):
    """Second ring pass: dq accumulates locally; (dk, dv) accumulators
    travel with their K/V chunks and are home after n hops."""
    from ..ops import pallas_kernels as _pk

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    qf = _to_bhtd(q)
    dof = _to_bhtd(do)
    delta = _pk.flash_delta(_to_bhtd(o_f), dof)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, hop):
        dq_acc, k_cur, v_cur, dk_acc, dv_acc = carry
        src = (idx - hop) % n
        kf, vf = _to_bhtd(k_cur), _to_bhtd(v_cur)

        def run(c):
            dq_h = _pk.flash_dq(qf, kf, vf, dof, lse, delta, c, scale)
            dk_h, dv_h = _pk.flash_dkv(qf, kf, vf, dof, lse, delta, c, scale)
            return (dq_h.astype(jnp.float32), dk_h.astype(jnp.float32),
                    dv_h.astype(jnp.float32))

        dq_h, dk_h, dv_h = _hop_cases(
            src, idx, causal,
            diag_fn=lambda: run(True),
            full_fn=lambda: run(False),
            skip_fn=lambda: (jnp.zeros_like(dq_acc), jnp.zeros_like(dk_acc),
                             jnp.zeros_like(dv_acc)))
        dq_acc = dq_acc + dq_h
        dk_acc = dk_acc + dk_h
        dv_acc = dv_acc + dv_h
        # the chunk gradients rotate with their chunk: after n hops each
        # (dk, dv) accumulator is back on the chunk's owner
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        dk_next = lax.ppermute(dk_acc, axis_name, perm)
        dv_next = lax.ppermute(dv_acc, axis_name, perm)
        return (dq_acc, k_next, v_next, dk_next, dv_next), None

    zeros3 = lambda: _pvary(jnp.zeros((B * H, Tl, D), jnp.float32), axis_name)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (zeros3(), k, v, zeros3(), zeros3()), jnp.arange(n))
    return (_from_bhtd(dq.astype(q.dtype), B, H),
            _from_bhtd(dk.astype(k.dtype), B, H),
            _from_bhtd(dv.astype(v.dtype), B, H))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_core(q, k, v, axis_name, causal, scale):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out


def _ring_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, res, g):
    q, k, v, o_f, lse = res
    return _ring_bwd_impl(q, k, v, o_f, lse, g, axis_name, causal, scale)


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Ring attention over a sharded sequence axis.

    Call inside shard_map; q/k/v are the local (B, T/n, H, D) chunks of a
    globally (B, T, H, D) tensor sharded on `axis_name`.  Returns the local
    output chunk.  Equivalent to full softmax attention over the global
    sequence (verified against local_attention in tests).

    Both directions run the Pallas flash kernels per hop: the forward
    combines per-chunk (out, logsumexp) pairs; the backward is a second
    ring in which (dk, dv) accumulators rotate with their chunks.  Peak
    HBM is O(T/n · D) per chip in both directions — the T×T score matrix
    never exists, even at training time."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_core(q, k, v, axis_name, bool(causal), float(scale))


def _seq2head_impl(x, axis_name):
    # (B, Tl, H, D) -> (B, Tl, n, H/n, D) -> a2a over n -> (B, T, H/n, D)
    n = lax.psum(1, axis_name)
    B, Tl, H, D = x.shape
    x = x.reshape(B, Tl, n, H // n, D)
    x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=0,
                       tiled=False)
    # leading axis now n × B? all_to_all with split_axis=2, concat_axis=0
    # yields (n*B, Tl, H/n, D) — reorder to (B, n*Tl, H/n, D)
    x = x.reshape(n, B, Tl, H // n, D)
    x = x.transpose(1, 0, 2, 3, 4).reshape(B, n * Tl, H // n, D)
    return x


def _head2seq_impl(x, axis_name):
    # exact inverse of _seq2head_impl: (B, T, H/n, D) -> (B, Tl, H, D).
    # concat_axis=2 puts the gathered head-GROUP axis back in front of
    # the within-group axis, so the final reshape restores the original
    # head order h = group * (H/n) + i (concat_axis=3 — the historical
    # spelling — silently permuted heads whenever H/n > 1)
    n = lax.psum(1, axis_name)
    B, T, Hn, D = x.shape
    Tl = T // n
    x = x.reshape(B, n, Tl, Hn, D).transpose(1, 0, 2, 3, 4)
    x = lax.all_to_all(x.reshape(n, B, Tl, Hn, D), axis_name,
                       split_axis=0, concat_axis=2, tiled=False)
    return x.reshape(B, Tl, Hn * n, D)


# The two reshards are bijections (every element changes rank exactly
# once), so each one's VJP is simply the other applied to the cotangent
# — spelled as custom_vjp both because it is exact and because jax
# 0.4.x mis-shapes the transpose of the untiled all_to_all, which would
# otherwise make the Ulysses path untrainable.
@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _seq2head(x, axis_name):
    return _seq2head_impl(x, axis_name)


def _seq2head_fwd(x, axis_name):
    return _seq2head_impl(x, axis_name), None


def _seq2head_bwd(axis_name, _res, g):
    return (_head2seq_impl(g, axis_name),)


_seq2head.defvjp(_seq2head_fwd, _seq2head_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _head2seq(x, axis_name):
    return _head2seq_impl(x, axis_name)


def _head2seq_fwd(x, axis_name):
    return _head2seq_impl(x, axis_name), None


def _head2seq_bwd(axis_name, _res, g):
    return (_seq2head_impl(g, axis_name),)


_head2seq.defvjp(_head2seq_fwd, _head2seq_bwd)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """All-to-all (Ulysses) sequence parallelism.

    Local chunks (B, T/n, H, D) are re-sharded to (B, T, H/n, D) with one
    all_to_all, attended fully per local head group, and re-sharded back.
    Requires H % n == 0.  Differentiable: the swap-back pair's VJPs are
    the inverse reshards, so forward+backward is 8 all_to_alls total —
    the ``ulysses_attention`` budget row pins exactly those bytes."""
    qg = _seq2head(q, axis_name)
    kg = _seq2head(k, axis_name)
    vg = _seq2head(v, axis_name)
    o = local_attention(qg, kg, vg, causal=causal, scale=scale)
    return _head2seq(o, axis_name)


def _seq_sharded_spec(mesh, axis):
    return NamedSharding(mesh, PartitionSpec(None, axis, None, None))


def _shard_map(fn, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with ``check_vma`` off by default: the Pallas
    interpret-mode lowering slices blocks with non-varying program-id
    indices, which the vma checker rejects; the kernels are correct
    under manual sharding."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def ring_attention_sharded(q, k, v, mesh, axis="sp", causal=False):
    """jit-able global entry: q/k/v are global (B, T, H, D) arrays; the
    function shards T over `axis` and runs ring attention."""
    spec = PartitionSpec(None, axis, None, None)
    fn = _shard_map(partial(ring_attention, axis_name=axis, causal=causal),
                    mesh, (spec, spec, spec), spec)
    return fn(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh, axis="sp", causal=False):
    spec = PartitionSpec(None, axis, None, None)
    fn = _shard_map(partial(ulysses_attention, axis_name=axis,
                            causal=causal),
                    mesh, (spec, spec, spec), spec)
    return fn(q, k, v)
