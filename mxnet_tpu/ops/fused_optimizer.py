"""Fused Pallas kernels for the top-ranked mxfuse chains (docs/fusion.md).

The fusion pass (``analysis/fusion.py``) ranks the optimizer update as
the top memory-bound chain of every training step it models: a dozen
small elementwise eqns over the flat f32 parameter space, each reading
and writing full parameter-sized buffers.  The kernels here execute that
chain as ONE pass over HBM — read ``w``/``g``/state once, write the new
``w``/state once — mirroring the reference's fused
``optimizer_op-inl.h`` kernels (sgd_mom_update / adam_update) on the
TPU, plus the fused layernorm for the transformer tier's
layernorm→dense chain.

Numerics contract: every kernel computes the EXACT expression of the
unfused op it replaces (``ops/optimizer_ops.py`` — same order of
operations, same clip/rescale/wd placement), so fused and unfused
updates agree to float tolerance and the fused path is
bitwise-deterministic across runs (tests/test_fusion.py).  The flat
zero-padding tail provably stays zero (a zero ``(w, g, state)`` row maps
to a zero row under SGD/momentum/Adam), preserving ``parallel/zero.py``'s
resize-losslessness lemma.

Cost contract: every kernel DECLARES its cost model with the cost pass
(``declare_kernel_cost``) — bytes = one pass over operands + results —
and the ``fused_optimizer_update`` budget model pins that those declared
bytes equal the fusion pass's modeled ``fused_bytes`` for the chain
(FUS001, the declared-vs-tape parity gate).

``FUSED_OPTIMIZER`` is the **mutation seam** (the ``parallel/zero.py``
``ZERO1_RUNTIME_ALL_GATHER`` discipline): flipping it False makes every
fused spelling fall back to the unfused eqn chain, and the
``STATIC_BUDGETS.json`` gate must fail rc=2 with FUS001 named
(tests/test_fusion.py, subprocess).  Production code never touches it;
the *runtime* switch is :func:`fused_update_enabled` — on by default on
TPU, opt-in via ``MXTPU_FUSED_OPTIMIZER=1`` elsewhere (Pallas interpret
mode is correct but not fast on CPU, so the host default keeps the
unfused XLA spelling).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..analysis.cost import declare_kernel_cost
from .pallas_kernels import _sds, on_tpu, resolve_interpret

__all__ = ["FUSED_OPTIMIZER", "FUSED_LAYERNORM", "fused_update_enabled",
           "fused_layernorm_enabled", "supports", "fused_sgd",
           "fused_sgd_momentum", "fused_adam", "fused_optimizer_update",
           "fused_layer_norm"]

# budget-gate mutation seams (module docstring) — flipped only by tests
FUSED_OPTIMIZER = True
FUSED_LAYERNORM = True


def fused_update_enabled():
    """Should the runtime optimizer update go through the fused kernels?
    Seam AND (TPU, or forced via ``MXTPU_FUSED_OPTIMIZER=1``)."""
    if not FUSED_OPTIMIZER:
        return False
    force = os.environ.get("MXTPU_FUSED_OPTIMIZER")
    if force is not None:
        return force == "1"
    return on_tpu()


def fused_layernorm_enabled(feature_dim=None, dtype=None):
    """Should ``transformer.layers.layer_norm`` use the fused kernel?
    Seam AND (TPU with a lane-aligned f32 feature dim, or forced via
    ``MXTPU_FUSED_LAYERNORM=1``)."""
    if not FUSED_LAYERNORM:
        return False
    force = os.environ.get("MXTPU_FUSED_LAYERNORM")
    if force is not None:
        return force == "1"
    if not on_tpu():
        return False
    if dtype is not None and jnp.dtype(dtype) != jnp.float32:
        return False
    if feature_dim is not None and int(feature_dim) % 128:
        return False
    return True


def supports(opt):
    """``"sgd"`` / ``"adam"`` when ``opt`` is EXACTLY the registered SGD
    or Adam optimizer (subclasses like NAG/LBSGD override ``update`` and
    must keep the unfused path), else None."""
    from ..optimizer import SGD, Adam
    if type(opt) is SGD:
        return "sgd"
    if type(opt) is Adam:
        return "adam"
    return None


# ---------------------------------------------------------------------------
# flat (rows, 128) tiling for the 1-D parameter space
# ---------------------------------------------------------------------------
def _pad_rows(flat, block_rows):
    """(padded (rows, 128) view, rows): zero-pad the flat f32 vector to
    a whole number of ``(block_rows, 128)`` tiles.  The zero tail stays
    zero through every fused update (module docstring)."""
    p = int(flat.shape[0])
    rows = -(-p // 128)
    rows = -(-rows // block_rows) * block_rows
    padded = rows * 128
    if padded != p:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - p,), flat.dtype)])
    return flat.reshape(rows, 128), rows


def _block_rows(p):
    rows = -(-int(p) // 128)
    return 256 if rows >= 256 else -(-rows // 8) * 8


# ---------------------------------------------------------------------------
# the kernels: exact unfused-op expressions, one HBM pass
#
# Every kernel reads THREE scalars from SMEM — ``[lr, inv_scale, ok]``
# (``s_ref``, shape (1, 3) f32).  ``inv_scale`` is the mixed-precision
# loss-scale reciprocal applied to the gradient BEFORE clip (unscale +
# clip + update stays one kernel pass, docs/precision.md); ``ok`` is the
# grads-finite select-skip flag: when 0 the kernel writes the OLD
# weights and state back, so a loss-scale-skipped step is a true no-op
# in the same single HBM pass.  The f32 path passes (inv_scale=1, ok=1)
# — same spelling, so analysis and runtime can never drift.
# ---------------------------------------------------------------------------
def _prep_g(g, inv_scale, rescale_grad, clip_gradient):
    g = (rescale_grad * inv_scale) * g
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g


def _fused_sgd_kernel(s_ref, w_ref, g_ref, ow_ref, *, wd, rescale_grad,
                      clip_gradient):
    # ops/optimizer_ops.py sgd_update: w' = (1 - lr*wd)*w - lr*clip(r*g)
    lr = s_ref[0, 0]
    ok = s_ref[0, 2]
    w = w_ref[...]
    g = _prep_g(g_ref[...], s_ref[0, 1], rescale_grad, clip_gradient)
    ow_ref[...] = jnp.where(ok > 0.0, (1.0 - lr * wd) * w - lr * g, w)


def _fused_sgd_mom_kernel(s_ref, w_ref, g_ref, m_ref, ow_ref, om_ref, *,
                          momentum, wd, rescale_grad, clip_gradient):
    # ops/optimizer_ops.py sgd_mom_update:
    #   m' = momentum*m - lr*wd*w - lr*clip(r*g); w' = w + m'
    lr = s_ref[0, 0]
    ok = s_ref[0, 2]
    w = w_ref[...]
    m = m_ref[...]
    g = _prep_g(g_ref[...], s_ref[0, 1], rescale_grad, clip_gradient)
    new_m = momentum * m - lr * wd * w - lr * g
    ow_ref[...] = jnp.where(ok > 0.0, w + new_m, w)
    om_ref[...] = jnp.where(ok > 0.0, new_m, m)


def _fused_adam_kernel(s_ref, w_ref, g_ref, m_ref, v_ref, ow_ref,
                       om_ref, ov_ref, *, beta1, beta2, epsilon, wd,
                       rescale_grad, clip_gradient):
    # ops/optimizer_ops.py adam_update (s_ref[0, 0] carries the
    # bias-corrected lr_t, computed outside exactly as Adam.update does):
    #   g = clip(r*g + wd*w); m' = b1*m + (1-b1)*g;
    #   v' = b2*v + (1-b2)*g²; w' = w - lr_t*m'/(sqrt(v') + eps)
    lr_t = s_ref[0, 0]
    ok = s_ref[0, 2]
    w = w_ref[...]
    m = m_ref[...]
    v = v_ref[...]
    g = (rescale_grad * s_ref[0, 1]) * g_ref[...] + wd * w
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_m = beta1 * m + (1.0 - beta1) * g
    new_v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    ow_ref[...] = jnp.where(
        ok > 0.0, w - lr_t * new_m / (jnp.sqrt(new_v) + epsilon), w)
    om_ref[...] = jnp.where(ok > 0.0, new_m, m)
    ov_ref[...] = jnp.where(ok > 0.0, new_v, v)


def _scalars(lr, inv_scale, ok):
    """The (1, 3) f32 SMEM operand ``[lr, inv_scale, ok]`` — each entry
    may be a python float or a traced scalar."""
    parts = [jnp.asarray(s, jnp.float32).reshape(1)
             for s in (lr, inv_scale, ok)]
    return jnp.concatenate(parts).reshape(1, 3)


def _flat_call(kernel, scalars, arrays, n_out, aliases, interpret):
    """Run one fused flat kernel over the padded (rows, 128) space."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = resolve_interpret(interpret)
    p = int(arrays[0].shape[0])
    # off-TPU (interpret) there is no VMEM budget: one whole-array
    # block per call keeps the interpreter's per-grid-step overhead out
    # of the fused pass (the host bench measures this path)
    br = max(-(-p // 128), 1) if interpret else _block_rows(p)
    tiles = [_pad_rows(a.astype(jnp.float32), br)[0] for a in arrays]
    rows = int(tiles[0].shape[0])
    blk = pl.BlockSpec((br, 128), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [blk] * len(tiles),
        out_specs=tuple([blk] * n_out) if n_out > 1 else blk,
        out_shape=tuple(_sds((rows, 128), jnp.float32, arrays[0])
                        for _ in range(n_out)) if n_out > 1
        else _sds((rows, 128), jnp.float32, arrays[0]),
        input_output_aliases=dict(aliases),
        name=kernel.func.__name__,
        interpret=interpret,
    )(scalars, *tiles)
    if n_out == 1:
        outs = (outs,)
    return tuple(o.reshape(-1)[:p] for o in outs)


def fused_sgd(w, g, lr, *, wd=0.0, rescale_grad=1.0, clip_gradient=None,
              inv_scale=1.0, ok=1.0, interpret=None):
    """Plain SGD over the flat f32 space as one fused pass."""
    kernel = functools.partial(
        _fused_sgd_kernel, wd=float(wd),
        rescale_grad=float(rescale_grad), clip_gradient=clip_gradient)
    (nw,) = _flat_call(kernel, _scalars(lr, inv_scale, ok), (w, g), 1,
                       {1: 0}, interpret)
    return nw


def fused_sgd_momentum(w, g, m, lr, *, momentum, wd=0.0,
                       rescale_grad=1.0, clip_gradient=None,
                       inv_scale=1.0, ok=1.0, interpret=None):
    """SGD+momentum over the flat f32 space as one fused pass:
    ``(new_w, new_m)``, matching ``nd.sgd_mom_update`` elementwise."""
    kernel = functools.partial(
        _fused_sgd_mom_kernel, momentum=float(momentum), wd=float(wd),
        rescale_grad=float(rescale_grad), clip_gradient=clip_gradient)
    return _flat_call(kernel, _scalars(lr, inv_scale, ok), (w, g, m), 2,
                      {1: 0, 3: 1}, interpret)


def fused_adam(w, g, m, v, lr_t, *, beta1, beta2, epsilon, wd=0.0,
               rescale_grad=1.0, clip_gradient=None, inv_scale=1.0,
               ok=1.0, interpret=None):
    """Adam over the flat f32 space as one fused pass:
    ``(new_w, new_m, new_v)``; ``lr_t`` is the bias-corrected rate."""
    kernel = functools.partial(
        _fused_adam_kernel, beta1=float(beta1), beta2=float(beta2),
        epsilon=float(epsilon), wd=float(wd),
        rescale_grad=float(rescale_grad), clip_gradient=clip_gradient)
    return _flat_call(kernel, _scalars(lr_t, inv_scale, ok),
                      (w, g, m, v), 3, {1: 0, 3: 1, 4: 2}, interpret)


def fused_optimizer_update(opt, index, w_flat, g_flat, state_raw, lr, t,
                           inv_scale=1.0, ok=1.0, interpret=None):
    """Fused twin of ``parallel.functional.functional_optimizer_update``
    for the flat f32 space: same ``(new_w, new_state_raw)`` contract,
    same lr/wd-mult resolution (static mults, traced base lr), same
    update expressions — one kernel pass instead of the eqn chain.
    ``inv_scale``/``ok`` are the mixed-precision loss-scale reciprocal
    and grads-finite select-skip flag (both default to the f32 path's
    no-op values).  ``supports(opt)`` must be truthy."""
    kind = supports(opt)
    if kind is None:
        raise ValueError("fused update supports SGD/Adam exactly; got %s"
                         % type(opt).__name__)
    wd = opt._get_wd(index)                      # static float
    if index in opt.param_dict:
        lmult = opt.param_dict[index].lr_mult
    elif index in opt.lr_mult:
        lmult = opt.lr_mult[index]
    elif index in opt.idx2name:
        lmult = opt.lr_mult.get(opt.idx2name[index], 1.0)
    else:
        lmult = 1.0
    lr = lr * lmult if lmult != 1.0 else lr
    if kind == "sgd":
        if state_raw is None:
            nw = fused_sgd(w_flat, g_flat, lr, wd=wd,
                           rescale_grad=opt.rescale_grad,
                           clip_gradient=opt.clip_gradient,
                           inv_scale=inv_scale, ok=ok,
                           interpret=interpret)
            return nw, None
        nw, nm = fused_sgd_momentum(
            w_flat, g_flat, state_raw, lr, momentum=opt.momentum, wd=wd,
            rescale_grad=opt.rescale_grad,
            clip_gradient=opt.clip_gradient, inv_scale=inv_scale,
            ok=ok, interpret=interpret)
        return nw, nm
    m, v = state_raw
    # the exact bias-corrected rate Adam.update computes
    lr_t = lr * ((1 - opt.beta2 ** t) ** 0.5) / (1 - opt.beta1 ** t)
    nw, nm, nv = fused_adam(
        w_flat, g_flat, m, v, lr_t, beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, wd=wd, rescale_grad=opt.rescale_grad,
        clip_gradient=opt.clip_gradient, inv_scale=inv_scale, ok=ok,
        interpret=interpret)
    return nw, (nm, nv)


# ---------------------------------------------------------------------------
# fused layernorm: the transformer tier's layernorm→dense-epilogue chain
# ---------------------------------------------------------------------------
def _fused_ln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    # transformer/layers.py layer_norm, one VMEM-resident pass per row
    # block: (x - mu) * rsqrt(var + eps) * scale + bias
    x = x_ref[...]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    o_ref[...] = xc * jax.lax.rsqrt(var + eps) * s_ref[...] + b_ref[...]


def _ln_fwd_impl(x, scale, bias, eps, interpret):
    from jax.experimental import pallas as pl

    interpret = resolve_interpret(interpret)
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= int(s)
    x2 = x.reshape(rows, d)
    if interpret:
        br = max(rows, 1)         # one block: no per-grid-step overhead
    else:
        br = 256 if rows >= 256 else -(-rows // 8) * 8
    rp = -(-rows // br) * br
    if rp != rows:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((rp - rows, d), x2.dtype)])
    kernel = functools.partial(_fused_ln_kernel, eps=float(eps))
    out = pl.pallas_call(
        kernel,
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=_sds((rp, d), x.dtype, x),
        name="_fused_ln_kernel",
        interpret=interpret,
    )(x2, scale.reshape(1, d), bias.reshape(1, d))
    return out[:rows].reshape(lead + (d,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_core(x, scale, bias, eps):
    return _ln_fwd_impl(x, scale, bias, eps, None)


def _ln_fwd(x, scale, bias, eps):
    return _ln_fwd_impl(x, scale, bias, eps, None), (x, scale)


def _ln_bwd(eps, res, g):
    # standard layernorm backward, recomputed from x (flash-style: the
    # forward saves no mean/rstd buffers — backward HBM is O(inputs))
    x, scale = res
    mu = x.mean(axis=-1, keepdims=True)
    xc = x - mu
    var = (xc * xc).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    red = tuple(range(x.ndim - 1))
    dbias = g.sum(axis=red)
    dscale = (g * xhat).sum(axis=red)
    dxhat = g * scale
    dx = rstd * (dxhat - dxhat.mean(axis=-1, keepdims=True)
                 - xhat * (dxhat * xhat).mean(axis=-1, keepdims=True))
    return dx, dscale, dbias


_ln_core.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm over the last dim as one fused Pallas pass (forward);
    backward recomputes statistics in XLA.  Differentiable drop-in for
    ``transformer.layers.layer_norm``."""
    return _ln_core(x, scale, bias, float(eps))


# ---------------------------------------------------------------------------
# declared cost models (analysis/cost.py KERNEL_COSTS): one pass over
# operands + results — the byte contract FUS001 pins against the fusion
# pass's modeled fused_bytes
# ---------------------------------------------------------------------------
def _aval_bytes_of(eqn):
    import numpy as _np
    br = bw = 0
    for a in eqn.invars:
        aval = a.aval
        n = 1
        for d in getattr(aval, "shape", ()):
            n *= int(d)
        br += n * _np.dtype(aval.dtype).itemsize
    for v in eqn.outvars:
        aval = v.aval
        n = 1
        for d in getattr(aval, "shape", ()):
            n *= int(d)
        bw += n * _np.dtype(aval.dtype).itemsize
    return br, bw


def _elementwise_cost(eqn, flops_per_elem, trans_per_elem=0):
    br, bw = _aval_bytes_of(eqn)
    n = 1
    for d in eqn.outvars[0].aval.shape:
        n *= int(d)
    return {"flops": flops_per_elem * n,
            "transcendentals": trans_per_elem * n,
            "bytes_read": br, "bytes_written": bw}


@declare_kernel_cost("_fused_sgd_kernel")
def _cost_fused_sgd(eqn):
    # per element: (r*inv)*g, clip?, (1-lr*wd)*w, lr*g, sub, select-skip
    return _elementwise_cost(eqn, 6)


@declare_kernel_cost("_fused_sgd_mom_kernel")
def _cost_fused_sgd_mom(eqn):
    # per element: (r*inv)*g, clip?, momentum*m, lr*wd*w, lr*g, 2 subs,
    # 1 add, 2 select-skips
    return _elementwise_cost(eqn, 10)


@declare_kernel_cost("_fused_adam_kernel")
def _cost_fused_adam(eqn):
    # the 12-op Adam chain + the unscale multiply and 3 select-skips
    cost = _elementwise_cost(eqn, 16)
    n = 1
    for d in eqn.outvars[0].aval.shape:
        n *= int(d)
    cost["transcendentals"] = n           # sqrt(v')
    return cost


@declare_kernel_cost("_fused_ln_kernel")
def _cost_fused_ln(eqn):
    cost = _elementwise_cost(eqn, 8)
    rows = 1
    shape = eqn.outvars[0].aval.shape
    for d in shape[:-1]:
        rows *= int(d)
    cost["transcendentals"] = rows        # rsqrt per row
    return cost
