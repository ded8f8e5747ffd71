"""Registry + execution for mxgen generated Pallas kernels.

``analysis/codegen.py`` lowers the top fusion chains of the shipped
tapes into kernel SOURCE; this module is where that source becomes a
real kernel: ``register_generated`` exec's it, records the
``GeneratedKernel``, and auto-declares its ``KERNEL_COSTS`` entry from
the chain's modeled fused bytes — so FUS001 declared-vs-tape parity
holds by construction, and a generated kernel can never land unpriced
(COST006 closes the registry side; the AST sweep in
``analysis/fusion.py`` cannot see exec'd sources).

Execution (``generated_call``) mirrors the ``ops/fused_optimizer.py``
house style: interpret mode off-TPU, whole-array refs by default (one
grid step — correct for broadcasts and reduction epilogues inside the
body), and an optional row-tiled ``(block_rows, 128)`` path for the
flat-tileable pure-elementwise kernels whose block choice the seeded
autotune picks (``analysis.codegen.autotune_block_rows``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..analysis.cost import declare_kernel_cost
from .pallas_kernels import resolve_interpret

from jax.experimental import pallas as pl

GENERATED_KERNELS = {}      # name -> GeneratedKernel


class GeneratedKernel:
    """One registered generated kernel: the exec'd fn + the lowered
    chain's metadata (avals, byte contract, equivalence status)."""

    __slots__ = ("name", "fn", "src", "tag", "rank", "kind", "prims",
                 "n_ops", "in_avals", "out_avals", "bytes_read",
                 "bytes_written", "flops", "transcendentals",
                 "unfused_bytes", "fused_bytes", "bytes_saved",
                 "block_rows", "equivalence_ok", "equivalence_err")

    def __init__(self, lk, fn):
        self.name = lk.name
        self.fn = fn
        self.src = lk.src
        self.tag = lk.tag
        self.rank = lk.rank
        self.kind = lk.kind
        self.prims = list(lk.prims)
        self.n_ops = lk.n_ops
        self.in_avals = list(lk.in_avals)
        self.out_avals = list(lk.out_avals)
        self.bytes_read = int(lk.bytes_read)
        self.bytes_written = int(lk.bytes_written)
        self.flops = int(lk.flops)
        self.transcendentals = int(lk.transcendentals)
        self.unfused_bytes = int(lk.unfused_bytes)
        self.fused_bytes = int(lk.fused_bytes)
        self.bytes_saved = int(lk.bytes_saved)
        self.block_rows = None
        self.equivalence_ok = False
        self.equivalence_err = None


def register_generated(lk):
    """exec a LoweredKernel's source and register it: registry entry +
    auto-declared cost model (the chain's fused-byte split, verbatim —
    parity with the fusion pass is an identity, not a measurement).

    The kernel arrives UNPROVEN (``equivalence_ok=False``): callers run
    the auto-equivalence check and mark it, or GEN002 names them."""
    from ..analysis import codegen as cg

    if lk.src is None:
        raise ValueError("chain %r is not lowerable: %s"
                         % (lk.name, [f.rule_id for f in lk.findings]))
    fn = cg.compile_kernel_source(lk)
    gk = GeneratedKernel(lk, fn)
    GENERATED_KERNELS[lk.name] = gk

    @declare_kernel_cost(lk.name)
    def _cost(eqn, _gk=gk):
        return {"flops": _gk.flops,
                "transcendentals": _gk.transcendentals,
                "bytes_read": _gk.bytes_read,
                "bytes_written": _gk.bytes_written}

    return gk


def _rank1(shape):
    return shape if len(shape) else (1,)


def generated_call(gk, *arrays, interpret=None, block_rows=None):
    """Run a generated kernel over its external inputs, returning the
    chain's external outputs (in lowered order).

    Default: whole-array refs, one grid step — valid for every lowered
    body (broadcast/reduce shapes are baked in).  ``block_rows`` (or the
    kernel's autotuned choice) row-tiles the flat-tileable kernels over
    a ``(block_rows, 128)`` grid; padding rows are sliced off."""
    interpret = resolve_interpret(interpret)
    block_rows = block_rows or gk.block_rows
    if block_rows:
        return _tiled_call(gk, arrays, block_rows, interpret)
    ins = []
    for aval, x in zip(gk.in_avals, arrays):
        x = jnp.asarray(x)
        ins.append(x.reshape((1,)) if x.ndim == 0 else x)
    out_shape = [jax.ShapeDtypeStruct(_rank1(tuple(a.shape)), a.dtype)
                 for a in gk.out_avals]
    outs = pl.pallas_call(gk.fn, out_shape=out_shape, name=gk.name,
                          interpret=interpret)(*ins)
    return [o.reshape(tuple(a.shape))
            for o, a in zip(outs, gk.out_avals)]


def _tiled_call(gk, arrays, block_rows, interpret):
    """Row-tiled path for flat-tileable (pure elementwise, single 1-D
    shape) kernels: flat -> zero-padded (grid*block_rows, 128) blocks.
    Padding flows through the elementwise body and is discarded."""
    cols = 128
    n = int(gk.in_avals[0].shape[0])
    rows = -(-n // cols)
    grid = max(-(-rows // block_rows), 1)
    padded = grid * block_rows * cols

    def blocked(x):
        x = jnp.asarray(x).reshape((-1,))
        return jnp.pad(x, (0, padded - n)).reshape((-1, cols))

    ins = [blocked(x) for x in arrays]
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((grid * block_rows, cols), a.dtype)
                 for a in gk.out_avals]
    outs = pl.pallas_call(
        gk.fn, grid=(grid,),
        in_specs=[spec] * len(ins), out_specs=[spec] * len(out_shape),
        out_shape=out_shape, name=gk.name, interpret=interpret)(*ins)
    return [o.reshape((-1,))[:n] for o in outs]


_SHIPPED = None


def build_shipped_generated(autotune=False):
    """Register the shipped top-N chains of every target tape as
    generated kernels (memoized per process): exec + cost declaration +
    the auto-equivalence check that GEN002 demands.  ``autotune=True``
    additionally picks block rows for the flat-tileable ones (seeded,
    disk-cached — see ``analysis.codegen.autotune_block_rows``)."""
    global _SHIPPED
    from ..analysis import codegen as cg

    if _SHIPPED is None:
        kernels = []
        for lk in cg.shipped_lowered():
            if lk.src is None:
                continue        # GEN001 already names it
            gk = register_generated(lk)
            ok, err = cg.equivalence_check_host(lk)
            gk.equivalence_ok = bool(ok)
            gk.equivalence_err = float(err)
            kernels.append(gk)
        _SHIPPED = kernels
    if autotune:
        for gk in _SHIPPED:
            lk = _lowered_of(gk)
            if gk.block_rows is None and lk is not None \
                    and cg.flat_tileable(lk):
                gk.block_rows = cg.autotune_block_rows(gk)
    return list(_SHIPPED)


def _lowered_of(gk):
    from ..analysis import codegen as cg

    for lk in cg.shipped_lowered():
        if lk.name == gk.name:
            return lk
    return None
