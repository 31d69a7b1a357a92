"""Pallas TPU fused RMSNorm (forward + backward).

Every block of every assigned architecture runs 2+ RMSNorms per layer; the
naive HLO chain (square -> mean -> rsqrt -> mul -> mul) makes multiple HBM
passes over the (B*S, d) activation.  The forward reads x once and writes y
once, with the f32 reduction done in VMEM; with ``save_residuals`` it also
emits the per-row reciprocal RMS (rstd) — the only statistic the backward
needs.

The backward is one pass over (x, dy): per row-block it computes

    dx     = rstd * (dy * scale - x * rstd^2 * mean_d(dy * scale * x))
    dscale = sum_rows(dy * x * rstd)            (per-block partial)

and the tiny (n_blocks, d) dscale partials are summed outside the kernel —
cross-row reduction inside would serialize the grid.  Rows are tiled
(block_rows x d); d is padded by the dispatch layer to the 128-lane
boundary if needed.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import default_interpret


def _kernel(x_ref, scale_ref, o_ref, rstd_ref, *, eps: float, d_real: int):
    x = x_ref[...].astype(jnp.float32)          # (br, d)
    # mean of squares over the REAL feature width (padding contributes 0)
    var = jnp.sum(x * x, axis=-1, keepdims=True) / d_real
    rstd = jax.lax.rsqrt(var + eps)
    y = x * rstd * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    if rstd_ref is not None:
        rstd_ref[...] = rstd


def rmsnorm_fwd(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
                save_residuals: bool = False,
                interpret: Optional[bool] = None):
    """x (rows, d); scale (d,).  Returns normalized x (same dtype), plus the
    per-row rstd (rows,) f32 when ``save_residuals``."""
    rows, d = x.shape
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    if interpret is None:
        interpret = default_interpret()
    kern = functools.partial(_kernel, eps=eps, d_real=d)
    out_specs = [pl.BlockSpec((br, d), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((rows, d), x.dtype)]
    if save_residuals:
        # rstd rides as a (rows, 1) column: Mosaic tiles the last two block
        # dims, and a 1-D (br,) block of a longer array is not tile-aligned
        out_specs.append(pl.BlockSpec((br, 1), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((rows, 1), jnp.float32))
    else:
        def kern(x_ref, scale_ref, o_ref, _full=kern):
            _full(x_ref, scale_ref, o_ref, None)
    out = pl.pallas_call(
        kern,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, scale)
    if save_residuals:
        return out[0], out[1][:, 0]
    return out[0]


def _bwd_kernel(x_ref, scale_ref, rstd_ref, dy_ref, dx_ref, dscale_ref, *,
                d_real: int):
    x = x_ref[...].astype(jnp.float32)           # (br, d)
    dy = dy_ref[...].astype(jnp.float32)
    s = scale_ref[...].astype(jnp.float32)       # (d,)
    r = rstd_ref[...]                            # (br, 1)
    dys = dy * s[None, :]
    c = jnp.sum(dys * x, axis=-1, keepdims=True) / d_real
    dx = (dys - x * (r * r) * c) * r
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dscale_ref[...] = jnp.sum(dy * x * r, axis=0)[None, None, :]


def rmsnorm_bwd(x, scale, rstd, dy, *, block_rows: int = 256,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-pass dx/dscale from the saved rstd.  x/dy (rows, d); scale (d,);
    rstd (rows,) f32.  Returns (dx (rows, d) x.dtype, dscale (d,) f32)."""
    rows, d = x.shape
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    if interpret is None:
        interpret = default_interpret()
    n_blocks = rows // br
    dx, dscale_part = pl.pallas_call(
        functools.partial(_bwd_kernel, d_real=d),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, scale, rstd[:, None], dy)
    return dx, dscale_part.sum((0, 1))
