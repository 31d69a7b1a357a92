"""Pallas TPU flash attention backward (dq, dk, dv).

Same tiling philosophy as the forward: score tiles are *recomputed* from
(q, k) one (block_q x block_k) MXU matmul at a time, softmax probabilities
are reconstructed from the forward's saved log-sum-exp (``p = exp(s - lse)``
— no second online pass), and the f32 accumulators live in VMEM scratch
across the innermost (arbitrary-order) grid dimension.

Two kernels, mirroring the classic FlashAttention-2 split:

  * ``dq``:  grid (batch, q_heads, n_q_blocks, n_k_blocks), KV innermost —
    each q block accumulates ``sum_k ds @ k`` across its KV tiles.  The
    softmax-jacobian correction ``delta = rowsum(do * o)`` is computed
    in-kernel on the first KV step (the o/do tiles are already resident —
    one fewer HBM pass than a separate precompute) and emitted as a second
    output for the dkv kernel to consume.
  * ``dkv``: grid (batch, q_heads, n_k_blocks, n_q_blocks), Q innermost —
    each (head, k block) accumulates ``p^T @ do`` and ``ds^T @ q`` across
    the q tiles that attend into it.

Fully-masked score tiles (upper-triangular causal tiles, tiles behind the
sliding window) are *skipped*: the matmul body is predicated on
``tile_live`` so the MXU never touches tiles whose softmax weight is
exactly zero.  Accumulator init/flush stay unconditional — they key off
grid position, not mask content.

K/V move to head-major (B, Hkv, S, D) and lse/delta ride as (B, Hq, S, 1),
so every block spans the two minor dims Mosaic tiles.  GQA uses the
forward's ``h // group`` BlockSpec index-map trick for the K/V *reads*
(repeated KV heads never touch HBM); the dk/dv *writes* are
per-query-head (a block revisited by every head of a group across outer
grid steps cannot accumulate safely), and the cheap ``(Hkv, G)`` group-sum
happens in jnp outside the kernel — identical to the blockwise-jnp path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import default_interpret
from repro.kernels.flash_attention import NEG, tile_live, tile_mask


def _recompute_p(q, k, lse, iq, ik, *, block_q, block_k, causal, window,
                 scale):
    """(block_q, block_k) softmax tile from saved statistics."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = tile_mask(iq, ik, block_q, block_k, causal, window)
    s = jnp.where(mask, s, NEG)
    return jnp.exp(s - lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
               delta_ref, dq_acc_ref, delta_acc_ref, *, causal: bool,
               window: Optional[int], block_q: int, block_k: int, n_k: int,
               scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        # fused delta: rowsum(do * o) over the q tile, once per q block
        delta = jnp.sum(do_ref[0, 0].astype(jnp.float32) *
                        o_ref[0, 0].astype(jnp.float32), axis=1,
                        keepdims=True)
        delta_acc_ref[...] = delta
        delta_ref[0, 0] = delta

    def _compute():
        q = q_ref[0, 0]                  # (bq, D)
        k = k_ref[0, 0]                  # (bk, D)
        v = v_ref[0, 0]                  # (bk, D)
        do = do_ref[0, 0]                # (bq, D)
        p = _recompute_p(q, k, lse_ref[0, 0], iq, ik, block_q=block_q,
                         block_k=block_k, causal=causal, window=window,
                         scale=scale)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_acc_ref[...]) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = tile_live(iq, ik, block_q, block_k, causal, window)
    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, causal: bool,
                window: Optional[int], block_q: int, block_k: int,
                n_q: int, scale: float):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _compute():
        q = q_ref[0, 0]                  # (bq, D)
        k = k_ref[0, 0]                  # (bk, D)
        v = v_ref[0, 0]                  # (bk, D)
        do = do_ref[0, 0]                # (bq, D)
        p = _recompute_p(q, k, lse_ref[0, 0], iq, ik, block_q=block_q,
                         block_k=block_k, causal=causal, window=window,
                         scale=scale)
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = tile_live(iq, ik, block_q, block_k, causal, window)
    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q/o/do (B,S,Hq,D); k,v (B,S,Hkv,D); lse (B,Hq,S) f32.

    Returns (dq (B,S,Hq,D), dk (B,S,Hkv,D), dv (B,S,Hkv,D)).
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_k = s // bq, s // bk
    if interpret is None:
        interpret = default_interpret()
    scale = d ** -0.5

    qh = jnp.moveaxis(q, 1, 2)                      # (B,Hq,S,D)
    doh = jnp.moveaxis(do, 1, 2)
    oh = jnp.moveaxis(o, 1, 2)
    kh = jnp.moveaxis(k, 1, 2)                      # (B,Hkv,S,D)
    vh = jnp.moveaxis(v, 1, 2)
    lse = lse[..., None]                            # (B,Hq,S,1)

    # --- dq (+ fused delta): grid (B, Hq, n_q, n_k), KV innermost ----------
    dq, delta = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          block_q=bq, block_k=bk, n_k=n_k, scale=scale),
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh, doh, oh, lse)
    dq = dq.swapaxes(1, 2)

    # --- dk/dv: grid (B, Hq, n_k, n_q), Q innermost -------------------------
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          block_q=bq, block_k=bk, n_q=n_q, scale=scale),
        grid=(b, hq, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, ik, iq: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ik, iq, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ik, iq, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, ik, iq: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, ik, iq: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b_, h, ik, iq: (b_, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ik, iq: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, ik, iq: (b_, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)

    # group-sum the per-query-head dk/dv back to kv heads: (B,Hq,S,D) ->
    # (B,S,Hkv,D).  One small reduce; the kernels stay write-disjoint.
    dk = dk_h.reshape(b, hkv, g, s, d).sum(2).swapaxes(1, 2)
    dv = dv_h.reshape(b, hkv, g, s, d).sum(2).swapaxes(1, 2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
