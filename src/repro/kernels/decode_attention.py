"""Pallas TPU decode attention: one query token per sequence vs a KV cache.

The memory-bound phase of serving: each step streams the KV cache from HBM
once.  Grid: (batch, n_kv_blocks).  Each K/V block holds all Hkv heads of
``block_k`` cache rows — the cache's own (B, L, Hkv, D) layout, which Mosaic
accepts because the block spans the two minor dims whole — and the body
walks the kv heads as static slices.  All G query heads that share a KV head
are packed into one (G x D) @ (D x block_k) MXU matmul, so GQA costs one
cache read regardless of the query-head fan-out.  Online softmax state lives
in VMEM scratch across the innermost KV dimension.

Positions are **per slot** (continuous batching): ``pos (B,)`` is each
sequence's current decode position and ``kpos (B, L)`` the absolute position
held by each of its cache slots (-1 = unwritten), so every batch row can sit
at a different decode depth — a just-admitted request next to one that is
thousands of tokens deep.  ``kpos`` also handles ring-buffer
(sliding-window) caches where slot order is rotated.  Lockstep callers pass
broadcast views; the dispatch layer normalizes scalar ``pos`` / 1-D ``kpos``
automatically.

Two entry points share the kernel body:

  * ``decode_attention_fwd``      — normalized output (B, Hq, D).
  * ``decode_attention_partials`` — per-call ``(acc, m, l)`` flash-decoding
    partials, for the context-parallel path: each seq shard runs the kernel
    over its local cache slice and the cross-shard combine is an O(B*Hq*D)
    psum of the partials (dispatch's ``pallas_cp`` arm) instead of an
    all-gather of the multi-GB cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import default_interpret

NEG = -1e30
# scoped VMEM the attention kernels may claim; v5e has 128 MiB per core and
# the compiler's default scope (16 MiB) is too small for all-head blocks
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(pos_ref, q_ref, k_ref, v_ref, kpos_ref, *refs,
            n_heads: int, n_k: int, scale: float, partials: bool,
            quant: bool):
    if quant:
        ks_ref, vs_ref, *refs = refs
    if partials:
        acc_out_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kpos = kpos_ref[0]                      # (1, bk) — this row's slot map
    pos = pos_ref[pl.program_id(0)]         # this row's decode position
    valid = (kpos >= 0) & (kpos <= pos)
    if quant:
        # the HBM stream stays int8; the per-(row, head) scales arrive as
        # a (bk, Hkv) block, transposed once so each head's scales are a
        # (1, bk) lane row that scales its scores and probabilities
        ks = ks_ref[0].T                    # (Hkv, bk)
        vs = vs_ref[0].T
    # the K/V block holds every kv head of bk cache rows, so the cache is
    # streamed in its own (B, L, Hkv, D) layout; heads are static slices
    for h in range(n_heads):
        q = q_ref[0, h]                     # (G, D)
        k = k_ref[0, :, h, :]               # (bk, D)
        v = v_ref[0, :, h, :]               # (bk, D)
        if quant:
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * ks[h:h + 1]             # q.(k8 * ks) == (q.k8) * ks
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[h]                   # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        pv = p * vs[h:h + 1] if quant else p  # p.(v8 * vs) == (p * vs).v8
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        if partials:
            # unnormalized flash-decoding state; a fully-masked slice keeps
            # m=NEG, so its correction exp(m - pmax(m)) underflows to 0 and
            # the slice vanishes in the cross-shard combine
            acc_out_ref[0] = acc_ref[...]
            m_out_ref[0] = m_ref[...]
            l_out_ref[0] = l_ref[...]
        else:
            l_safe = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def kv_block_rows(length: int, hkv: int, d: int, itemsize: int,
                  cap: int = 1024) -> int:
    """Key rows per all-head K/V block: the largest multiple of 128 that
    divides ``length``, is at most ``cap`` and keeps the block within
    2 MiB of VMEM (the lanes pad D up to 128); 128 at least, since the
    kpos block's lane dim must be a multiple of 128.  A ``length`` that
    128 does not divide (dispatch sends those to jnp on a chip) gets its
    largest power-of-two divisor up to ``cap``."""
    if length % 128:
        bk = min(cap, length)
        while length % bk:
            bk //= 2
        return bk
    row_bytes = hkv * max(d, 128) * itemsize
    return max((bk for bk in range(128, min(cap, length) + 1, 128)
                if length % bk == 0 and bk * row_bytes <= 2 << 20),
               default=128)


def _per_slot(kpos, pos, batch: int):
    """Normalize lockstep (kpos (L,), pos ()) inputs to the per-slot layout
    the kernel reads (kpos (B, L), pos (B,))."""
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (batch,) + kpos.shape)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    return kpos, pos


def _call(q, k_cache, v_cache, kpos, pos, *, block_k: int, partials: bool,
          interpret: Optional[bool], k_scale=None, v_scale=None):
    b, hq, d = q.shape
    length = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = hq // hkv
    bk = min(block_k, length)
    assert length % bk == 0
    n_k = length // bk
    quant = k_scale is not None
    kpos, pos = _per_slot(kpos, pos, b)
    if interpret is None:
        interpret = default_interpret()

    qg = q.reshape(b, hkv, g, d)
    kern = functools.partial(_kernel, n_heads=hkv, n_k=n_k,
                             scale=d ** -0.5, partials=partials, quant=quant)
    # Mosaic tiles the last two block dims: every block below either spans
    # them whole or is (8, 128)-aligned there, so the caches keep their
    # (B, L, Hkv, D) layout and kpos rides as (B, 1, L)
    heads = pl.BlockSpec((1, hkv, g, d), lambda b_, ik: (b_, 0, 0, 0))
    stats = pl.BlockSpec((1, hkv, g, 1), lambda b_, ik: (b_, 0, 0, 0))
    if partials:
        out_specs = [heads, stats, stats]
        out_shape = [jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32)]
    else:
        out_specs = heads
        out_shape = jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype)
    rows = pl.BlockSpec((1, bk, hkv, d), lambda b_, ik: (b_, ik, 0, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),   # pos (B,)
        heads,
        rows,
        rows,
        pl.BlockSpec((1, 1, bk), lambda b_, ik: (b_, 0, ik)),
    ]
    operands = [pos.astype(jnp.int32), qg, k_cache, v_cache,
                kpos.astype(jnp.int32)[:, None, :]]
    if quant:
        # per-(row, head) f32 scales (B, L, Hkv, 1) ride next to the caches,
        # their unit lane dim dropped (a free reshape)
        scales = pl.BlockSpec((1, bk, hkv), lambda b_, ik: (b_, ik, 0))
        in_specs += [scales, scales]
        operands += [k_scale.astype(jnp.float32)[..., 0],
                     v_scale.astype(jnp.float32)[..., 0]]
    return pl.pallas_call(
        kern,
        grid=(b, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)


def decode_attention_fwd(q, k_cache, v_cache, kpos, pos, *,
                         block_k: int = 1024,
                         interpret: Optional[bool] = None,
                         k_scale=None, v_scale=None) -> jnp.ndarray:
    """q (B,Hq,D); caches (B,L,Hkv,D); kpos (B,L) [or (L,) lockstep];
    pos (B,) [or () lockstep] -> (B,Hq,D).

    With ``k_scale``/``v_scale`` ((B, L, Hkv, 1) f32) the caches are int8
    and dequantized inside the kernel body (VMEM), so HBM traffic stays
    int8."""
    b, hq, d = q.shape
    out = _call(q, k_cache, v_cache, kpos, pos, block_k=block_k,
                partials=False, interpret=interpret,
                k_scale=k_scale, v_scale=v_scale)
    return out.reshape(b, hq, d)


def decode_attention_partials(q, k_cache, v_cache, kpos, pos, *,
                              block_k: int = 1024,
                              interpret: Optional[bool] = None,
                              k_scale=None, v_scale=None):
    """Flash-decoding partials over a (local) cache slice.

    Same shapes as ``decode_attention_fwd`` but returns the unnormalized
    online-softmax state ``(acc (B,Hkv,G,D) f32, m (B,Hkv,G) f32,
    l (B,Hkv,G) f32)``; the caller combines across slices with
    ``o = psum(acc * exp(m - pmax(m))) / psum(l * exp(m - pmax(m)))``.
    """
    acc, m, l = _call(q, k_cache, v_cache, kpos, pos, block_k=block_k,
                      partials=True, interpret=interpret,
                      k_scale=k_scale, v_scale=v_scale)
    return acc, m[..., 0], l[..., 0]
