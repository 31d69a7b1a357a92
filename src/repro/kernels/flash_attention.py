"""Pallas TPU flash attention (prefill/train forward).

TPU adaptation of the GPU flash algorithm: instead of warp-level softmax
reductions, each grid step computes a (block_q x block_k) score tile as a
single MXU matmul with the online-softmax state (m, l, acc) held in VMEM
scratch across the innermost (arbitrary-order) KV grid dimension.  Block
shapes are MXU-aligned (multiples of 128 on the contracting/lane dims).

Grid: (batch, q_heads, n_q_blocks, n_k_blocks), KV innermost.  The
wrapper moves q, k and v to head-major (B, H, S, D) so every block spans
the two minor dims Mosaic tiles; the per-row log-sum-exp rides as
(B, Hq, S, 1).  GQA: the k/v BlockSpec index maps q-head h to kv-head
h // group, so repeated KV heads are never materialized in HBM or VMEM.

``flash_attention_append`` decouples the q and kv grid dimensions for
chunked prefill (Sq != Sk): C/bq query blocks at absolute positions
``pos0 + i`` scan ceil(Sk/bk) key blocks covering the cache prefix plus
the chunk, with causal/sliding-window masks on absolute positions from a
runtime per-row ``kpos`` map (the decode kernel's validity convention)
and the ``tile_live`` skip for provably-dead prefix tiles.  It reads the
key stream — in serving, the KV cache — in its own (B, Sk, Hkv, D) layout,
one all-head block of key rows per grid step, and walks the heads in the
body, so no step transposes or copies the cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import default_interpret
from repro.kernels.decode_attention import NEG, VMEM_LIMIT


def tile_mask(iq, ik, block_q: int, block_k: int, causal: bool,
              window: Optional[int]):
    """(block_q, block_k) validity mask for score tile (iq, ik).  Shared by
    the forward and backward kernels — the backward reconstructs softmax
    tiles from the forward's saved lse, so the masks must stay identical.
    (The append kernel builds its own mask from the runtime kpos map.)"""
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def tile_live(iq, ik, block_q: int, block_k: int, causal: bool,
              window: Optional[int], q_offset: int = 0):
    """Scalar predicate: does score tile (iq, ik) contain ANY valid entry?

    The complement of ``tile_mask(...).any()`` but computable from the two
    program ids alone (no iota materialization), so kernels can predicate
    the whole tile body with ``pl.when``.  Returns None when no mask is
    active (every tile live) so callers can skip the guard entirely.
    ``q_offset`` places q rows at absolute positions like ``tile_mask``;
    it is only meaningful when key row index == absolute key position
    (a linear cache layout — ring layouts must not skip tiles).
    """
    live = None
    if causal:
        # live iff the smallest kpos can be <= the largest qpos
        live = ik * block_k <= q_offset + (iq + 1) * block_q - 1
    if window is not None:
        # live iff the largest kpos clears the smallest qpos' window floor
        w_live = (ik + 1) * block_k - 1 > q_offset + iq * block_q - window
        live = w_live if live is None else live & w_live
    return live


def masked_tile_fraction(s: int, block_q: int, block_k: int, causal: bool,
                         window: Optional[int]) -> float:
    """Fraction of (iq, ik) score tiles that are fully masked — the work
    the bwd kernels skip (``tile_live`` evaluated on plain ints)."""
    n_q, n_k = s // block_q, s // block_k
    dead = 0
    for iq in range(n_q):
        for ik in range(n_k):
            live = tile_live(iq, ik, block_q, block_k, causal, window)
            dead += live is not None and not live
    return dead / float(n_q * n_k)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: Optional[int], block_q: int, block_k: int,
            n_k: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                      # (bq, D)
    k = k_ref[0, 0]                      # (bk, D)
    v = v_ref[0, 0]                      # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    mask = tile_mask(iq, ik, block_q, block_k, causal, window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + \
        jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 512, block_k: int = 512,
                        save_residuals: bool = False,
                        interpret: Optional[bool] = None):
    """q (B,S,Hq,D); k,v (B,S,Hkv,D) -> (B,S,Hq,D).

    With ``save_residuals`` also returns the per-row log-sum-exp
    (B,Hq,S) f32 — the statistic the backward kernel needs to
    reconstruct softmax tiles without a second online pass."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_k = s // bq, s // bk
    if interpret is None:
        interpret = default_interpret()

    grid = (b, hq, n_q, n_k)
    kern = functools.partial(
        _kernel, causal=causal, window=window, block_q=bq, block_k=bk,
        n_k=n_k, scale=d ** -0.5)
    out_specs = [pl.BlockSpec((1, 1, bq, d),
                              lambda b_, h, iq, ik: (b_, h, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, hq, s, d), q.dtype)]
    if save_residuals:
        out_specs.append(pl.BlockSpec((1, 1, bq, 1),
                                      lambda b_, h, iq, ik: (b_, h, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, hq, s, 1), jnp.float32))
    else:
        def kern(q_ref, k_ref, v_ref, o_ref, *scratch, _full=kern):
            _full(q_ref, k_ref, v_ref, o_ref, None, *scratch)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))
    o = out[0].swapaxes(1, 2)
    if save_residuals:
        return o, out[1][..., 0]
    return o


# ---------------------------------------------------------------------------
# append mode (chunked prefill): Sq != Sk with a q-offset grid
# ---------------------------------------------------------------------------

def _append_kernel(q_ref, k_ref, v_ref, kpos_ref, *refs, pos0: int,
                   window: Optional[int], block_q: int, block_k: int,
                   n_k: int, group: int, n_kv_heads: int, scale: float,
                   kpos_linear: bool, quant: bool):
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, m_ref, l_ref, acc_ref = refs
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile skip: on a linear key layout (key row index == absolute
    # position where valid) whole prefix tiles beyond the causal bound /
    # window floor are provably dead and the body never runs; rotated
    # (ring) layouts visit every tile and rely on the kpos mask alone
    live = tile_live(iq, ik, block_q, block_k, True, window,
                     q_offset=pos0) if kpos_linear else None

    def _body():
        # causal/window on ABSOLUTE positions: q row r sits at
        # pos0 + iq*bq + r; the key positions come from the runtime kpos
        # row map (-1 = unwritten slot), same validity the decode kernel
        # applies per cache row
        qpos = pos0 + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kp = kpos_ref[0]                     # (1, bk)
        mask = (kp >= 0) & (kp <= qpos)
        if window is not None:
            mask &= kp > qpos - window
        if quant:
            # the int8 key stream's per-(row, head) scales arrive as a
            # (bk, Hkv) block, transposed once so each head's scales are a
            # (1, bk) lane row that scales its scores and probabilities
            ks = ks_ref[0].T                 # (Hkv, bk)
            vs = vs_ref[0].T
        for hk in range(n_kv_heads):
            k = k_ref[0, :, hk, :]           # (bk, D)
            v = v_ref[0, :, hk, :]           # (bk, D)
            if quant:
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            for h in range(hk * group, (hk + 1) * group):
                q = q_ref[0, h]              # (bq, D)
                if quant:
                    q = q.astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quant:
                    s = s * ks[hk:hk + 1]    # q.(k8 * ks) == (q.k8) * ks
                s = jnp.where(mask, s, NEG)

                m_prev = m_ref[h]            # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
                pv = p * vs[hk:hk + 1] if quant else p
                acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                    pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

    if live is None:
        _body()
    else:
        pl.when(live)(_body)

    @pl.when(ik == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention_append(q, k, v, kpos, *, pos0: int,
                           window: Optional[int] = None,
                           block_q: int = 512, block_k: int = 512,
                           kpos_linear: bool = False,
                           interpret: Optional[bool] = None,
                           k_scale=None, v_scale=None):
    """Append-mode flash forward: a prompt chunk against a longer key
    stream (the KV-cache prefix plus the chunk itself).

    q (B, C, Hq, D) — chunk queries at absolute positions ``pos0 + i``;
    k, v (B, Sk, Hkv, D) — the key stream; kpos (B, Sk) [or (Sk,)] the
    absolute position held by each key row (-1 = invalid).  Returns
    (B, C, Hq, D).  The q and kv grid dimensions are decoupled
    (``n_q = C/bq``, ``n_k = Sk/bk``), so Sq != Sk is in-grid; causal and
    sliding-window masks evaluate on absolute positions.  With
    ``k_scale``/``v_scale`` ((B, Sk, Hkv, 1) f32) the key stream is int8
    and dequantized inside the kernel body.  Serving-only: no residuals,
    no backward."""
    b, c, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, c)
    bk = min(block_k, sk)
    assert c % bq == 0 and sk % bk == 0, (c, sk, bq, bk)
    n_q, n_k = c // bq, sk // bk
    quant = k_scale is not None
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, sk))
    if interpret is None:
        interpret = default_interpret()

    kern = functools.partial(
        _append_kernel, pos0=pos0, window=window, block_q=bq, block_k=bk,
        n_k=n_k, group=g, n_kv_heads=hkv, scale=d ** -0.5,
        kpos_linear=kpos_linear, quant=quant)
    rows = pl.BlockSpec((1, bk, hkv, d), lambda b_, iq, ik: (b_, ik, 0, 0))
    heads = pl.BlockSpec((1, hq, bq, d), lambda b_, iq, ik: (b_, 0, iq, 0))
    in_specs = [
        heads,
        rows,
        rows,
        pl.BlockSpec((1, 1, bk), lambda b_, iq, ik: (b_, 0, ik)),
    ]
    operands = [jnp.moveaxis(q, 1, 2), k, v,
                kpos.astype(jnp.int32)[:, None, :]]
    if quant:
        # scales (B, Sk, Hkv, 1) with the unit lane dim dropped (free)
        scales = pl.BlockSpec((1, bk, hkv), lambda b_, iq, ik: (b_, ik, 0))
        in_specs += [scales, scales]
        operands += [k_scale.astype(jnp.float32)[..., 0],
                     v_scale.astype(jnp.float32)[..., 0]]
    out = pl.pallas_call(
        kern,
        grid=(b, n_q, n_k),
        in_specs=in_specs,
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((b, hq, c, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((hq, bq, 1), jnp.float32),
                        pltpu.VMEM((hq, bq, 1), jnp.float32),
                        pltpu.VMEM((hq, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    return out.swapaxes(1, 2)
