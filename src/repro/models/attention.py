"""Grouped-query attention with RoPE / M-RoPE, sliding windows and KV caches.

Three entry points:
  * ``attend_train``   — full-sequence causal (or bidirectional) attention.
  * ``attend_decode``  — one new token against a pre-filled KV cache.
  * ``cross_attend``   — decoder query over encoder memory (Whisper).

The jnp paths here (``sdpa``, masks, the blockwise flash in
``flash_jnp``) are the reference implementations; the Pallas kernels in
``repro.kernels`` implement the same math with explicit VMEM tiling and are
validated against these in tests.  Backend selection — which of the two
families a call lowers through, bare or shard_map'd over a mesh — lives
entirely in ``repro.kernels.dispatch``; the entry points here just forward
``backend`` (default ``"auto"``) to it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.kernels import dispatch, kv_quant
from repro.models import common as cm

NEG_INF = -1e30


class AttnParams(NamedTuple):
    pass  # attention params are plain dicts; NamedTuple kept for doc purposes


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool = False,
                   out_bias: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": cm.init_linear(ks[0], d_model, n_heads * head_dim, bias=qkv_bias),
        "wk": cm.init_linear(ks[1], d_model, n_kv_heads * head_dim, bias=qkv_bias),
        "wv": cm.init_linear(ks[2], d_model, n_kv_heads * head_dim, bias=qkv_bias),
        "wo": cm.init_linear(ks[3], n_heads * head_dim, d_model, bias=out_bias),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hkv * n_rep, D)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def sdpa(q, k, v, mask, *, scale: Optional[float] = None) -> jnp.ndarray:
    """q (B,Sq,H,D), k/v (B,Sk,H,D), mask broadcastable to (B,H,Sq,Sk)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_mask(sq: int, sk: int, *, window: Optional[int] = None,
                offset: int = 0) -> jnp.ndarray:
    """(1, 1, Sq, Sk) boolean mask.  ``offset`` = absolute position of q row 0
    minus position of k col 0.  ``window`` keeps only the last ``window`` keys
    (sliding-window / chunked-local attention)."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m[None, None]


def attend_train(params: dict, x: jnp.ndarray, cos, sin, cfg,
                 *, window: Optional[int] = None, use_rope: bool = True,
                 bidirectional: bool = False,
                 backend: str = "auto") -> jnp.ndarray:
    """Full-sequence self attention.  x (B, S, d_model)."""
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(cm.linear(params["wq"], x), n_h, hd)
    k = _split_heads(cm.linear(params["wk"], x), n_kv, hd)
    v = _split_heads(cm.linear(params["wv"], x), n_kv, hd)
    if use_rope:
        rd = getattr(cfg, "rotary_dim", None)
        q = cm.apply_rope(q, cos, sin, rotary_dim=rd)
        k = cm.apply_rope(k, cos, sin, rotary_dim=rd)
    # Megatron-TP: attention is head-local on the model axis; without these
    # constraints GSPMD re-gathers K/V blocks inside the flash scan.
    q = ctx.constrain(q, "attn_q")
    k = ctx.constrain(k, "attn_kv")
    v = ctx.constrain(v, "attn_kv")
    o = dispatch.flash_attention(q, k, v, causal=not bidirectional,
                                 window=window, backend=backend)
    b, s = x.shape[:2]
    return cm.linear(params["wo"], o.reshape(b, s, n_h * hd))


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, cache_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    """Cache for one attention layer.  ``index`` is the next write slot; for
    ring caches (sliding window) writes wrap modulo ``cache_len``.

    ``dtype=int8`` makes the cache quantized: ``k``/``v`` store int8 rows
    and per-(row, head) f32 scales ride alongside as ``ks``/``vs``
    (batch, cache_len, Hkv, 1) — rank-matched so sharding specs and
    engine scatters treat them exactly like the payload.  Zero-init
    scales dequantize to zeros; kpos masks unwritten rows anyway."""
    cache = {
        "k": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
        "index": jnp.zeros((), jnp.int32),
    }
    if kv_quant.is_quantized(dtype):
        cache["ks"] = jnp.zeros((batch, cache_len, n_kv_heads, 1),
                                jnp.float32)
        cache["vs"] = jnp.zeros((batch, cache_len, n_kv_heads, 1),
                                jnp.float32)
    return cache


class PagedLayout(NamedTuple):
    """Static description of a paged cache: fixed-size pages in a shared
    pool, per-slot page tables.  Page 0 is the reserved garbage sink —
    writes through unmapped table rows land there and reads mask them out
    via kpos — so allocators hand out pages 1..n_pages-1."""
    page_size: int
    n_pages: int


def init_paged_kv_cache(batch: int, cache_len: int, n_kv_heads: int,
                        head_dim: int, *, page_size: int, n_pages: int,
                        dtype=jnp.bfloat16) -> dict:
    """Paged cache for one attention layer: a shared page pool ``kp``/``vp``
    (n_pages, page_size, Hkv, D) plus a per-slot page table ``pt``
    (batch, cache_len // page_size) int32 (-1 = unmapped).  The logical
    per-slot length is exactly ``cache_len``, so ``cache_len`` must divide
    into whole pages — the dense gathered view then has the contiguous
    layout's shapes bit-for-bit."""
    if cache_len % page_size:
        raise ValueError(f"cache_len {cache_len} must be a multiple of "
                         f"page_size {page_size} (whole-page slots)")
    max_pages = cache_len // page_size
    cache = {
        "kp": jnp.zeros((n_pages, page_size, n_kv_heads, head_dim), dtype),
        "vp": jnp.zeros((n_pages, page_size, n_kv_heads, head_dim), dtype),
        "pt": jnp.full((batch, max_pages), -1, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }
    if kv_quant.is_quantized(dtype):
        # scale pools ride the page pool: same leading (page, offset) dims,
        # so page COW / refcount / sharding logic applies verbatim
        cache["kps"] = jnp.zeros((n_pages, page_size, n_kv_heads, 1),
                                 jnp.float32)
        cache["vps"] = jnp.zeros((n_pages, page_size, n_kv_heads, 1),
                                 jnp.float32)
    return cache


def _decode_cp_rule(cache_len: int) -> Optional[dict]:
    """The active ``decode_cp`` rule when it actually owns this cache's
    sequence dim (divisible into one slice per shard), else None."""
    cp = (ctx.current_rules() or {}).get("decode_cp")
    if cp is None:
        return None
    n = cp["n_shards"]
    if cache_len % n != 0 or cache_len < n:
        return None
    return cp


def _update_kv_cache_cp(cache: dict, k, v, slot, cp, ks=None, vs=None
                        ) -> tuple:
    """Write each row's new K/V on the owning sequence shard only.

    The cache's sequence dim is sharded over ``cp['seq_axes']``; a plain
    dynamic_update_slice would make GSPMD re-gather the multi-GB cache, so
    the write is a predicated update inside shard_map — each shard updates
    its slice iff the row's slot falls in its range.  ``slot`` is per batch
    row (B,) (continuous batching) or a lockstep scalar.  (The attention
    over the updated cache then routes through ``dispatch.decode_attention``,
    which resolves the matching ``pallas_cp`` combine.)

    Quantized caches pass the already-quantized rows plus their scales
    (``ks``/``vs`` (B, 1, Hkv, 1)); the rank-matched scale leaves take the
    exact same predicated write.  Returns (ck, cv) or (ck, cv, cks, cvs).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import decode_cp_spec

    # same layout spec the dispatch combine uses — the write and the
    # attention must agree on the cache's partitioning
    b = k.shape[0]
    spec = decode_cp_spec(cp, batch=b)
    mesh, seq_axes = spec.mesh, spec.seq_axes
    cache_len = cache["k"].shape[1]
    l_loc = cache_len // cp["n_shards"]
    slot = jnp.broadcast_to(jnp.asarray(slot), (b,))
    if ks is None:
        new_rows = (k, v)
        leaves = (cache["k"], cache["v"])
    else:
        new_rows = (k, v, ks, vs)
        leaves = (cache["k"], cache["v"], cache["ks"], cache["vs"])
    n = len(leaves)

    def write(slot_, *args):
        new, old = args[:n], args[n:]
        # shard coordinate along the (possibly multi-axis) seq sharding
        idx = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        local_slot = slot_ - idx * l_loc               # (B_loc,)
        in_range = (local_slot >= 0) & (local_slot < l_loc)
        ls = jnp.clip(local_slot, 0, l_loc - 1)
        rows = jnp.arange(old[0].shape[0])
        sel = in_range[:, None, None]                  # vs (B_loc, Hkv, D)
        return tuple(
            od.at[rows, ls].set(
                jnp.where(sel, nw[:, 0].astype(od.dtype), od[rows, ls]))
            for nw, od in zip(new, old))

    return jax.shard_map(write, mesh=mesh,
                         in_specs=(P(spec.batch),) + (spec.new_kv,) * n +
                         (spec.kv,) * n,
                         out_specs=(spec.kv,) * n,
                         check_vma=False)(slot, *new_rows, *leaves)


def attend_decode(params: dict, x: jnp.ndarray, cache: dict, pos: jnp.ndarray,
                  cfg, *, window: Optional[int] = None, use_rope: bool = True,
                  backend: str = "auto"):
    """One-token decode.  x (B, 1, d_model); pos — absolute position, either
    a lockstep scalar () or per-slot (B,) (continuous batching: every batch
    row decodes at its own depth; writes, RoPE and the validity mask are all
    per row).

    Returns (out (B, 1, d_model), new_cache).  When ``window`` is set the
    cache is a ring buffer of length == window (sub-linear memory for
    long-context decode); otherwise cache_len == max seq and slot == pos.

    One entry point serves both cache layouts: when the ``decode_cp`` rules
    own the cache's sequence dim, the cache write is a predicated
    shard_map'd update on the owning shard and ``dispatch.decode_attention``
    resolves to the ``pallas_cp`` flash-decoding combine; otherwise the
    write is a plain (per-row) update and dispatch shard_maps over
    (batch, heads) / runs the bare kernel.
    """
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b = x.shape[0]
    per_slot = jnp.ndim(pos) == 1
    q = _split_heads(cm.linear(params["wq"], x), n_h, hd)
    k = _split_heads(cm.linear(params["wk"], x), n_kv, hd)
    v = _split_heads(cm.linear(params["wv"], x), n_kv, hd)
    if use_rope:
        qpos = pos[:, None] if per_slot else pos[None, None]
        cos, sin = cm.rope_cos_sin(qpos, hd, cfg.rope_theta)
        rd = getattr(cfg, "rotary_dim", None)
        q = cm.apply_rope(q, cos, sin, rotary_dim=rd)
        k = cm.apply_rope(k, cos, sin, rotary_dim=rd)

    if "kp" in cache:
        # paged layout: write through the page table, read through the
        # page-gathered dispatch arm.  Linear caches only — a rotating
        # window has no reusable prefix, so ring layers stay contiguous.
        if window is not None:
            raise ValueError("paged KV caches do not support sliding "
                             "windows; keep ring layers contiguous")
        quant = "kps" in cache
        ps = cache["kp"].shape[1]
        cache_len = cache["pt"].shape[1] * ps
        pt = cache["pt"]
        pidx = pos // ps
        off = pos % ps
        if per_slot:
            page = pt[jnp.arange(b), pidx]             # (B,)
        else:
            page = pt[:, pidx]                         # (B,) scalar col
        # unmapped rows write the page-0 garbage sink; kpos masks them
        page_w = jnp.maximum(page, 0)
        if quant:
            # quantize-on-write: each new row lands as int8 + its own
            # per-(row, head) scale, so no existing page row is rescanned
            k, k_sc = kv_quant.quantize(k)             # (B,1,Hkv,{D,1})
            v, v_sc = kv_quant.quantize(v)
        kp = cache["kp"].at[page_w, off].set(k[:, 0].astype(cache["kp"].dtype))
        vp = cache["vp"].at[page_w, off].set(v[:, 0].astype(cache["vp"].dtype))
        new_cache = {"kp": kp, "vp": vp, "pt": pt,
                     "index": jnp.max(pos) + 1}
        kps = vps = None
        if quant:
            kps = cache["kps"].at[page_w, off].set(k_sc[:, 0])
            vps = cache["vps"].at[page_w, off].set(v_sc[:, 0])
            new_cache["kps"], new_cache["vps"] = kps, vps
        o = dispatch.decode_attention_paged(q[:, 0], kp, vp, pt, pos,
                                            length=cache_len,
                                            k_scale=kps, v_scale=vps,
                                            backend=backend)[:, None]
        return cm.linear(params["wo"], o.reshape(b, 1, n_h * hd)), new_cache

    quant = "ks" in cache
    if quant:
        k, k_sc = kv_quant.quantize(k)                 # (B,1,Hkv,{D,1})
        v, v_sc = kv_quant.quantize(v)
    cache_len = cache["k"].shape[1]
    # full cache: slot == pos (pos < cache_len); ring cache: wrap around.
    slot = pos % cache_len
    cp = _decode_cp_rule(cache_len)
    cks = cvs = None
    if cp is not None:
        if quant:
            ck, cv, cks, cvs = _update_kv_cache_cp(cache, k, v, slot, cp,
                                                   ks=k_sc, vs=v_sc)
        else:
            ck, cv = _update_kv_cache_cp(cache, k, v, slot, cp)
    elif per_slot:
        rows = jnp.arange(b)
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
        if quant:
            cks = cache["ks"].at[rows, slot].set(k_sc[:, 0])
            cvs = cache["vs"].at[rows, slot].set(v_sc[:, 0])
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        if quant:
            cks = jax.lax.dynamic_update_slice(
                cache["ks"], k_sc, (0, slot, 0, 0))
            cvs = jax.lax.dynamic_update_slice(
                cache["vs"], v_sc, (0, slot, 0, 0))
    new_cache = {"k": ck, "v": cv, "index": jnp.max(pos) + 1}
    if quant:
        new_cache["ks"], new_cache["vs"] = cks, cvs

    kpos = _cache_positions(cache_len, pos, window)
    o = dispatch.decode_attention(q[:, 0], ck, cv, kpos, pos,
                                  k_scale=cks, v_scale=cvs,
                                  backend=backend)[:, None]
    return cm.linear(params["wo"], o.reshape(b, 1, n_h * hd)), new_cache


def _cache_positions(cache_len: int, pos: jnp.ndarray,
                     window: Optional[int]) -> jnp.ndarray:
    """Absolute position of each cache slot; -1 for not-yet-written slots.
    pos () -> (L,); per-slot pos (B,) -> (B, L)."""
    idx = jnp.arange(cache_len)
    if jnp.ndim(pos) == 1:
        pos = pos[:, None]                             # (B, 1) vs (L,)
    if window is None:
        return jnp.where(idx <= pos, idx, -1)
    # ring buffer: slot s holds position p iff p % cache_len == s and
    # pos - cache_len < p <= pos.
    cand = pos - (pos % cache_len) + idx
    cand = jnp.where(cand > pos, cand - cache_len, cand)
    return jnp.where(cand >= 0, cand, -1)


# ---------------------------------------------------------------------------
# chunked flash prefill
# ---------------------------------------------------------------------------

def attend_prefill(params: dict, x: jnp.ndarray, cache: dict, pos0: int,
                   cfg, *, window: Optional[int] = None,
                   use_rope: bool = True, backend: str = "auto",
                   true_len: Optional[jnp.ndarray] = None):
    """Prefill one prompt chunk.  x (B, C, d_model) covers absolute positions
    [pos0, pos0 + C) — the same positions for every row (prompts are
    right-padded to a common length; ``true_len`` (B,) optionally carries
    each row's real prompt length so ring writes can mask padding, and the
    caller's logit gather / per-slot decode handle the rest).

    Writes the chunk's K/V into cache rows [pos0, pos0 + C) (ring wrap for
    window caches) and returns (out (B, C, d_model), new_cache).  ``pos0``
    is a static python int.  Every chunk — first and later alike — runs
    one ``dispatch.flash_attention_append`` call: the chunk's queries at
    absolute positions [pos0, pos0 + C) attend the key stream
    (cache prefix + the chunk's own K/V) under the kernel's q-offset grid,
    with ring caches passing the same per-row kpos validity the decode
    kernel uses.  There is no masked-sdpa prefix branch; unaligned smoke
    shapes fall back to the jnp append oracle inside dispatch.
    """
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, c, _ = x.shape
    q = _split_heads(cm.linear(params["wq"], x), n_h, hd)
    k = _split_heads(cm.linear(params["wk"], x), n_kv, hd)
    v = _split_heads(cm.linear(params["wv"], x), n_kv, hd)
    if use_rope:
        positions = pos0 + jnp.arange(c)[None]         # (1, C)
        cos, sin = cm.rope_cos_sin(positions, hd, cfg.rope_theta)
        rd = getattr(cfg, "rotary_dim", None)
        q = cm.apply_rope(q, cos, sin, rotary_dim=rd)
        k = cm.apply_rope(k, cos, sin, rotary_dim=rd)

    if "kp" in cache:
        if window is not None:
            raise ValueError("paged KV caches do not support sliding "
                             "windows; keep ring layers contiguous")
        quant = "kps" in cache
        ps = cache["kp"].shape[1]
        cache_len = cache["pt"].shape[1] * ps
        if pos0 + c > cache_len:
            raise ValueError(
                f"prefill chunk [{pos0}, {pos0 + c}) overflows the "
                f"{cache_len}-slot paged cache; chunk the prompt to fit")
        pt = cache["pt"]
        positions = pos0 + jnp.arange(c)               # (C,)
        pi = positions // ps
        offs = positions % ps
        pages = pt[:, pi]                              # (B, C)
        end = jnp.full((b,), pos0 + c, jnp.int32) if true_len is None \
            else jnp.minimum(pos0 + c, true_len.astype(jnp.int32))
        # mask BOTH unmapped pages and padding positions >= true_len: a
        # right-padded row must not clobber a shared page another slot's
        # real tokens (or decode output) live in — invalid writes land in
        # the page-0 sink instead
        valid = (positions[None, :] < end[:, None]) & (pages > 0)
        page_w = jnp.where(valid, pages, 0)
        k_sc = v_sc = None
        if quant:
            # quantize once; the same bytes land in the pool AND feed this
            # chunk's attention, so prefill and later decode reads see
            # identical dequantized values
            k, k_sc = kv_quant.quantize(k)             # (B,C,Hkv,{D,1})
            v, v_sc = kv_quant.quantize(v)
        kp = cache["kp"].at[page_w, offs[None, :]].set(
            k.astype(cache["kp"].dtype))
        vp = cache["vp"].at[page_w, offs[None, :]].set(
            v.astype(cache["vp"].dtype))
        new_cache = {"kp": kp, "vp": vp, "pt": pt,
                     "index": jnp.asarray(pos0 + c, jnp.int32)}
        kps = vps = None
        if quant:
            kps = cache["kps"].at[page_w, offs[None, :]].set(k_sc)
            vps = cache["vps"].at[page_w, offs[None, :]].set(v_sc)
            new_cache["kps"], new_cache["vps"] = kps, vps
        # key stream: the PRE-write pool holds the prefix [0, pos0) —
        # the chunk's own K/V ride alongside as dense tensors
        o = dispatch.flash_attention_append_paged(
            q, cache["kp"], cache["vp"], pt, k, v, pos0=pos0,
            k_scale=cache.get("kps"), v_scale=cache.get("vps"),
            ks_chunk=k_sc, vs_chunk=v_sc,
            backend=backend)
        return cm.linear(params["wo"], o.reshape(b, c, n_h * hd)), new_cache

    quant = "ks" in cache
    k_sc = v_sc = None
    if quant:
        # quantize the chunk once: the cache write and this chunk's own
        # key stream use the same int8 bytes + scales, so prefill
        # attention matches what decode later reads back
        k, k_sc = kv_quant.quantize(k)                 # (B,C,Hkv,{D,1})
        v, v_sc = kv_quant.quantize(v)
    cache_len = cache["k"].shape[1]
    cks = cvs = None
    if window is None:
        if pos0 + c > cache_len:
            # a full cache has no wrap semantics: writing past the end
            # would clobber real prompt rows that kpos still reports as
            # valid — loud trace-time failure, the caller must size its
            # chunk grid to the cache (serve._chunk_grid)
            raise ValueError(
                f"prefill chunk [{pos0}, {pos0 + c}) overflows the "
                f"{cache_len}-slot full cache; chunk the prompt to fit")
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos0, 0, 0))
        if quant:
            cks = jax.lax.dynamic_update_slice(
                cache["ks"], k_sc, (0, pos0, 0, 0))
            cvs = jax.lax.dynamic_update_slice(
                cache["vs"], v_sc, (0, pos0, 0, 0))
    else:
        # ring cache: slot s must end up holding the LAST written position
        # p ≡ s (mod cache_len) with pos0 <= p < end[row].  Computed as a
        # per-slot gather instead of a scatter, which (a) has no duplicate
        # -index ordering hazard when C > cache_len and (b) takes a
        # per-row ``end`` — rows shorter than the padded chunk grid
        # (true_len) simply stop writing at their real prompt length, so
        # right-padded admission chunks can no longer alias ring rows that
        # kpos attributes to real earlier positions.
        end = jnp.full((b,), pos0 + c, jnp.int32) if true_len is None \
            else jnp.minimum(pos0 + c, true_len.astype(jnp.int32))
        idx = jnp.arange(cache_len)
        last = end[:, None] - 1                              # (B, 1)
        p_cand = last - ((last - idx[None, :]) % cache_len)  # (B, L)
        valid = p_cand >= pos0
        sel = jnp.clip(p_cand - pos0, 0, c - 1)
        gk = jnp.take_along_axis(k.astype(cache["k"].dtype),
                                 sel[:, :, None, None], axis=1)
        gv = jnp.take_along_axis(v.astype(cache["v"].dtype),
                                 sel[:, :, None, None], axis=1)
        ck = jnp.where(valid[:, :, None, None], gk, cache["k"])
        cv = jnp.where(valid[:, :, None, None], gv, cache["v"])
        if quant:
            gks = jnp.take_along_axis(k_sc, sel[:, :, None, None], axis=1)
            gvs = jnp.take_along_axis(v_sc, sel[:, :, None, None], axis=1)
            cks = jnp.where(valid[:, :, None, None], gks, cache["ks"])
            cvs = jnp.where(valid[:, :, None, None], gvs, cache["vs"])
    # strong int32: a weak-typed scalar here would retrace the decode step
    # that consumes this cache
    new_cache = {"k": ck, "v": cv, "index": jnp.asarray(pos0 + c, jnp.int32)}
    if quant:
        new_cache["ks"], new_cache["vs"] = cks, cvs

    # key stream for the append call: the pre-chunk cache prefix (rows a
    # ring write above may have evicted are only positions no chunk query
    # can still see) plus the chunk's own K/V from this projection
    ks_all = vs_all = None
    if pos0 == 0:
        k_all, v_all = k, v
        ks_all, vs_all = k_sc, v_sc
        kpos_all = jnp.arange(c)
        linear = True
    elif window is None:
        cast = (lambda x: x) if quant else (lambda x: x.astype(q.dtype))
        k_all = jnp.concatenate([cast(cache["k"][:, :pos0]), k], axis=1)
        v_all = jnp.concatenate([cast(cache["v"][:, :pos0]), v], axis=1)
        if quant:
            ks_all = jnp.concatenate([cache["ks"][:, :pos0], k_sc], axis=1)
            vs_all = jnp.concatenate([cache["vs"][:, :pos0], v_sc], axis=1)
        kpos_all = jnp.arange(pos0 + c)
        linear = True
    else:
        cast = (lambda x: x) if quant else (lambda x: x.astype(q.dtype))
        k_all = jnp.concatenate([cast(cache["k"]), k], axis=1)
        v_all = jnp.concatenate([cast(cache["v"]), v], axis=1)
        if quant:
            ks_all = jnp.concatenate([cache["ks"], k_sc], axis=1)
            vs_all = jnp.concatenate([cache["vs"], v_sc], axis=1)
        kpos_pre = _cache_positions(cache_len, jnp.asarray(pos0 - 1),
                                    window)
        kpos_all = jnp.concatenate([kpos_pre, pos0 + jnp.arange(c)])
        linear = False
    o = dispatch.flash_attention_append(q, k_all, v_all, kpos_all,
                                        pos0=pos0, window=window,
                                        kpos_linear=linear,
                                        k_scale=ks_all, v_scale=vs_all,
                                        backend=backend)
    return cm.linear(params["wo"], o.reshape(b, c, n_h * hd)), new_cache


# ---------------------------------------------------------------------------
# speculative verify + deferred commit
# ---------------------------------------------------------------------------

def attend_verify(params: dict, x: jnp.ndarray, cache: dict,
                  pos: jnp.ndarray, cfg, *, shift: int,
                  window: Optional[int] = None, use_rope: bool = True,
                  backend: str = "auto"):
    """Score a K-token draft chunk per slot WITHOUT touching the cache.

    x (B, K, d_model) — row j's drafted tokens at absolute positions
    ``pos[j] + i`` (per-slot depths; rows whose real draft is shorter
    than K carry pad tokens — pad keys sit at positions the causal mask
    already hides from every valid query, and pad-query outputs are
    discarded by the caller).  ``shift`` is a static upper bound on
    ``pos`` (the engine's logical cache length) for the dispatch
    re-basing trick.

    Returns (out (B, K, d_model), pending) where ``pending`` holds the
    chunk's K/V rows (already quantized for int8 caches — the exact
    bytes ``commit_kv`` writes) so acceptance can commit 1..K rows
    *after* the host-side accept decision.  Because nothing is written
    here, KV rollback on rejection is a no-op by construction; only the
    page table (engine side) carries speculative state."""
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, kq, _ = x.shape
    q = _split_heads(cm.linear(params["wq"], x), n_h, hd)
    k = _split_heads(cm.linear(params["wk"], x), n_kv, hd)
    v = _split_heads(cm.linear(params["wv"], x), n_kv, hd)
    if use_rope:
        positions = pos[:, None] + jnp.arange(kq)[None]  # (B, K) true qpos
        cos, sin = cm.rope_cos_sin(positions, hd, cfg.rope_theta)
        rd = getattr(cfg, "rotary_dim", None)
        q = cm.apply_rope(q, cos, sin, rotary_dim=rd)
        k = cm.apply_rope(k, cos, sin, rotary_dim=rd)

    if "kp" in cache:
        if window is not None:
            raise ValueError("paged KV caches do not support sliding "
                             "windows; keep ring layers contiguous")
        quant = "kps" in cache
        ps = cache["kp"].shape[1]
        cache_len = cache["pt"].shape[1] * ps
        k_sc = v_sc = None
        if quant:
            # quantize once: these bytes feed the verify attention AND are
            # what commit_kv later writes, so verify logits match
            # post-commit decode reads exactly
            k, k_sc = kv_quant.quantize(k)               # (B,K,Hkv,{D,1})
            v, v_sc = kv_quant.quantize(v)
        o = dispatch.flash_attention_verify_paged(
            q, cache["kp"], cache["vp"], cache["pt"], k, v, pos=pos,
            length=cache_len, k_scale=cache.get("kps"),
            v_scale=cache.get("vps"), ks_chunk=k_sc, vs_chunk=v_sc,
            backend=backend)
        pending = {"k": k, "v": v}
        if quant:
            pending["ks"], pending["vs"] = k_sc, v_sc
        return cm.linear(params["wo"], o.reshape(b, kq, n_h * hd)), pending

    quant = "ks" in cache
    k_sc = v_sc = None
    if quant:
        k, k_sc = kv_quant.quantize(k)
        v, v_sc = kv_quant.quantize(v)
    cache_len = cache["k"].shape[1]
    cast = (lambda t: t) if quant else (lambda t: t.astype(q.dtype))
    # key stream: the whole (pre-write) cache + the chunk's own K/V.  The
    # prefix kpos masks everything at or past each row's pos — cache rows
    # there are stale (verify never wrote them) — and the chunk rows carry
    # their true absolute positions.  The decode convention (`pos` = the
    # row currently being processed) means committed rows end at pos - 1.
    k_all = jnp.concatenate([cast(cache["k"]), k], axis=1)
    v_all = jnp.concatenate([cast(cache["v"]), v], axis=1)
    ks_all = vs_all = None
    if quant:
        ks_all = jnp.concatenate([cache["ks"], k_sc], axis=1)
        vs_all = jnp.concatenate([cache["vs"], v_sc], axis=1)
    kpos_pre = _cache_positions(cache_len, pos - 1, window)    # (B, L)
    kpos_all = jnp.concatenate(
        [kpos_pre, pos[:, None] + jnp.arange(kq)[None]], axis=1)
    o = dispatch.flash_attention_verify(q, k_all, v_all, kpos_all,
                                        pos=pos, shift=shift,
                                        window=window, k_scale=ks_all,
                                        v_scale=vs_all, backend=backend)
    pending = {"k": k, "v": v}
    if quant:
        pending["ks"], pending["vs"] = k_sc, v_sc
    return cm.linear(params["wo"], o.reshape(b, kq, n_h * hd)), pending


def commit_kv(cache: dict, pending: dict, pos: jnp.ndarray,
              n_acc: jnp.ndarray, *, window: Optional[int] = None) -> dict:
    """Scatter the accepted prefix of a verify chunk into the cache.

    ``pending`` is ``attend_verify``'s per-layer chunk K/V (B,K,...);
    row j commits rows i < n_acc[j] at positions pos[j] + i (ring wrap
    for window caches, page-table indirection for paged).  Rejected and
    pad rows write nowhere: masked paged writes land in the page-0
    garbage sink, masked contiguous writes rewrite the row's current
    value.  K is small and static, so this unrolls to K scatters."""
    b, kq = pending["k"].shape[0], pending["k"].shape[1]
    rows = jnp.arange(b)
    if "kp" in cache:
        quant = "kps" in cache
        ps = cache["kp"].shape[1]
        m = cache["pt"].shape[1]
        pt = cache["pt"]
        kp, vp = cache["kp"], cache["vp"]
        kps, vps = cache.get("kps"), cache.get("vps")
        for i in range(kq):
            p = pos + i
            pidx = jnp.minimum(p // ps, m - 1)
            off = p % ps
            page = pt[rows, pidx]
            ok = (i < n_acc) & (page > 0)
            page_w = jnp.where(ok, page, 0)
            kp = kp.at[page_w, off].set(pending["k"][:, i].astype(kp.dtype))
            vp = vp.at[page_w, off].set(pending["v"][:, i].astype(vp.dtype))
            if quant:
                kps = kps.at[page_w, off].set(pending["ks"][:, i])
                vps = vps.at[page_w, off].set(pending["vs"][:, i])
        new_cache = {"kp": kp, "vp": vp, "pt": pt,
                     "index": jnp.max(pos + n_acc).astype(jnp.int32)}
        if quant:
            new_cache["kps"], new_cache["vps"] = kps, vps
        return new_cache

    quant = "ks" in cache
    cache_len = cache["k"].shape[1]
    ck, cv = cache["k"], cache["v"]
    cks, cvs = cache.get("ks"), cache.get("vs")
    for i in range(kq):
        p = pos + i
        if window is not None:
            slot = p % cache_len
        else:
            # masked rows may sit past the cache end; clamp the index and
            # let the where() below rewrite the current value harmlessly
            slot = jnp.minimum(p, cache_len - 1)
        sel = (i < n_acc)[:, None, None]
        ck = ck.at[rows, slot].set(
            jnp.where(sel, pending["k"][:, i].astype(ck.dtype),
                      ck[rows, slot]))
        cv = cv.at[rows, slot].set(
            jnp.where(sel, pending["v"][:, i].astype(cv.dtype),
                      cv[rows, slot]))
        if quant:
            cks = cks.at[rows, slot].set(
                jnp.where(sel, pending["ks"][:, i], cks[rows, slot]))
            cvs = cvs.at[rows, slot].set(
                jnp.where(sel, pending["vs"][:, i], cvs[rows, slot]))
    new_cache = {"k": ck, "v": cv,
                 "index": jnp.max(pos + n_acc).astype(jnp.int32)}
    if quant:
        new_cache["ks"], new_cache["vs"] = cks, cvs
    return new_cache


# ---------------------------------------------------------------------------
# cross attention (Whisper decoder)
# ---------------------------------------------------------------------------

def cross_attend(params: dict, x: jnp.ndarray, memory_kv: tuple, cfg
                 ) -> jnp.ndarray:
    """x (B, Sq, d); memory_kv = (k, v) each (B, Sm, Hkv, D) precomputed."""
    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, sq, _ = x.shape
    q = _split_heads(cm.linear(params["wq"], x), n_h, hd)
    k, v = memory_kv
    k = _repeat_kv(k.astype(q.dtype), n_h // n_kv)
    v = _repeat_kv(v.astype(q.dtype), n_h // n_kv)
    o = sdpa(q, k, v, None)
    return cm.linear(params["wo"], o.reshape(b, sq, n_h * hd))


def memory_kv(params: dict, mem: jnp.ndarray, cfg) -> tuple:
    """Precompute cross-attention K/V from encoder output (B, Sm, d)."""
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim
    k = _split_heads(cm.linear(params["wk"], mem), n_kv, hd)
    v = _split_heads(cm.linear(params["wv"], mem), n_kv, hd)
    return (k, v)
