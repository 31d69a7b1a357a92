"""Mesh-aware kernel dispatch: the single entry point to the Pallas kernels.

Every attention / norm / optimizer call in the model layer routes through
here with ``backend="auto"``.  Resolution is keyed off the *lowering
target* — the dispatch mesh installed via ``repro.distributed.ctx.use_mesh``
(its device platform), not ``jax.default_backend()`` — so a CPU host
lowering a TPU mesh program picks the kernels the mesh will actually run.

Decision table (see DESIGN.md §kernel-dispatch for the full rationale):

  mesh (devices>1)  platform  shape alignment          -> backend
  ----------------  --------  -----------------------  --------------------
  decode_cp rules   any       local slice aligned      pallas_cp (decode
                                                       only; interpret
                                                       off-TPU)
  decode_cp rules   any       slice/batch misaligned   jnp (reason logged)
  yes               any       aligned + axes divide    pallas_shard_map
                                                       (interpret off-TPU)
  yes               any       axes don't divide        jnp (reason logged)
  no / 1-device     tpu       aligned                  pallas
  no / 1-device     cpu/gpu   any                      jnp (reason logged)
  any               any       seq/rows misaligned      jnp (reason logged)
  rules, no mesh    any       any                      jnp (reason logged)

``flash_attention_append`` (op ``flash_append``) follows the same table
with its own alignment row: the chunk C and key stream Sk must both be
128-multiples (linear layouts have Sk == pos0 + C, so chunk-multiple
pos0 and a 128-multiple chunk size keep every chunk of a prompt on the
fused path).

The shard_map'd paths partition (batch -> data axes, heads -> model) using
the specs from ``repro.distributed.sharding.attention_shard_spec``; the
``custom_vjp`` is defined *around* the shard_mapped calls so gradients flow
under a mesh (a bare ``pallas_call`` has no GSPMD partitioning rule — this
layer is what lets mesh training keep its fused kernels).  ``pallas_cp``
is the serving counterpart: the ``decode_cp`` rules shard the KV cache's
*sequence* dim, each shard runs the partials-emitting decode kernel over
its slice, and the flash-decoding combine is a psum of (m, l, acc) over
the rule's seq axes.  ``rmsnorm`` shard_maps over row blocks (replicated
scale, psum'd dscale) except under the seq-parallel residual layout,
which stays an explicit fallback.

Dispatch resolves at trace time; ``ctx.use_mesh`` / ``ctx.sharding_rules``
fold a dispatch token into the jit cache key (``compat.set_trace_token``)
so one jitted callable re-lowered under a different mesh re-resolves
instead of replaying the stale cached trace.

All alignment checks (MXU 128-lane sequence blocks, GQA head-group
divisibility, mesh-axis divisibility) live here, in one place, and every
resolution is recorded with its reason — ``decision_log()`` /
``decision_summary()`` let tests and the dry-run report *why* a given call
fell back to jnp.
"""
from __future__ import annotations

import functools
import threading
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.distributed.sharding import (AttnShardSpec, DecodeCPSpec,
                                        RowShardSpec, attention_shard_spec,
                                        decode_cp_shard_spec,
                                        rmsnorm_shard_spec)
from repro.kernels import ref
from repro.kernels.decode_attention import (_per_slot, decode_attention_fwd,
                                            decode_attention_partials,
                                            kv_block_rows)
from repro.kernels.flash_attention import (
    flash_attention_append as flash_attention_append_fwd,
    flash_attention_fwd)
from repro.kernels.flash_attention_bwd import flash_attention_bwd
from repro.kernels.rmsnorm import rmsnorm_bwd, rmsnorm_fwd
from repro.kernels.shared_rmsprop import rmsprop_update_2d

LANES = 1024
_BACKENDS = ("auto", "jnp", "pallas", "pallas_shard_map")


# ---------------------------------------------------------------------------
# decision log
# ---------------------------------------------------------------------------

class Decision(NamedTuple):
    op: str
    backend: str  # "pallas" | "pallas_shard_map" | "pallas_cp" | "jnp"
    reason: str
    platform: str
    mesh_axes: Optional[Tuple[Tuple[str, int], ...]]


_LOG_LOCK = threading.Lock()
_LOG_CAP = 512
_log: list = []


def _decide(op: str, backend: str, reason: str,
            mesh=None, platform: Optional[str] = None) -> Decision:
    d = Decision(op, backend, reason,
                 platform or ctx.current_platform(),
                 tuple(dict(mesh.shape).items()) if mesh is not None
                 else None)
    with _LOG_LOCK:
        if len(_log) >= _LOG_CAP:
            del _log[:_LOG_CAP // 2]
        _log.append(d)
    return d


def decision_log() -> list:
    """Decisions recorded since the last clear (trace-time, newest last)."""
    with _LOG_LOCK:
        return list(_log)


def clear_decision_log() -> None:
    with _LOG_LOCK:
        _log.clear()


def last_decision(op: str) -> Optional[Decision]:
    with _LOG_LOCK:
        for d in reversed(_log):
            if d.op == op:
                return d
    return None


def decision_summary() -> list:
    """Deduped (op, backend, reason) counts — the dry-run's 'why did this
    lower the way it did' record."""
    counts: dict = {}
    for d in decision_log():
        key = (d.op, d.backend, d.reason)
        counts[key] = counts.get(key, 0) + 1
    return [{"op": op, "backend": be, "reason": rs, "count": n}
            for (op, be, rs), n in sorted(counts.items())]


def _quant_note(decision: Decision, quant: bool) -> Decision:
    """Amend the just-logged decision row with the int8-cache marker.

    Quantization does not change routing — every arm (bare pallas,
    shard_map, pallas_cp, paged delegates, jnp fallback) handles the int8
    cache — so the resolvers stay dtype-blind and the row's *reason* gains
    a suffix saying how the arm consumes the quantized bytes."""
    if not quant:
        return decision
    suffix = ("; int8 kv dequantized for jnp fallback"
              if decision.backend == "jnp"
              else "; int8 kv dequant-in-kernel")
    amended = decision._replace(reason=decision.reason + suffix)
    with _LOG_LOCK:
        if _log and _log[-1] == decision:
            _log[-1] = amended
    return amended


def _mesh_for_dispatch():
    """(mesh, platform) of the lowering target; mesh None when dispatch
    should treat the run as single-device."""
    mesh = ctx.current_mesh()
    platform = ctx.current_platform()
    if mesh is not None and ctx.mesh_devices(mesh) <= 1:
        mesh = None
    return mesh, platform


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------

def _flash_blocks(s: int) -> int:
    # largest block <= 512 dividing s (s is a multiple of 128 on this
    # path, so this terminates at >= 128)
    b = min(512, s)
    while s % b:
        b //= 2
    return b


def _flash_fwd_call(q, k, v, causal, window, shard, interpret,
                    save_residuals):
    def call(q, k, v):
        bq = bk = _flash_blocks(q.shape[1])
        return flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=bq, block_k=bk,
                                   save_residuals=save_residuals,
                                   interpret=interpret)
    if shard is None:
        return call(q, k, v)
    out_specs = (shard.qo, shard.lse) if save_residuals else shard.qo
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=(shard.qo, shard.kv, shard.kv),
                         out_specs=out_specs, check_vma=False)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_pallas(q, k, v, causal, window, shard, interpret):
    return _flash_fwd_call(q, k, v, causal, window, shard, interpret, False)


def _flash_pallas_fwd(q, k, v, causal, window, shard, interpret):
    o, lse = _flash_fwd_call(q, k, v, causal, window, shard, interpret, True)
    return o, (q, k, v, o, lse)


def _flash_pallas_bwd(causal, window, shard, interpret, res, do):
    q, k, v, o, lse = res

    def call(q, k, v, o, lse, do):
        bq = bk = _flash_blocks(q.shape[1])
        return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                   window=window, block_q=bq, block_k=bk,
                                   interpret=interpret)
    if shard is None:
        return call(q, k, v, o, lse, do)
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=(shard.qo, shard.kv, shard.kv, shard.qo,
                                   shard.lse, shard.qo),
                         out_specs=(shard.qo, shard.kv, shard.kv),
                         check_vma=False)(q, k, v, o, lse, do)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "shard",
                                             "interpret"))
def _flash_call(q, k, v, causal, window, shard, interpret):
    return _flash_pallas(q, k, v, causal, window, shard, interpret)


def _flash_dense(q, k, v, causal, window):
    """jnp fallback — same flavor selection the model layer used to do:
    blockwise (never materializes S x S) for long causal sequences, dense
    sdpa otherwise."""
    s = q.shape[1]
    from repro.models import attention as attn
    if causal and s >= 2048 and s % 512 == 0:
        from repro.models.flash_jnp import flash_attention_jnp
        return flash_attention_jnp(q, k, v, True, window, 512)
    n_rep = q.shape[2] // k.shape[2]
    kk = attn._repeat_kv(k, n_rep)
    vv = attn._repeat_kv(v, n_rep)
    mask = attn.causal_mask(s, s, window=window) if causal else None
    return attn.sdpa(q, kk, vv, mask)


def _resolve_flash(b: int, s: int, hq: int, hkv: int, backend: str
                   ) -> Tuple[Decision, Optional[AttnShardSpec], bool]:
    if hq % hkv != 0:
        # every implementation (kernels, blockwise, reference) groups q
        # heads over kv heads — a non-multiple count is a config error
        raise ValueError(f"GQA needs q heads to be a multiple of kv "
                         f"heads, got {hq}/{hkv}")
    mesh, platform = _mesh_for_dispatch()
    interpret = platform != "tpu"
    aligned = 128 <= s and s % 128 == 0
    if backend == "jnp":
        return _decide("flash_attention", "jnp", "explicit backend"), \
            None, interpret
    if backend == "pallas":
        if not aligned:
            return _decide("flash_attention", "jnp",
                           f"explicit pallas but seq {s} below kernel "
                           "minimum (128-multiple); naive reference"), \
                None, interpret
        return _decide("flash_attention", "pallas", "explicit backend"), \
            None, interpret
    if backend == "pallas_shard_map":
        if not aligned:
            raise ValueError(f"cannot shard_map attention: seq {s} not "
                             "MXU-aligned (need a multiple of 128)")
        raw_mesh = ctx.current_mesh()   # honor even a 1-device mesh
        if raw_mesh is None:
            raise ValueError("backend='pallas_shard_map' needs a mesh "
                             "installed via ctx.use_mesh")
        spec, why = attention_shard_spec(raw_mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            raise ValueError(f"cannot shard_map attention: {why}")
        return _decide("flash_attention", "pallas_shard_map",
                       "explicit backend", raw_mesh), spec, interpret
    # auto
    if not aligned:
        return _decide("flash_attention", "jnp",
                       f"seq {s} not MXU-aligned (need a multiple of "
                       "128)"), None, interpret
    if mesh is not None:
        spec, why = attention_shard_spec(mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            return _decide("flash_attention", "jnp", why, mesh), \
                None, interpret
        return _decide("flash_attention", "pallas_shard_map",
                       "mesh axes divide batch/heads", mesh), \
            spec, interpret
    if ctx.current_rules():
        return _decide("flash_attention", "jnp",
                       "sharding rules active without a dispatch mesh "
                       "(install it via ctx.use_mesh)"), None, interpret
    if platform == "tpu":
        return _decide("flash_attention", "pallas",
                       "single-device tpu, aligned"), None, False
    return _decide("flash_attention", "jnp",
                   f"platform {platform}: Pallas kernels run interpret-"
                   "only off-TPU"), None, interpret


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    backend: str = "auto") -> jnp.ndarray:
    """q (B,S,Hq,D); k,v (B,S,Hkv,D) -> (B,S,Hq,D).

    Differentiable end-to-end on every backend: the Pallas paths carry a
    custom VJP whose backward is the fused recompute kernel pair in
    ``flash_attention_bwd`` (shard_mapped under a mesh); jnp fallbacks
    differentiate through their reference implementations."""
    assert backend in _BACKENDS, backend
    b, s, hq, _ = q.shape
    decision, shard, interpret = _resolve_flash(b, s, hq, k.shape[2],
                                                backend)
    if decision.backend == "jnp":
        if backend == "pallas":     # sub-kernel smoke shape: keep the
            return ref.flash_attention_ref(q, k, v, causal=causal,
                                           window=window)  # naive oracle
        return _flash_dense(q, k, v, causal, window)
    return _flash_call(q, k, v, causal, window, shard, interpret)


# ---------------------------------------------------------------------------
# append-mode flash attention (chunked prefill: Sq != Sk, q-offset grid)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("pos0", "window",
                                             "kpos_linear", "shard",
                                             "interpret"))
def _append_call(q, k, v, kpos, ks, vs, pos0, window, kpos_linear, shard,
                 interpret):
    def call(q, k, v, kpos, ks=None, vs=None):
        bq = _flash_blocks(q.shape[1])
        bk = kv_block_rows(*k.shape[1:], k.dtype.itemsize, cap=512)
        return flash_attention_append_fwd(q, k, v, kpos, pos0=pos0,
                                          window=window, block_q=bq,
                                          block_k=bk,
                                          kpos_linear=kpos_linear,
                                          interpret=interpret,
                                          k_scale=ks, v_scale=vs)
    if shard is None:
        return call(q, k, v, kpos, ks, vs)
    base = (shard.qo, shard.kv, shard.kv, shard.kpos_decode)
    if ks is None:
        return jax.shard_map(call, mesh=shard.mesh, in_specs=base,
                             out_specs=shard.qo, check_vma=False)(q, k, v, kpos)
    # the rank-4 scale tensors (B, Sk, Hkv, 1) shard exactly like the
    # caches they annotate
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=base + (shard.kv, shard.kv),
                         out_specs=shard.qo,
                         check_vma=False)(q, k, v, kpos, ks, vs)


def _append_dense(q, k, v, kpos, pos0, window):
    """jnp fallback — dense sdpa with the kpos mask (XLA CPU lowers the
    4-D repeat_kv einsum better than the grouped 5-D oracle einsum)."""
    from repro.models import attention as attn
    c = q.shape[1]
    n_rep = q.shape[2] // k.shape[2]
    kk = attn._repeat_kv(k, n_rep)
    vv = attn._repeat_kv(v, n_rep)
    qpos = pos0 + jnp.arange(c)
    mask = (kpos[:, None, :] >= 0) & \
        (kpos[:, None, :] <= qpos[None, :, None])          # (B, C, Sk)
    if window is not None:
        mask &= kpos[:, None, :] > qpos[None, :, None] - window
    return attn.sdpa(q, kk, vv, mask[:, None])


def _resolve_append(b: int, c: int, sk: int, hq: int, hkv: int,
                    pos0: int, backend: str
                    ) -> Tuple[Decision, Optional[AttnShardSpec], bool]:
    """Append arm alignment rules: the chunk (c) and the key stream (sk)
    both need MXU-aligned 128-multiples; on the linear cache layout
    sk == pos0 + c, so a 128-multiple chunk size and chunk-multiple pos0
    make every chunk of a prompt eligible (serve rounds --chunk)."""
    if hq % hkv != 0:
        raise ValueError(f"GQA needs q heads to be a multiple of kv "
                         f"heads, got {hq}/{hkv}")
    mesh, platform = _mesh_for_dispatch()
    interpret = platform != "tpu"
    aligned = (128 <= c and c % 128 == 0 and 128 <= sk and sk % 128 == 0)
    why_align = (f"chunk {c} / key stream {sk} (pos0={pos0}) not "
                 "MXU-aligned (need 128-multiples)")
    if backend == "jnp":
        return _decide("flash_append", "jnp", "explicit backend"), \
            None, interpret
    if backend == "pallas":
        if not aligned:
            return _decide("flash_append", "jnp",
                           f"explicit pallas but {why_align}; naive "
                           "reference"), None, interpret
        return _decide("flash_append", "pallas", "explicit backend"), \
            None, interpret
    if backend == "pallas_shard_map":
        if not aligned:
            raise ValueError(f"cannot shard_map append attention: "
                             f"{why_align}")
        raw_mesh = ctx.current_mesh()   # honor even a 1-device mesh
        if raw_mesh is None:
            raise ValueError("backend='pallas_shard_map' needs a mesh "
                             "installed via ctx.use_mesh")
        spec, why = attention_shard_spec(raw_mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            raise ValueError(f"cannot shard_map append attention: {why}")
        return _decide("flash_append", "pallas_shard_map",
                       "explicit backend", raw_mesh), spec, interpret
    # auto
    if not aligned:
        return _decide("flash_append", "jnp", why_align), None, interpret
    if mesh is not None:
        spec, why = attention_shard_spec(mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            return _decide("flash_append", "jnp", why, mesh), \
                None, interpret
        return _decide("flash_append", "pallas_shard_map",
                       "mesh axes divide batch/heads", mesh), \
            spec, interpret
    if ctx.current_rules():
        return _decide("flash_append", "jnp",
                       "sharding rules active without a dispatch mesh "
                       "(install it via ctx.use_mesh)"), None, interpret
    if platform == "tpu":
        return _decide("flash_append", "pallas",
                       "single-device tpu, aligned"), None, False
    return _decide("flash_append", "jnp",
                   f"platform {platform}: Pallas kernels run interpret-"
                   "only off-TPU"), None, interpret


def flash_attention_append(q, k, v, kpos, *, pos0: int,
                           window: Optional[int] = None,
                           kpos_linear: bool = False,
                           k_scale=None, v_scale=None,
                           backend: str = "auto") -> jnp.ndarray:
    """Append-mode flash attention for chunked prefill.

    q (B,C,Hq,D) — a prompt chunk at absolute positions ``pos0 + i``;
    k,v (B,Sk,Hkv,D) — the key stream (cache prefix + chunk); kpos
    (B,Sk) [or (Sk,), broadcast] — absolute position per key row (-1 =
    invalid, the decode kernel's validity convention) -> (B,C,Hq,D).

    ``kpos_linear`` asserts key row index == absolute position wherever
    valid (full linear caches) and enables the ``tile_live`` prefix-tile
    skip; ring (rotated) layouts must leave it False.  With
    ``k_scale``/``v_scale`` ((B,Sk,Hkv,1) f32) the key stream is int8 and
    dequantized inside the kernel (jnp fallbacks dequantize up front) —
    same routing rules, annotated decision rows.  Serving-only: forward,
    no VJP.  Under a mesh the kernel shard_maps over (batch, heads) with
    the same ``AttnShardSpec`` the train/decode kernels use (kpos
    batch-sharded with q, scales sharded like the caches)."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    b, c, hq, _ = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, sk))
    decision, shard, interpret = _resolve_append(b, c, sk, hq, hkv, pos0,
                                                 backend)
    decision = _quant_note(decision, quant)
    if decision.backend == "jnp":
        if backend == "pallas":     # sub-kernel smoke shape: keep the
            if quant:               # naive oracle
                return ref.flash_attention_append_quant_ref(
                    q, k, v, k_scale, v_scale, kpos, pos0=pos0,
                    window=window)
            return ref.flash_attention_append_ref(q, k, v, kpos,
                                                  pos0=pos0,
                                                  window=window)
        if quant:
            k = ref.dequant_ref(k, k_scale, q.dtype)
            v = ref.dequant_ref(v, v_scale, q.dtype)
        return _append_dense(q, k, v, kpos, pos0, window)
    return _append_call(q, k, v, kpos, k_scale, v_scale, pos0, window,
                        kpos_linear, shard, interpret)


# ---------------------------------------------------------------------------
# decode attention (serving)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shard", "interpret"))
def _decode_call(q, k_cache, v_cache, kpos, pos, ks, vs, shard, interpret):
    def call(q, kc, vc, kpos, pos, ks=None, vs=None):
        bk = kv_block_rows(*kc.shape[1:], kc.dtype.itemsize)
        return decode_attention_fwd(q, kc, vc, kpos, pos, block_k=bk,
                                    interpret=interpret,
                                    k_scale=ks, v_scale=vs)
    if shard is None:
        return call(q, k_cache, v_cache, kpos, pos, ks, vs)
    base = (shard.q_decode, shard.kv, shard.kv, shard.kpos_decode,
            shard.pos_decode)
    if ks is None:
        return jax.shard_map(call, mesh=shard.mesh, in_specs=base,
                             out_specs=shard.q_decode,
                             check_vma=False)(q, k_cache, v_cache, kpos, pos)
    # rank-4 scales (B, L, Hkv, 1) shard exactly like the caches
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=base + (shard.kv, shard.kv),
                         out_specs=shard.q_decode,
                         check_vma=False)(q, k_cache, v_cache, kpos, pos,
                                          ks, vs)


@functools.partial(jax.jit, static_argnames=("shard", "interpret"))
def _decode_cp_call(q, k_cache, v_cache, kpos, pos, ks, vs, shard,
                    interpret):
    """Context-parallel flash decoding: the cache's sequence dim is sharded
    over ``shard.seq_axes``; each shard runs the partials kernel over its
    slice and the combine is an O(B*Hq*D) psum of (m, l, acc) — the same
    correction math the pure-jnp ``attend_decode_cp`` combine used, now fed
    by the Pallas kernel."""
    axes = shard.seq_axes

    def call(q, kc, vc, kp, p, ks=None, vs=None):
        bk = kv_block_rows(*kc.shape[1:], kc.dtype.itemsize)
        acc, m, l = decode_attention_partials(q, kc, vc, kp, p, block_k=bk,
                                              interpret=interpret,
                                              k_scale=ks, v_scale=vs)
        m_max = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - m_max)
        l_tot = jax.lax.psum(l * corr, axes)
        acc_tot = jax.lax.psum(acc * corr[..., None], axes)
        o = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
        b, hkv, g, d = acc.shape
        return o.reshape(b, hkv * g, d).astype(q.dtype)

    base = (shard.q_decode, shard.kv, shard.kv, shard.kpos,
            shard.pos_decode)
    if ks is None:
        return jax.shard_map(call, mesh=shard.mesh, in_specs=base,
                             out_specs=shard.q_decode,
                             check_vma=False)(q, k_cache, v_cache, kpos, pos)
    # the seq-sharded cache slice carries its seq-sharded scale slice
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=base + (shard.kv, shard.kv),
                         out_specs=shard.q_decode,
                         check_vma=False)(q, k_cache, v_cache, kpos, pos,
                                          ks, vs)


def _decode_dense(q, k_cache, v_cache, kpos, pos):
    from repro.models import attention as attn
    n_rep = q.shape[1] // k_cache.shape[2]
    kk = attn._repeat_kv(k_cache.astype(q.dtype), n_rep)
    vv = attn._repeat_kv(v_cache.astype(q.dtype), n_rep)
    valid = (kpos >= 0) & (kpos <= pos[:, None])      # (B, L) per slot
    mask = valid[:, None, None, :]
    return attn.sdpa(q[:, None], kk, vv, mask)[:, 0]


def _resolve_decode(b: int, length: int, hq: int, hkv: int, backend: str
                    ) -> Tuple[Decision, Any, bool]:
    """Returns (decision, spec, interpret); spec is an ``AttnShardSpec``
    for the (batch, heads) shard_map arm, a ``DecodeCPSpec`` for the
    context-parallel arm, or None."""
    if hq % hkv != 0:
        raise ValueError(f"GQA needs q heads to be a multiple of kv "
                         f"heads, got {hq}/{hkv}")
    mesh, platform = _mesh_for_dispatch()
    interpret = platform != "tpu"
    aligned = 128 <= length and length % 128 == 0
    rules = ctx.current_rules() or {}
    if backend == "jnp":
        return _decide("decode_attention", "jnp", "explicit backend"), \
            None, interpret
    if backend == "pallas":
        if not aligned:
            return _decide("decode_attention", "jnp",
                           f"explicit pallas but cache length {length} "
                           "below kernel minimum (128-multiple); naive "
                           "reference"), None, interpret
        return _decide("decode_attention", "pallas", "explicit backend"), \
            None, interpret
    if backend == "pallas_shard_map":
        raw_mesh = ctx.current_mesh()   # honor even a 1-device mesh
        if raw_mesh is None:
            raise ValueError("backend='pallas_shard_map' needs a mesh "
                             "installed via ctx.use_mesh")
        # misalignment is a logged fallback (like every auto arm), not a
        # crash: serving batch/head counts vary per request
        if not aligned:
            return _decide("decode_attention", "jnp",
                           f"explicit shard_map but cache length {length} "
                           "not MXU-aligned (need a multiple of 128); "
                           "reference", raw_mesh), None, interpret
        spec, why = attention_shard_spec(raw_mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            return _decide("decode_attention", "jnp",
                           f"explicit shard_map but {why}; reference",
                           raw_mesh), None, interpret
        return _decide("decode_attention", "pallas_shard_map",
                       "explicit backend", raw_mesh), spec, interpret
    if not aligned:
        return _decide("decode_attention", "jnp",
                       f"cache length {length} not MXU-aligned (need a "
                       "multiple of 128)"), None, interpret
    cp = rules.get("decode_cp")
    if cp is not None:
        cp_mesh = cp["mesh"]
        cp_interpret = ctx.mesh_platform(cp_mesh) != "tpu"
        spec, why = decode_cp_shard_spec(cp, batch=b, length=length)
        if spec is None:
            return _decide("decode_attention", "jnp",
                           f"decode_cp rules own the cache but {why}",
                           cp_mesh, ctx.mesh_platform(cp_mesh)), \
                None, cp_interpret
        return _decide("decode_attention", "pallas_cp",
                       "decode_cp layout: partials kernel per seq shard "
                       "+ (m,l,acc) psum combine",
                       cp_mesh, ctx.mesh_platform(cp_mesh)), \
            spec, cp_interpret
    if mesh is not None:
        spec, why = attention_shard_spec(mesh, batch=b, n_q_heads=hq,
                                         n_kv_heads=hkv)
        if spec is None:
            return _decide("decode_attention", "jnp", why, mesh), \
                None, interpret
        return _decide("decode_attention", "pallas_shard_map",
                       "mesh axes divide batch/heads", mesh), \
            spec, interpret
    if rules:
        return _decide("decode_attention", "jnp",
                       "sharding rules active without a dispatch mesh"), \
            None, interpret
    if platform == "tpu":
        return _decide("decode_attention", "pallas",
                       "single-device tpu, aligned"), None, False
    return _decide("decode_attention", "jnp",
                   f"platform {platform}: Pallas kernels run interpret-"
                   "only off-TPU"), None, interpret


def decode_attention(q, k_cache, v_cache, kpos, pos=None, *,
                     k_scale=None, v_scale=None,
                     backend: str = "auto") -> jnp.ndarray:
    """q (B,Hq,D); caches (B,L,Hkv,D); kpos (B,L); pos (B,) -> (B,Hq,D).

    Positions are per batch slot (continuous batching: every sequence can
    be at its own decode depth).  Lockstep callers may pass kpos (L,) and
    scalar pos — both are broadcast to the per-slot layout here, so the
    scalar-``pos`` path is a thin wrapper over the same kernels.

    One fast path serves both cache layouts: under the replicated-cache
    layout the kernel is shard_mapped over (batch, heads); when the
    ``decode_cp`` rules own the cache's sequence dim it resolves to
    ``pallas_cp`` — the partials kernel per sequence shard plus the
    flash-decoding psum combine.

    With ``k_scale``/``v_scale`` ((B,L,Hkv,1) f32) the caches are int8;
    every arm consumes them (dequant inside the kernel bodies, up-front
    dequant on the jnp fallback) under the same routing rules, with the
    decision row annotated."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    b, hq, _ = q.shape
    length, hkv = k_cache.shape[1], k_cache.shape[2]
    if pos is None:
        pos = jnp.max(kpos, axis=-1) if kpos.ndim == 2 else jnp.max(kpos)
    # normalization helper shared with the kernel entry points
    kpos, pos = _per_slot(kpos, pos, b)
    decision, shard, interpret = _resolve_decode(b, length, hq, hkv,
                                                 backend)
    decision = _quant_note(decision, quant)
    if decision.backend == "jnp":
        if backend == "pallas":     # sub-kernel smoke shape: keep the
            if quant:               # naive oracle
                return ref.decode_attention_quant_ref(
                    q, k_cache, v_cache, k_scale, v_scale, kpos, pos)
            return ref.decode_attention_ref(q, k_cache, v_cache, kpos,
                                            pos)
        if quant:
            k_cache = ref.dequant_ref(k_cache, k_scale, q.dtype)
            v_cache = ref.dequant_ref(v_cache, v_scale, q.dtype)
        return _decode_dense(q, k_cache, v_cache, kpos, pos)
    if decision.backend == "pallas_cp":
        return _decode_cp_call(q, k_cache, v_cache, kpos, pos, k_scale,
                               v_scale, shard, interpret)
    return _decode_call(q, k_cache, v_cache, kpos, pos, k_scale, v_scale,
                        shard, interpret)


# ---------------------------------------------------------------------------
# paged KV cache layout (page pool + per-slot page table)
# ---------------------------------------------------------------------------
#
# The paged arms are an indirection layer, not a new kernel family: the
# decode and append kernels already read key validity from a runtime
# per-row ``kpos`` map, so a paged cache lowers as (1) a page-table gather
# producing a dense per-slot view, (2) the paged kpos map (-1 on unmapped
# pages), then (3) a delegated call into the existing ``decode_attention``
# / ``flash_attention_append`` arms.  The gathered view is *statically*
# sliced to the logical cache length so the delegated call sees the exact
# shapes the contiguous layout produces — paged and contiguous compute
# streams are bitwise identical, which is what the engine parity tests
# pin.  Alignment rule: ``page_size`` must be a 128-multiple so page
# boundaries coincide with the kernels' key-block tiles; smaller or odd
# page sizes fall back to the jnp oracle with a logged reason.  Every
# paged call logs two decision rows — its own (op ``decode_paged`` /
# ``append_paged``) plus the delegated op's row.

def _paged_misalignment(page_size: int) -> Optional[str]:
    if page_size < 128 or page_size % 128 != 0:
        return (f"page size {page_size} not MXU-aligned (need a "
                "128-multiple so page boundaries coincide with key-block "
                "tiles)")
    return None


def decode_attention_paged(q, k_pool, v_pool, page_table, pos, *,
                           length: Optional[int] = None,
                           k_scale=None, v_scale=None,
                           backend: str = "auto") -> jnp.ndarray:
    """Paged-layout decode.  q (B,Hq,D); pools (P,page_size,Hkv,D);
    page_table (B,M) int32 (-1 = unmapped, 0 = reserved garbage sink);
    pos (B,) or scalar -> (B,Hq,D).

    ``length`` statically truncates the gathered view to the logical
    cache length (M * page_size may over-cover); passing the contiguous
    layout's cache_len makes the delegated call's shapes — and therefore
    its dispatch decision and reduction order — identical to the
    contiguous path.  With ``k_scale``/``v_scale`` ((P,page_size,Hkv,1)
    f32) the pools are int8; the scale pools are gathered through the
    same page table and ride into the delegated call — the contiguous
    quant arms do the rest."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    ps = k_pool.shape[1]
    m = page_table.shape[1]
    length = m * ps if length is None else length
    why = _paged_misalignment(ps)
    if why is None and (length < 128 or length % 128 != 0):
        why = (f"logical length {length} not MXU-aligned (need a "
               "128-multiple)")
    if why is not None:
        if quant:
            _decide("decode_paged", "jnp",
                    why + "; int8 kv dequantized for jnp fallback")
            return ref.decode_attention_paged_quant_ref(
                q, k_pool, v_pool, k_scale, v_scale, page_table, pos,
                length=length)
        _decide("decode_paged", "jnp", why)
        return ref.decode_attention_paged_ref(q, k_pool, v_pool,
                                              page_table, pos,
                                              length=length)
    with jax.named_scope("paged_gather"):
        k = ref.paged_gather_ref(k_pool, page_table)[:, :length]
        v = ref.paged_gather_ref(v_pool, page_table)[:, :length]
        kpos = ref.paged_kpos_ref(page_table, ps)[:, :length]
        ks = vs = None
        if quant:
            ks = ref.paged_gather_ref(k_scale, page_table)[:, :length]
            vs = ref.paged_gather_ref(v_scale, page_table)[:, :length]
    o = decode_attention(q, k, v, kpos, pos, k_scale=ks, v_scale=vs,
                         backend=backend)
    inner = last_decision("decode_attention")
    _decide("decode_paged", inner.backend if inner else "jnp",
            "page-gathered dense view, delegated to decode_attention" +
            ("; int8 pool + scale pool gathered together" if quant else ""))
    return o


def flash_attention_append_paged(q, k_pool, v_pool, page_table,
                                 k_chunk, v_chunk, *, pos0: int,
                                 k_scale=None, v_scale=None,
                                 ks_chunk=None, vs_chunk=None,
                                 backend: str = "auto") -> jnp.ndarray:
    """Paged-layout append-mode prefill.  q (B,C,Hq,D) at absolute
    positions pos0 + i; pools hold the already-written prefix [0, pos0)
    behind page_table (B,M); k_chunk/v_chunk (B,C,Hkv,D) are the chunk's
    own K/V (not yet in the pool, or written by the caller — the key
    stream uses these tensors, not pool rows).

    Linear layouts only (no window: ring caches stay contiguous).  The
    gathered prefix keeps key row index == absolute position wherever
    mapped, so the delegated call runs with ``kpos_linear=True`` and
    keeps the tile_live prefix-tile skip.

    Quantized pools pass scale pools via ``k_scale``/``v_scale`` and the
    chunk *already quantized* (int8 chunk + ``ks_chunk``/``vs_chunk``
    (B,C,Hkv,1)) — the same bytes the caller's cache write lands, so
    prefill attention and later decode reads see identical dequantized
    values."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    ps = k_pool.shape[1]
    b, c = q.shape[0], q.shape[1]
    why = _paged_misalignment(ps)
    if why is not None:
        if quant:
            _decide("append_paged", "jnp",
                    why + "; int8 kv dequantized for jnp fallback")
            return ref.flash_attention_append_paged_quant_ref(
                q, k_pool, v_pool, k_scale, v_scale, page_table,
                k_chunk, v_chunk, ks_chunk, vs_chunk, pos0=pos0)
        _decide("append_paged", "jnp", why)
        return ref.flash_attention_append_paged_ref(
            q, k_pool, v_pool, page_table, k_chunk, v_chunk, pos0=pos0)
    ks_all = vs_all = None
    if pos0 == 0:
        k_all, v_all = k_chunk, v_chunk
        ks_all, vs_all = ks_chunk, vs_chunk
        kpos = jnp.arange(c)
    else:
        with jax.named_scope("paged_gather"):
            n_pre = -(-pos0 // ps)
            pt = page_table[:, :n_pre]
            k_pre = ref.paged_gather_ref(k_pool, pt)[:, :pos0]
            v_pre = ref.paged_gather_ref(v_pool, pt)[:, :pos0]
            if not quant:
                k_pre = k_pre.astype(q.dtype)
                v_pre = v_pre.astype(q.dtype)
            kpos_pre = ref.paged_kpos_ref(pt, ps)[:, :pos0]
            k_all = jnp.concatenate([k_pre, k_chunk], axis=1)
            v_all = jnp.concatenate([v_pre, v_chunk], axis=1)
            kpos_chunk = jnp.broadcast_to(pos0 + jnp.arange(c), (b, c))
            kpos = jnp.concatenate([kpos_pre, kpos_chunk], axis=1)
            if quant:
                ks_pre = ref.paged_gather_ref(k_scale, pt)[:, :pos0]
                vs_pre = ref.paged_gather_ref(v_scale, pt)[:, :pos0]
                ks_all = jnp.concatenate([ks_pre, ks_chunk], axis=1)
                vs_all = jnp.concatenate([vs_pre, vs_chunk], axis=1)
    o = flash_attention_append(q, k_all, v_all, kpos, pos0=pos0,
                               kpos_linear=True, k_scale=ks_all,
                               v_scale=vs_all, backend=backend)
    inner = last_decision("flash_append")
    _decide("append_paged", inner.backend if inner else "jnp",
            "page-gathered prefix + chunk, delegated to flash_append" +
            ("; int8 pool + scale pool gathered together" if quant else ""))
    return o


# ---------------------------------------------------------------------------
# speculative verify (ragged per-row depths as one append chunk)
# ---------------------------------------------------------------------------
#
# Verification of k drafted tokens is exactly a k-token append chunk —
# except each batch row sits at its own decode depth ``pos[j]``, while
# ``flash_attention_append`` wants one static ``pos0``.  Both masks the
# append kernel applies are relative: causal is ``kpos <= qpos`` and the
# sliding window is ``kpos > qpos - window``, so adding a common constant
# to every key position *and* every query position of one row changes
# nothing.  Re-basing row j by ``shift - pos[j]`` (``shift`` a static
# upper bound on pos — callers pass the logical cache length) therefore
# turns the ragged verify batch into a single append call at
# ``pos0 = shift``, with no new kernel and no per-row loop.  RoPE stays
# the model layer's job at the *true* absolute positions.

def flash_attention_verify(q, k, v, kpos, *, pos, shift: int,
                           window: Optional[int] = None,
                           k_scale=None, v_scale=None,
                           backend: str = "auto") -> jnp.ndarray:
    """Speculative-verify attention: score K drafted tokens per slot in
    one fused append launch.

    q (B,K,Hq,D) — row j's draft chunk at absolute positions
    ``pos[j] + i`` (decode's per-slot depths, not prefill's static
    pos0); k,v (B,Sk,Hkv,D) — key stream (cache prefix + the chunk's
    own K/V); kpos (B,Sk) absolute position per key row (-1 invalid);
    pos (B,) int32; ``shift`` static, >= every pos -> (B,K,Hq,D).

    Rows shift by different amounts, so key row index no longer equals
    shifted position: the delegated call always runs with
    ``kpos_linear=False`` (ring layouts required that anyway).  With
    ``k_scale``/``v_scale`` the key stream is int8 — scales ride into
    the delegated quant arm unchanged (the shift touches positions
    only, never payloads)."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    b = q.shape[0]
    sk = k.shape[1]
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, sk))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kpos_s = jnp.where(kpos >= 0, kpos - pos[:, None] + shift, -1)
    o = flash_attention_append(q, k, v, kpos_s, pos0=shift, window=window,
                               kpos_linear=False, k_scale=k_scale,
                               v_scale=v_scale, backend=backend)
    inner = last_decision("flash_append")
    _decide("flash_verify", inner.backend if inner else "jnp",
            f"per-row depths re-based to static pos0 (shift={shift}), "
            "delegated to flash_append" +
            ("; int8 key stream + scales ride through" if quant else ""))
    return o


def flash_attention_verify_paged(q, k_pool, v_pool, page_table,
                                 k_chunk, v_chunk, *, pos, length: int,
                                 k_scale=None, v_scale=None,
                                 ks_chunk=None, vs_chunk=None,
                                 backend: str = "auto") -> jnp.ndarray:
    """Paged-layout speculative verify.  q (B,K,Hq,D) at absolute
    positions ``pos[j] + i``; pools hold the committed prefix behind
    page_table (B,M); k_chunk/v_chunk (B,K,Hkv,D) are the draft chunk's
    own K/V (NOT in the pool — commit happens after acceptance, so the
    pool never needs rolling back); ``length`` statically truncates the
    gathered view to the logical cache length.

    Speculatively pre-allocated pages may already be mapped for
    positions >= pos[j] but hold garbage rows, so the gathered prefix
    kpos is clamped to ``<= pos - 1`` per row — uncommitted pool rows
    are invisible no matter what the allocator did ahead of the verify.
    Quantized pools gather their scale pools through the same table and
    take the chunk already quantized (``ks_chunk``/``vs_chunk``), the
    same int8 bytes a later commit writes — verify logits and
    post-commit decode reads see identical dequantized values."""
    assert backend in _BACKENDS, backend
    quant = k_scale is not None
    ps = k_pool.shape[1]
    b, kq = q.shape[0], q.shape[1]
    n_pre = -(-length // ps)
    pt = page_table[:, :n_pre]
    with jax.named_scope("paged_gather"):
        k_pre = ref.paged_gather_ref(k_pool, pt)[:, :length]
        v_pre = ref.paged_gather_ref(v_pool, pt)[:, :length]
        if not quant:
            k_pre = k_pre.astype(q.dtype)
            v_pre = v_pre.astype(q.dtype)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        kpos_pre = ref.paged_kpos_ref(pt, ps)[:, :length]
        kpos_pre = jnp.where(kpos_pre <= pos[:, None] - 1, kpos_pre, -1)
        kpos_chunk = pos[:, None] + jnp.arange(kq)
        kpos = jnp.concatenate([kpos_pre, kpos_chunk], axis=1)
        k_all = jnp.concatenate([k_pre, k_chunk], axis=1)
        v_all = jnp.concatenate([v_pre, v_chunk], axis=1)
        ks_all = vs_all = None
        if quant:
            ks_pre = ref.paged_gather_ref(k_scale, pt)[:, :length]
            vs_pre = ref.paged_gather_ref(v_scale, pt)[:, :length]
            ks_all = jnp.concatenate([ks_pre, ks_chunk], axis=1)
            vs_all = jnp.concatenate([vs_pre, vs_chunk], axis=1)
    o = flash_attention_verify(q, k_all, v_all, kpos, pos=pos,
                               shift=length, k_scale=ks_all,
                               v_scale=vs_all, backend=backend)
    inner = last_decision("flash_verify")
    _decide("verify_paged", inner.backend if inner else "jnp",
            "page-gathered prefix (kpos clamped below each row's pos) "
            "+ draft chunk, delegated to flash_verify" +
            ("; int8 pool + scale pool gathered together" if quant else ""))
    return o


# ---------------------------------------------------------------------------
# fused rmsnorm (fwd + one-pass vjp)
# ---------------------------------------------------------------------------

def _rmsnorm_fwd_call(x2, scale, eps, shard, interpret, save_residuals):
    def call(x2, scale):
        return rmsnorm_fwd(x2, scale, eps=eps,
                           save_residuals=save_residuals,
                           interpret=interpret)
    if shard is None:
        return call(x2, scale)
    from jax.sharding import PartitionSpec as P
    out_specs = (shard.rows, shard.rstd) if save_residuals else shard.rows
    return jax.shard_map(call, mesh=shard.mesh,
                         in_specs=(shard.rows, P(None)),
                         out_specs=out_specs, check_vma=False)(x2, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm_pallas(x2, scale, eps, shard, interpret):
    return _rmsnorm_fwd_call(x2, scale, eps, shard, interpret, False)


def _rmsnorm_pallas_fwd(x2, scale, eps, shard, interpret):
    y, rstd = _rmsnorm_fwd_call(x2, scale, eps, shard, interpret, True)
    return y, (x2, scale, rstd)


def _rmsnorm_pallas_bwd(eps, shard, interpret, res, dy):
    x2, scale, rstd = res

    def call(x2, scale, rstd, dy):
        dx, dscale = rmsnorm_bwd(x2, scale, rstd, dy, interpret=interpret)
        if shard is not None:
            # scale is replicated: sum the per-shard dscale partials
            dscale = jax.lax.psum(dscale, shard.axes)
        return dx, dscale
    if shard is None:
        dx, dscale = call(x2, scale, rstd, dy)
    else:
        from jax.sharding import PartitionSpec as P
        dx, dscale = jax.shard_map(call, mesh=shard.mesh,
                                   in_specs=(shard.rows, P(None), shard.rstd,
                                             shard.rows),
                                   out_specs=(shard.rows, P(None)),
                                   check_vma=False)(x2, scale, rstd, dy)
    return dx, dscale.astype(scale.dtype)


_rmsnorm_pallas.defvjp(_rmsnorm_pallas_fwd, _rmsnorm_pallas_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "shard", "interpret"))
def _rmsnorm_call(x2, scale, eps, shard, interpret):
    return _rmsnorm_pallas(x2, scale, eps, shard, interpret)


def _resolve_rmsnorm(rows: int, d: int, backend: str
                     ) -> Tuple[Decision, Optional[RowShardSpec], bool]:
    mesh, platform = _mesh_for_dispatch()
    interpret = platform != "tpu"
    aligned = rows >= 8 and d % 128 == 0
    if backend == "jnp":
        return _decide("rmsnorm", "jnp", "explicit backend"), None, \
            interpret
    if backend in ("pallas", "pallas_shard_map"):
        if not aligned:
            return _decide("rmsnorm", "jnp",
                           f"explicit pallas but rows={rows}/d={d} below "
                           "tile minimum (8 rows, 128-lane d); "
                           "reference"), None, interpret
        if backend == "pallas_shard_map":
            raw_mesh = ctx.current_mesh()   # honor even a 1-device mesh
            if raw_mesh is None:
                raise ValueError("backend='pallas_shard_map' needs a mesh "
                                 "installed via ctx.use_mesh")
            spec, why = rmsnorm_shard_spec(raw_mesh, rows=rows,
                                           rules=ctx.current_rules())
            if spec is None:
                return _decide("rmsnorm", "jnp",
                               f"explicit shard_map but {why}; reference",
                               raw_mesh), None, interpret
            return _decide("rmsnorm", "pallas_shard_map",
                           "explicit backend", raw_mesh), spec, interpret
        return _decide("rmsnorm", "pallas", "explicit backend"), None, \
            interpret
    if not aligned:
        return _decide("rmsnorm", "jnp",
                       f"rows={rows}/d={d} below tile minimum (8 rows, "
                       "128-lane d)"), None, interpret
    if mesh is not None:
        spec, why = rmsnorm_shard_spec(mesh, rows=rows,
                                       rules=ctx.current_rules())
        if spec is None:
            return _decide("rmsnorm", "jnp", why, mesh), None, interpret
        return _decide("rmsnorm", "pallas_shard_map",
                       "row blocks divide the mesh axes; scale "
                       "replicated, dscale psum'd in the vjp", mesh), \
            spec, interpret
    if ctx.current_rules():
        return _decide("rmsnorm", "jnp",
                       "sharding rules active without a dispatch mesh "
                       "(install it via ctx.use_mesh)"), None, interpret
    if platform == "tpu":
        return _decide("rmsnorm", "pallas", "single-device tpu, aligned"), \
            None, False
    return _decide("rmsnorm", "jnp",
                   f"platform {platform}: Pallas kernels run interpret-"
                   "only off-TPU"), None, interpret


def rmsnorm(x, scale, *, eps: float = 1e-6,
            backend: str = "auto") -> jnp.ndarray:
    """Fused RMSNorm over the last dim of an arbitrary-rank activation.

    Differentiable on every backend: the Pallas paths carry the one-pass
    dx/dscale vjp from ``rmsnorm_bwd`` (shard_mapped over row blocks under
    a mesh, with the dscale partials psum'd); the jnp path is plain AD
    through the reference."""
    assert backend in _BACKENDS, backend
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    decision, shard, interpret = _resolve_rmsnorm(rows, d, backend)
    if decision.backend == "jnp":
        return ref.rmsnorm_ref(x, scale, eps=eps)
    y = _rmsnorm_call(x.reshape(rows, d), scale, eps, shard, interpret)
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# fused shared-RMSProp
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lr", "alpha", "eps"))
def rmsprop_update(g, grad, *, lr, alpha: float = 0.99,
                   eps: float = 0.1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Shared-RMSProp for an arbitrary-shaped parameter leaf.
    Returns (new_g, update)."""
    shape = g.shape
    n = g.size
    if n < LANES:
        return ref.rmsprop_update_ref(g, grad, lr=lr, alpha=alpha, eps=eps)
    rows = -(-n // LANES)
    pad = rows * LANES - n
    gf = jnp.pad(g.reshape(-1), (0, pad)).reshape(rows, LANES)
    df = jnp.pad(grad.reshape(-1), (0, pad)).reshape(rows, LANES)
    br = 256
    while rows % br:
        br //= 2
    new_g, upd = rmsprop_update_2d(gf, df, jnp.asarray(lr, g.dtype),
                                   alpha=alpha, eps=eps, block_rows=br)
    unpad = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unpad(new_g), unpad(upd)


# ---------------------------------------------------------------------------
# op registry — the dispatch contract, machine-checked by tools/audit
# ---------------------------------------------------------------------------

class OpContract(NamedTuple):
    """One dispatch op's invariants, in checkable form.

    ``tools/audit``'s contract passes cross-check every row: the entry is
    callable, the named jnp oracle (and quant oracle, when the op carries
    an int8 arm) exists in ``ref``, the resolver's every return path emits
    a decision row, delegating ops name a registered delegate, and quant
    ops annotate their rows via ``_quant_note`` / inline int8 reasons.
    New ops MUST be registered here — the auditor also checks the reverse
    direction (any public entry with a ``backend`` parameter that is
    missing from the registry fails the audit)."""
    entry: Any                    # public dispatch callable
    oracle: str                   # jnp oracle name in kernels/ref.py
    quant_oracle: Optional[str]   # int8 oracle name; None = no quant arm
    resolver: Optional[str]       # _resolve_* fn emitting decision rows,
    #                               None for delegating/registry-free ops
    delegate: Optional[str]       # op key this arm delegates to (paged
    #                               indirection), else None


KERNEL_OPS = {
    "flash_attention": OpContract(flash_attention, "flash_attention_ref",
                                  None, "_resolve_flash", None),
    "flash_append": OpContract(flash_attention_append,
                               "flash_attention_append_ref",
                               "flash_attention_append_quant_ref",
                               "_resolve_append", None),
    "decode_attention": OpContract(decode_attention, "decode_attention_ref",
                                   "decode_attention_quant_ref",
                                   "_resolve_decode", None),
    "decode_paged": OpContract(decode_attention_paged,
                               "decode_attention_paged_ref",
                               "decode_attention_paged_quant_ref",
                               None, "decode_attention"),
    "append_paged": OpContract(flash_attention_append_paged,
                               "flash_attention_append_paged_ref",
                               "flash_attention_append_paged_quant_ref",
                               None, "flash_append"),
    "flash_verify": OpContract(flash_attention_verify,
                               "flash_attention_append_ref",
                               "flash_attention_append_quant_ref",
                               None, "flash_append"),
    "verify_paged": OpContract(flash_attention_verify_paged,
                               "flash_attention_append_paged_ref",
                               "flash_attention_append_paged_quant_ref",
                               None, "flash_verify"),
    "rmsnorm": OpContract(rmsnorm, "rmsnorm_ref", None, "_resolve_rmsnorm",
                          None),
    "rmsprop_update": OpContract(rmsprop_update, "rmsprop_update_ref",
                                 None, None, None),
}
