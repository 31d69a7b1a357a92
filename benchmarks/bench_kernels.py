"""Kernel microbenchmarks: Pallas (interpret on CPU — indicative only) vs
the jnp reference path; plus the blockwise flash vs naive attention, the
masked-tile skip fractions of the fused backward, and the shard_map'd
(mesh-dispatched) fwd+bwd path."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.distributed import ctx
from repro.kernels import dispatch, ref
from repro.kernels.flash_attention import masked_tile_fraction
from repro.launch.mesh import make_mesh


def run() -> list:
    key = jax.random.key(0)
    rows = []
    b, s, hq, hkv, d = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))

    ref_fn = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    us_ref = common.timed(ref_fn, q, k, v, iters=3)
    rows.append({"name": "attention_ref_jnp", "us_per_call": us_ref,
                 "derived": f"s={s}"})
    from repro.models.flash_jnp import flash_attention_jnp
    fl_fn = jax.jit(lambda q, k, v: flash_attention_jnp(q, k, v, True,
                                                        None, 256))
    us_fl = common.timed(fl_fn, q, k, v, iters=3)
    rows.append({"name": "attention_flash_jnp", "us_per_call": us_fl,
                 "derived": f"vs_ref={us_ref/us_fl:.2f}x"})

    # fwd+bwd through the Pallas kernel's custom VJP (interpret on CPU) vs
    # AD through the blockwise-jnp path — the training hot-path comparison
    grad_pl = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        dispatch.flash_attention(q, k, v, causal=True, backend="pallas")),
        argnums=(0, 1, 2)))
    us_gpl = common.timed(grad_pl, q, k, v, iters=3)
    rows.append({"name": "attention_pallas_fwd_bwd", "us_per_call": us_gpl,
                 "derived": f"s={s} dq+dk+dv"})
    grad_jnp = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention_jnp(q, k, v, True, None, 256)), argnums=(0, 1, 2)))
    us_gj = common.timed(grad_jnp, q, k, v, iters=3)
    rows.append({"name": "attention_flash_jnp_fwd_bwd", "us_per_call": us_gj,
                 "derived": f"vs_pallas={us_gpl/us_gj:.2f}x"})

    # masked-tile skip fractions: the share of (bq x bk) score tiles the
    # fused backward predicates away instead of computing zero tiles
    for name, win, blk in (("causal", None, 128), ("causal", None, 512),
                           ("window128", 128, 128)):
        frac = masked_tile_fraction(s, blk, blk, True, win)
        rows.append({"name": f"bwd_skipped_tiles_{name}_b{blk}",
                     "us_per_call": 0.0,
                     "derived": f"s={s} skipped={frac:.3f}"})

    # shard_map'd dispatch (mesh over local devices): fwd and fwd+bwd —
    # on a multi-device host this is the path backend="auto" picks under
    # a mesh; on one device it is the same kernels through a trivial mesh
    n_dev = len(jax.devices())
    if hkv % n_dev == 0:
        mesh_shape = (1, n_dev)      # heads over model
    elif b % n_dev == 0:
        mesh_shape = (n_dev, 1)      # batch over data
    else:
        mesh_shape = (1, 1)          # trivial mesh, same kernels
    mesh = make_mesh(mesh_shape, ("data", "model"))
    with ctx.use_mesh(mesh):
        sh_fwd = jax.jit(lambda q, k, v: dispatch.flash_attention(
            q, k, v, causal=True, backend="pallas_shard_map"))
        us_sf = common.timed(sh_fwd, q, k, v, iters=3)
        rows.append({"name": "attention_sharded_fwd", "us_per_call": us_sf,
                     "derived": f"mesh={dict(mesh.shape)}"})
        sh_grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            dispatch.flash_attention(q, k, v, causal=True,
                                     backend="pallas_shard_map")),
            argnums=(0, 1, 2)))
        us_sg = common.timed(sh_grad, q, k, v, iters=3)
        rows.append({"name": "attention_sharded_fwd_bwd",
                     "us_per_call": us_sg,
                     "derived": f"vs_single={us_gpl/us_sg:.2f}x"})

    # decode attention: serving tokens/sec for both cache layouts through
    # the dispatch layer (one fast path serves both — PR 3)
    L = 4096
    kc = jax.random.normal(ks[1], (b, L, hkv, d))
    vc = jax.random.normal(ks[2], (b, L, hkv, d))
    pos = jnp.asarray(L - 1)
    kpos = jnp.arange(L)
    qd = jax.random.normal(ks[0], (b, hq, d))
    dec_ref = jax.jit(lambda q, k, v, kp, p: ref.decode_attention_ref(
        q, k, v, kp, p))
    us_dref = common.timed(dec_ref, qd, kc, vc, kpos, pos, iters=3)
    rows.append({"name": "decode_ref_jnp", "us_per_call": us_dref,
                 "derived": f"L={L} tok_s={b * 1e6 / us_dref:.1f}"})

    # replicated-cache layout: shard_map over (batch, heads)
    with ctx.use_mesh(mesh):
        dec_sh = jax.jit(lambda q, k, v, kp, p: dispatch.decode_attention(
            q, k, v, kp, p, backend="pallas_shard_map"))
        us_dsh = common.timed(dec_sh, qd, kc, vc, kpos, pos, iters=3)
        rows.append({"name": "decode_sharded_bh", "us_per_call": us_dsh,
                     "derived": f"L={L} mesh={dict(mesh.shape)} "
                                f"tok_s={b * 1e6 / us_dsh:.1f}"})

    # context-parallel layout: seq-sharded cache, partials kernel + psum
    # combine (the pallas_cp arm the decode_cp rules resolve to)
    n_cp = mesh.shape["model"]
    cp_rules = {"decode_cp": {"mesh": mesh, "seq_axes": ("model",),
                              "dp_axes": ("data",), "n_shards": n_cp}}
    with ctx.sharding_rules(cp_rules):
        dec_cp = jax.jit(lambda q, k, v, kp, p: dispatch.decode_attention(
            q, k, v, kp, p))
        us_dcp = common.timed(dec_cp, qd, kc, vc, kpos, pos, iters=3)
        d = dispatch.last_decision("decode_attention")
        rows.append({"name": "decode_cp_seqshard", "us_per_call": us_dcp,
                     "derived": f"L={L} shards={n_cp} "
                                f"backend={d.backend if d else '?'} "
                                f"tok_s={b * 1e6 / us_dcp:.1f}"})

    # decode cache-dtype sweep through auto dispatch: measured tok/s next
    # to the analytic cache bytes each decoded token streams (the int8
    # win is the bytes column — off-TPU the jnp arm dequantizes up front,
    # so the wall-time ratio is indicative, the bytes ratio is the
    # roofline term).  int8 rows carry the f32 scale reads too.
    from repro.kernels import kv_quant
    hd = qd.shape[-1]
    for kvname in ("f32", "int8"):
        if kvname == "int8":
            k8, ksc = kv_quant.quantize(kc)
            v8, vsc = kv_quant.quantize(vc)
            fn = jax.jit(lambda q, k, v, kp, p, ks, vs:
                         dispatch.decode_attention(q, k, v, kp, p,
                                                   k_scale=ks, v_scale=vs))
            us_kv = common.timed(fn, qd, k8, v8, kpos, pos, ksc, vsc,
                                 iters=3)
            cache_b = 2 * b * L * hkv * (hd + 4)
        else:
            fn = jax.jit(lambda q, k, v, kp, p:
                         dispatch.decode_attention(q, k, v, kp, p))
            us_kv = common.timed(fn, qd, kc, vc, kpos, pos, iters=3)
            cache_b = 2 * b * L * hkv * hd * 4
        rows.append({"name": f"decode_kv_{kvname}", "us_per_call": us_kv,
                     "derived": f"L={L} tok_s={b * 1e6 / us_kv:.1f} "
                                f"cache_B_tok={cache_b}"})

    # fused rmsprop (jnp ref — the pallas path is interpret-mode on CPU)
    g = jnp.abs(jax.random.normal(ks[0], (1024, 1024)))
    dg = jax.random.normal(ks[1], (1024, 1024))
    rms_ref = jax.jit(lambda g, d: ref.rmsprop_update_ref(g, d, lr=1e-3))
    us_rms = common.timed(rms_ref, g, dg, iters=5)
    rows.append({"name": "rmsprop_ref_jnp", "us_per_call": us_rms,
                 "derived": "1M params"})
    common.save_rows("kernels_micro", rows)
    return rows
