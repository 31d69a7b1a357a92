"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle.

``backend="pallas"`` pins the dispatch layer to the bare kernels — on this
CPU suite auto dispatch would (correctly) resolve to jnp, which is covered
separately in test_dispatch_mesh.py."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ref

KEY = jax.random.key(42)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window,causal",
    [
        (2, 256, 4, 1, 64, None, True),
        (1, 512, 8, 2, 64, None, True),
        (2, 512, 4, 4, 128, 128, True),
        (1, 256, 2, 2, 64, None, False),
        (1, 1024, 8, 8, 64, 256, True),
    ])
def test_flash_attention_sweep(b, s, hq, hkv, d, window, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = dispatch.flash_attention(q, k, v, causal=causal, window=window,
                              backend="pallas")
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window,causal",
    [
        (2, 256, 4, 1, 64, None, True),     # GQA g=4
        (1, 512, 8, 2, 64, None, True),     # GQA g=4, 512 blocks
        (2, 256, 4, 4, 128, 128, True),     # sliding window, MHA
        (1, 256, 2, 2, 64, None, False),    # bidirectional
        (1, 512, 4, 2, 64, 256, True),      # GQA + window
    ])
def test_flash_attention_grad_sweep(b, s, hq, hkv, d, window, causal, dtype):
    """jax.grad through the Pallas kernel (fused bwd) vs the blockwise-jnp
    custom-vjp oracle, on dq, dk and dv."""
    from repro.models.flash_jnp import flash_attention_jnp
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    do = jax.random.normal(ks[3], (b, s, hq, d), dtype)

    def loss_pl(q, k, v):
        o = dispatch.flash_attention(q, k, v, causal=causal, window=window,
                                backend="pallas")
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    def loss_ref(q, k, v):
        o = flash_attention_jnp(q, k, v, causal, window, 128)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-2
    for got, want, name in zip(g_pl, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


def test_flash_attention_grad_matches_sdpa():
    """End-to-end AD through the kernel vs the naive softmax reference."""
    b, s, hq, hkv, d = 1, 256, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    g_pl = jax.grad(lambda q, k, v: jnp.sum(
        dispatch.flash_attention(q, k, v, causal=True, backend="pallas") ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda q, k, v: jnp.sum(
        ref.flash_attention_ref(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_pl, g_rf, ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4,
                                   err_msg=name)


def test_flash_fwd_save_residuals_lse():
    """The saved lse matches logsumexp of the masked scaled scores."""
    from repro.kernels.flash_attention import flash_attention_fwd
    b, s, hq, hkv, d = 1, 256, 2, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    o, lse = flash_attention_fwd(q, k, v, causal=True, block_q=128,
                                 block_k=128, save_residuals=True)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    want = jax.scipy.special.logsumexp(logits, axis=-1)     # (B,Hq,S)
    np.testing.assert_allclose(lse, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,length,hq,hkv,d,frac",
    [
        (2, 512, 4, 1, 64, 0.5),
        (1, 1024, 8, 2, 128, 0.9),
        (2, 256, 4, 4, 64, 0.1),
        (1, 2048, 16, 2, 64, 1.0),
    ])
def test_decode_attention_sweep(b, length, hq, hkv, d, frac, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, length, hkv, d), dtype)
    vc = jax.random.normal(ks[2], (b, length, hkv, d), dtype)
    pos = jnp.array(int(frac * (length - 1)), jnp.int32)
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    out = dispatch.decode_attention(q, kc, vc, kpos, pos, backend="pallas")
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_decode_attention_ring_cache():
    """Ring-buffer (sliding window) cache: slots hold rotated positions."""
    b, length, h, d = 1, 256, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, length, h, d))
    vc = jax.random.normal(ks[2], (b, length, h, d))
    pos = jnp.array(1000, jnp.int32)   # far beyond cache_len
    idx = jnp.arange(length)
    cand = pos - (pos % length) + idx
    kpos = jnp.where(cand > pos, cand - length, cand)
    out = dispatch.decode_attention(q, kc, vc, kpos, pos, backend="pallas")
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("itemsize", [1, 2, 4])
def test_kv_block_rows_tile_aligned(itemsize):
    """Every 128-multiple cache length gets a K/V block that divides it,
    is a multiple of 128 (the kpos block's lane dim) and stays within the
    2 MiB block budget once the lanes pad D=64 to 128."""
    from repro.kernels.decode_attention import kv_block_rows
    for length in range(128, 4096 + 1, 128):
        bk = kv_block_rows(length, 32, 64, itemsize)
        assert length % bk == 0 and bk % 128 == 0, (length, bk)
        assert bk == 128 or bk * 32 * 128 * itemsize <= 2 << 20


@pytest.mark.parametrize("shape", [(64,), (1000,), (128, 128), (7, 321),
                                   (3, 5, 7)])
@pytest.mark.parametrize("lr", [1e-4, 1e-2])
def test_rmsprop_kernel_sweep(shape, lr):
    ks = jax.random.split(KEY, 2)
    g = jnp.abs(jax.random.normal(ks[0], shape))
    dg = jax.random.normal(ks[1], shape)
    new_g, upd = dispatch.rmsprop_update(g, dg, lr=lr)
    ng_ref, upd_ref = ref.rmsprop_update_ref(g, dg, lr=lr)
    np.testing.assert_allclose(new_g, ng_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(upd, upd_ref, rtol=1e-5, atol=1e-9)


def test_flash_jnp_blockwise_matches_kernel():
    """The three implementations (naive, blockwise-jnp, Pallas) agree."""
    from repro.models.flash_jnp import flash_attention_jnp
    ks = jax.random.split(KEY, 3)
    b, s, hq, hkv, d = 1, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o_jnp = flash_attention_jnp(q, k, v, True, None, 128)
    o_pl = dispatch.flash_attention(q, k, v, causal=True, backend="pallas")
    np.testing.assert_allclose(o_jnp, o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(o_pl, o_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(8, 128), (2, 16, 256), (64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_sweep(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape, dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (shape[-1],))
    out = dispatch.rmsnorm(x, scale, backend="pallas")
    want = ref.rmsnorm_ref(x, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_bwd_skips_fully_masked_tiles():
    """Small blocks + small window => whole score tiles fully masked in the
    bwd grids; the predicated kernels must still match the jnp oracle."""
    from repro.kernels.flash_attention import masked_tile_fraction
    from repro.kernels.flash_attention_bwd import flash_attention_bwd
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.flash_jnp import flash_attention_jnp
    b, s, hq, hkv, d, win = 1, 512, 4, 2, 64, 128
    assert masked_tile_fraction(s, 128, 128, True, win) > 0.4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    do = jax.random.normal(ks[3], (b, s, hq, d))
    o, lse = flash_attention_fwd(q, k, v, causal=True, window=win,
                                 block_q=128, block_k=128,
                                 save_residuals=True)
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                     window=win, block_q=128, block_k=128)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention_jnp(q, k, v, True, win, 128) * do),
        argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip((dq, dk, dv), g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2, err_msg=name)


def test_ops_shim_is_gone_and_lint_passes():
    """kernels.ops served one deprecation cycle and is deleted; the tree
    must not import it (enforced by the repro-audit ``no-ops-import``
    pass — run through the ``python -m tools.audit`` runner here so the
    lint is also a tier-1 test)."""
    import importlib
    import subprocess
    import sys
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.kernels.ops")  # lint: allow-ops-ref
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.audit", "--strict",
         "--only", "no-ops-import"],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.parametrize("shape", [(64, 256), (2, 16, 128)])
def test_rmsnorm_vjp_kernel_matches_ad(shape):
    """The fused one-pass dx/dscale backward vs AD through the reference."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], shape)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (shape[-1],))
    dy = jax.random.normal(ks[2], shape)

    def loss(fn):
        return lambda x, s: jnp.sum(fn(x, s).astype(jnp.float32) * dy)

    g_pl = jax.grad(loss(lambda x, s: dispatch.rmsnorm(
        x, s, backend="pallas")), argnums=(0, 1))(x, scale)
    g_rf = jax.grad(loss(lambda x, s: ref.rmsnorm_ref(x, s)),
                    argnums=(0, 1))(x, scale)
    for got, want, name in zip(g_pl, g_rf, ("dx", "dscale")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
