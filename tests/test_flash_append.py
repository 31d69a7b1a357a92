"""Append-mode flash attention: kernel parity, dispatch resolution, and
multi-chunk prefill equivalence.

The append kernel decouples the q and kv grid dimensions (chunk queries at
absolute positions ``pos0 + i`` over the cache prefix plus the chunk), so
every prefill chunk — not just the first — runs the fused path.  The jnp
oracle in ``ref.flash_attention_append_ref`` is the allclose target, and
is itself pinned against the masked-sdpa construction the old
``attend_prefill`` prefix branch used.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ctx
from repro.kernels import dispatch, ref
from repro.models import model as M
from repro.launch.mesh import make_mesh

KEY = jax.random.key(11)


def _qkv(b, c, sk, hq, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, c, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    return q, k, v


def _linear_kpos(sk, pos0, c):
    idx = jnp.arange(sk)
    return jnp.where(idx < pos0 + c, idx, -1)


def _ring_kpos(length, pos0):
    """Rotated ring prefix: slot s holds the largest position ≡ s (mod
    length) written before pos0 (-1 if none)."""
    idx = jnp.arange(length)
    pos = pos0 - 1
    cand = pos - (pos % length) + idx
    cand = jnp.where(cand > pos, cand - length, cand)
    return jnp.where(cand >= 0, cand, -1)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,c,pos0,hq,hkv,d,window",
    [
        (1, 128, 0, 4, 4, 64, None),       # chunk 0 == square causal
        (2, 128, 256, 4, 1, 64, None),     # GQA g=4, later chunk
        (1, 256, 256, 8, 2, 64, None),     # GQA g=4, 256-wide chunk
        (1, 128, 384, 4, 4, 64, 128),      # window: prefix tiles skipped
        (1, 128, 1920, 4, 2, 64, None),    # deep prefix (final 2048 chunk)
    ])
def test_append_kernel_matches_oracle(b, c, pos0, hq, hkv, d, window,
                                      dtype):
    sk = pos0 + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d, dtype)
    kpos = _linear_kpos(sk, pos0, c)
    out = dispatch.flash_attention_append(q, k, v, kpos, pos0=pos0,
                                          window=window, kpos_linear=True,
                                          backend="pallas")
    want = ref.flash_attention_append_ref(q, k, v, kpos, pos0=pos0,
                                          window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_append_kernel_ring_prefix():
    """Rotated (ring) key layout: kpos carries the rotation, no tile skip
    (kpos_linear=False), and a per-batch-row kpos exercises the (B, Sk)
    layout."""
    b, c, pos0, hq, hkv, d, window = 2, 128, 1024, 4, 2, 64, 256
    ring_len = 256
    sk = ring_len + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d)
    kpos = jnp.concatenate([_ring_kpos(ring_len, pos0),
                            pos0 + jnp.arange(c)])
    kpos = jnp.broadcast_to(kpos, (b, sk))
    out = dispatch.flash_attention_append(q, k, v, kpos, pos0=pos0,
                                          window=window,
                                          kpos_linear=False,
                                          backend="pallas")
    want = ref.flash_attention_append_ref(q, k, v, kpos, pos0=pos0,
                                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_append_oracle_matches_masked_sdpa():
    """The oracle reproduces the masked-sdpa construction the old
    ``attend_prefill`` prefix branch used (concat + repeat_kv + where)."""
    from repro.models import attention as attn
    b, c, pos0, hq, hkv, d = 1, 64, 96, 4, 2, 64
    sk = pos0 + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d)
    kpos = jnp.arange(sk)
    got = ref.flash_attention_append_ref(q, k, v, kpos, pos0=pos0)
    qpos = pos0 + jnp.arange(c)
    mask = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
    n_rep = hq // hkv
    want = attn.sdpa(q, attn._repeat_kv(k, n_rep),
                     attn._repeat_kv(v, n_rep), mask[None, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# dispatch resolution
# ---------------------------------------------------------------------------

def test_append_dispatch_fallback_reasons():
    b, c, pos0, hq, hkv, d = 1, 128, 128, 4, 2, 64
    sk = pos0 + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d)
    kpos = jnp.arange(sk)

    # auto on a bare CPU host: jnp with the platform reason
    dispatch.clear_decision_log()
    dispatch.flash_attention_append(q, k, v, kpos, pos0=pos0)
    dec = dispatch.last_decision("flash_append")
    assert dec.backend in ("jnp", "pallas")   # pallas iff a TPU host
    if dec.backend == "jnp":
        assert "platform" in dec.reason

    # misaligned chunk: logged fallback even under explicit pallas
    q2, k2, v2 = _qkv(b, 96, pos0 + 96, hq, hkv, d)
    dispatch.clear_decision_log()
    out = dispatch.flash_attention_append(q2, k2, v2,
                                          jnp.arange(pos0 + 96),
                                          pos0=pos0, backend="pallas")
    dec = dispatch.last_decision("flash_append")
    assert dec.backend == "jnp" and "not MXU-aligned" in dec.reason
    want = ref.flash_attention_append_ref(q2, k2, v2,
                                          jnp.arange(pos0 + 96),
                                          pos0=pos0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    # rules without a dispatch mesh: jnp with the install-a-mesh reason
    with ctx.sharding_rules({"residual": None}):
        dispatch.clear_decision_log()
        dispatch.flash_attention_append(q, k, v, kpos, pos0=pos0)
        dec = dispatch.last_decision("flash_append")
        assert dec.backend == "jnp" and "without a dispatch mesh" \
            in dec.reason

    # broken GQA grouping is a config error, not a fallback
    with pytest.raises(ValueError, match="GQA"):
        dispatch.flash_attention_append(q[:, :, :3], k, v, kpos,
                                        pos0=pos0)


def test_append_dispatch_shard_map_1dev_mesh():
    """Explicit shard_map honors even a 1-device mesh (bench idiom) and
    matches the oracle."""
    b, c, pos0, hq, hkv, d = 2, 128, 128, 4, 2, 64
    sk = pos0 + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d)
    kpos = _linear_kpos(sk, pos0, c)
    mesh = make_mesh((1, 1), ("data", "model"))
    with ctx.use_mesh(mesh):
        out = dispatch.flash_attention_append(
            q, k, v, kpos, pos0=pos0, kpos_linear=True,
            backend="pallas_shard_map")
    want = ref.flash_attention_append_ref(q, k, v, kpos, pos0=pos0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_append_dispatch_auto_mesh_2dev():
    """Auto dispatch under a 2-device mesh resolves the shard_map'd append
    arm (heads over 'model') and matches the oracle — the arm the serve
    engine's admission prefill rides under a mesh."""
    b, c, pos0, hq, hkv, d = 1, 128, 256, 4, 2, 64
    sk = pos0 + c
    q, k, v = _qkv(b, c, sk, hq, hkv, d)
    kpos = _linear_kpos(sk, pos0, c)
    mesh = make_mesh((1, 2), ("data", "model"))
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        out = dispatch.flash_attention_append(q, k, v, kpos, pos0=pos0,
                                              kpos_linear=True)
        dec = dispatch.last_decision("flash_append")
        assert dec.backend == "pallas_shard_map", dec
    want = ref.flash_attention_append_ref(q, k, v, kpos, pos0=pos0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# model-level multi-chunk prefill
# ---------------------------------------------------------------------------

def _prefill_chain(cfg, params, tokens, cache_len, chunk, true_len=None):
    b, s = tokens.shape
    cache = M.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    outs = []
    for p0 in range(0, s, chunk):
        o, cache = M.prefill_step(cfg, params, cache,
                                  {"tokens": tokens[:, p0:p0 + chunk]},
                                  p0, true_len)
        outs.append(o["logits"])
    return jnp.concatenate(outs, axis=1), cache


def test_prefill_chunks_match_forward_gqa():
    """Multi-chunk prefill == teacher-forced forward on a GQA variant
    (q heads grouped 4:1 over kv heads) with a ragged final chunk."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              n_kv_heads=1)
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 2, 20            # chunks of 8: ragged final chunk of 4
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    full = M.forward(cfg, params, {"tokens": tokens})["logits"]
    got, _ = _prefill_chain(cfg, params, tokens, 24, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-3, rtol=2e-3)


def test_prefill_ring_true_len_masks_padding():
    """Ring-cache writes stop at each row's true_len: a short row padded
    to the grid must decode exactly like the unpadded prompt (the
    aliasing case that used to gate rings out of engine prefill)."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    cache_len = 26
    long_p, short_p = 20, 4     # padded grid driven by the long row
    tokens = jax.random.randint(jax.random.key(2), (2, long_p), 0,
                                cfg.vocab_size)
    true_len = jnp.asarray([long_p, short_p], jnp.int32)
    _, cache = _prefill_chain(cfg, params, tokens, cache_len, 8,
                              true_len=true_len)

    # reference: the short prompt alone, exact-length chunks
    _, ref_cache = _prefill_chain(cfg, params, tokens[1:2, :short_p],
                                  cache_len, 4)
    # per-slot decode over the padded 2-row cache: row 1 must behave as if
    # it had never seen the padding
    got, _ = M.decode_step(cfg, params, cache,
                           {"tokens": jnp.zeros((2, 1), jnp.int32)},
                           jnp.asarray([long_p, short_p]))
    want, _ = M.decode_step(cfg, params, ref_cache,
                            {"tokens": jnp.zeros((1, 1), jnp.int32)},
                            jnp.asarray(short_p))
    np.testing.assert_allclose(np.asarray(got["logits"][1:2]),
                               np.asarray(want["logits"]),
                               atol=2e-3, rtol=2e-3)
