"""Per-slot decode attention: every batch row at its own decode depth.

The continuous-batching engine decodes a slot table where a just-admitted
request (pos = its prompt length) sits next to sequences thousands of
tokens deep and next to drained slots.  These tests sweep ragged ``pos
(B,)`` / ``kpos (B, L)`` through every dispatch arm against the jnp
oracle; the multi-device arms need
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (CI's host-mesh
leg).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import ctx
from repro.kernels import dispatch, ref
from repro.launch.mesh import make_mesh

KEY = jax.random.key(7)
MULTI = len(jax.devices()) >= 2


def _ragged_kpos(pos, length):
    idx = jnp.arange(length)
    return jnp.where(idx[None, :] <= pos[:, None], idx[None, :], -1)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize(
    "b,length,hq,hkv,d,poss",
    [
        # just-admitted (0), mid-stream, cache-full (L-1 = finished depth)
        (3, 256, 8, 2, 64, (0, 130, 255)),          # GQA g=4
        (2, 512, 4, 4, 64, (17, 400)),              # MHA
        (4, 128, 4, 1, 128, (0, 1, 64, 127)),       # MQA, wide head
    ])
def test_perslot_parity(backend, b, length, hq, hkv, d, poss):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray(poss, jnp.int32)
    kpos = _ragged_kpos(pos, length)
    out = dispatch.decode_attention(q, kc, vc, kpos, pos, backend=backend)
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_perslot_ring_kpos():
    """Per-row ring-buffer kpos: each slot map rotated by its own pos."""
    b, length, h, d = 3, 256, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, length, h, d))
    vc = jax.random.normal(ks[2], (b, length, h, d))
    pos = jnp.asarray([1000, 300, 255], jnp.int32)
    idx = jnp.arange(length)
    cand = pos[:, None] - (pos[:, None] % length) + idx[None, :]
    cand = jnp.where(cand > pos[:, None], cand - length, cand)
    kpos = jnp.where(cand >= 0, cand, -1)
    out = dispatch.decode_attention(q, kc, vc, kpos, pos, backend="pallas")
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_lockstep_is_thin_wrapper():
    """Scalar pos / (L,) kpos must produce bit-identical results to the
    broadcast per-slot layout (existing train/dryrun callers untouched)."""
    b, length, hq, hkv, d = 2, 256, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray(100, jnp.int32)
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    a = dispatch.decode_attention(q, kc, vc, kpos, pos, backend="pallas")
    bcast = dispatch.decode_attention(
        q, kc, vc, jnp.broadcast_to(kpos, (b, length)),
        jnp.full((b,), 100, jnp.int32), backend="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(bcast))


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_perslot_shard_map_parity():
    """(batch, heads) shard_map arm with ragged pos, batch on 'data'."""
    mesh = make_mesh((2, 1), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    b, length, hq, hkv, d = 4, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray([0, 511, 300, 64], jnp.int32)
    kpos = _ragged_kpos(pos, length)
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        assert dispatch.last_decision("decode_attention").backend == \
            "pallas_shard_map"
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_perslot_pallas_cp_parity():
    """Seq-sharded cache: the pallas_cp combine with ragged per-slot pos —
    a freshly-admitted row whose whole second shard is masked must coexist
    with a deep row that reads both shards."""
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    b, length, hq, hkv, d = 2, 512, 8, 2, 64     # GQA g=4
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray([5, 501], jnp.int32)
    kpos = _ragged_kpos(pos, length)
    rules = {"decode_cp": {"mesh": mesh, "seq_axes": ("model",),
                           "dp_axes": ("data",), "n_shards": 2}}
    with ctx.sharding_rules(rules):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        d_ = dispatch.last_decision("decode_attention")
        assert d_.backend == "pallas_cp", d_
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
