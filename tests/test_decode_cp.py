"""Context-parallel (flash-decoding) decode vs the dense decode path.

Since the unification (PR 3) there is one decode entry point:
``attend_decode`` writes the cache on the owning seq shard when the
``decode_cp`` rules apply and routes the attention through
``dispatch.decode_attention``, whose ``pallas_cp`` arm does the partials
kernel + psum combine (jnp fallback for misaligned smoke shapes — what the
(1, 1)-mesh cases here exercise).  The multi-device cases need
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (CI's host-mesh
matrix leg).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ctx, sharding
from repro.kernels import dispatch
from repro.models import model as M
from repro.launch.mesh import make_mesh

MESH = make_mesh((1, 1), ("data", "model"))
MULTI = len(jax.devices()) >= 2


@pytest.mark.parametrize("arch", ["qwen2-72b", "stablelm-1.6b",
                                  "llama4-scout-17b-a16e"])
def test_decode_cp_matches_dense(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    c1 = M.init_cache(cfg, b, s, dtype=jnp.float32)
    c2 = M.init_cache(cfg, b, s, dtype=jnp.float32)
    rules = sharding.decode_rules(cfg, MESH, batch_size=b)
    for t in range(s):
        tb = {"tokens": tokens[:, t:t + 1]}
        o1, c1 = M.decode_step(cfg, params, c1, tb, jnp.asarray(t))
        with jax.set_mesh(MESH), ctx.sharding_rules(rules):
            o2, c2 = M.decode_step(cfg, params, c2, tb, jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(o1["logits"]),
                                   np.asarray(o2["logits"]),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_decode_cp_multidevice_resolves_pallas_cp():
    """Full decode_step on a real 2-shard seq-sharded cache: the dispatch
    summary must show pallas_cp (no 'context-parallel rules own the cache'
    fallback), and logits must match the unruled dense path."""
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    b, cache_len, steps = 2, 256, 4
    tokens = jax.random.randint(jax.random.key(1), (b, steps), 0,
                                cfg.vocab_size)
    mesh = make_mesh((1, 2), ("data", "model"))
    c1 = M.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    c2 = M.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    rules = sharding.decode_rules(cfg, mesh, batch_size=b)
    assert rules["decode_cp"]["n_shards"] == 2
    for t in range(steps):
        tb = {"tokens": tokens[:, t:t + 1]}
        o1, c1 = M.decode_step(cfg, params, c1, tb, jnp.asarray(t))
        with jax.set_mesh(mesh), ctx.sharding_rules(rules):
            dispatch.clear_decision_log()
            o2, c2 = M.decode_step(cfg, params, c2, tb, jnp.asarray(t))
            d = dispatch.last_decision("decode_attention")
            assert d is not None and d.backend == "pallas_cp", d
            assert not any("context-parallel rules own the cache" in
                           r["reason"] and r["backend"] == "jnp"
                           for r in dispatch.decision_summary())
        np.testing.assert_allclose(np.asarray(o1["logits"]),
                                   np.asarray(o2["logits"]),
                                   atol=2e-4, rtol=2e-4)


def test_decode_cp_ring_cache():
    """Sliding-window ring cache under context-parallel decode."""
    import dataclasses
    cfg = get_config("stablelm-1.6b").reduced()
    cfg = dataclasses.replace(cfg, block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 1, 24
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    full = M.forward(cfg, params, {"tokens": tokens})["logits"]
    cache = M.init_cache(cfg, b, s, dtype=jnp.float32)
    rules = sharding.decode_rules(cfg, MESH, batch_size=b)
    outs = []
    with jax.set_mesh(MESH), ctx.sharding_rules(rules):
        for t in range(s):
            out, cache = M.decode_step(cfg, params, cache,
                                       {"tokens": tokens[:, t:t + 1]},
                                       jnp.asarray(t))
            outs.append(out["logits"][:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=2e-3, rtol=2e-3)
