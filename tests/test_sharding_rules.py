"""Sharding-rule unit tests using an abstract 16x16 mesh (no devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import abstract_mesh
from repro.configs import get_config
from repro.distributed import sharding
from repro.launch import specs as specs_mod

MESH = abstract_mesh((16, 16), ("data", "model"))
MESH3 = abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def _spec_of(shard):
    return tuple(shard.spec)


def test_param_rules_dense():
    cfg = get_config("qwen2-72b")
    p_specs = specs_mod.params_specs(cfg)
    shards = sharding.param_shardings(cfg, MESH, p_specs)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(shards)[0]}
    # scanned layers: leading stack dim unsharded, (F, M) layout for wq
    wq = next(v for k, v in flat.items() if k.endswith("attn/wq/w"))
    assert _spec_of(wq) == (None, "data", "model")
    wo = next(v for k, v in flat.items() if k.endswith("attn/wo/w"))
    assert _spec_of(wo) == (None, "model", "data")
    emb = next(v for k, v in flat.items() if "embed/table" in k)
    assert _spec_of(emb) == ("model", "data")


def test_odd_vocab_drops_model_axis():
    cfg = get_config("minicpm-2b")    # vocab 122753 (odd)
    p_specs = specs_mod.params_specs(cfg)
    shards = sharding.param_shardings(cfg, MESH, p_specs)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(shards)[0]}
    emb = next(v for k, v in flat.items() if "embed/table" in k)
    spec = _spec_of(emb)
    assert spec[0] is None            # vocab axis dropped (not divisible)


def test_batch_shardings_multi_pod():
    tree = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32),
            "positions": jax.ShapeDtypeStruct((3, 256, 128), jnp.int32)}
    shards = sharding.batch_shardings(MESH3, tree, batch_size=256)
    assert tuple(shards["tokens"].spec)[0] == ("pod", "data")
    assert tuple(shards["positions"].spec) == (None, ("pod", "data"), None)


def test_cache_shardings_batch1_context_parallel():
    cfg = get_config("qwen2-72b")
    cache = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["init_cache"])
        .init_cache(cfg, 1, 4096, dtype=jnp.bfloat16))
    shards = sharding.cache_shardings(cfg, MESH, cache, batch_size=1)
    k_shard = jax.tree_util.tree_flatten_with_path(shards)[0]
    kv = [s for path, s in k_shard
          if str(path[-1].key) in ("k", "v")][0]
    spec = tuple(kv.spec)
    # batch=1: seq dim takes data+model (full-mesh context parallelism)
    assert spec[2] == ("data", "model")


def test_cache_shardings_paged_pool():
    """Paged pool leaves: batch-sharded serving puts Hkv on 'model' (same
    dim the gathered dense view shards); batch=1 context parallelism puts
    the PAGE dim on the seq axes (whole 128-row pages per shard); page
    tables replicate (they are gather/scatter indices)."""
    from repro.models import attention as attn
    from repro.models import model as M

    cfg = get_config("qwen2-72b")
    layout = attn.PagedLayout(page_size=128, n_pages=256)
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, 16, 4096, dtype=jnp.bfloat16,
                             paged=layout))
    flat = {str(path[-1].key): s for path, s in
            jax.tree_util.tree_flatten_with_path(
                sharding.cache_shardings(cfg, MESH, cache,
                                         batch_size=256))[0]}
    off = 1 if len(cfg.layer_kinds()) > 1 else 0
    kp = tuple(flat["kp"].spec)
    assert kp[off + 2] is None or kp[off + 2] == "model"
    assert kp[off + 0] is None                     # pages whole, batch path
    assert tuple(flat["pt"].spec) == ()            # replicated indices

    # batch=1: the page dim takes the seq axes (256 pages % 256 mesh == 0)
    flat1 = {str(path[-1].key): s for path, s in
             jax.tree_util.tree_flatten_with_path(
                 sharding.cache_shardings(cfg, MESH, cache,
                                          batch_size=1))[0]}
    kp1 = tuple(flat1["kp"].spec)
    assert kp1[off + 0] == ("data", "model")
    assert tuple(flat1["pt"].spec) == ()


def test_cache_shardings_quant_scale_leaves():
    """int8 cache: the f32 scale leaves (ks/vs contiguous, kps/vps paged)
    are rank-matched to their payload and must take the payload's spec on
    every leading dim, trailing singleton unsharded — the property that
    lets COW copies, admission scatters, and the engine's bdim scan treat
    payload and scale identically."""
    from repro.models import attention as attn
    from repro.models import model as M

    cfg = get_config("qwen2-72b")
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, 16, 4096, dtype=jnp.bfloat16,
                             kv_dtype=jnp.int8))
    flat = {str(path[-1].key): s for path, s in
            jax.tree_util.tree_flatten_with_path(
                sharding.cache_shardings(cfg, MESH, cache,
                                         batch_size=256))[0]}
    for pay, sc in (("k", "ks"), ("v", "vs")):
        pspec, sspec = tuple(flat[pay].spec), tuple(flat[sc].spec)
        assert sspec[:-1] == pspec[:-1], (pay, pspec, sspec)
        assert sspec[-1] is None

    layout = attn.PagedLayout(page_size=128, n_pages=256)
    paged = jax.eval_shape(
        lambda: M.init_cache(cfg, 16, 4096, dtype=jnp.bfloat16,
                             paged=layout, kv_dtype=jnp.int8))
    off = 1 if len(cfg.layer_kinds()) > 1 else 0
    for bsz, page_axes in ((256, None), (1, ("data", "model"))):
        flatp = {str(path[-1].key): s for path, s in
                 jax.tree_util.tree_flatten_with_path(
                     sharding.cache_shardings(cfg, MESH, paged,
                                              batch_size=bsz))[0]}
        for pay, sc in (("kp", "kps"), ("vp", "vps")):
            pspec = tuple(flatp[pay].spec)
            sspec = tuple(flatp[sc].spec)
            assert sspec[:-1] == pspec[:-1], (bsz, pay, pspec, sspec)
            assert sspec[-1] is None
            assert pspec[off + 0] == page_axes  # CP pages follow payload


def test_activation_rules_gqa_fallback():
    cfg = get_config("qwen2-72b")     # kv=8 < model=16
    rules = sharding.activation_rules(MESH, batch_size=256, cfg=cfg)
    assert tuple(rules["attn_q"].spec)[2] == "model"
    # non-divisible kv heads: sequence-sharded pin (perf iter #8)
    assert tuple(rules["attn_kv"].spec)[1] == "model"


def test_activation_rules_odd_heads_seq_sharded():
    cfg = get_config("minicpm-2b")    # 36 heads, 16-way model axis
    rules = sharding.activation_rules(MESH, batch_size=256, cfg=cfg)
    assert tuple(rules["attn_q"].spec)[1] == "model"
    assert tuple(rules["attn_q"].spec)[2] is None
