"""Mesh-aware kernel dispatch: resolution, lowering, and parity.

The shard_map tests need a multi-device host; CI runs a matrix leg with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so they execute on
every PR (they skip on a plain single-device run).  The full GQA x mask
parity sweep carries the ``slow`` marker; one case per mesh orientation
stays fast.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import ctx
from repro.kernels import dispatch, ref
from repro.models import attention as attn
from repro.models.flash_jnp import flash_attention_jnp
from repro.launch.mesh import make_mesh

MULTI = len(jax.devices()) >= 2
KEY = jax.random.key(7)


class _Cfg:
    n_heads, n_kv_heads, head_dim = 4, 2, 64
    rope_theta = 10000.0


def _qkv(b, s, hq, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    return (jax.random.normal(ks[0], (b, s, hq, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype),
            jax.random.normal(ks[3], (b, s, hq, d), dtype))


# ---------------------------------------------------------------------------
# resolution + fallback reasons (single-device)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.default_backend() != "cpu", reason="cpu-only check")
def test_auto_cpu_single_device_picks_jnp_with_reason():
    q, k, v, _ = _qkv(1, 256, 4, 2, 64)
    dispatch.clear_decision_log()
    out = dispatch.flash_attention(q, k, v, causal=True)
    d = dispatch.last_decision("flash_attention")
    assert d.backend == "jnp"
    assert "interpret-only" in d.reason
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_auto_misaligned_seq_records_reason():
    q, k, v, _ = _qkv(1, 192, 4, 2, 64)
    dispatch.clear_decision_log()
    dispatch.flash_attention(q, k, v, causal=True)
    d = dispatch.last_decision("flash_attention")
    assert d.backend == "jnp"
    assert "MXU-aligned" in d.reason


def test_rules_without_mesh_fall_back():
    from jax.sharding import PartitionSpec as P
    q, k, v, _ = _qkv(1, 256, 4, 2, 64)
    dispatch.clear_decision_log()
    with ctx.sharding_rules({"residual": P()}):
        dispatch.flash_attention(q, k, v, causal=True)
    d = dispatch.last_decision("flash_attention")
    assert d.backend == "jnp"
    assert "without a dispatch mesh" in d.reason


def test_decision_summary_feeds_hlo_analysis():
    from repro.launch import hlo_analysis
    q, k, v, _ = _qkv(1, 192, 4, 2, 64)
    dispatch.clear_decision_log()
    dispatch.flash_attention(q, k, v, causal=True)
    summ = hlo_analysis.kernel_dispatch_summary()
    assert any(r["op"] == "flash_attention" and r["backend"] == "jnp"
               and "MXU-aligned" in r["reason"] for r in summ)


# ---------------------------------------------------------------------------
# lowering inspection (>= 2 host devices)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
@pytest.mark.parametrize("mesh_shape", [(2, 1), (1, 2)])
def test_attend_train_auto_lowers_shard_map_pallas(mesh_shape):
    """backend="auto" under a mesh: attend_train must lower through the
    shard_map'd Pallas kernel (asserted on the lowered module), and fall
    back to jnp with a recorded reason when no mesh is installed."""
    cfg = _Cfg()
    params = attn.init_attention(jax.random.key(0), 256, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim)
    x = jax.random.normal(KEY, (2, 256, 256))

    def fn(x):
        return attn.attend_train(params, x, None, None, cfg,
                                 use_rope=False)

    mesh = make_mesh(mesh_shape, ("data", "model"))
    jitted = jax.jit(fn)
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        lowered = jitted.lower(x)
        d = dispatch.last_decision("flash_attention")
        assert d.backend == "pallas_shard_map", d
        assert "shmap_body" in lowered.as_text()
        assert "shard_map" in str(jax.make_jaxpr(fn)(x))

    # the SAME jitted callable re-lowered outside the mesh must re-resolve
    # (ctx folds a dispatch token into the jit cache key — without it jax
    # would replay the mesh trace by function identity)
    dispatch.clear_decision_log()
    lowered = jitted.lower(x)
    d = dispatch.last_decision("flash_attention")
    assert d.backend == "jnp" and d.reason
    assert "shmap_body" not in lowered.as_text()


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_auto_mesh_indivisible_heads_falls_back():
    mesh = make_mesh((1, 2), ("data", "model"))
    q, k, v, _ = _qkv(1, 256, 3, 3, 64)    # 3 heads on a 2-way model axis
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        out = dispatch.flash_attention(q, k, v, causal=True)
    d = dispatch.last_decision("flash_attention")
    assert d.backend == "jnp"
    assert "do not divide" in d.reason
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# parity: shard_map'd Pallas vs jnp oracle (fwd + grads)
# ---------------------------------------------------------------------------

def _parity_case(mesh_shape, b, s, hq, hkv, d, window, causal, dtype):
    mesh = make_mesh(mesh_shape, ("data", "model"))
    q, k, v, do = _qkv(b, s, hq, hkv, d, dtype)

    def loss_sharded(q, k, v):
        o = dispatch.flash_attention(q, k, v, causal=causal, window=window)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    def loss_ref(q, k, v):
        o = flash_attention_jnp(q, k, v, causal, window, 128)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        o = jax.jit(lambda q, k, v: dispatch.flash_attention(
            q, k, v, causal=causal, window=window))(q, k, v)
        assert dispatch.last_decision("flash_attention").backend == \
            "pallas_shard_map"
        g_sh = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
    want = flash_attention_jnp(q, k, v, causal, window, 128)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want_g, name in zip(g_sh, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want_g, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
@pytest.mark.parametrize("mesh_shape,window", [((2, 1), None),
                                               ((1, 2), 128)])
def test_sharded_parity_fast(mesh_shape, window):
    """One causal-GQA case per mesh orientation (data- and head-sharded)."""
    _parity_case(mesh_shape, 2, 256, 4, 2, 64, window, True, jnp.float32)


@pytest.mark.slow
@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "mesh_shape,b,s,hq,hkv,d,window,causal",
    [
        ((2, 1), 2, 256, 4, 1, 64, None, True),    # GQA g=4, data-sharded
        ((1, 2), 2, 512, 8, 2, 64, None, True),    # GQA g=4, head-sharded
        ((1, 2), 1, 512, 4, 2, 64, 256, True),     # GQA + sliding window
        ((2, 2) if len(jax.devices()) >= 4 else (2, 1),
         2, 256, 4, 2, 64, 128, True),             # window, (both axes)
        ((1, 2), 1, 256, 2, 2, 64, None, False),   # bidirectional MHA
    ])
def test_sharded_parity_sweep(mesh_shape, b, s, hq, hkv, d, window, causal,
                              dtype):
    _parity_case(mesh_shape, b, s, hq, hkv, d, window, causal, dtype)


# ---------------------------------------------------------------------------
# decode under a mesh
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_sharded_decode_parity():
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    b, length, hq, hkv, d = 2, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray(300, jnp.int32)
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        assert dispatch.last_decision("decode_attention").backend == \
            "pallas_shard_map"
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_decode_shard_map_misaligned_is_logged_fallback():
    """Explicit backend="pallas_shard_map": non-divisible heads / misaligned
    cache length fall back to jnp with a logged reason instead of raising
    (serving batch/head counts vary per request)."""
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    pos = jnp.asarray(100, jnp.int32)
    with ctx.use_mesh(mesh):
        # 3 heads on a 2-way model axis, batch 1 on a 1-way data axis
        q = jax.random.normal(ks[0], (1, 3, 64))
        kc = jax.random.normal(ks[1], (1, 256, 3, 64))
        vc = jax.random.normal(ks[2], (1, 256, 3, 64))
        kpos = jnp.where(jnp.arange(256) <= pos, jnp.arange(256), -1)
        dispatch.clear_decision_log()
        out = dispatch.decode_attention(q, kc, vc, kpos, pos,
                                        backend="pallas_shard_map")
        d = dispatch.last_decision("decode_attention")
        assert d.backend == "jnp"
        assert "explicit shard_map but" in d.reason
        want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

        # misaligned cache length (192): fallback, not ValueError
        kc2 = jax.random.normal(ks[1], (2, 192, 2, 64))
        vc2 = jax.random.normal(ks[2], (2, 192, 2, 64))
        q2 = jax.random.normal(ks[0], (2, 4, 64))
        kpos2 = jnp.where(jnp.arange(192) <= pos, jnp.arange(192), -1)
        dispatch.clear_decision_log()
        dispatch.decode_attention(q2, kc2, vc2, kpos2, pos,
                                  backend="pallas_shard_map")
        d = dispatch.last_decision("decode_attention")
        assert d.backend == "jnp" and "not MXU-aligned" in d.reason


# ---------------------------------------------------------------------------
# context-parallel (pallas_cp) decode: the unified flash-decoding path
# ---------------------------------------------------------------------------

def _cp_rule(mesh, seq_axes=("model",), dp_axes=("data",)):
    n = 1
    for a in seq_axes:
        n *= mesh.shape[a]
    return {"decode_cp": {"mesh": mesh, "seq_axes": tuple(seq_axes),
                          "dp_axes": tuple(dp_axes), "n_shards": n}}


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_decode_cp_pallas_parity():
    """Seq-sharded cache + GQA + ragged kpos: the pallas_cp combine must
    match the jnp oracle to <= 1e-5 and the decision must record it — the
    'context-parallel rules own the cache -> jnp' fallback is gone."""
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    b, length, hq, hkv, d = 2, 512, 8, 2, 64     # GQA g=4
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray(300, jnp.int32)
    # ragged validity: every 3rd slot unwritten (ring-style holes)
    kpos = jnp.where((jnp.arange(length) % 3 != 0)
                     & (jnp.arange(length) <= pos), jnp.arange(length), -1)
    with ctx.sharding_rules(_cp_rule(mesh)):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        d = dispatch.last_decision("decode_attention")
        assert d.backend == "pallas_cp", d
        assert "psum combine" in d.reason
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_decode_cp_one_shard_fully_masked():
    """pos inside the first shard's slice: the second shard is all-masked
    (m = -inf) and must vanish in the combine, not poison it."""
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    b, length = 1, 256
    q = jax.random.normal(ks[0], (b, 4, 64))
    kc = jax.random.normal(ks[1], (b, length, 2, 64))
    vc = jax.random.normal(ks[2], (b, length, 2, 64))
    pos = jnp.asarray(5, jnp.int32)       # only slots 0..5 valid
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    with ctx.sharding_rules(_cp_rule(mesh)):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        assert dispatch.last_decision("decode_attention").backend == \
            "pallas_cp"
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
@pytest.mark.parametrize(
    "b,length,hq,hkv,d,pos,dp_axes",
    [
        (2, 512, 8, 2, 64, 300, ("data",)),    # GQA g=4
        (1, 1024, 4, 1, 64, 1023, ()),         # MQA, full cache
        (2, 256, 4, 4, 64, 17, ("data",)),     # MHA, mostly-empty cache
        (4, 512, 8, 4, 128, 400, ("data",)),   # wide head_dim
    ])
def test_decode_cp_parity_sweep(b, length, hq, hkv, d, pos, dp_axes):
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, length, hkv, d))
    vc = jax.random.normal(ks[2], (b, length, hkv, d))
    pos = jnp.asarray(pos, jnp.int32)
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    rules = {"decode_cp": {"mesh": mesh, "seq_axes": ("model",),
                           "dp_axes": dp_axes, "n_shards": 2}}
    with ctx.sharding_rules(rules):
        dispatch.clear_decision_log()
        out = jax.jit(lambda *a: dispatch.decode_attention(*a))(
            q, kc, vc, kpos, pos)
        assert dispatch.last_decision("decode_attention").backend == \
            "pallas_cp"
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_decode_cp_fallback_reason_sweep():
    """Where the old code had a blanket 'decode_cp -> jnp' branch, the
    resolver now falls back only when the layout cannot serve the call —
    each with a logged reason (and numeric parity through the jnp path)."""
    mesh = make_mesh((1, 2), ("data", "model"))
    ks = jax.random.split(KEY, 3)
    pos = jnp.asarray(100, jnp.int32)
    q = jax.random.normal(ks[0], (2, 4, 64))

    def decode(length, rules):
        kc = jax.random.normal(ks[1], (2, length, 2, 64))
        vc = jax.random.normal(ks[2], (2, length, 2, 64))
        kpos = jnp.where(jnp.arange(length) <= pos,
                         jnp.arange(length), -1)
        with ctx.sharding_rules(rules):
            dispatch.clear_decision_log()
            out = dispatch.decode_attention(q, kc, vc, kpos, pos)
            d = dispatch.last_decision("decode_attention")
        want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        return d

    # local slice 192 not MXU-aligned
    d = decode(384, _cp_rule(mesh))
    assert d.backend == "jnp"
    assert "decode_cp rules own the cache but" in d.reason
    assert "not MXU-aligned" in d.reason
    # length does not divide the shard count
    bad = _cp_rule(mesh)
    bad["decode_cp"]["n_shards"] = 3
    d = decode(512, bad)
    assert d.backend == "jnp" and "does not divide" in d.reason
    # aligned layout resolves pallas_cp (the old blanket fallback is gone)
    d = decode(512, _cp_rule(mesh))
    assert d.backend == "pallas_cp"
    assert "context-parallel rules own the cache" not in "".join(
        r["reason"] for r in dispatch.decision_summary()
        if r["backend"] == "jnp")


# ---------------------------------------------------------------------------
# trace-cache token: one jitted callable across meshes
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_mesh_switch_relowers_with_new_resolution():
    """Regression for the trace-cache bug: dispatch resolves at trace time
    and jax caches traces by function identity, so without the ctx dispatch
    token a re-lowered jit would replay the stale mesh's decision.  Jit
    once, switch meshes via ctx.use_mesh, assert the new resolution."""
    ks = jax.random.split(KEY, 3)
    b, length = 2, 512
    q = jax.random.normal(ks[0], (b, 4, 64))
    kc = jax.random.normal(ks[1], (b, length, 2, 64))
    vc = jax.random.normal(ks[2], (b, length, 2, 64))
    pos = jnp.asarray(300, jnp.int32)
    kpos = jnp.where(jnp.arange(length) <= pos, jnp.arange(length), -1)
    jitted = jax.jit(lambda *a: dispatch.decode_attention(*a))

    mesh = make_mesh((1, 2), ("data", "model"))
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        out_mesh = jitted(q, kc, vc, kpos, pos)
        assert dispatch.last_decision("decode_attention").backend == \
            "pallas_shard_map"
    # same callable under decode_cp rules: resolution must flip to
    # pallas_cp, not replay the (batch, heads) shard_map trace
    with ctx.sharding_rules(_cp_rule(mesh)):
        dispatch.clear_decision_log()
        out_cp = jitted(q, kc, vc, kpos, pos)
        d = dispatch.last_decision("decode_attention")
        assert d is not None and d.backend == "pallas_cp", d
    # and back outside any mesh: jnp (re-resolved again)
    dispatch.clear_decision_log()
    out_plain = jitted(q, kc, vc, kpos, pos)
    d = dispatch.last_decision("decode_attention")
    assert d is not None and d.backend == "jnp"
    want = ref.decode_attention_ref(q, kc, vc, kpos, pos)
    for got in (out_mesh, out_cp, out_plain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_mesh_reentry_hits_trace_cache():
    """The token must key by value, not by entry: re-installing an equal
    mesh/rule state restores the old cache key (no spurious retrace)."""
    q, k, v, _ = _qkv(1, 256, 4, 2, 64)
    traces = []

    @jax.jit
    def fn(q, k, v):
        traces.append(1)
        return dispatch.flash_attention(q, k, v, causal=True)

    fn(q, k, v)
    assert len(traces) == 1
    mesh = make_mesh((len(jax.devices()), 1)
                         if MULTI else (1, 1), ("data", "model"))
    with ctx.use_mesh(mesh):
        fn(q, k, v)
        n_mesh = len(traces)
        assert n_mesh == 2
    fn(q, k, v)                       # restored state: cache hit
    assert len(traces) == n_mesh
    with ctx.use_mesh(mesh):          # equal mesh: cache hit
        fn(q, k, v)
    assert len(traces) == n_mesh


# ---------------------------------------------------------------------------
# rmsnorm under a mesh: row-block shard_map
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
@pytest.mark.parametrize("mesh_shape", [(2, 1), (1, 2)])
def test_rmsnorm_auto_mesh_shard_map_parity(mesh_shape):
    """Under a mesh rmsnorm now shard_maps over row blocks (scale
    replicated, dscale psum'd) instead of silently downgrading to jnp."""
    mesh = make_mesh(mesh_shape, ("data", "model"))
    x = jax.random.normal(KEY, (4, 8, 128))
    scale = jnp.ones((128,)) * 1.5

    def loss(x, scale):
        return jnp.sum(dispatch.rmsnorm(x, scale) ** 2)

    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        y = jax.jit(lambda x, s: dispatch.rmsnorm(x, s))(x, scale)
        d = dispatch.last_decision("rmsnorm")
        assert d.backend == "pallas_shard_map", d
        g = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, scale)
    want = ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    g_ref = jax.grad(lambda x, s: jnp.sum(ref.rmsnorm_ref(x, s) ** 2),
                     argnums=(0, 1))(x, scale)
    for got, want_g, name in zip(g, g_ref, ("dx", "dscale")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_g),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
def test_rmsnorm_seq_parallel_residual_explicit_fallback():
    """Megatron-SP seq-parallel residual keeps its explicit fallback
    reason (rows are sharded over 'model'; a row-block shard_map would
    re-gather the residual stream)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1, 2), ("data", "model"))
    x = jax.random.normal(KEY, (4, 8, 128))
    scale = jnp.ones((128,))
    rules = {"residual": NamedSharding(mesh, P(None, "model", None))}
    with ctx.use_mesh(mesh), ctx.sharding_rules(rules):
        dispatch.clear_decision_log()
        out = dispatch.rmsnorm(x, scale)
        d = dispatch.last_decision("rmsnorm")
    assert d.backend == "jnp"
    assert "seq-parallel residual" in d.reason
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.rmsnorm_ref(x, scale)),
                               atol=1e-5, rtol=1e-5)


def test_rmsnorm_rules_without_mesh_fall_back():
    from jax.sharding import PartitionSpec as P
    x = jax.random.normal(KEY, (4, 8, 128))
    scale = jnp.ones((128,))
    with ctx.sharding_rules({"residual": P()}):
        dispatch.clear_decision_log()
        dispatch.rmsnorm(x, scale)
    d = dispatch.last_decision("rmsnorm")
    assert d.backend == "jnp"
    assert "without a dispatch mesh" in d.reason
