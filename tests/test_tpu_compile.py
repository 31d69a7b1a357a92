"""Mosaic lowering guard: compile every Pallas kernel of the main paths for
a described TPU v5e chip at stablelm-1.6b widths.

Interpret mode never checks block layouts against the TPU's tiling rules,
so these compiles are what catches a kernel that the chip's compiler would
refuse.  No chip is needed: the topology is described, not attached, and
only shapes are passed.  The topology is described inside a module-scoped
fixture, never at import, so every test worker collects the same tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (decode_attention as da, flash_attention as fa,
                           flash_attention_bwd as fb, rmsnorm as rn,
                           shared_rmsprop as sr)

# stablelm-1.6b: 32 q/kv heads of 64; serve batch 8 at cache_len 2048
B, S, H, D = 8, 2048, 32, 64
CHUNK = 128
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_fwd(one_chip):
    _compile(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, causal=True, save_residuals=True, interpret=False),
        one_chip, *[((B, S, H, D), BF16)] * 3)


def test_flash_bwd(one_chip):
    _compile(lambda q, k, v, o, lse, do: fb.flash_attention_bwd(
        q, k, v, o, lse, do, causal=True, interpret=False),
        one_chip, ((B, S, H, D), BF16), ((B, S, H, D), BF16),
        ((B, S, H, D), BF16), ((B, S, H, D), BF16), ((B, H, S), F32),
        ((B, S, H, D), BF16))


# Sk = 384: a prefill chunk at pos0 = 256, whose key stream length is no
# power of two
@pytest.mark.parametrize("sk", [S, 3 * CHUNK])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_flash_append(one_chip, quant, sk):
    kv = I8 if quant else BF16
    bk = da.kv_block_rows(sk, H, D, 1 if quant else 2, cap=512)

    def fn(q, k, v, kpos, ks, vs):
        return fa.flash_attention_append(
            q, k, v, kpos, pos0=sk - CHUNK, block_q=CHUNK, block_k=bk,
            kpos_linear=True, interpret=False,
            k_scale=ks if quant else None, v_scale=vs if quant else None)
    _compile(fn, one_chip, ((B, CHUNK, H, D), BF16), ((B, sk, H, D), kv),
             ((B, sk, H, D), kv), ((B, sk), I32), ((B, sk, H, 1), F32),
             ((B, sk, H, 1), F32))


@pytest.mark.parametrize("partials", [False, True], ids=["fwd", "partials"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_decode(one_chip, quant, partials):
    kv = I8 if quant else BF16
    bk = da.kv_block_rows(S, H, D, 1 if quant else 2)
    entry = da.decode_attention_partials if partials \
        else da.decode_attention_fwd

    def fn(q, k, v, kpos, pos, ks, vs):
        return entry(q, k, v, kpos, pos, block_k=bk, interpret=False,
                     k_scale=ks if quant else None,
                     v_scale=vs if quant else None)
    _compile(fn, one_chip, ((B, H, D), BF16), ((B, S, H, D), kv),
             ((B, S, H, D), kv), ((B, S), I32), ((B,), I32),
             ((B, S, H, 1), F32), ((B, S, H, 1), F32))


def test_rmsnorm(one_chip):
    _compile(lambda x, s: rn.rmsnorm_fwd(x, s, save_residuals=True,
                                         interpret=False),
             one_chip, ((B * 1024, 2048), BF16), ((2048,), BF16))
    _compile(lambda x, s, r, dy: rn.rmsnorm_bwd(x, s, r, dy,
                                                interpret=False),
             one_chip, ((B * 1024, 2048), BF16), ((2048,), BF16),
             ((B * 1024,), F32), ((B * 1024, 2048), BF16))


def test_rmsprop(one_chip):
    _compile(lambda g, d, lr: sr.rmsprop_update_2d(g, d, lr,
                                                   interpret=False),
             one_chip, ((4096, 1024), F32), ((4096, 1024), F32), ((), F32))
