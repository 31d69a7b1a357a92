"""The plain reference against the program at small sizes on the CPU: the
stablelm block (LayerNorm, 25% partial rotary, MHA), the yi block (RMSNorm,
GQA, theta 5e6) and the A3C token loss with its gradients."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bc_tiny
from benchmarks.chip import weights
from benchmarks.chip.reference import a3c as RA
from benchmarks.chip.reference import model as R


def _setup(name, layers=2):
    cfgj, cfg = bc_tiny.tiny_config(name, layers)
    named, params = weights.make(cfg, 1234, serve=False)
    tokens = jax.random.randint(jax.random.key(5), (2, 128), 0,
                                cfg.vocab_size)
    return cfgj, cfg, named, params, tokens


@pytest.mark.parametrize("name", ["stablelm-1.6b", "yi-6b"])
def test_reference_logits_match_the_program(name):
    from repro.models import model as M
    cfgj, cfg, named, params, tokens = _setup(name)
    want = M.forward(cfg, params, {"tokens": tokens})["logits"]
    a = R.Arch.of(cfgj)
    got = jax.vmap(lambda t: R.forward(a, named, t, q_block=64))(tokens)
    scale = float(jnp.std(got))
    assert scale > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4 * scale, rtol=0)


def test_stablelm_partial_rotary_is_the_programs():
    """The program takes the inverse frequencies over the whole head; the
    reference follows it (a stated departure).  The published convention,
    over the rotated dims only, gives other logits."""
    from repro.models import model as M
    cfgj, cfg, named, params, tokens = _setup("stablelm-1.6b")
    assert cfg.rotary_dim == 16 and cfg.hd == 64
    want = np.asarray(M.forward(cfg, params, {"tokens": tokens})["logits"])
    a = R.Arch.of(cfgj)
    published = dataclasses.replace(a, freq_dim=a.rot_dim)
    other = np.asarray(jax.vmap(lambda t: R.forward(published, named, t,
                                                    q_block=64))(tokens))
    assert np.max(np.abs(other - want)) > 100 * 2e-4 * np.std(want)


@pytest.mark.parametrize("name", ["stablelm-1.6b", "yi-6b"])
def test_int8_control_moves_the_logits(name):
    """The control is the same model: its logits move by int8 rounding,
    well past f32 round-off and well short of their own spread."""
    cfgj, cfg, named, params, tokens = _setup(name)
    a = R.Arch.of(cfgj)
    ref = R.forward(a, named, tokens[0], q_block=64)
    low = R.forward(a, named, tokens[0], quant=True, q_block=64)
    d = float(jnp.sqrt(jnp.mean(jnp.square(low - ref))))
    assert 1e-3 * float(jnp.std(ref)) < d < 0.2 * float(jnp.std(ref))


def test_a3c_loss_and_gradients_match_the_program():
    from repro.core import llm_a3c
    from repro.data.pipeline import TokenPipeline
    cfgj, cfg, named, params, _ = _setup("stablelm-1.6b-a3c-train")
    batch = TokenPipeline(vocab=cfg.vocab_size, seq_len=128,
                          global_batch=2).batch(jax.random.key(3), 0)
    (loss, _), grads = jax.value_and_grad(
        lambda p: llm_a3c.a3c_token_loss(cfg, p, batch), has_aux=True)(
        params)
    a, h = R.Arch.of(cfgj), RA.Hyper.of(cfgj["training"])
    P = dict(named)
    G = {k: jnp.zeros_like(v) for k, v in P.items()}
    ref_loss, norms = RA.Trainer(a, h).step(P, G, batch, 0, [0, 1])
    assert ref_loss == pytest.approx(float(loss), rel=1e-5)
    _, _, names = weights.layout(cfg)
    for name, g in zip(names, jax.tree_util.tree_leaves(grads)):
        if name.startswith("layers."):
            for i in range(g.shape[0]):
                assert norms[f"{name}[{i}]"] == pytest.approx(
                    float(jnp.linalg.norm(g[i])), rel=1e-4, abs=1e-7), name
        else:
            assert norms[name] == pytest.approx(
                float(jnp.linalg.norm(g)), rel=1e-4, abs=1e-7), name


def test_returns_are_the_longest_n_step_returns():
    r = jnp.asarray([1.0, 0.0, 2.0])
    d = jnp.asarray([0.5, 0.5, 0.0])
    got = RA.returns(r, d, jnp.asarray(10.0))
    # R2 = 2 + 0 * 10, R1 = 0 + 0.5 * 2, R0 = 1 + 0.5 * 1
    np.testing.assert_allclose(np.asarray(got), [1.5, 1.0, 2.0])
