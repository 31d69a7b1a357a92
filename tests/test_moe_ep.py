"""Expert-parallel MoE (shard_map all-to-all) vs the dense oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe as moe_mod
from repro.models import moe_ep
from repro.launch.mesh import make_mesh

MESH = make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("top_k,cf", [(1, 1.25), (2, 1.25), (2, 4.0)])
def test_ep_matches_dense_oracle(top_k, cf):
    p = moe_mod.init_moe(jax.random.key(0), 16, 32, 4)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16))
    y_d, lb_d = moe_mod.moe_apply(p, x, top_k=top_k, capacity_factor=cf)
    y_e, lb_e = moe_ep.moe_apply_ep(p, x, top_k=top_k, capacity_factor=cf,
                                    act="silu", mesh=MESH,
                                    dp_axes=("data",))
    np.testing.assert_allclose(y_d, y_e, atol=1e-6)
    np.testing.assert_allclose(lb_d, lb_e, atol=1e-6)


def test_ep_gradients_match():
    p = moe_mod.init_moe(jax.random.key(0), 16, 32, 4)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16))

    g1 = jax.grad(lambda p: moe_mod.moe_apply(
        p, x, top_k=2, capacity_factor=1.25)[0].sum())(p)
    g2 = jax.grad(lambda p: moe_ep.moe_apply_ep(
        p, x, top_k=2, capacity_factor=1.25, act="silu", mesh=MESH,
        dp_axes=("data",))[0].sum())(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_ep_activated_by_rules_in_train_step():
    """The model dispatches to EP when the sharding context provides it."""
    from repro.configs import get_config
    from repro.core import llm_a3c
    from repro.distributed import ctx, sharding
    from repro.models import model as M

    cfg = get_config("granite-moe-1b-a400m").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 2, 16
    batch = {"tokens": jax.random.randint(jax.random.key(1), (b, s), 0,
                                          cfg.vocab_size),
             "rewards": jnp.zeros((b, s)),
             "discounts": jnp.full((b, s), 0.99)}
    plain, _ = llm_a3c.a3c_token_loss(cfg, params, batch)
    rules = sharding.activation_rules(MESH, batch_size=b, cfg=cfg)
    assert "moe_ep" in rules
    with jax.set_mesh(MESH), ctx.sharding_rules(rules):
        ep, _ = llm_a3c.a3c_token_loss(cfg, params, batch)
    np.testing.assert_allclose(float(plain), float(ep), rtol=1e-5)
