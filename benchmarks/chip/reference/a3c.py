"""Plain float32 reference of the A3C token-loss training step with the
shared-RMSProp update (Mnih et al. 2016, Alg. 3 and Eq. 8-9).

State s_t is the token prefix, the action the next token, the policy the LM
head's softmax and the critic the value head.  Each position's return is the
longest n-step return to the end of the sequence, bootstrapped from the
last position's value (no gradient through the bootstrap); the last
position takes no action.  The loss is the mean over the positions that act
of ``-log pi(a|s) * A - beta * H(pi) + value_coef * (R - V)^2``, with the
advantage ``A = R - V`` held constant.  RMSProp keeps one accumulator per
parameter, ``g = alpha g + (1 - alpha) grad^2``, and steps
``theta -= lr grad / sqrt(g + eps)``, with ``lr`` annealed linearly to zero
over ``total_steps``.

It runs layer by layer so that it fits one chip beside nothing else: the
forward pass keeps only each layer's input, the backward pass takes one
layer's vector-Jacobian product at a time, and each layer is updated as soon
as its gradient is known.  The LM head and the loss run one sequence at a
time.  So it holds the parameters and the accumulator, never a whole
gradient.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import model as R


@dataclasses.dataclass(frozen=True)
class Hyper:
    lr: float
    total_steps: int
    alpha: float
    eps: float
    gamma: float
    beta: float
    value_coef: float

    @classmethod
    def of(cls, t: dict) -> "Hyper":
        return cls(lr=t["lr"], total_steps=t["total_steps"],
                   alpha=t["rmsprop_alpha"], eps=t["rmsprop_eps"],
                   gamma=t["gamma"], beta=t["entropy_beta"],
                   value_coef=t["value_coef"])

    def lr_at(self, step: int) -> float:
        return self.lr * min(max(1.0 - step / self.total_steps, 0.0), 1.0)


def returns(rewards, discounts, bootstrap):
    """R_t = r_t + d_t R_{t+1}, R_S = bootstrap; (S,) each."""
    def body(carry, x):
        r, d = x
        carry = r + d * carry
        return carry, carry
    _, rets = jax.lax.scan(body, bootstrap, (rewards, discounts),
                           reverse=True)
    return rets


def row_loss(a: R.Arch, h: Hyper, head_p, x, tokens, rewards, discounts,
             n_act, quant: bool):
    """One sequence's share of the batch loss (its sum over acting
    positions, divided by the batch's count of them)."""
    logits, values = R.head(a, head_p, x, quant=quant)
    actions = jnp.roll(tokens, -1)
    rets = returns(rewards, discounts, jax.lax.stop_gradient(values[-1]))
    act = jnp.ones_like(rewards).at[-1].set(0.0)
    adv = jax.lax.stop_gradient(rets - values)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[:, None], -1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, -1)
    total = (-jnp.sum(logp_a * adv * act)
             + h.value_coef * jnp.sum((rets - values) ** 2 * act)
             - h.beta * jnp.sum(ent * act))
    return total / n_act


class Trainer:
    """Runs reference steps on a dict of named f32 arrays in place."""

    def __init__(self, a: R.Arch, h: Hyper, *, quant: bool = False):
        self.a, self.h, self.quant = a, h, quant

        def fwd(lp, x):
            pos = jnp.arange(x.shape[1])
            return jax.vmap(lambda xi: R.block(a, lp, xi, pos, quant=quant))(
                x)

        self._fwd = jax.jit(fwd)

        def bwd(lp, x, dx):
            _, vjp = jax.vjp(fwd, lp, x)
            return vjp(dx)

        self._bwd = jax.jit(bwd)
        self._row = jax.jit(jax.value_and_grad(
            lambda hp, x, t, r, d, n: row_loss(a, h, hp, x, t, r, d, n,
                                               quant), argnums=(0, 1)))

        def upd(p, g, grad, lr):
            g = h.alpha * g + (1 - h.alpha) * jnp.square(grad)
            return p - lr * grad / jnp.sqrt(g + h.eps), g

        self._upd = jax.jit(upd, donate_argnums=(0, 1))

        def upd_at(p, g, grad, i, lr):
            pi, gi = upd(p[i], g[i], grad, lr)
            return p.at[i].set(pi), g.at[i].set(gi)

        self._upd_at = jax.jit(upd_at, donate_argnums=(0, 1))

        def embed_grad(table, tokens, dx):
            return jnp.zeros(table.shape, jnp.float32).at[tokens].add(dx)

        self._embed_grad = jax.jit(embed_grad)
        self._sumsq = jax.jit(lambda x: jnp.sum(jnp.square(x)))

    def step(self, P: Dict[str, jax.Array], G: Dict[str, jax.Array],
             batch: dict, step: int, rows: List[int],
             sample: Optional[Callable[[str, jax.Array], None]] = None):
        """One step on ``rows`` of ``batch``; updates P and G in place and
        returns (loss, {leaf: gradient norm}), a stacked leaf's layers
        named ``name[i]``.  ``sample(name, gradient)``, if given, sees each
        leaf's gradient under the same names."""
        see = sample or (lambda name, g: None)
        a, lr = self.a, self.h.lr_at(step)
        idx = np.asarray(rows)
        tokens = batch["tokens"][idx]
        n_act = float(len(rows) * (tokens.shape[1] - 1))
        x = R.embed(P, tokens, self.quant)
        xs = [x]
        for i in range(a.n_layers):
            xs.append(self._fwd(self._layer(P, i), xs[-1]))
        head_keys = [k for k in P if not k.startswith("layers.")
                     and k != "embed.table"]
        hp = {k: P[k] for k in head_keys}
        loss, ghead, dxs = 0.0, None, []
        for r, row in enumerate(idx):
            val, (gh, dx) = self._row(hp, xs[-1][r], tokens[r],
                                      batch["rewards"][row],
                                      batch["discounts"][row], n_act)
            loss += float(val)
            ghead = gh if ghead is None else jax.tree.map(jnp.add, ghead, gh)
            dxs.append(dx)
        del hp
        norms = {}
        for k in head_keys:
            norms[k] = float(self._sumsq(ghead[k])) ** 0.5
            see(k, ghead[k])
            P[k], G[k] = self._upd(P[k], G[k], ghead[k], lr)
        del ghead
        dx = jnp.stack(dxs)
        for i in reversed(range(a.n_layers)):
            glp, dx = self._bwd(self._layer(P, i), xs[i], dx)
            for k, gk in glp.items():
                name = "layers." + k
                norms[f"{name}[{i}]"] = float(self._sumsq(gk)) ** 0.5
                see(f"{name}[{i}]", gk)
                P[name], G[name] = self._upd_at(P[name], G[name], gk, i, lr)
        ge = self._embed_grad(P["embed.table"], tokens, dx)
        norms["embed.table"] = float(self._sumsq(ge)) ** 0.5
        see("embed.table", ge)
        P["embed.table"], G["embed.table"] = self._upd(
            P["embed.table"], G["embed.table"], ge, lr)
        return loss, norms

    @staticmethod
    def _layer(P, i):
        return {k: v[i] for k, v in R.layer_params(P).items()}


def change_norms(name: str, now, then) -> Dict[str, float]:
    """Norm of ``now - then`` for one leaf; a stacked leaf per layer."""
    d = now.astype(jnp.float32) - then.astype(jnp.float32)
    if name.startswith("layers."):
        per = np.asarray(jnp.sqrt(jnp.sum(jnp.square(d),
                                          axis=tuple(range(1, d.ndim)))))
        return {f"{name}[{i}]": float(v) for i, v in enumerate(per)}
    return {name: float(jnp.sqrt(jnp.sum(jnp.square(d))))}
