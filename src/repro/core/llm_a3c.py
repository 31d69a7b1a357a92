"""A3C at LLM scale: the paper's Alg. 3 loss applied to a token-level MDP.

State s_t = token prefix, action a_t = tokens[t+1], policy = LM head softmax,
critic = value head.  The n-step return recursion runs over the sequence
axis — every position gets the "longest possible" forward-view return exactly
as in the paper, with the final position's value as bootstrap.

This is the ``train_step`` that the multi-pod dry-run lowers for every
assigned architecture: the actor-learner groups live on the ``data`` mesh
axis, tensor parallelism on ``model``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.returns import n_step_returns
from repro.models import model as M
from repro.models.config import ModelConfig


def a3c_token_loss(cfg: ModelConfig, params, batch: Dict[str, Any], *,
                   gamma: float = 0.99, beta: float = 0.01,
                   value_coef: float = 0.5):
    """batch: tokens (B,S) [or embeds/enc_frames per family], rewards (B,S),
    discounts (B,S) = gamma * (1 - done).  Position t's reward is for the
    transition prefix[:t] --tokens[t+1]--> prefix[:t+1]."""
    out = M.forward(cfg, params, batch)
    logits = out["logits"].astype(jnp.float32)        # (B, S, V)
    values = out["value"]                             # (B, S)

    if "actions" in batch:
        actions = batch["actions"]                    # (B, S) explicit
    else:
        actions = jnp.roll(batch["tokens"], -1, axis=1)
    rewards = batch["rewards"]
    discounts = batch["discounts"]

    # returns over the sequence axis (time-major for the scan)
    bootstrap = jax.lax.stop_gradient(values[:, -1])
    rets = n_step_returns(jnp.moveaxis(rewards, 1, 0),
                          jnp.moveaxis(discounts, 1, 0),
                          bootstrap)
    rets = jnp.moveaxis(rets, 0, 1)                   # (B, S)

    valid = jnp.ones_like(rewards).at[:, -1].set(0.0)  # last pos: no action
    nvalid = jnp.maximum(valid.sum(), 1.0)
    adv = jax.lax.stop_gradient(rets - values)

    logp_all = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp_all, actions[..., None], -1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)

    pol_loss = -jnp.sum(logp_a * adv * valid) / nvalid
    v_loss = value_coef * jnp.sum((rets - values) ** 2 * valid) / nvalid
    ent_loss = -beta * jnp.sum(entropy * valid) / nvalid
    aux = cfg.aux_loss_weight * out.get("aux_loss", 0.0)
    loss = pol_loss + v_loss + ent_loss + aux
    metrics = {"loss": loss, "pol": pol_loss, "value": v_loss,
               "entropy": -ent_loss / max(beta, 1e-9), "aux": aux,
               "mean_return": jnp.sum(rets * valid) / nvalid}
    return loss, metrics


def make_train_step(cfg: ModelConfig, opt, *, gamma: float = 0.99,
                    beta: float = 0.01, lr0: float = 7e-4,
                    total_steps: int = 100_000):
    """Synchronous (T2) data-parallel train step — the A2C limit of A3C.
    Under pjit the cross-group gradient reduction is the all-reduce the
    compiler inserts for the data axis."""
    from repro.optim import optimizers as opt_mod
    from repro.optim import schedules

    def train_step(params, opt_state, batch, step):
        lr = schedules.linear_anneal(lr0, step.astype(jnp.float32),
                                     float(total_steps))
        grads, metrics = jax.grad(
            lambda p: a3c_token_loss(cfg, p, batch, gamma=gamma,
                                     beta=beta),
            has_aux=True)(params)
        # one scope over the update and its application: XLA fuses the two,
        # and a fusion carries its root's (the application's) scope
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, lr)
            params = opt_mod.apply_updates(params, updates)
        return params, opt_state, metrics

    return train_step


def _stream_keys(key, sids, pos, b):
    """One PRNG key per batch row, derived from (stream id, logical
    position) — NOT from the engine step count, so a speculative run
    that commits 3 tokens in one step and a plain decode that takes 3
    steps draw identical streams for identical tokens."""
    sids = jnp.broadcast_to(jnp.asarray(sids), (b,)).astype(jnp.uint32)
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,)).astype(jnp.uint32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, sids)
    return jax.vmap(jax.random.fold_in)(keys, pos)


def sample_slot_tokens(logits, key, *, sample: bool = True, sids=None,
                       pos=None):
    """Per-slot sampling: logits (B, V), one threaded PRNG key.

    With ``sids``/``pos`` (the serve engine's path) each row draws from
    the ``fold_in(fold_in(key, sids[j]), pos[j])`` stream — keyed by the
    request's identity and the *logical position of the sampled token*,
    so streams are invariant to batching, slot assignment, preemption
    and speculation (a token is the same draw no matter how many verify
    tokens committed alongside it).  Legacy callers omit both and get
    the per-slot-index fold (caller folds the step index into ``key``).
    """
    if not sample:
        return jnp.argmax(logits, axis=-1)
    b = logits.shape[0]
    if sids is None:
        keys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(b))
    else:
        keys = _stream_keys(key, sids, pos, b)
    return jax.vmap(jax.random.categorical)(keys, logits)


def make_serve_step(cfg: ModelConfig, *, sample: bool = True):
    """One-token decode step for the actor/serving path (decode shapes).
    Returns (token (B,), value (B,), cache).

    ``pos`` is a lockstep scalar or per-slot (B,) (continuous batching);
    ``key`` is a *threaded* jax PRNG key.  Engine callers pass ``sids``
    (per-slot stream ids, e.g. request ids): the generated token at
    logical position pos + 1 then draws from the (sid, pos + 1) stream —
    invariant to speculation and scheduling.  Legacy callers omit
    ``sids`` and fold the step index into ``key`` themselves."""

    def serve_step(params, cache, batch, pos, key, sids=None):
        out, cache = M.decode_step(cfg, params, cache, batch, pos)
        logits = out["logits"][:, -1].astype(jnp.float32)
        if sids is None:
            token = sample_slot_tokens(logits, key, sample=sample)
        else:
            token = sample_slot_tokens(logits, key, sample=sample,
                                       sids=sids, pos=pos + 1)
        value = out["value"][:, -1] if "value" in out else \
            jnp.zeros(logits.shape[0])
        return token, value, cache

    return serve_step


def make_verify_step(cfg: ModelConfig, shift: int, *, sample: bool = True):
    """Fused speculative round for the serve engine: ONE jitted call
    scores a (B, K) batch of per-slot draft chunks (row j's current
    token + drafts at positions pos[j] + i), decides acceptance, and
    commits exactly the accepted rows' KV — a single launch per round
    (a separate host-decided commit launch doubled per-round dispatch
    overhead, which is most of what speculation amortises).

    Returns ``verify_step(params, cache, batch, pos, key, sids, k_eff,
    remaining) -> (targets (B, K) int32, n_acc (B,) int32, cache)``:
    ``targets[j, i]`` is the token the target model emits after
    consuming position pos[j] + i — greedy argmax, or a draw from the
    (sid, pos + i + 1) stream, the *same* derivation ``make_serve_step``
    uses, so accepted sampled tokens are bit-identical to
    non-speculative decode.  The accept rule is the longest draft
    prefix matching the targets (within row j's effective k) plus the
    bonus target token, clamped to ``remaining[j]`` (the request's
    unused generation budget; 0 marks an idle row, which accepts and
    commits nothing).  ``shift`` is the engine's logical cache length
    (static re-basing bound).

    Verify itself writes nothing — ``M.verify_step`` returns the chunk
    K/V as ``pendings`` and ``M.commit_step`` scatters rows i <
    n_acc[j] in the same launch, so KV rollback on rejection stays a
    no-op by construction, and every output the host reads is forced
    together (no partially-dispatched cache state outlives the
    round)."""

    def verify_step(params, cache, batch, pos, key, sids, k_eff,
                    remaining):
        out, pendings = M.verify_step(cfg, params, cache, batch, pos,
                                      shift)
        logits = out["logits"].astype(jnp.float32)      # (B, K, V)
        b, kq, _ = logits.shape
        if not sample:
            targets = jnp.argmax(logits, axis=-1)
        else:
            tpos = pos[:, None] + 1 + jnp.arange(kq)[None]   # (B, K)
            skeys = jax.vmap(jax.random.fold_in, (None, 0))(
                key, jnp.broadcast_to(jnp.asarray(sids), (b,))
                .astype(jnp.uint32))
            pkeys = jax.vmap(jax.vmap(jax.random.fold_in, (None, 0)))(
                skeys, tpos.astype(jnp.uint32))
            targets = jax.vmap(jax.vmap(jax.random.categorical))(pkeys,
                                                                 logits)
        targets = targets.astype(jnp.int32)
        # accept: a_j = leading run of draft/target matches inside row
        # j's effective k (same rule as the old host loop: position i
        # counts iff i < k_eff - 1 and every draft up to i matched)
        match = batch["tokens"][:, 1:] == targets[:, :-1]    # (B, K-1)
        in_k = jnp.arange(kq - 1)[None, :] < (k_eff[:, None] - 1)
        run = jnp.cumprod((match & in_k).astype(jnp.int32), axis=1)
        n_acc = jnp.minimum(run.sum(axis=1) + 1,
                            remaining).astype(jnp.int32)
        cache = M.commit_step(cfg, cache, pendings, pos, n_acc)
        return targets, n_acc, cache

    return verify_step


def make_prefill_step(cfg: ModelConfig):
    """Chunked flash prefill for the serve engine: one jitted call runs a
    whole (B, C) prompt chunk through the flash forward path and writes the
    KV caches in blocks — replacing C single-token ``serve_step`` launches.
    Returns None when the architecture's caches can't be block-written
    (SSM / xLSTM / enc-dec); callers fall back to the decode loop.

    The returned fn is ``prefill_step(params, cache, batch, pos0,
    true_len) -> (logits (B, C, V), cache)`` with ``pos0`` static (one
    trace per chunk offset).

    Ring (sliding-window) architectures chunk-prefill too: ``true_len``
    (B,) carries each row's real prompt length and the ring cache write
    masks rows past it, so right-padded admission chunks can no longer
    alias ring rows that the decode-side kpos attributes to real earlier
    positions (the gate PR 4 had to place here).  Exact-chunk callers may
    leave ``true_len`` None."""
    if not M.supports_chunked_prefill(cfg):
        return None

    @functools.partial(jax.jit, static_argnames=("pos0",))
    def prefill_step(params, cache, batch, pos0=0, true_len=None):
        out, cache = M.prefill_step(cfg, params, cache, batch, pos0,
                                    true_len)
        return out["logits"].astype(jnp.float32), cache

    return prefill_step
