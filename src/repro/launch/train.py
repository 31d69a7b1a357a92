"""End-to-end training driver.

Two modes:
  * ``--mode rl``  — the paper's experiments: asynchronous actor-learners
    (T1 Hogwild simulation or T2 sync) with one of the four algorithms on a
    vectorized JAX environment, paper networks (repro.models.atari).
  * ``--mode llm`` — the assigned-architecture path: A3C token-level RL on
    a (reduced or full) backbone with the synthetic TokenMDP pipeline, data-
    parallel over local devices (or the dry-run mesh via launch/dryrun.py).

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode rl --env catch \
      --algo a3c --workers 8 --frames 200000
  PYTHONPATH=src python -m repro.launch.train --mode llm --arch stablelm-1.6b \
      --reduced --steps 200 --seq 128 --batch 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def run_rl(args) -> dict:
    from repro.core import agents, async_runner
    from repro.envs import make
    from repro.envs.api import flatten_obs
    from repro.models import atari as nets

    env = make(args.env)
    if len(env.obs_shape) > 1:
        env = flatten_obs(env)
    algo = agents.ALGORITHMS[args.algo](
        **({"continuous": True} if env.continuous else {}))
    key = jax.random.key(args.seed)
    params = nets.init_mlp_agent_params(
        key, env.obs_shape[0], env.n_actions, hidden=args.hidden,
        continuous=env.continuous)
    cfg = async_runner.RunnerConfig(
        n_workers=args.workers, t_max=args.t_max, lr0=args.lr,
        total_frames=args.frames, mode=args.runner_mode,
        optimizer=args.optimizer, shared_stats=not args.per_worker_stats,
        target_interval=args.target_interval)
    init_state, round_fn = async_runner.make_runner(algo, env, params, cfg)
    st = init_state(jax.random.key(args.seed + 1))
    history = []
    t0 = time.time()
    rounds = args.frames // (cfg.n_workers * cfg.t_max)
    for i in range(rounds):
        st, m = round_fn(st)
        if i % max(1, rounds // 20) == 0 or i == rounds - 1:
            rec = {"round": i, "frames": int(st["frames"]),
                   "ep_ret": float(m["ep_ret"]), "loss": float(m["loss"]),
                   "wall_s": round(time.time() - t0, 1)}
            history.append(rec)
            print(json.dumps(rec), flush=True)
    if args.checkpoint:
        from repro import checkpoint
        checkpoint.save(args.checkpoint, st["params"])
        print(f"saved params to {args.checkpoint}")
    return {"history": history, "final_ep_ret": history[-1]["ep_ret"]}


def run_llm(args) -> dict:
    import contextlib

    from repro.configs import get_config
    from repro.core import llm_a3c
    from repro.data.pipeline import TokenPipeline
    from repro.distributed import ctx
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as M
    from repro.optim import optimizers as opt_mod

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.key(args.seed)
    params = M.init_params(cfg, key)
    opt = opt_mod.OPTIMIZERS[args.optimizer]()
    opt_state = opt.init(params)
    pipe = TokenPipeline(vocab=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch)
    # multi-device host: install a data-parallel dispatch mesh so the
    # kernel dispatch layer shard_maps the Pallas kernels over the batch
    # (backend choice itself is automatic — keyed off the mesh platform)
    n_dev = jax.local_device_count()
    mesh_ctx = contextlib.nullcontext()
    if n_dev > 1 and args.batch % n_dev == 0:
        mesh_ctx = ctx.use_mesh(make_debug_mesh(data=n_dev, model=1))
    # params and optimizer state are rebound every step: donating them lets
    # the update write in place instead of holding two copies on device
    train_step = jax.jit(llm_a3c.make_train_step(
        cfg, opt, lr0=args.lr, total_steps=args.steps), donate_argnums=(0, 1))
    history = []
    t0 = time.time()
    # dispatch resolves at trace time, so the mesh stays installed for the
    # whole loop (first call traces)
    with mesh_ctx:
        for step in range(args.steps):
            batch = pipe.batch(jax.random.key(args.seed + 2), step)
            params, opt_state, metrics = train_step(
                params, opt_state, batch, jnp.asarray(step))
            if step % max(1, args.steps // 20) == 0 \
                    or step == args.steps - 1:
                rec = {"step": step,
                       "loss": float(metrics["loss"]),
                       "mean_return": float(metrics["mean_return"]),
                       "entropy": float(metrics["entropy"]),
                       "wall_s": round(time.time() - t0, 1)}
                history.append(rec)
                print(json.dumps(rec), flush=True)
    if args.checkpoint:
        from repro import checkpoint
        checkpoint.save(args.checkpoint, params)
        print(f"saved params to {args.checkpoint}")
    return {"history": history}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["rl", "llm"], default="rl")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--optimizer", default="shared_rmsprop",
                    choices=["shared_rmsprop", "rmsprop", "momentum_sgd"])
    ap.add_argument("--lr", type=float, default=7e-3)
    # rl
    ap.add_argument("--env", default="catch")
    ap.add_argument("--algo", default="a3c",
                    choices=["a3c", "one_step_q", "one_step_sarsa",
                             "n_step_q"])
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--t-max", type=int, default=5)
    ap.add_argument("--frames", type=int, default=100_000)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--runner-mode", default="hogwild",
                    choices=["hogwild", "sync"])
    ap.add_argument("--per-worker-stats", action="store_true")
    ap.add_argument("--target-interval", type=int, default=2_000)
    # llm
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    if args.mode == "rl":
        run_rl(args)
    else:
        run_llm(args)


if __name__ == "__main__":
    main()
