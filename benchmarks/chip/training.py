"""The training cell: the window drives ``llm_a3c.make_train_step``.

Set-up builds one object, the step jitted and donated as ``train.run_llm``
builds it, with f32 master weights made from the seed and the shared-RMSProp
state, and drives it through its first step on a batch of the program's
``TokenPipeline`` made on the device.  That step is the one the reference
follows.  The same object then runs steps back to back for the window, one
loss read per step.

What is compared with the reference, all of the first step:

* its loss;
* each leaf's gradient as the optimizer got it, read from its state after
  the step (``|grad| = sqrt(g / (1 - alpha))`` from ``g = 0``): the norm of
  the whole leaf, and the elements of a sample drawn from the seed;
* each leaf's change, the parameters step two receives: its norm, and the
  same sample's elements.

A stacked leaf counts layer by layer.  Norms of whole leaves average
rounding away, so a step computed in int8 reads within 4x of a sound one
there; element by element it does not (PERF.md).  Later steps are not
compared: the first RMSProp step from ``g = 0`` moves every weight whose
gradient is not tiny by about ``10 lr`` whatever the gradient's size, so
the loss swings by hundreds between steps and the rounding of bf16 against
f32 reaches the second and third steps' losses and changes as noise of
1-60% (PERF.md).
"""
from __future__ import annotations

import dataclasses
import gc
import time
import zlib
from typing import Dict, List, Optional

import numpy as np

from . import common, weights

CHECK_STEPS = 1
# elements of each leaf (a stacked leaf's layer) compared one by one
SAMPLE = 1 << 16


def sample_of(seed32: int, key: str, x) -> np.ndarray:
    """The elements of ``x`` at indices drawn from the seed and ``key``
    (all of them in a leaf no larger than the sample), in f32."""
    import jax.numpy as jnp
    n = int(np.prod(x.shape))
    if n <= SAMPLE:
        idx = np.arange(n)
    else:
        rng = np.random.default_rng([seed32, zlib.crc32(key.encode())])
        idx = rng.integers(0, n, SAMPLE)
    flat = jnp.reshape(x, (-1,))
    return np.asarray(flat[jnp.asarray(idx)], np.float32)


def samples(seed32: int, name: str, x) -> Dict[str, np.ndarray]:
    """``sample_of`` each leaf, a stacked leaf per layer."""
    if name.startswith("layers."):
        return {f"{name}[{i}]": sample_of(seed32, f"{name}[{i}]", x[i])
                for i in range(x.shape[0])}
    return {name: sample_of(seed32, name, x)}


def change_samples(seed32: int, name: str, now, then):
    a, b = samples(seed32, name, now), samples(seed32, name, then)
    return {k: a[k] - b[k] for k in a}


def _sums(names: List[str], leaves) -> Dict[str, float]:
    """Sum of each leaf, a stacked leaf per layer."""
    import jax.numpy as jnp
    out = {}
    for name, x in zip(names, leaves):
        if name.startswith("layers."):
            v = np.asarray(jnp.sum(x, axis=tuple(range(1, x.ndim))))
            out.update({f"{name}[{i}]": float(e) for i, e in enumerate(v)})
        else:
            out[name] = float(jnp.sum(x))
    return out


@dataclasses.dataclass
class Cell:
    cfgj: dict
    traffic: dict
    cfg: object
    step_fn: object
    batch_fn: object
    data_key: object
    params: object
    opt_state: object
    seed32: int
    got: dict                  # the first step's readings, as compare takes
    batches: list
    check_s: float             # time of the readings taken in set-up
    step: int = CHECK_STEPS


def setup(cfgj: dict, tj: dict, seed: int) -> Cell:
    import jax
    import jax.numpy as jnp
    from repro.core import llm_a3c
    from repro.data.pipeline import TokenPipeline
    from repro.optim import optimizers as opt_mod

    from .reference import a3c as RA
    t = cfgj["training"]
    cfg = common.model_config(cfgj)
    key = common.seed32(seed)
    shapes, _, names = weights.layout(cfg)
    _, params = weights.make(cfg, key, serve=False)
    opt = opt_mod.OPTIMIZERS[t["optimizer"]](alpha=t["rmsprop_alpha"],
                                              eps=t["rmsprop_eps"])
    opt_state = opt.init(params)
    step_fn = jax.jit(llm_a3c.make_train_step(
        cfg, opt, gamma=t["gamma"], beta=t["entropy_beta"], lr0=t["lr"],
        total_steps=t["total_steps"]), donate_argnums=(0, 1))
    pipe = TokenPipeline(vocab=cfg.vocab_size, seq_len=tj["seq"],
                         global_batch=tj["batch"], gamma=t["gamma"])
    batch_fn = jax.jit(pipe.batch)
    data_key = jax.random.fold_in(jax.random.key(key), 1)

    losses, batches = [], []
    got = {"grad_norms": {}, "grad_samples": {}}
    check_s = 0.0
    for i in range(CHECK_STEPS):
        batch = batch_fn(data_key, i)
        batches.append(batch)
        params, opt_state, m = step_fn(params, opt_state, batch,
                                       jnp.asarray(i, jnp.int32))
        losses.append(float(m["loss"]))
        if i == 0:
            t0 = time.perf_counter()
            # g = (1 - alpha) grad^2 after one step from g = 0
            scale = 1.0 / (1 - t["rmsprop_alpha"])
            gl = jax.tree_util.tree_leaves(opt_state["g"])
            got["grad_norms"] = {k: (v * scale) ** 0.5
                                 for k, v in _sums(names, gl).items()}
            for name, g in zip(names, gl):
                got["grad_samples"].update(
                    {k: np.sqrt(v * scale)
                     for k, v in samples(key, name, g).items()})
            check_s += time.perf_counter() - t0
    # the change, before the next step takes the parameters
    t0 = time.perf_counter()
    got["delta_norms"], got["delta_samples"] = {}, {}
    for name, now in zip(names, jax.tree_util.tree_leaves(params)):
        then = weights.leaf(shapes, name, key, serve=False)
        got["delta_norms"].update(RA.change_norms(name, now, then))
        got["delta_samples"].update(change_samples(key, name, now, then))
    got["losses"] = losses
    check_s += time.perf_counter() - t0
    return Cell(cfgj, tj, cfg, step_fn, batch_fn, data_key, params,
                opt_state, key, got, batches, check_s)


# the traced stretch: from this share of the window, for at most so long
TRACE_FROM, TRACE_SECONDS = 0.25, 5.0


def run_window(cell: Cell, seconds: float, trace_dir: Optional[str] = None):
    """Steps back to back until ``seconds`` have passed; one loss read per
    step.  Returns (steps, elapsed, stretch) where ``stretch`` is
    (t0, t1, steps) of the traced part or None.  The profiler runs from
    before the window opens until after it closes, so that neither its
    start nor its stop stalls a step; the ``bench.*`` spans mark the
    stretch it is read over."""
    import contextlib

    import jax
    import jax.numpy as jnp

    def span(name):
        if not tracing:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench." + name)

    trace_at = TRACE_FROM * seconds
    trace_to = trace_at + min(TRACE_SECONDS, 0.5 * seconds)
    stretch, tracing = None, False
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    steps = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_dir is not None and stretch is None and now >= trace_at:
            stretch, tracing = [time.perf_counter() - t0, None, steps], True
        elif tracing and now >= trace_to:
            stretch[1], stretch[2] = time.perf_counter() - t0, \
                steps - stretch[2]
            tracing = False
        if now >= seconds:
            break
        with span("batch"):
            batch = cell.batch_fn(cell.data_key, cell.step)
        with span("train_step"):
            cell.params, cell.opt_state, m = cell.step_fn(
                cell.params, cell.opt_state, batch,
                jnp.asarray(cell.step, jnp.int32))
            loss = float(m["loss"])
        if not np.isfinite(loss):
            raise FloatingPointError(f"step {cell.step}: loss {loss}")
        cell.step += 1
        steps += 1
    elapsed = time.perf_counter() - t0
    if tracing:
        stretch[1], stretch[2] = elapsed, steps - stretch[2]
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return steps, elapsed, stretch


def free_program(cell: Cell) -> None:
    cell.params = cell.opt_state = None
    cell.step_fn = None
    gc.collect()


def reference_run(cell: Cell, *, quant: bool = False,
                  rows: Optional[List[int]] = None) -> dict:
    """The reference's steps from the same weights and batches: losses,
    the first step's gradient norms and samples, and the change over the
    steps, in the form ``compare`` takes."""
    import jax

    from .reference import a3c as RA
    from .reference import model as R
    a, h = R.Arch.of(cell.cfgj), RA.Hyper.of(cell.cfgj["training"])
    named, _ = weights.make(cell.cfg, cell.seed32, serve=False)
    P = dict(named)
    del named
    G = {k: jax.numpy.zeros_like(v) for k, v in P.items()}
    tr = RA.Trainer(a, h, quant=quant)
    rows = list(range(cell.traffic["batch"])) if rows is None else rows
    losses, grad_norms, grad_samples = [], {}, {}

    def see(key, g):
        grad_samples[key] = np.abs(sample_of(cell.seed32, key, g))

    for i, batch in enumerate(cell.batches):
        loss, norms = tr.step(P, G, batch, i, rows,
                              sample=see if i == 0 else None)
        losses.append(loss)
        if i == 0:
            grad_norms = norms
    del G
    gc.collect()
    shapes = weights.layout(cell.cfg)[0]
    delta, delta_samples = {}, {}
    for name in sorted(P):
        then = weights.leaf(shapes, name, cell.seed32, serve=False)
        delta.update(RA.change_norms(name, P[name], then))
        delta_samples.update(change_samples(cell.seed32, name, P[name],
                                            then))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "grad_samples": grad_samples,
            "delta_samples": delta_samples}


def compare(got: dict, ref: dict) -> dict:
    """The five numbers ``correct`` holds to limits.

    * ``loss_rel_gap``: ``|loss - ref| / |ref|``, the largest over steps;
    * ``grad_norm_rel_gap`` and ``delta_norm_rel_gap``: over leaves, the
      largest gap between the two norms of a leaf, against the larger of
      the reference's norm of that leaf and of the median leaf;
    * ``grad_elem_err`` and ``delta_elem_err``: over leaves, the largest
      root-mean-square difference of the sampled elements, against the
      larger of the reference's root-mean-square of that leaf's sample and
      of the median leaf's.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change.
    """
    lg = max(abs(a - b) / max(abs(b), 1e-30)
             for a, b in zip(got["losses"], ref["losses"]))

    def worst(a: Dict[str, float], b: Dict[str, float], keep):
        med = float(np.median([b[k] for k in keep]))
        gaps = {k: abs(a[k] - b[k]) / max(b[k], med, 1e-30) for k in keep}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    rg = ref["grad_norms"]
    gmed = float(np.median(list(rg.values())))
    moving = [k for k in rg if rg[k] >= 1e-3 * gmed]

    def rms(x):
        return float(np.sqrt(np.mean(np.square(x))))

    def worst_elem(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray],
                   keep):
        scale = {k: rms(b[k]) for k in keep}
        med = float(np.median(list(scale.values())))
        errs = {k: rms(a[k] - b[k]) / max(scale[k], med, 1e-30)
                for k in keep}
        k = max(errs, key=errs.get)
        return errs[k], k

    g, gk = worst(got["grad_norms"], rg, list(rg))
    d, dk = worst(got["delta_norms"], ref["delta_norms"], moving)
    ge, gek = worst_elem(got["grad_samples"], ref["grad_samples"], list(rg))
    de, dek = worst_elem(got["delta_samples"], ref["delta_samples"], moving)
    return {"loss_rel_gap": lg, "grad_norm_rel_gap": g,
            "delta_norm_rel_gap": d, "grad_elem_err": ge,
            "delta_elem_err": de, "worst_grad_leaf": gk,
            "worst_delta_leaf": dk, "worst_grad_elem_leaf": gek,
            "worst_delta_elem_leaf": dek,
            "leaves_still": len(rg) - len(moving)}
