"""Random weights made by the benchmark, on the device, from the seed.

The leaves are named by their path in the program's parameter tree with the
per-cycle tuple index dropped, e.g. ``layers.attn.wq.w`` (stacked over
layers, ``(L, d, Hq*hd)``) or ``lm_head.w``.  The program gets the tree
built from these very arrays; the reference reads the same arrays by name.
Neither side makes the other's weights.

Each leaf is drawn from ``fold_in(key, i)`` for its index ``i`` in the sorted
names, so one leaf can be drawn again alone (``leaf``), which the training
check uses to take the change of each leaf after three steps without
holding a second copy of the model.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.tree_util import DictKey, SequenceKey


def _name(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, DictKey):
            parts.append(str(p.key))
        elif isinstance(p, SequenceKey):
            if parts != ["layers"] or p.idx != 0:
                raise ValueError(f"unsupported parameter path {path}: only "
                                 "one block kind per cycle is handled")
        else:
            raise ValueError(f"unsupported parameter path {path}")
    return ".".join(parts)


def layout(cfg) -> Tuple[Dict[str, jax.ShapeDtypeStruct], object, list]:
    """(name -> shape, the program's treedef, names in tree order)."""
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_name(p) for p, _ in flat]
    return dict(zip(names, (s for _, s in flat))), treedef, names


def _draw(name: str, shape, key, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(".scale"):
        x = 1.0 + 0.1 * z
    elif name.endswith(".bias") or name.endswith(".b"):
        x = 0.02 * z
    elif name == "embed.table":
        x = z
    else:                       # a matrix: unit-variance outputs
        x = z / math.sqrt(shape[-2])
    return x.astype(dtype)


def _dtype(shape, serve: bool):
    # the program serves matrices in bf16 and keeps vectors in f32
    # (models.model.cast_params); it trains from f32 masters
    return jnp.bfloat16 if serve and len(shape) >= 2 else jnp.float32


def leaf(shapes: Dict[str, jax.ShapeDtypeStruct], name: str, seed32: int,
         *, serve: bool) -> jax.Array:
    """One named leaf, drawn exactly as ``make`` draws it; ``shapes`` is
    ``layout(cfg)[0]``."""
    order = sorted(shapes)
    shape = shapes[name].shape
    key = jax.random.fold_in(jax.random.key(seed32), order.index(name))
    return jax.jit(lambda k: _draw(name, shape, k, _dtype(shape, serve)))(
        key)


def make(cfg, seed32: int, *, serve: bool):
    """All leaves in one jitted call.  Returns (named dict, program tree),
    both holding the same device arrays."""
    shapes, treedef, names = layout(cfg)
    order = sorted(shapes)

    @jax.jit
    def draw(key):
        return {n: _draw(n, shapes[n].shape,
                         jax.random.fold_in(key, order.index(n)),
                         _dtype(shapes[n].shape, serve))
                for n in order}

    named = draw(jax.random.key(seed32))
    tree = jax.tree_util.tree_unflatten(treedef, [named[n] for n in names])
    return named, tree
