"""Expert-parallel MoE dispatch via shard_map + explicit all-to-all.

The pure-jnp ``moe_apply`` (moe.py) expresses dispatch as a dynamic scatter,
which GSPMD cannot shard — it replicates the (T·k, d) dispatch operand on
every device (~1.5 TB/device/step for granite train_4k; EXPERIMENTS.md
§Perf iter #4).  This module is the TPU-native formulation (GShard /
DeepSpeed-MoE pattern):

  per device: route local tokens -> pack per-expert send buffer (E, C, d)
  all_to_all over the `model` axis (experts live there)   <- the real cost
  local grouped expert matmuls on (E_loc, tp*C, d)
  all_to_all back -> local combine with gates

Token shards: batch over the data axes, sequence over `model` (the
sequence-parallel residual layout), so every device routes a distinct token
slice.  Expert weights are sharded over `model` only (E_loc = E / tp per
device, replicated over data — the FSDP saving is tiny next to the
dispatch-traffic saving).

The dense path remains the oracle: with a (1, 1) mesh the two are
numerically identical (tests/test_moe_ep.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as cm


def _local_route(router, xf, *, top_k: int, n_experts: int, cap: int):
    """Route T_loc tokens; build the (E, cap, d) send buffer."""
    t, d = xf.shape
    logits = xf.astype(jnp.float32) @ router                  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    assign1 = jax.nn.one_hot(eidx[:, 0], n_experts, dtype=jnp.float32)
    lb_loss = n_experts * jnp.sum(assign1.mean(0) * probs.mean(0))

    e_flat = eidx.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), top_k)
    onehot = jax.nn.one_hot(e_flat, n_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                              e_flat[:, None], 1)[:, 0]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, 0)

    send = jnp.zeros((n_experts, cap, d), xf.dtype)
    send = send.at[e_flat, pos_c].add(
        xf[tok_flat] * keep[:, None].astype(xf.dtype), mode="drop")
    route = {"e_flat": e_flat, "pos": pos_c, "keep": keep,
             "tok": tok_flat,
             "gates": gates.reshape(-1).astype(xf.dtype)}
    return send, route, lb_loss


def _local_combine(out_buf, route, t: int, d: int):
    gathered = out_buf[route["e_flat"], route["pos"]] * \
        (route["gates"] * route["keep"].astype(out_buf.dtype))[:, None]
    return jnp.zeros((t, d), out_buf.dtype).at[route["tok"]].add(gathered)


def moe_apply_ep(p: dict, x: jnp.ndarray, *, top_k: int,
                 capacity_factor: float, act: str,
                 mesh, dp_axes: Tuple[str, ...],
                 tp_axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE.  x (B, S, d); S must divide by |tp_axis|."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    tp = mesh.shape[tp_axis]
    assert e % tp == 0, (e, tp)
    dp = 1
    for a in dp_axes:
        dp = dp * mesh.shape[a]
    t_loc = (b // dp if b % dp == 0 else b) * (s // tp)
    cap = int(max(top_k, capacity_factor * t_loc * top_k / e))

    f = cm.ACTIVATIONS[act]

    def local_fn(router, w_gate, w_up, w_down, xl):
        bl, sl, _ = xl.shape
        xf = xl.reshape(bl * sl, d)
        send, route, lb = _local_route(router, xf, top_k=top_k,
                                       n_experts=e, cap=cap)
        # exchange: (E, C, d) -> (E_loc, tp*C, d); experts to their owners
        recv = jax.lax.all_to_all(send, tp_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        h = f(jnp.einsum("ecd,edf->ecf", recv, w_gate,
                         preferred_element_type=jnp.float32).astype(xl.dtype)) \
            * jnp.einsum("ecd,edf->ecf", recv, w_up,
                         preferred_element_type=jnp.float32).astype(xl.dtype)
        out = jnp.einsum("ecf,efd->ecd", h, w_down,
                         preferred_element_type=jnp.float32).astype(xl.dtype)
        back = jax.lax.all_to_all(out, tp_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        y = _local_combine(back, route, bl * sl, d)
        lb = jax.lax.pmean(lb, (tp_axis,) + tuple(dp_axes))
        return y.reshape(bl, sl, d), lb

    dp_spec = dp_axes if (dp_axes and b % dp == 0) else None
    x_spec = P(dp_spec, tp_axis, None)
    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None),                 # router replicated
                  P(tp_axis, None, None),        # experts on model axis
                  P(tp_axis, None, None),
                  P(tp_axis, None, None),
                  x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return out
