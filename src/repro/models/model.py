"""Config-driven backbone assembly.

Public API (uniform across all 10 assigned architectures):

  init_params(cfg, key)                        -> params pytree
  forward(cfg, params, batch)                  -> {"logits", "value", "aux_loss"}
  init_cache(cfg, batch, cache_len)            -> cache pytree
  decode_step(cfg, params, cache, batch, pos)  -> ({"logits", "value"}, cache)

``batch`` is a dict: {"tokens": (B,S) int32} or {"embeds": (B,S,d)} (VLM /
audio stub), optionally {"positions": (3,B,S)} for M-RoPE and
{"enc_frames": (B,F,d)} for the Whisper encoder (handled in encdec.py).

Layers whose pattern tiles evenly (and with no Zamba2 shared block) are
stacked and driven by ``lax.scan`` so an 80-layer model compiles as one loop;
heterogeneous stacks fall back to a python loop.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.config import ModelConfig

Params = Any


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def _init_block(cfg: ModelConfig, kind: str, key) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind in ("attn", "attn_local"):
        p = {
            "ln1": cm.init_norm(cfg.norm, d),
            "attn": attn.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.hd, qkv_bias=cfg.qkv_bias),
            "ln2": cm.init_norm(cfg.norm, d),
        }
        if cfg.n_experts:
            p["moe"] = moe_mod.init_moe(ks[1], d, cfg.d_ff_expert,
                                        cfg.n_experts)
        elif cfg.d_ff:
            p["mlp"] = mlp_mod.init_gated_mlp(ks[1], d, cfg.d_ff)
        return p
    if kind == "mamba2":
        return {
            "ln1": cm.init_norm(cfg.norm, d),
            "mamba": ssm_mod.init_mamba2(
                ks[0], d, d_state=cfg.ssm_state, n_heads=cfg.ssm_heads,
                head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
                conv_width=cfg.ssm_conv_width),
        }
    if kind == "mlstm":
        return {
            "ln1": cm.init_norm(cfg.norm, d),
            "mlstm": xlstm_mod.init_mlstm(ks[0], d, n_heads=cfg.n_heads,
                                          expand=cfg.lstm_expand,
                                          conv_width=cfg.ssm_conv_width),
        }
    if kind == "slstm":
        return {
            "ln1": cm.init_norm(cfg.norm, d),
            "slstm": xlstm_mod.init_slstm(ks[0], d, n_heads=cfg.n_heads),
        }
    raise ValueError(f"unknown block kind {kind}")


def _apply_block_train(cfg: ModelConfig, kind: str, p: Params, x, cos, sin,
                       aux):
    """One residual block, training (full-sequence) mode."""
    window = cfg.sliding_window if kind == "attn_local" else None
    if kind in ("attn", "attn_local"):
        h = attn.attend_train(p["attn"], cm.apply_norm(cfg.norm, p["ln1"], x),
                              cos, sin, cfg, window=window)
        # seq-parallel block outputs: turns the model-axis gradient
        # all-reduce into a reduce-scatter (Megatron-SP, perf iter #2)
        x = x + ctx.constrain(h, "residual")
        y = cm.apply_norm(cfg.norm, p["ln2"], x)
        if cfg.n_experts:
            ep = (ctx.current_rules() or {}).get("moe_ep")
            if ep is not None and y.shape[1] % ep["tp"] == 0:
                # explicit expert-parallel all-to-all (perf iter #4)
                from repro.models import moe_ep as moe_ep_mod
                y, lb = moe_ep_mod.moe_apply_ep(
                    p["moe"], y, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, act=cfg.act,
                    mesh=ep["mesh"], dp_axes=ep["dp_axes"])
            else:
                y, lb = moe_mod.moe_apply(
                    p["moe"], y, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, act=cfg.act)
            aux = aux + lb
        else:
            y = mlp_mod.gated_mlp(p["mlp"], y, act=cfg.act)
        return x + ctx.constrain(y, "residual"), aux
    if kind == "mamba2":
        return x + ctx.constrain(ssm_mod.mamba2_train(
            p["mamba"], cm.apply_norm(cfg.norm, p["ln1"], x), cfg),
            "residual"), aux
    if kind == "mlstm":
        return x + ctx.constrain(xlstm_mod.mlstm_train(
            p["mlstm"], cm.apply_norm(cfg.norm, p["ln1"], x), cfg),
            "residual"), aux
    if kind == "slstm":
        return x + ctx.constrain(xlstm_mod.slstm_train(
            p["slstm"], cm.apply_norm(cfg.norm, p["ln1"], x), cfg),
            "residual"), aux
    raise ValueError(kind)


def _block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                 dtype, paged=None, kv_dtype=None) -> Params:
    # kv_dtype overrides dtype for ATTENTION caches only — recurrent state
    # below keeps `dtype` (int8 SSM/LSTM state would be numerically
    # meaningless; only KV rows carry the quantization scheme)
    kvd = dtype if kv_dtype is None else kv_dtype
    if kind == "attn":
        if paged is not None:
            # shared page pool + per-slot page table; ring layers below
            # keep contiguous caches (a rotating window has no reusable
            # prefix to share)
            return attn.init_paged_kv_cache(
                batch, cache_len, cfg.n_kv_heads, cfg.hd,
                page_size=paged.page_size, n_pages=paged.n_pages,
                dtype=kvd)
        return attn.init_kv_cache(batch, cache_len, cfg.n_kv_heads, cfg.hd,
                                  kvd)
    if kind == "attn_local":
        clen = min(cache_len, cfg.sliding_window or cache_len)
        return attn.init_kv_cache(batch, clen, cfg.n_kv_heads, cfg.hd, kvd)
    if kind == "mamba2":
        return ssm_mod.init_mamba2_state(batch, cfg, dtype)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_state(batch, cfg.d_model, cfg.n_heads,
                                          expand=cfg.lstm_expand,
                                          conv_width=cfg.ssm_conv_width)
    if kind == "slstm":
        return xlstm_mod.init_slstm_state(batch, cfg.d_model, cfg.n_heads)
    raise ValueError(kind)


def _apply_block_decode(cfg: ModelConfig, kind: str, p: Params, x, cache,
                        pos):
    window = cfg.sliding_window if kind == "attn_local" else None
    if kind in ("attn", "attn_local"):
        # one decode path for both cache layouts: attend_decode routes the
        # context-parallel (decode_cp-ruled) case through the dispatch
        # layer's pallas_cp arm itself
        h, cache = attn.attend_decode(
            p["attn"], cm.apply_norm(cfg.norm, p["ln1"], x),
            cache, pos, cfg, window=window)
        x = x + h
        y = cm.apply_norm(cfg.norm, p["ln2"], x)
        if cfg.n_experts:
            y, _ = moe_mod.moe_apply(p["moe"], y, top_k=cfg.top_k,
                                     capacity_factor=cfg.capacity_factor,
                                     act=cfg.act)
        else:
            y = mlp_mod.gated_mlp(p["mlp"], y, act=cfg.act)
        return x + y, cache
    if kind == "mamba2":
        h, cache = ssm_mod.mamba2_decode(
            p["mamba"], cm.apply_norm(cfg.norm, p["ln1"], x), cache, cfg)
        return x + h, cache
    if kind == "mlstm":
        h, cache = xlstm_mod.mlstm_decode(
            p["mlstm"], cm.apply_norm(cfg.norm, p["ln1"], x), cache, cfg)
        return x + h, cache
    if kind == "slstm":
        h, cache = xlstm_mod.slstm_decode(
            p["slstm"], cm.apply_norm(cfg.norm, p["ln1"], x), cache, cfg)
        return x + h, cache
    raise ValueError(kind)


def _apply_block_prefill(cfg: ModelConfig, kind: str, p: Params, x, cache,
                         pos0: int, true_len=None):
    """One residual block over a whole prompt chunk, writing the KV cache.
    Only attention blocks support this (checked by
    ``supports_chunked_prefill``); recurrent caches need their own scan.
    ``true_len`` (B,) masks ring-cache writes past each row's real prompt
    length (right-padded admission chunks)."""
    window = cfg.sliding_window if kind == "attn_local" else None
    if kind not in ("attn", "attn_local"):
        raise NotImplementedError(
            f"chunked prefill is KV-cache only, got block kind {kind}")
    h, cache = attn.attend_prefill(
        p["attn"], cm.apply_norm(cfg.norm, p["ln1"], x), cache, pos0, cfg,
        window=window, true_len=true_len)
    x = x + h
    y = cm.apply_norm(cfg.norm, p["ln2"], x)
    if cfg.n_experts:
        y, _ = moe_mod.moe_apply(p["moe"], y, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor,
                                 act=cfg.act)
    else:
        y = mlp_mod.gated_mlp(p["mlp"], y, act=cfg.act)
    return x + y, cache


# ---------------------------------------------------------------------------
# stacking helpers
# ---------------------------------------------------------------------------

def _use_scan(cfg: ModelConfig) -> bool:
    return (cfg.n_layers % len(cfg.block_cycle) == 0
            and cfg.shared_attn_every == 0
            and not cfg.is_encdec)


def _n_cycles(cfg: ModelConfig) -> int:
    return cfg.n_layers // len(cfg.block_cycle)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    if cfg.is_encdec:
        from repro.models import encdec
        return encdec.init_params(cfg, key)
    keys = jax.random.split(key, cfg.n_layers + 5)
    kinds = cfg.layer_kinds()
    p: Dict[str, Params] = {
        "embed": cm.init_embedding(keys[-1], cfg.vocab_size, cfg.d_model),
        "final_norm": cm.init_norm(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = cm.init_linear(keys[-2], cfg.d_model, cfg.vocab_size)
    if cfg.value_head:
        p["value_head"] = cm.init_linear(keys[-3], cfg.d_model, 1)
    if cfg.shared_attn_every:
        # Zamba2: one shared attention+MLP block reused at every k-th layer
        p["shared_attn"] = _init_block(
            cfg, "attn", keys[-4])

    layer_ps = [_init_block(cfg, kinds[i], keys[i])
                for i in range(cfg.n_layers)]
    if _use_scan(cfg):
        cyc = len(cfg.block_cycle)
        cycles = [tuple(layer_ps[i * cyc + j] for j in range(cyc))
                  for i in range(_n_cycles(cfg))]
        p["layers"] = _stack(cycles)
    else:
        p["layers"] = layer_ps
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def cast_params(cfg: ModelConfig, params: Params) -> Params:
    """Mixed precision: cast matrix params to the compute dtype (bf16 on
    TPU); vectors (norm scales, biases, SSM time constants) stay f32.  Master
    params and optimizer state remain f32 — this cast sits inside the loss so
    gradients flow back to the f32 masters."""
    dt = jnp.dtype(cfg.dtype)
    if dt == jnp.float32:
        return params

    def c(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32 and x.ndim >= 2:
            return x.astype(dt)
        return x

    return jax.tree.map(c, params)


def _embed_inputs(cfg: ModelConfig, params, batch) -> jnp.ndarray:
    if "embeds" in batch:
        x = batch["embeds"]
    else:
        x = cm.embed(params["embed"], batch["tokens"])
    return x.astype(cfg.dtype)


def _rope_tables(cfg: ModelConfig, batch, s: int):
    if cfg.mrope_sections is not None:
        pos = batch.get("positions")
        if pos is None:
            b = (batch.get("tokens", batch.get("embeds"))).shape[0]
            pos = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
        return cm.mrope_cos_sin(pos, cfg.hd, cfg.rope_theta,
                                cfg.mrope_sections)
    positions = jnp.arange(s)[None]                      # (1, S)
    return cm.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)


def _lm_head(cfg: ModelConfig, params: Params, x) -> jnp.ndarray:
    """Logits of the final hidden states, under the ``lm_head`` scope, so
    that a device trace can time the head apart from the layers."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return x @ params["embed"]["table"].T.astype(x.dtype)
        return cm.linear(params["lm_head"], x, dtype=x.dtype)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> Dict[str, jnp.ndarray]:
    params = cast_params(cfg, params)
    if cfg.is_encdec:
        from repro.models import encdec
        return encdec.forward(cfg, params, batch)
    x = _embed_inputs(cfg, params, batch)
    s = x.shape[1]
    cos, sin = _rope_tables(cfg, batch, s)
    aux = jnp.zeros((), jnp.float32)
    kinds = cfg.layer_kinds()

    if _use_scan(cfg):
        cyc_kinds = cfg.block_cycle

        def cycle_fn(x, aux, cyc_params):
            for j, kind in enumerate(cyc_kinds):
                x, aux = _apply_block_train(cfg, kind, cyc_params[j], x,
                                            cos, sin, aux)
            return x, aux

        if cfg.remat:
            cycle_fn = jax.checkpoint(cycle_fn)

        def body(carry, cyc_params):
            x, aux = carry
            x, aux = cycle_fn(x, aux, cyc_params)
            # sequence-parallel residual stream between cycles (Megatron-SP):
            # keeps the saved scan carry sharded over the model axis.
            x = ctx.constrain(x, "residual")
            return (x, aux), None

        x = ctx.constrain(x, "residual")
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["layers"])
    else:
        step_fn = _apply_block_train
        if cfg.remat:
            step_fn = jax.checkpoint(_apply_block_train,
                                     static_argnums=(0, 1))
        for i, kind in enumerate(kinds):
            x, aux = step_fn(cfg, kind, params["layers"][i], x, cos, sin,
                             aux)
            x = ctx.constrain(x, "residual")
            if cfg.shared_attn_every and (i + 1) % cfg.shared_attn_every == 0:
                x, aux = step_fn(cfg, "attn", params["shared_attn"], x,
                                 cos, sin, aux)
                x = ctx.constrain(x, "residual")

    x = cm.apply_norm(cfg.norm, params["final_norm"], x)
    out = {"aux_loss": aux}
    out["logits"] = _lm_head(cfg, params, x)
    if cfg.value_head:
        out["value"] = cm.linear(params["value_head"], x)[..., 0] \
            .astype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, paged=None, kv_dtype=None) -> Params:
    """``paged`` — an ``attention.PagedLayout`` switches every global
    (kind == "attn") layer to the page-pool layout; sliding-window and
    recurrent layers keep their contiguous/recurrent state either way.

    ``kv_dtype`` overrides ``dtype`` for attention KV caches only (int8
    adds per-row scale leaves; recurrent state keeps ``dtype``)."""
    if cfg.is_encdec:
        from repro.models import encdec
        return encdec.init_cache(cfg, batch, cache_len, dtype)
    kinds = cfg.layer_kinds()
    caches = [_block_cache(cfg, k, batch, cache_len, dtype, paged, kv_dtype)
              for k in kinds]
    cache: Dict[str, Any] = {}
    if _use_scan(cfg):
        cyc = len(cfg.block_cycle)
        per_cycle = [tuple(caches[i * cyc + j] for j in range(cyc))
                     for i in range(_n_cycles(cfg))]
        cache["layers"] = _stack(per_cycle)
    else:
        cache["layers"] = caches
    if cfg.shared_attn_every:
        n_apps = cfg.n_layers // cfg.shared_attn_every
        cache["shared"] = [
            _block_cache(cfg, "attn", batch, cache_len, dtype,
                         kv_dtype=kv_dtype)
            for _ in range(n_apps)]
    return cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                batch: Dict[str, jnp.ndarray], pos: jnp.ndarray):
    """One-token decode.  batch: {"tokens": (B,1)} or {"embeds": (B,1,d)};
    pos int32 — current absolute position, lockstep scalar () or per-slot
    (B,) (continuous batching; KV-cache blocks handle ragged depths, the
    recurrent blocks are position-free).  Returns (out, new_cache)."""
    params = cast_params(cfg, params)
    if cfg.is_encdec:
        from repro.models import encdec
        return encdec.decode_step(cfg, params, cache, batch, pos)
    x = _embed_inputs(cfg, params, batch)
    kinds = cfg.layer_kinds()

    if _use_scan(cfg):
        cyc_kinds = cfg.block_cycle

        def body(x, inp):
            cyc_params, cyc_cache = inp
            new_caches = []
            for j, kind in enumerate(cyc_kinds):
                x, c = _apply_block_decode(cfg, kind, cyc_params[j], x,
                                           cyc_cache[j], pos)
                new_caches.append(c)
            return x, tuple(new_caches)

        x, new_cache = jax.lax.scan(body, x,
                                    (params["layers"], cache["layers"]))
        cache = dict(cache)
        cache["layers"] = new_cache
    else:
        new_caches = []
        new_shared = []
        shared_i = 0
        for i, kind in enumerate(kinds):
            x, c = _apply_block_decode(cfg, kind, params["layers"][i], x,
                                       cache["layers"][i], pos)
            new_caches.append(c)
            if cfg.shared_attn_every and (i + 1) % cfg.shared_attn_every == 0:
                x, cs = _apply_block_decode(cfg, "attn", params["shared_attn"],
                                            x, cache["shared"][shared_i], pos)
                new_shared.append(cs)
                shared_i += 1
        cache = dict(cache)
        cache["layers"] = new_caches
        if cfg.shared_attn_every:
            cache["shared"] = new_shared

    x = cm.apply_norm(cfg.norm, params["final_norm"], x)
    out = {}
    out["logits"] = _lm_head(cfg, params, x)
    if cfg.value_head:
        out["value"] = cm.linear(params["value_head"], x)[..., 0] \
            .astype(jnp.float32)
    return out, cache


# ---------------------------------------------------------------------------
# speculative verify + deferred commit
# ---------------------------------------------------------------------------

def _apply_block_verify(cfg: ModelConfig, kind: str, p: Params, x, cache,
                        pos, shift: int):
    """One residual block over a per-slot K-token draft chunk, cache
    read-only.  Returns (x, pending) — the chunk K/V ``commit_step``
    scatters for accepted rows."""
    window = cfg.sliding_window if kind == "attn_local" else None
    if kind not in ("attn", "attn_local"):
        raise NotImplementedError(
            f"speculative verify is KV-cache only, got block kind {kind}")
    h, pending = attn.attend_verify(
        p["attn"], cm.apply_norm(cfg.norm, p["ln1"], x), cache, pos, cfg,
        shift=shift, window=window)
    x = x + h
    y = cm.apply_norm(cfg.norm, p["ln2"], x)
    if cfg.n_experts:
        y, _ = moe_mod.moe_apply(p["moe"], y, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor,
                                 act=cfg.act)
    else:
        y = mlp_mod.gated_mlp(p["mlp"], y, act=cfg.act)
    return x + y, pending


def verify_step(cfg: ModelConfig, params: Params, cache: Params,
                batch: Dict[str, jnp.ndarray], pos: jnp.ndarray,
                shift: int):
    """Speculative verify: batch {"tokens": (B, K)} — row j's current
    token + drafts at absolute positions pos[j] + i; pos (B,) int32;
    ``shift`` a static upper bound on pos (the logical cache length).

    Unlike ``decode_step`` this writes NOTHING: it returns
    (out {"logits" (B, K, V)}, pendings) where ``pendings`` mirrors
    ``cache["layers"]`` with each attention layer's chunk K/V, and the
    caller commits the accepted prefix via ``commit_step`` after the
    host-side accept decision — KV rollback on rejection is therefore a
    no-op by construction.  Requires ``supports_chunked_prefill`` (the
    engine gates speculation the same way)."""
    if not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"{cfg.name}: speculative verify needs attention-only caches")
    params = cast_params(cfg, params)
    x = _embed_inputs(cfg, params, batch)
    kinds = cfg.layer_kinds()

    if _use_scan(cfg):
        cyc_kinds = cfg.block_cycle

        def body(x, inp):
            cyc_params, cyc_cache = inp
            pendings = []
            for j, kind in enumerate(cyc_kinds):
                x, pend = _apply_block_verify(cfg, kind, cyc_params[j], x,
                                              cyc_cache[j], pos, shift)
                pendings.append(pend)
            return x, tuple(pendings)

        x, pendings = jax.lax.scan(body, x,
                                   (params["layers"], cache["layers"]))
    else:
        pendings = []
        for i, kind in enumerate(kinds):
            x, pend = _apply_block_verify(cfg, kind, params["layers"][i], x,
                                          cache["layers"][i], pos, shift)
            pendings.append(pend)

    x = cm.apply_norm(cfg.norm, params["final_norm"], x)
    out = {}
    out["logits"] = _lm_head(cfg, params, x)
    return out, pendings


def commit_step(cfg: ModelConfig, cache: Params, pendings,
                pos: jnp.ndarray, n_acc: jnp.ndarray) -> Params:
    """Commit the accepted prefix of a verify chunk: row j writes pending
    rows i < n_acc[j] at positions pos[j] + i into every layer's cache
    (ring wrap / page-table indirection per layout).  n_acc[j] == 0
    writes nothing for that row."""
    kinds = cfg.layer_kinds()

    if _use_scan(cfg):
        cyc_kinds = cfg.block_cycle

        def body(carry, inp):
            cyc_cache, cyc_pend = inp
            new_caches = []
            for j, kind in enumerate(cyc_kinds):
                window = cfg.sliding_window if kind == "attn_local" else None
                new_caches.append(attn.commit_kv(cyc_cache[j], cyc_pend[j],
                                                 pos, n_acc, window=window))
            return carry, tuple(new_caches)

        _, new_cache = jax.lax.scan(body, jnp.zeros(()),
                                    (cache["layers"], pendings))
        cache = dict(cache)
        cache["layers"] = new_cache
    else:
        new_caches = []
        for i, kind in enumerate(kinds):
            window = cfg.sliding_window if kind == "attn_local" else None
            new_caches.append(attn.commit_kv(cache["layers"][i],
                                             pendings[i], pos, n_acc,
                                             window=window))
        cache = dict(cache)
        cache["layers"] = new_caches
    return cache


# ---------------------------------------------------------------------------
# chunked flash prefill
# ---------------------------------------------------------------------------

def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill block-writes KV caches; recurrent states (SSM,
    xLSTM) and the enc-dec family would need state-returning train scans —
    those architectures fall back to the token-by-token decode loop."""
    return (not cfg.is_encdec
            and not cfg.shared_attn_every
            and all(k in ("attn", "attn_local") for k in cfg.layer_kinds()))


def prefill_step(cfg: ModelConfig, params: Params, cache: Params,
                 batch: Dict[str, jnp.ndarray], pos0: int = 0,
                 true_len=None):
    """Prefill one whole prompt chunk.  batch: {"tokens": (B, C)} (or
    embeds) covering absolute positions [pos0, pos0 + C); pos0 is a static
    python int (one compile per chunk offset — offsets are multiples of the
    chunk size, so a handful of traces serve any prompt length).

    Every attention layer — every chunk, not just the first — runs through
    one ``dispatch.flash_attention_append`` launch (q-offset grid over the
    cache prefix plus the chunk) and writes its KV cache rows in one
    block — replacing C single-token ``decode_step`` launches, the
    dominant serving-latency term for long prompts.  Returns
    (out {"logits" (B, C, V), ...}, new_cache); callers gather each row's
    true last-prompt-token logits (prompts are right-padded; ``true_len``
    (B,) additionally masks ring-cache writes past each row's real length,
    which is what lets right-padded engine admission chunk sliding-window
    architectures) and continue with per-slot decode.
    """
    if not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"{cfg.name}: chunked prefill needs attention-only caches")
    params = cast_params(cfg, params)
    x = _embed_inputs(cfg, params, batch)
    kinds = cfg.layer_kinds()

    if _use_scan(cfg):
        cyc_kinds = cfg.block_cycle

        def body(x, inp):
            cyc_params, cyc_cache = inp
            new_caches = []
            for j, kind in enumerate(cyc_kinds):
                x, c = _apply_block_prefill(cfg, kind, cyc_params[j], x,
                                            cyc_cache[j], pos0, true_len)
                new_caches.append(c)
            return x, tuple(new_caches)

        x, new_cache = jax.lax.scan(body, x,
                                    (params["layers"], cache["layers"]))
        cache = dict(cache)
        cache["layers"] = new_cache
    else:
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c = _apply_block_prefill(cfg, kind, params["layers"][i], x,
                                        cache["layers"][i], pos0, true_len)
            new_caches.append(c)
        cache = dict(cache)
        cache["layers"] = new_caches

    x = cm.apply_norm(cfg.norm, params["final_norm"], x)
    out = {}
    out["logits"] = _lm_head(cfg, params, x)
    if cfg.value_head:
        out["value"] = cm.linear(params["value_head"], x)[..., 0] \
            .astype(jnp.float32)
    return out, cache
