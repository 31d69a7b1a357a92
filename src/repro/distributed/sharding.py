"""Sharding rules for the production mesh.

Mesh axes:
  pod    — outer replica groups (multi-pod only; delayed-sync merge axis)
  data   — actor-learner groups (the paper's "threads"); batch + FSDP axis
  model  — tensor parallelism: heads / d_ff / vocab / experts / SSM heads

Parameter layout is 2-D sharded (FSDP x TP), MaxText-style: the contracting
d_model dim of every big matrix lives on ``data``, the parallel dim (heads,
ffn, vocab, experts) on ``model``.  Caches for decode are context-parallel:
the sequence dim of KV caches is sharded (over ``model``, and additionally
over ``data`` when the batch is too small to use it).

All rules are name-based on the pytree path, with a leading ``None`` added
automatically for stacked (scanned) layers.
"""
from __future__ import annotations

import re
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch-parallel axes: ('pod','data') on the multi-pod mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# kernel-dispatch partitioning (consumed by repro.kernels.dispatch)
# ---------------------------------------------------------------------------

class AttnShardSpec(NamedTuple):
    """How to shard_map the attention kernels over a mesh.

    ``batch`` is the PartitionSpec entry for the batch dim (axis name, tuple
    of names, or None for replicated); ``heads`` likewise for the head dims.
    Hashable by construction so dispatch can use it as a jit static arg.
    """
    mesh: Any              # jax.sharding.Mesh
    batch: Any             # None | str | tuple of axis names
    heads: Optional[str]   # None | "model"

    @property
    def qo(self) -> P:
        """q / o / do / dq: (B, S, Hq, D) — batch on data, heads on model."""
        return P(self.batch, None, self.heads, None)

    @property
    def kv(self) -> P:
        """k / v / dk / dv and KV caches: (B, S|L, Hkv, D)."""
        return P(self.batch, None, self.heads, None)

    @property
    def lse(self) -> P:
        """lse / delta residuals: (B, Hq, S)."""
        return P(self.batch, self.heads, None)

    @property
    def q_decode(self) -> P:
        """decode q / o: (B, Hq, D)."""
        return P(self.batch, self.heads, None)

    @property
    def kpos_decode(self) -> P:
        """per-slot kpos (B, L): batch sharded with q, slots replicated."""
        return P(self.batch, None)

    @property
    def pos_decode(self) -> P:
        """per-slot pos (B,)."""
        return P(self.batch)


class DecodeCPSpec(NamedTuple):
    """How to shard_map the context-parallel (flash-decoding) decode kernel.

    The KV cache's *sequence* dim is sharded over ``seq_axes`` (the
    ``decode_cp`` rule's axes — 'model', plus the data axes for batch=1
    long-context decode); each shard runs the partials kernel over its
    cache slice and the combine is a psum of (m, l, acc) over ``seq_axes``.
    Heads stay shard-local (the model axis is spent on the sequence).
    Hashable by construction so dispatch can use it as a jit static arg.
    """
    mesh: Any                        # jax.sharding.Mesh
    batch: Any                       # None | str | tuple of axis names
    seq_axes: Tuple[str, ...]        # cache sequence sharding axes

    @property
    def _seq(self):
        return self.seq_axes if len(self.seq_axes) > 1 else self.seq_axes[0]

    @property
    def q_decode(self) -> P:
        """decode q / o: (B, Hq, D) — replicated over the seq axes."""
        return P(self.batch, None, None)

    @property
    def kv(self) -> P:
        """KV caches (B, L, Hkv, D): sequence dim sharded."""
        return P(self.batch, self._seq, None, None)

    @property
    def new_kv(self) -> P:
        """The step's new k/v token (B, 1, Hkv, D): replicated over seq."""
        return P(self.batch, None, None, None)

    @property
    def kpos(self) -> P:
        """per-slot kpos (B, L): batch with q, slots sliced along the same
        seq sharding as the cache."""
        return P(self.batch, self._seq)

    @property
    def pos_decode(self) -> P:
        """per-slot pos (B,): replicated over the seq axes."""
        return P(self.batch)


def decode_cp_spec(rule: dict, *, batch: int) -> DecodeCPSpec:
    """Layout (no alignment policy) for the context-parallel decode path:
    how the ``decode_cp`` rule from :func:`decode_rules` partitions the
    cache and the step tensors over its mesh.  The single source for both
    the model-layer cache write and the dispatch-layer combine — they must
    agree on the cache's partitioning."""
    mesh = rule["mesh"]
    seq_axes = tuple(rule["seq_axes"])
    dp_axes = tuple(rule.get("dp_axes") or ())
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    dp: Any = dp_axes if (dp_axes and dp_size > 1
                          and batch % dp_size == 0) else None
    if isinstance(dp, tuple) and len(dp) == 1:
        dp = dp[0]
    return DecodeCPSpec(mesh, dp, seq_axes)


def decode_cp_shard_spec(rule: dict, *, batch: int, length: int
                         ) -> Tuple[Optional[DecodeCPSpec], str]:
    """Dispatch policy for the unified context-parallel decode path.

    Returns (spec, "") or (None, reason) when the Pallas combine cannot
    serve this call — cache length not divisible into MXU-aligned local
    slices.  (The cache *write* only needs divisibility, so it uses
    :func:`decode_cp_spec` directly.)
    """
    seq_axes = tuple(rule["seq_axes"])
    n_shards = int(rule["n_shards"])
    if length % n_shards != 0:
        return None, (f"cache length {length} does not divide over the "
                      f"{n_shards}-shard seq axes {seq_axes}")
    l_loc = length // n_shards
    if n_shards > 1 and (l_loc < 128 or l_loc % 128 != 0):
        return None, (f"local cache slice {l_loc} (of {length} over "
                      f"{n_shards} shards) not MXU-aligned (need a "
                      "multiple of 128)")
    return decode_cp_spec(rule, batch=batch), ""


class RowShardSpec(NamedTuple):
    """Row-block shard_map spec for the fused rmsnorm: the (rows, d)
    activation's row dim over ``axes``, scale replicated.  Hashable so
    dispatch can use it as a jit static arg."""
    mesh: Any
    axes: Tuple[str, ...]

    @property
    def rows(self) -> P:
        return P(self.axes if len(self.axes) > 1 else self.axes[0], None)

    @property
    def rstd(self) -> P:
        """per-row residual (rows,) f32."""
        return P(self.axes if len(self.axes) > 1 else self.axes[0])


def _spec_mentions(spec, axis: str, dim: int) -> bool:
    """Does PartitionSpec ``spec`` put ``axis`` on dimension ``dim``?"""
    entries = tuple(spec)
    if dim >= len(entries):
        return False
    e = entries[dim]
    return axis in e if isinstance(e, tuple) else e == axis


def rmsnorm_shard_spec(mesh, *, rows: int, rules=None
                       ) -> Tuple[Optional[RowShardSpec], str]:
    """Partitioning for the shard_map'd fused rmsnorm.

    Rows (= batch*seq) are normalized independently, so they shard over
    every mesh axis whose product divides them; scale is replicated and
    the vjp's dscale is psum'd over the row axes.  The one layout this
    must NOT touch is the Megatron-SP seq-parallel residual: there the
    activation's seq dim is already sharded over 'model', and a row-block
    shard_map would re-gather it — that stays an explicit fallback.
    """
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    r = (rules or {}).get("residual")
    if r is not None and msize > 1 and \
            _spec_mentions(getattr(r, "spec", r), "model", 1):
        return None, ("seq-parallel residual shards rows over 'model'; "
                      "row-block shard_map would re-gather the residual "
                      "stream (explicit fallback, see DESIGN.md "
                      "§kernel-dispatch)")
    axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    if not axes:
        # degenerate 1-device mesh: replicated (benches may force it)
        return RowShardSpec(mesh, tuple(mesh.axis_names)[:1] or ("data",)), ""
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if rows % n != 0 or rows // n < 8:
        return None, (f"rows={rows} do not divide into >=8-row blocks "
                      f"over the {n}-device mesh axes {axes}")
    return RowShardSpec(mesh, axes), ""


def attention_shard_spec(mesh, *, batch: int, n_q_heads: int,
                         n_kv_heads: int
                         ) -> Tuple[Optional[AttnShardSpec], str]:
    """Partitioning for the shard_map'd Pallas attention kernels.

    Batch goes over the data axes, q *and* kv heads over ``model`` —
    contiguous head blocks keep every GQA group local to its shard (shard j
    owns q heads [j*hq/m, (j+1)*hq/m) and exactly the kv heads they read,
    because hq/m = g * hkv/m).  The sequence dim stays unsharded: the flash
    grid scans it on-chip, and causal/window masks use absolute positions.

    Returns (spec, "") or (None, reason) when the mesh axes divide neither
    tensor dim — the dispatcher records the reason and falls back to jnp.
    """
    d_ax = data_axes(mesh)
    d_size = 1
    for a in d_ax:
        d_size *= mesh.shape[a]
    m_size = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if d_size == 1 and m_size == 1:
        # degenerate 1-device mesh: everything replicated (benches force
        # the shard_map path through it; auto dispatch never picks it)
        return AttnShardSpec(mesh, None, None), ""

    dp: Any = d_ax if (d_ax and batch % d_size == 0 and d_size > 1) else None
    if isinstance(dp, tuple) and len(dp) == 1:
        dp = dp[0]
    heads = None
    if m_size > 1:
        if n_q_heads % m_size == 0 and n_kv_heads % m_size == 0:
            heads = "model"
        else:
            return None, (f"heads ({n_q_heads}q/{n_kv_heads}kv) do not "
                          f"divide the {m_size}-way model axis")
    if dp is None and heads is None:
        return None, (f"mesh axes divide neither batch={batch} "
                      f"(data={d_size}) nor heads (model={m_size})")
    return AttnShardSpec(mesh, dp, heads), ""


# ---------------------------------------------------------------------------
# parameter sharding
# ---------------------------------------------------------------------------

# (regex on path, spec for the UNSTACKED param). "F" = fsdp/data axis,
# "M" = model axis; resolved per-mesh.
_PARAM_RULES = [
    (r"embed/table$",              ("M", "F")),
    (r"lm_head/w$",                ("F", "M")),
    (r"value_head/w$",             ("F", None)),
    (r"(wq|wk|wv|up_x|up_z|w_in|ff_gate|ff_up)/w$", ("F", "M")),
    (r"(wo|down|ff_down|out_proj)/w$",              ("M", "F")),
    (r"(gate|up)/w$",              ("F", "M")),
    (r"(mlp/fc1|fc1)/w$",          ("F", "M")),
    (r"(mlp/fc2|fc2)/w$",          ("M", "F")),
    (r"in_proj/w$",                ("F", "M")),
    (r"(wq|wk|wv)/b$",             ("M",)),
    (r"(gate|up|fc1)/b$",          ("M",)),
    (r"router$",                   ("F", None)),
    # expert weights: EP over model only (shard_map all-to-all dispatch
    # owns them per-device; replicating over data costs ~MBs and removes a
    # per-layer gather — perf iter #4)
    (r"w_(gate|up)$",              ("M", None, None)),  # (E, d, f)
    (r"w_down$",                   ("M", None, None)),  # (E, f, d)
    (r"conv_w$",                   (None, "M")),
    (r"conv_b$",                   ("M",)),
    (r"(A_log|D|dt_bias)$",        ("M",)),
    (r"(mamba|mlstm)/norm/scale$", ("M",)),
    (r"w_[if]/w$",                 ("F", None)),
    # sLSTM recurrent weights: sharded (iter #9 measured the alternative —
    # replicating them moves the per-step collective from a 1 MB activation
    # psum to a 16.8 MB gradient-accumulator psum, 2x worse; the real fix
    # is a shard_map'd recurrence with deferred dr reduction, future work)
    (r"slstm/r$",                  (None, "F", "M")),   # (H, hd, 4hd)
]


def _resolve(spec_tpl, mesh: Mesh, *, fsdp: bool = True):
    d_ax = data_axes(mesh)
    out = []
    for s in spec_tpl:
        if s == "M":
            out.append("model")
        elif s == "F":
            # jax canonicalizes P(('data',)) to P('data'): emit the bare
            # name so specs compare equal to jax's
            ax = d_ax if (fsdp and d_ax) else None
            out.append(ax[0] if isinstance(ax, tuple) and len(ax) == 1
                       else ax)
        else:
            out.append(None)
    return P(*out)


def param_spec(path_str: str, leaf, mesh: Mesh, *, stacked: bool,
               fsdp: bool = True) -> P:
    for pat, tpl in _PARAM_RULES:
        if re.search(pat, path_str):
            spec = _resolve(tpl, mesh, fsdp=fsdp)
            if len(spec) > leaf.ndim:
                return P()  # degenerate (smoke-size) leaf: replicate
            if stacked and leaf.ndim == len(spec) + 1:
                return P(*((None,) + tuple(spec)))
            return spec
    return P()  # norms, small biases, scalars: replicated


def _divisible(leaf, spec: P, mesh: Mesh) -> bool:
    for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size != 0:
            return False
    return True


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_tree,
                    *, fsdp: bool = True):
    """params_tree: pytree of ShapeDtypeStruct (or arrays)."""
    from repro.models import model as M
    stacked = M._use_scan(cfg)

    def one(path, leaf):
        ps = _path_str(path)
        is_stacked = stacked and ps.startswith("layers")
        spec = param_spec(ps, leaf, mesh, stacked=is_stacked, fsdp=fsdp)
        if not _divisible(leaf, spec, mesh):
            # drop offending axes rather than fail (e.g. 4-head xLSTM)
            new = []
            for dim, ax in zip(leaf.shape,
                               tuple(spec) + (None,) * leaf.ndim):
                if ax is None:
                    new.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                new.append(ax if dim % size == 0 else None)
            spec = P(*new)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_tree)


# ---------------------------------------------------------------------------
# batch and cache sharding
# ---------------------------------------------------------------------------

def batch_shardings(mesh: Mesh, batch_tree, *, batch_size: int):
    """Shard the leading batch dim over the data axes (when divisible)."""
    d_ax = data_axes(mesh)
    dp_size = 1
    for a in d_ax:
        dp_size *= mesh.shape[a]
    dp: Any = d_ax if (d_ax and batch_size % dp_size == 0) else None

    def one(path, leaf):
        ps = _path_str(path)
        if ps.endswith("positions"):               # (3, B, S)
            return NamedSharding(mesh, P(None, dp, None))
        return NamedSharding(mesh, P(*((dp,) + (None,) * (leaf.ndim - 1))))

    return jax.tree_util.tree_map_with_path(one, batch_tree)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_tree,
                    *, batch_size: int):
    """Context-parallel decode caches.

    KV caches (B, L, Hkv, hd): seq dim over 'model'; batch over data axes
    when divisible, otherwise the seq dim additionally takes the data axes
    (batch=1 long-context decode -> full-mesh context parallelism).
    SSM/LSTM states: shard the head/state dims over 'model' when divisible.
    """
    from repro.models import model as M
    stacked = M._use_scan(cfg)
    d_ax = data_axes(mesh)
    dp_size = 1
    for a in d_ax:
        dp_size *= mesh.shape[a]
    batch_ok = bool(d_ax) and batch_size % dp_size == 0
    b_ax: Any = d_ax if batch_ok else None
    seq_ax: Any = "model" if batch_ok else (d_ax + ("model",)
                                            if d_ax else "model")

    def shard_state(ps, leaf, base_rank_offset):
        """SSM / LSTM states: try model on the largest non-batch dim."""
        nd = leaf.ndim
        spec = [None] * nd
        if nd >= 1:
            spec[base_rank_offset] = b_ax          # batch dim
        # choose the last dim divisible by model size for the model axis
        for d in range(nd - 1, base_rank_offset, -1):
            if leaf.shape[d] % mesh.shape["model"] == 0 and \
                    leaf.shape[d] >= mesh.shape["model"]:
                spec[d] = "model"
                break
        return P(*spec)

    def one(path, leaf):
        ps = _path_str(path)
        off = 1 if (stacked and ps.startswith("layers")) else 0
        if leaf.ndim == 0 or ps.endswith("index"):
            return NamedSharding(mesh, P())
        if re.search(r"/(kp|vp|kps|vps)$", ps) and leaf.ndim >= 4:
            # paged pool (P, page_size, Hkv, hd) [+leading stack dim] and
            # its rank-matched scale pools (P, page_size, Hkv, 1): no
            # batch dim to give the data axes.  Replicated-cache layout:
            # heads on 'model' (the same dim the gathered dense view
            # shards); context-parallel layout: the page dim takes the seq
            # axes — page boundaries are 128-multiples, so whole pages per
            # shard keep the gathered slices MXU-aligned.
            hkv = leaf.shape[off + 2]
            n_pages = leaf.shape[off + 0]
            m_size = mesh.shape["model"] if "model" in mesh.axis_names else 1
            if batch_ok:
                heads = "model" if (m_size > 1 and hkv % m_size == 0
                                    and hkv >= m_size) else None
                spec = (None,) * off + (None, None, heads, None)
            else:
                axes = seq_ax if isinstance(seq_ax, tuple) else (seq_ax,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                pages = seq_ax if n_pages % size == 0 else None
                spec = (None,) * off + (pages, None, None, None)
            return NamedSharding(mesh, P(*spec))
        if re.search(r"/pt$", ps):
            # page tables are gather/scatter indices — replicate
            return NamedSharding(mesh, P())
        if re.search(r"/(k|v|ks|vs)$", ps) and leaf.ndim >= 4:
            # (B, L, Hkv, hd) [+leading stack dim]; int8 caches carry
            # rank-matched scale leaves (B, L, Hkv, 1) that take the same
            # (batch, seq) spec — the trailing singleton stays unsharded
            cache_len = leaf.shape[off + 1]
            seq = seq_ax
            # guard divisibility of the seq dim
            axes = seq if isinstance(seq, tuple) else (seq,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if cache_len % size != 0:
                seq = None
            spec = (None,) * off + (b_ax, seq, None, None)
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, shard_state(ps, leaf, off))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def decode_rules(cfg: ModelConfig, mesh: Mesh, *, batch_size: int):
    """Context-parallel decode (flash-decoding combine) rule set."""
    d_ax = data_axes(mesh)
    dp_size = 1
    for a in d_ax:
        dp_size *= mesh.shape[a]
    batch_ok = bool(d_ax) and batch_size % dp_size == 0
    seq_axes = ("model",) if batch_ok else tuple(d_ax) + ("model",)
    n = 1
    for a in seq_axes:
        n *= mesh.shape[a]
    return {"decode_cp": {"mesh": mesh, "seq_axes": seq_axes,
                          "dp_axes": d_ax if batch_ok else (),
                          "n_shards": n}}


def opt_state_shardings(cfg: ModelConfig, mesh: Mesh, params_shardings):
    """Optimizer state mirrors the parameter layout (g has params' shape)."""
    return {"g": params_shardings}


def activation_rules(mesh: Mesh, *, batch_size: int,
                     cfg: ModelConfig = None):
    """Logical activation constraints installed via repro.distributed.ctx."""
    d_ax = data_axes(mesh)
    dp_size = 1
    for a in d_ax:
        dp_size *= mesh.shape[a]
    dp: Any = d_ax if (d_ax and batch_size % dp_size == 0) else None
    msize = mesh.shape["model"]
    rules = {
        # Megatron-style sequence parallelism for the saved residual stream
        "residual": NamedSharding(mesh, P(dp, "model", None)),
        # expert-parallel MoE buffer (E, C, d)
        "expert_buffer": NamedSharding(mesh, P("model", None, None)),
        # Megatron-TP attention: heads local to the model axis
        "attn_q": NamedSharding(mesh, P(dp, None, "model", None)),
        "attn_kv": NamedSharding(mesh, P(dp, None, "model", None)),
    }
    if cfg is not None:
        # when the head count does not divide the TP degree, head-local
        # attention is impossible; pin the SEQUENCE dim instead
        # (context-parallel flash: q rows stay local, KV blocks broadcast
        # per scan step — perf iters #7/#8).  Forcing replication here
        # regressed minicpm/llama4 prefill 5-19x; free GSPMD choice left
        # whisper prefill at 2.1 TB of per-block psums.
        seq_sharded = NamedSharding(mesh, P(dp, "model", None, None))
        if cfg.n_heads % msize != 0:
            rules["attn_q"] = seq_sharded
        if cfg.n_kv_heads % msize != 0:
            rules["attn_kv"] = seq_sharded
    if cfg is not None and cfg.n_experts:
        rules["moe_ep"] = {"mesh": mesh, "tp": msize,
                           "dp_axes": d_ax if dp is not None else ()}
    return rules
