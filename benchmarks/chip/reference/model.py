"""Plain float32 reference of the dense decoder the configurations describe.

No kernels, no cache, no batching: one sequence at a time, every matrix
product at ``Precision.HIGHEST`` (a TPU runs an f32 product in bf16 passes
otherwise).  Weights are read by name from the benchmark's own arrays
(``benchmarks/chip/weights.py``) and cast to f32 one layer at a time inside
a scan, so a model whose f32 copy would not fit beside its bf16 weights
still runs.  Nothing here imports the program.

The block, as the configuration files state it:

* stablelm-1.6b: LayerNorm (with bias, eps 1e-5), rotary on the first 25% of
  each head (16 of 64 dims), multi-head attention (32 q = 32 kv heads),
  SwiGLU MLP, untied LM head.
* yi-6b: RMSNorm, full rotary with theta 5e6, grouped-query attention
  (32 q heads over 4 kv heads, 8:1), SwiGLU MLP, untied LM head.

Departures from the published models, each stated in the configuration's
``departures`` and followed here because the program computes it so: no
q/k/v bias in stablelm-1.6b; the rotary inverse frequencies of a partial
rotary taken over the whole head dim (``rope_inv_freq_dim``); yi-6b's RMSNorm
eps 1e-6.  Both models carry the A3C value head (a ``d x 1`` linear on the
final norm), which the training loss uses and serving ignores.

``quant=True`` is the control, the model computed in int8 (w8a8): every
weight matrix in int8 with one scale per output column, every activation
entering a matrix product in int8 with one scale per token, and K/V in int8
with one scale per token and head, each dequantized into the same f32
arithmetic.  The products' backward passes run in int8 too: each operand of
the two gradient products is rounded with one scale per row or column of
the dimension it keeps, as an int8 training step would (elsewhere the
rounding passes gradients straight through).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    n_heads: int
    n_kv: int
    hd: int
    vocab: int
    n_layers: int
    norm: str
    eps: float
    rot_dim: int
    freq_dim: int
    theta: float

    @classmethod
    def of(cls, c: dict) -> "Arch":
        return cls(d=c["hidden_size"], n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], hd=c["head_dim"],
                   vocab=c["vocab_size"], n_layers=c["num_hidden_layers"],
                   norm=c["norm"],
                   eps=c.get("layer_norm_eps", c.get("rms_norm_eps")),
                   rot_dim=int(round(c["partial_rotary_factor"]
                                     * c["head_dim"])),
                   freq_dim=c["assumed"]["rope_inv_freq_dim"],
                   theta=float(c["rope_theta"]))


def int8_dequant(x, axis):
    """Symmetric absmax int8 along ``axis``, back in f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _int8(x, axis):
    return x + jax.lax.stop_gradient(int8_dequant(x, axis) - x)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


@jax.custom_vjp
def int8_matmul(h, w):
    """``h (.., S, K) @ w (K, N)`` with both operands through int8: ``h``
    per token, ``w`` per output column."""
    return _mm(int8_dequant(h, -1), int8_dequant(w, -2))


def _int8_matmul_fwd(h, w):
    return int8_matmul(h, w), (h, w)


def _int8_matmul_bwd(res, g):
    h, w = res
    dh = _mm(int8_dequant(g, -1), int8_dequant(w, -1).T)
    h2, g2 = h.reshape(-1, h.shape[-1]), g.reshape(-1, g.shape[-1])
    dw = _mm(int8_dequant(h2, -2).T, int8_dequant(g2, -2))
    return dh, dw


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def matmul(h, w, quant: bool):
    """``h @ w`` at full f32 precision; for the control, in int8 forward
    and backward."""
    w = w.astype(jnp.float32)
    return int8_matmul(h, w) if quant else _mm(h, w)


def norm(a: Arch, x, p: dict, prefix: str):
    if a.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + a.eps) * p[prefix + ".scale"]
                + p[prefix + ".bias"])
    if a.norm == "rmsnorm":
        ms = jnp.mean(jnp.square(x), -1, keepdims=True)
        return x / jnp.sqrt(ms + a.eps) * p[prefix + ".scale"]
    raise ValueError(a.norm)


def rope(a: Arch, x, positions):
    """x (S, H, hd); the first ``rot_dim`` dims rotate as two halves."""
    half = a.rot_dim // 2
    inv = 1.0 / (a.theta ** (jnp.arange(0, a.freq_dim, 2, dtype=jnp.float32)
                             / a.freq_dim))[:half]
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:a.rot_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., a.rot_dim:]], axis=-1)


def attention(a: Arch, q, k, v, q_block: int):
    """Causal attention of one sequence.  q (S, Hq, hd), k/v (S, Hkv, hd);
    queries go in blocks of ``q_block`` rows to bound the score matrix."""
    s = q.shape[0]
    g = a.n_heads // a.n_kv
    qb = min(q_block, s)
    qg = q.reshape(s // qb, qb, a.n_kv, g, a.hd)
    kpos = jnp.arange(s)

    def one(args):
        i, qi = args
        sc = jnp.einsum("qkgd,skd->qkgs", qi, k, precision=HI) * a.hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[:, None, None, :],
                       sc, -jnp.inf)
        return jnp.einsum("qkgs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(one, (jnp.arange(s // qb), qg))
    return o.reshape(s, a.n_heads * a.hd)


def block(a: Arch, lp: dict, x, positions, *, quant: bool = False,
          q_block: int = 512):
    """One residual block of one sequence, x (S, d) f32; ``lp`` holds the
    layer's arrays under names without the ``layers.`` prefix."""
    w = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def lin(h, name):
        y = matmul(h, w[name + ".w"], quant)
        return y + w[name + ".b"] if name + ".b" in w else y

    h = norm(a, x, w, "ln1")
    s = x.shape[0]
    q = rope(a, lin(h, "attn.wq").reshape(s, a.n_heads, a.hd), positions)
    k = rope(a, lin(h, "attn.wk").reshape(s, a.n_kv, a.hd), positions)
    v = lin(h, "attn.wv").reshape(s, a.n_kv, a.hd)
    if quant:
        k, v = _int8(k, -1), _int8(v, -1)
    x = x + lin(attention(a, q, k, v, q_block), "attn.wo")
    h = norm(a, x, w, "ln2")
    gate = lin(h, "mlp.gate")
    return x + lin(jax.nn.silu(gate) * lin(h, "mlp.up"), "mlp.down")


def layer_params(named: dict) -> dict:
    return {k[len("layers."):]: v for k, v in named.items()
            if k.startswith("layers.")}


def embed(named: dict, tokens, quant: bool = False):
    t = named["embed.table"][tokens].astype(jnp.float32)
    return _int8(t, -1) if quant else t


def head(a: Arch, named: dict, x, *, quant: bool = False):
    """Final norm, LM head and value head: (logits (S, V), values (S,))."""
    h = norm(a, x, {k: v.astype(jnp.float32) for k, v in named.items()
                    if k.startswith("final_norm.")}, "final_norm")
    logits = matmul(h, named["lm_head.w"], quant)
    values = matmul(h, named["value_head.w"], quant)[:, 0]
    return logits, values


def forward(a: Arch, named: dict, tokens, *, quant: bool = False,
            q_block: int = 512):
    """Logits (S, V) f32 of one token sequence (S,), layer by layer."""
    positions = jnp.arange(tokens.shape[0])
    x = embed(named, tokens, quant)

    def step(x, lp):
        return block(a, lp, x, positions, quant=quant, q_block=q_block), None

    x, _ = jax.lax.scan(step, x, layer_params(named))
    return head(a, named, x, quant=quant)[0]
