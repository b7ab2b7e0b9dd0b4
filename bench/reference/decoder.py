"""Plain float32 reference of the granite decoders the benchmark runs.

Written from the published architecture (pre-norm RMSNorm blocks, GQA
attention with rotary positions, SwiGLU feed-forward or a top-k routed
mixture of SwiGLU experts, tied embeddings), in straightforward
``jax.numpy`` at ``Precision.HIGHEST``. It imports nothing of the program.
Like the program, it leaves out granite's four scalar multipliers, adds
the RMSNorm epsilon the program adds (the file's ``as_run`` group) and
rotates adjacent pairs in RoPE; each is listed under ``departures`` in the
configuration file.
Its weights are drawn anew from the run's seed with the same draws the
program's ``model.init`` makes (the key tree and the normal draws are the
published initialisation of this repository's models), rounded to the
served storage type, and then held in float32.

Everything works layer by layer: one layer's weights exist at a time, so
the reference fits beside nothing else on one chip.

``quant="int4"`` is the control: every matrix-product operand is rounded to
symmetric int4 (seven levels a side, one scale per row of the left operand
and per column of the right operand, along the contraction axis), the
precision below the configuration's E4M3 operands.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256  # query rows per attention block: bounds the score block


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths a configuration file states, under the file's keys."""

    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    n_experts: int = 0
    top_k: int = 0
    weight_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim", c["hidden_size"] // c["num_attention_heads"]),
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            eps=float(c.get("as_run", {}).get("rms_norm_eps", c["rms_norm_eps"])),
            n_experts=c.get("num_local_experts", 0),
            top_k=c.get("num_experts_per_tok", 0),
            weight_dtype=c.get("weight_dtype", "bfloat16"),
        )


# -- weights ------------------------------------------------------------------


def _draw(key, shape, scale, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=0)
def embed_table(dims: Dims, key):
    k_embed = jax.random.split(key, 8)[0]
    return _draw(k_embed, (dims.vocab, dims.d_model), 0.02, dims.weight_dtype)


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(dims: Dims, key, layer):
    """Layer ``layer``'s weights, float32, drawn as the program draws them."""
    d, f, hd, dt = dims.d_model, dims.d_ff, dims.head_dim, dims.weight_dtype
    k_stack = jax.random.split(key, 8)[1]
    k_units = jax.random.split(k_stack)[0]
    k_unit = jax.random.split(k_units, dims.layers)[layer]
    k_block = jax.random.split(k_unit, 1)[0]
    ks = jax.random.split(k_block, 6)
    kq, kk, kv, ko = jax.random.split(ks[0], 4)
    w = {
        "q": _draw(kq, (d, dims.n_heads * hd), 1 / math.sqrt(d), dt),
        "k": _draw(kk, (d, dims.n_kv_heads * hd), 1 / math.sqrt(d), dt),
        "v": _draw(kv, (d, dims.n_kv_heads * hd), 1 / math.sqrt(d), dt),
        "o": _draw(ko, (dims.n_heads * hd, d), 1 / math.sqrt(dims.n_heads * hd), dt),
        "norm1": jnp.ones((d,), jnp.float32),
        "norm2": jnp.ones((d,), jnp.float32),
    }
    if dims.n_experts:
        e = dims.n_experts
        kr, ku, kg, kd = jax.random.split(ks[2], 4)
        s_in = 1.0 / jnp.sqrt(d)
        s_out = 1.0 / jnp.sqrt(f)
        w["router"] = jax.random.normal(kr, (d, e), jnp.float32) * 0.02
        w["up"] = _draw(ku, (e, d, f), s_in, dt)
        w["gate"] = _draw(kg, (e, d, f), s_in, dt)
        w["down"] = _draw(kd, (e, f, d), s_out, dt)
    else:
        ku, kg, kd = jax.random.split(ks[2], 3)
        w["up"] = _draw(ku, (d, f), 1 / math.sqrt(d), dt)
        w["gate"] = _draw(kg, (d, f), 1 / math.sqrt(d), dt)
        w["down"] = _draw(kd, (f, d), 1 / math.sqrt(f), dt)
    return w


# -- arithmetic ---------------------------------------------------------------


def int4(x, axis):
    """Symmetric int4 rounding, one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


@jax.custom_vjp
def _int4_mm(a, b):
    return jnp.matmul(int4(a, -1), int4(b, -2), precision=HIGHEST)


def _int4_mm_fwd(a, b):
    return _int4_mm(a, b), (a, b)


def _int4_mm_bwd(res, g):
    """The backward products take int4 operands too (the cotangent and the
    saved operands, each along its contraction axis)."""
    a, b = res
    da = _int4_mm(g, jnp.swapaxes(b, -1, -2))
    db = _int4_mm(jnp.swapaxes(a, -1, -2), g)
    # Operands broadcast over leading axes: sum each back to its shape.
    da = jnp.sum(da, axis=tuple(range(da.ndim - a.ndim))) if da.ndim > a.ndim else da
    db = jnp.sum(db, axis=tuple(range(db.ndim - b.ndim))) if db.ndim > b.ndim else db
    return da, db


_int4_mm.defvjp(_int4_mm_fwd, _int4_mm_bwd)


def mm(a, b, quant: str | None = None):
    """a (..., M, K) @ b (..., K, N) in float32; int4 operands under
    ``quant="int4"``, forward and backward."""
    if quant == "int4":
        return _int4_mm(a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotary positions on (B, S, H, hd), rotating adjacent pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def attention(q, k, v, quant):
    """Causal GQA. q (B, S, H, hd), k/v (B, S, Hkv, hd) -> (B, S, H*hd).
    Query rows go in blocks so the score block stays small."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2).transpose(0, 2, 3, 1)  # (B, H, hd, S)
    v = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)  # (B, H, S, hd)
    qb = min(Q_BLOCK, s)
    n_blk = -(-s // qb)
    qp = jnp.pad(q, ((0, 0), (0, n_blk * qb - s), (0, 0), (0, 0)))
    qp = qp.reshape(b, n_blk, qb, h, hd).transpose(1, 0, 3, 2, 4)  # (n,B,H,qb,hd)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        i, qi = args
        sc = mm(qi, k, quant) / math.sqrt(hd)  # (B, H, qb, S)
        q_pos = i * qb + jnp.arange(qb)
        sc = jnp.where(key_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), v, quant)  # (B, H, qb, hd)

    out = jax.lax.map(block, (jnp.arange(n_blk), qp))  # (n, B, H, qb, hd)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, n_blk * qb, h * hd)
    return out[:, :s]


def attn_block(dims: Dims, w, x, quant):
    b, s, _ = x.shape
    hd = dims.head_dim
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = rms_norm(x, w["norm1"], dims.eps)
    q = mm(h, w["q"], quant).reshape(b, s, dims.n_heads, hd)
    k = mm(h, w["k"], quant).reshape(b, s, dims.n_kv_heads, hd)
    v = mm(h, w["v"], quant).reshape(b, s, dims.n_kv_heads, hd)
    q, k = rope(q, pos, dims.rope_theta), rope(k, pos, dims.rope_theta)
    return x + mm(attention(q, k, v, quant), w["o"], quant)


def swiglu(x, up, gate, down, quant):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down, quant)


def route(dims: Dims, router, h):
    """Top-k experts per token and their renormalised softmax weights."""
    probs = jax.nn.softmax(jnp.matmul(h, router, precision=HIGHEST), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, dims.top_k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_i


_ROWS = 512  # routed rows per expert call, rounded up to this multiple


@functools.partial(jax.jit, static_argnames="quant")
def _expert(x, up, gate, down, quant=None):
    return swiglu(x, up, gate, down, quant)


def moe_ffn(dims: Dims, w, h, quant):
    """Each expert runs on the tokens routed to it, and only those."""
    shape = h.shape
    h2 = h.reshape(-1, shape[-1])
    top_p, top_i = jax.jit(functools.partial(route, dims))(w["router"], h2)
    top_p, top_i = np.asarray(top_p), np.asarray(top_i)
    out = jnp.zeros_like(h2)
    for e in range(dims.n_experts):
        tok, slot = np.nonzero(top_i == e)
        if not len(tok):
            continue
        n = -(-len(tok) // _ROWS) * _ROWS
        rows = np.zeros(n, np.int32)
        rows[:len(tok)] = tok
        wt = np.zeros((n, 1), np.float32)
        wt[:len(tok), 0] = top_p[tok, slot]
        y = _expert(h2[rows], w["up"][e], w["gate"][e], w["down"][e], quant=quant)
        out = out.at[rows].add(y * wt)  # padded rows carry weight 0
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnums=0, static_argnames="quant")
def dense_layer(dims: Dims, w, x, quant=None):
    x = attn_block(dims, w, x, quant)
    h = rms_norm(x, w["norm2"], dims.eps)
    return x + swiglu(h, w["up"], w["gate"], w["down"], quant)


@functools.partial(jax.jit, static_argnums=0, static_argnames="quant")
def _moe_attn(dims: Dims, w, x, quant=None):
    x = attn_block(dims, w, x, quant)
    return x, rms_norm(x, w["norm2"], dims.eps)


@functools.partial(jax.jit, static_argnums=0, static_argnames="quant")
def head(dims: Dims, table, final_scale, x, quant=None):
    """Logits (B, S, V) from the last hidden states."""
    return mm(rms_norm(x, final_scale, dims.eps), table.T, quant)


def layer(dims: Dims, w, x, quant=None):
    if not dims.n_experts:
        return dense_layer(dims, w, x, quant=quant)
    x, h = _moe_attn(dims, w, x, quant=quant)
    return x + moe_ffn(dims, w, h, quant)


def hidden(dims: Dims, key, tokens, quant=None):
    """Last hidden states (B, S, d) of token rows (B, S), layer by layer."""
    x = embed_table(dims, key)[jnp.asarray(tokens)]
    for i in range(dims.layers):
        x = layer(dims, layer_weights(dims, key, i), x, quant)
    return x


def logits_at(dims: Dims, key, tokens, rows, cols, quant=None):
    """Reference logits (N, V) at positions (rows[i], cols[i]) of the token
    rows (B, S)."""
    x = hidden(dims, key, tokens, quant)[jnp.asarray(rows), jnp.asarray(cols)]
    final = jnp.ones((dims.d_model,), jnp.float32)
    return head(dims, embed_table(dims, key), final, x[None], quant=quant)[0]
