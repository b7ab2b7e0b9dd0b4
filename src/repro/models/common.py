"""Shared model building blocks. Functional style: params are dict pytrees.

All matrix products route through a :class:`repro.engine.Engine` so the
paper's mixed-precision engine is the single GEMM substrate of every
architecture. Layer entry points accept an Engine (or, for compatibility,
a bare PrecisionPolicy coerced via ``as_engine``).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.engine import Engine, as_engine
from repro.obs import count_weight_prep

Params = dict[str, Any]


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    return {"w": w.astype(dtype)}


def dense_apply(p: Params, x, engine: Engine):
    return weight_apply(x, p["w"], engine, p.get("b"))


def weight_apply(x, w, engine: Engine, b=None, *, relaid: bool = False):
    """x @ w (+ b) for a GEMM weight ``w`` (a parameter, or an
    ``engine.LayerWeight``). Where the traced step must prepare w for the
    kernel (a cast or pad the engine needs, or the transpose or relayout
    its caller made: ``relaid``), the call is counted (``repro.obs``)."""
    engine = as_engine(engine)
    count_weight_prep(engine.weight_prep_bytes(x, w, relaid=relaid))
    return engine.linear(x, w, b)


def norm_init(d: int, kind: str = "rmsnorm", dtype=jnp.float32):
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def norm_apply(p: Params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def softcap(x, cap: float | None):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {
    "gelu": gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
}


# Rotary embeddings -----------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0):
    """Frequencies for RoPE over the first ``fraction`` of the head dim.

    ``fraction=0.5`` gives ChatGLM's 2d/partial rotary (rotate half the dim,
    pass the rest through).
    """
    rot = int(head_dim * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, fraction: float = 1.0):
    """x: (B, S, H, hd), positions: (B, S) int32."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, theta, fraction)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed_apply(p: Params, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed_apply(p: Params, x, engine: Engine):
    """Tied unembedding: logits = x @ table.T through the engine.

    A table prepared for serving (``Transformer.serving_params``) carries
    the product's weight as ``unembed``: transposed already, in the
    kernel's storage format, its vocabulary zero-padded to the lane. The
    padded logits are cut off here, so no padded id can be sampled."""
    if "unembed" in p:
        return weight_apply(x, p["unembed"], engine)[..., : p["table"].shape[0]]
    return weight_apply(x, p["table"].T, engine, relaid=True)
