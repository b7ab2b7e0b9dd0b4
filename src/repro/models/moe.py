"""Mixture-of-Experts FFN with two interchangeable implementations.

``dense``  — compute every expert for every token and weight by the router
             gates. Always correct, mesh-agnostic, E/k x wasted FLOPs. This is
             the verification oracle and the §Perf baseline.
``ep``     — expert parallelism under ``shard_map``: experts are sharded over
             the 'model' mesh axis; activations are replicated across 'model'
             between TP ops, so each model shard locally sorts its tokens by
             expert, gathers a fixed-capacity buffer per *local* expert, runs
             the expert FFN, and scatter-adds the gated outputs; a single
             psum over 'model' combines shards. No all-to-all — comm is one
             activation-sized all-reduce (docs/DESIGN.md).

Expert GEMMs go through the RedMulE Engine like every other projection.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.engine import Engine, as_engine
from repro.models import common


class MoEConfig(NamedTuple):
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    impl: str = "dense"  # dense | ep
    act: str = "swiglu"


def init(key, cfg: MoEConfig, dtype=jnp.bfloat16):
    kr, ku, kg, kd = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in = 1.0 / jnp.sqrt(d)
    s_out = 1.0 / jnp.sqrt(f)
    return {
        "router": {"w": (jax.random.normal(kr, (d, e), jnp.float32) * 0.02).astype(jnp.float32)},
        "up": (jax.random.normal(ku, (e, d, f), jnp.float32) * s_in).astype(dtype),
        "gate": (jax.random.normal(kg, (e, d, f), jnp.float32) * s_in).astype(dtype),
        "down": (jax.random.normal(kd, (e, f, d), jnp.float32) * s_out).astype(dtype),
    }


def _router(params, x2, cfg: MoEConfig):
    """x2: (T, d) -> (top-k probs (T, k), top-k ids (T, k), aux loss)."""
    logits = jnp.matmul(x2.astype(jnp.float32), params["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(top_i, cfg.n_experts, dtype=jnp.float32)
    fe = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = cfg.n_experts * jnp.sum(me * fe)
    return top_p, top_i, aux


def _expert_ffn(up_w, gate_w, down_w, x, cfg: MoEConfig, engine):
    h = common.weight_apply(x, up_w, engine)
    g = common.weight_apply(x, gate_w, engine)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
         if cfg.act == "swiglu" else common.gelu(g) * h)
    return common.weight_apply(h, down_w, engine)


def apply_dense(params, x, cfg: MoEConfig, engine: Engine):
    engine = as_engine(engine)
    b, s, d = x.shape
    e, f = cfg.n_experts, cfg.d_ff
    x2 = x.reshape(b * s, d)
    top_p, top_i, aux = _router(params, x2, cfg)
    # Gate matrix (T, E): zeros outside the top-k.
    gates = jnp.sum(
        jax.nn.one_hot(top_i, e, dtype=jnp.float32) * top_p[..., None], axis=1
    )
    # All experts as one wide GEMM: (T, d) @ (d, E*f).
    up_all = common.weight_apply(
        x2, params["up"].transpose(1, 0, 2).reshape(d, e * f), engine, relaid=True)
    gate_all = common.weight_apply(
        x2, params["gate"].transpose(1, 0, 2).reshape(d, e * f), engine, relaid=True)
    h = jax.nn.silu(gate_all.astype(jnp.float32)).astype(up_all.dtype) * up_all
    h = h.reshape(-1, e, f) * gates[..., None].astype(h.dtype)
    y = common.weight_apply(
        h.reshape(-1, e * f), params["down"].reshape(e * f, d), engine)
    return y.reshape(b, s, d), aux


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _ep_local(params, x, cfg: MoEConfig, engine: Engine, ep_axis: str):
    """Per-device body under shard_map. x: (B_l, S, d) local tokens
    (replicated over the 'model' axis); expert params sharded over ep_axis.
    """
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    e_local = params["up"].shape[0]
    n_shards = jax.lax.axis_size(ep_axis)
    shard = jax.lax.axis_index(ep_axis)
    e_total = e_local * n_shards

    top_p, top_i, aux = _router(params, x2, cfg)
    # Flatten assignments and sort by expert id.
    flat_e = top_i.reshape(-1)  # (t*k,)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)
    order = jnp.argsort(flat_e)
    se, sp, st = flat_e[order], flat_p[order], flat_t[order]
    counts = jnp.bincount(flat_e, length=e_total)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])

    cap = _ceil_to(int(t * cfg.top_k / e_total * cfg.capacity_factor) or 1, 8)
    # Pad sorted arrays so dynamic_slice windows never clamp short.
    se = jnp.pad(se, (0, cap), constant_values=-1)
    sp = jnp.pad(sp, (0, cap))
    st = jnp.pad(st, (0, cap))

    out = jnp.zeros((t, d), jnp.float32)
    for j in range(e_local):
        eg = shard * e_local + j  # global expert id
        start = starts[eg]
        tok = jax.lax.dynamic_slice_in_dim(st, start, cap)
        pj = jax.lax.dynamic_slice_in_dim(sp, start, cap)
        valid = jnp.arange(cap) < counts[eg]
        tok = jnp.where(valid, tok, 0)
        xin = jnp.take(x2, tok, axis=0)  # (cap, d)
        yj = _expert_ffn(
            params["up"][j], params["gate"][j], params["down"][j], xin, cfg, engine
        ).astype(jnp.float32)
        yj = yj * (pj * valid)[:, None]
        out = out.at[tok].add(jnp.where(valid[:, None], yj, 0.0))

    # Combine across expert shards in bf16 (halves the psum wire bytes; the
    # per-token partial sums were accumulated in f32 locally).
    out = jax.lax.psum(out.astype(x.dtype), ep_axis)
    aux = jax.lax.pmean(aux, ep_axis)
    return out.reshape(b, s, d), aux


def apply_ep(params, x, cfg: MoEConfig, engine: Engine, mesh, dp_axes, ep_axis):
    """Expert-parallel MoE. Experts sharded over ``ep_axis`` of ``mesh``."""
    body = functools.partial(
        _ep_local, cfg=cfg, engine=as_engine(engine), ep_axis=ep_axis
    )
    pspec = {
        "router": {"w": P()},
        "up": P(ep_axis),
        "gate": P(ep_axis),
        "down": P(ep_axis),
    }
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, P(dp_axes, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )(params, x)
    return y, aux


def apply(params, x, cfg: MoEConfig, engine: Engine, *, mesh=None,
          dp_axes=None, ep_axis=None):
    if cfg.impl == "ep" and mesh is not None and ep_axis is not None:
        return apply_ep(params, x, cfg, engine, mesh, dp_axes, ep_axis)
    return apply_dense(params, x, cfg, engine)
