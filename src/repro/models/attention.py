"""GQA attention with online-softmax KV chunking.

One implementation serves training, prefill and decode:
  - scores/values matmuls go through the RedMulE ``Engine``, so attention
    inherits the hybrid-FP8 policy like every other GEMM;
  - the KV axis is processed in chunks with an online softmax (flash-style),
    bounding memory at O(S * chunk) — required for the 32k-prefill shapes;
  - GQA via a group axis (no materialized head repeat);
  - optional logit softcap (gemma2) and sliding window (local layers);
  - the KV cache is a ring buffer with per-slot absolute positions, so local
    layers allocate only window-sized caches (this is what makes the 500k
    decode shape tractable for the hybrid archs), and it is stored in the
    policy's fp8 format when enabled (the paper's fp8-storage /
    16-bit-compute split applied to serving).

Two cache layouts share the same online-softmax core:
  - the ring buffer above (static-batch serving: every sequence at the same
    position), and
  - a paged pool (``repro.serving`` continuous batching): per-layer K/V live
    in one flat (n_pages * page_size, Hkv, hd) token pool, each request owns
    a page table, and the layer writes/reads through precomputed slot
    mappings (:class:`PagedInfo`). Positions and masks are then per-row
    (``(B, S)``) rather than shared, since every slot decodes at its own
    sequence length.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.engine import Engine, as_engine
from repro.models import common

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
POS_SENTINEL = jnp.iinfo(jnp.int32).max // 2  # marks unwritten cache slots


class PagedInfo(NamedTuple):
    """Slot mappings for one step over the serving StateStore (repro.serving).

    The indices are layer-invariant (every layer shares the page table), so
    the serving step computes them once and the stack threads them through as
    loop-invariant closure state.

    write_idx: (B*Sq,) flat token index into the pool's token axis for each
        fresh key/value; pad rows and inactive slots point into the null
        page (page 0), which is never read back as valid.
    read_idx: (B, L) flat pool indices covering each slot's page table in
        position order, or None to attend over the fresh k/v only
        (single-shot prefill). With Sq > 1 AND read_idx set (chunked
        prefill), the layer attends over [gathered pool tokens | fresh k/v].
    k_pos: key positions with POS_SENTINEL at invalid entries, matching the
        attended keys: (B, Sq) when read_idx is None, (B, L) for decode,
        (B, L + Sq) for chunked prefill.
    slots: (B,) state row per batch row — recurrent layers read/write their
        per-slot state pools through it (prefill gathers one row; decode
        covers all rows in order).
    starts: (B,) first absolute position of this chunk; start == 0 selects
        the fresh init state over the (stale, recycled) stored row.
    lengths: (B,) valid token count of each right-padded prefill row.
    active: (B,) decode commit mask — inactive rows (free slots, slots mid
        chunked-prefill) keep their recurrent state untouched.
    chunked: trace-time constant marking a chunked-prefill step (read_idx
        set AND fresh k/v appended) — distinguishes it from decode, which
        also sets read_idx but attends over the gathered keys only.
    pages: (B, pages_per_slot) physical page-table rows (NULL = 0), or None.
        Required on a decode step under a Pallas backend, which runs the
        fused paged flash-decode kernel; the XLA backend gathers through
        read_idx instead (the reference oracle the kernel is tested against).
    page_size: tokens per physical page (trace-time constant; only
        meaningful with ``pages``).
    """

    write_idx: jnp.ndarray
    read_idx: jnp.ndarray | None
    k_pos: jnp.ndarray
    slots: jnp.ndarray | None = None
    starts: jnp.ndarray | None = None
    lengths: jnp.ndarray | None = None
    active: jnp.ndarray | None = None
    chunked: bool = False
    pages: jnp.ndarray | None = None
    page_size: int = 0


class AttnConfig(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0
    softcap: float | None = None
    window: int | None = None  # sliding window (local attention)
    # KV-axis chunk of the online softmax: bounds the live score block at
    # (B, H, Sq, kv_chunk) fp32 — the knob trading scan steps for VMEM/HBM.
    kv_chunk: int = 512


def init(key, d_model: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(key, 4)
    dq = cfg.n_heads * cfg.head_dim
    dkv = cfg.n_kv_heads * cfg.head_dim
    return {
        "q": common.dense_init(kq, d_model, dq, dtype),
        "k": common.dense_init(kk, d_model, dkv, dtype),
        "v": common.dense_init(kv, d_model, dkv, dtype),
        "o": common.dense_init(ko, dq, d_model, dtype),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _attn_constraints(mesh_ctx, b, hkv, g, sq, sk=0):
    """Sharding for the (B, Hkv, G, Sq, hd) attention layout: prefer KV-head
    partitioning, then group partitioning (GQA with few KV heads), then
    query-sequence partitioning (ragged head counts, e.g. 56 heads @ TP16).
    Decode (sq == 1): shard the KV *sequence* over 'model' instead — the
    online-softmax max/sum reductions partition into per-shard partials +
    tiny psums (flash-decoding), so the cache is never replicated."""
    if mesh_ctx is None or mesh_ctx.mesh is None:
        return None
    import numpy as _np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_ctx.mesh
    tpx = getattr(mesh_ctx, "tp_axis", "model")
    tp = mesh.shape[tpx] if tpx is not None else 1
    n_dp = int(_np.prod([mesh.shape[a] for a in mesh_ctx.dp_axes])) if mesh_ctx.dp_axes else 1
    b_ax = mesh_ctx.dp_axes if b % n_dp == 0 and b >= n_dp else None
    if tpx is not None and hkv % tp == 0 and hkv >= tp:
        q_spec = P(b_ax, tpx, None, None, None)
        kv_spec = P(b_ax, tpx, None, None)
    elif tpx is not None and sq == 1 and sk % tp == 0 and sk >= tp:
        q_spec = P(b_ax, None, None, None, None)
        kv_spec = P(b_ax, None, tpx, None)  # KV-sequence sharding (decode)
    elif tpx is not None and g % tp == 0 and g >= tp:
        q_spec = P(b_ax, None, tpx, None, None)
        kv_spec = P(b_ax, None, None, None)
    elif tpx is not None and sq % tp == 0 and sq >= tp:
        q_spec = P(b_ax, None, None, tpx, None)
        kv_spec = P(b_ax, None, None, None)
    else:
        q_spec = P(b_ax, None, None, None, None)
        kv_spec = P(b_ax, None, None, None)
    return (NamedSharding(mesh, q_spec), NamedSharding(mesh, kv_spec))


def _online_attention(q, k, v, q_pos, k_pos, cfg: AttnConfig, engine: Engine,
                      causal=True, mesh_ctx=None):
    """q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd). Online softmax over Sk chunks.

    q_pos: (Sq,) or (B, Sq) absolute positions of queries; k_pos: (Sk,) or
    (B, Sk) positions of keys (POS_SENTINEL = invalid slot). 2D positions
    give every batch row its own mask — the continuous-batching decode path,
    where each slot sits at a different sequence length.
    Returns (B, Sq, Hq, hd).
    """
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    hkv = cfg.n_kv_heads
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)
    # (1, S) for shared positions, (B, S) for per-row; masks broadcast, so
    # the shared case never materializes per-batch masks.
    q_pos = jnp.atleast_2d(q_pos)
    k_pos = jnp.atleast_2d(k_pos)

    qh = q.reshape(b, sq, hkv, g, hd).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,Sq,hd)
    kh = k.transpose(0, 2, 1, 3)  # (B, Hkv, Sk, hd)
    vh = v.transpose(0, 2, 1, 3)
    shards = _attn_constraints(mesh_ctx, b, hkv, g, sq, sk)
    if shards is not None:
        qh = jax.lax.with_sharding_constraint(qh, shards[0])
        kh = jax.lax.with_sharding_constraint(kh, shards[1])
        vh = jax.lax.with_sharding_constraint(vh, shards[1])

    # Decode: single pass over the whole cache (scores are (B,H,1,Sk) — tiny)
    # so the KV-sequence sharding partitions the softmax reductions.
    chunk = sk if sq == 1 else min(cfg.kv_chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=POS_SENTINEL)
    kh = kh.reshape(b, hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vh = vh.reshape(b, hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    k_pos_c = k_pos.reshape(k_pos.shape[0], n_chunks, chunk).transpose(1, 0, 2)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, kp = xs  # (B, Hkv, C, hd) x2, (B|1, C)
        s = engine.matmul(qh, jnp.swapaxes(kc, -1, -2)[:, :, None])
        s = s.astype(jnp.float32) * scale
        s = common.softcap(s, cfg.softcap)
        valid = kp[:, None, :] != POS_SENTINEL  # (B|1, 1, C)
        if causal:
            mask = (kp[:, None, :] <= q_pos[:, :, None]) & valid
        else:
            mask = valid
        if cfg.window is not None:
            mask = mask & (kp[:, None, :] > q_pos[:, :, None] - cfg.window)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = engine.matmul(p.astype(q.dtype), vc[:, :, None]).astype(jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    if n_chunks == 1:
        (m, l, acc), _ = step((m0, l0, acc0), (kh[0], vh[0], k_pos_c[0]))
    else:
        # Flash-attention-style backward: recompute each chunk's scores in
        # the VJP instead of materializing (n_chunks, B, H, Sq, C) residuals
        # — the memory fix measured in EXPERIMENTS.md §Perf (hillclimb A.3).
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(step), (m0, l0, acc0), (kh, vh, k_pos_c)
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, hd)
    return out.astype(q.dtype)


def apply(
    params,
    x,
    positions,
    cfg: AttnConfig,
    engine: Engine,
    *,
    cache: dict | None = None,
    cross_kv: tuple | None = None,
    causal: bool = True,
    mesh_ctx=None,
    paged: PagedInfo | None = None,
):
    """Full attention layer. x: (B, S, D); positions: (S,) absolute, or
    (B, S) when every row sits at its own position (paged decode).

    cache (decode/prefill): {"k": (B, Smax, Hkv, hd), "v": ..., "pos": (Smax,),
    "index": ()} — ring buffer; writes of length S must not cross the ring
    boundary (always true: prefill starts at 0, decode writes length 1).
    With ``paged`` set, cache is instead the layer's flat token pool
    {"kp": (N, Hkv, hd), "vp": ...} written/read through the slot mappings.
    cross_kv: precomputed (k, v, k_pos) for encoder-decoder cross-attention.
    """
    engine = as_engine(engine)
    b, s, _ = x.shape
    q = _split_heads(common.dense_apply(params["q"], x, engine), cfg.n_heads, cfg.head_dim)
    if cross_kv is None:
        k = _split_heads(common.dense_apply(params["k"], x, engine), cfg.n_kv_heads, cfg.head_dim)
        v = _split_heads(common.dense_apply(params["v"], x, engine), cfg.n_kv_heads, cfg.head_dim)
        pos2d = jnp.broadcast_to(jnp.atleast_2d(positions), (b, s))
        q = common.apply_rope(q, pos2d, cfg.rope_theta, cfg.rope_fraction)
        k = common.apply_rope(k, pos2d, cfg.rope_theta, cfg.rope_fraction)
    else:
        k, v, cross_pos = cross_kv

    new_cache = None
    kernel_ctx = None
    if paged is not None and cache is not None and cross_kv is None:
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        ck = cache["kp"].at[paged.write_idx].set(
            k.reshape(b * s, hkv, hd).astype(cache["kp"].dtype)
        )
        cv = cache["vp"].at[paged.write_idx].set(
            v.reshape(b * s, hkv, hd).astype(cache["vp"].dtype)
        )
        new_cache = {"kp": ck, "vp": cv}
        if paged.read_idx is not None and paged.chunked:
            # Chunked prefill: attend over [earlier chunks' tokens gathered
            # through the page table | this chunk's fresh k/v]. paged.k_pos
            # already covers the concatenation (gathered entries at
            # positions >= chunk start are sentinel-masked, so the fresh
            # keys are never double-counted).
            k = jnp.concatenate(
                [ck[paged.read_idx].astype(engine.policy.compute), k], axis=1
            )
            v = jnp.concatenate(
                [cv[paged.read_idx].astype(engine.policy.compute), v], axis=1
            )
        elif paged.read_idx is not None:
            if engine.backend in ("pallas", "pallas_interpret"):
                if paged.pages is None or paged.active is None or s != 1:
                    raise ValueError(
                        "paged decode under a Pallas backend runs the "
                        "page-walk kernel and needs PagedInfo.pages and "
                        f".active with one query token (got pages="
                        f"{paged.pages is not None}, active="
                        f"{paged.active is not None}, tokens={s})"
                    )
                # Decode via the fused paged flash-decode kernel: the page
                # table is scalar-prefetched into the kernel, which walks
                # exactly the pages each slot owns (fp8 pools dequantize
                # in-tile). No gather, no padded contiguous copy.
                from repro.kernels import ops as kernel_ops

                kernel_ctx = kernel_ops.paged_decode_attention(
                    q[:, 0], ck, cv,
                    paged.pages, paged.starts, paged.active,
                    page_size=paged.page_size,
                    window=cfg.window, softcap=cfg.softcap,
                    backend=engine.backend,
                )[:, None]  # (B, 1, Hq, hd)
            else:
                # XLA decode: gather every slot's pages in position order
                # (the reference oracle of the kernel above).
                k = ck[paged.read_idx].astype(engine.policy.compute)
                v = cv[paged.read_idx].astype(engine.policy.compute)
        k_pos = paged.k_pos
    elif cache is not None and cross_kv is None:
        max_len = cache["k"].shape[1]
        if s > 1:
            # Single-shot prefill (from position 0): attend over the fresh
            # k/v; write only the last `max_len` tokens into the (possibly
            # window-sized) cache.
            keep = min(s, max_len)
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k[:, -keep:].astype(cache["k"].dtype), 0, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v[:, -keep:].astype(cache["v"].dtype), 0, axis=1
            )
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions[-keep:], 0, axis=0
            )
            # index counts ring *writes* (next slot = index % max_len), so the
            # oldest entry is always the one overwritten.
            new_cache = {"k": ck, "v": cv, "pos": cpos, "index": cache["index"] + keep}
            k_pos = positions
        else:
            # Decode: ring-buffer append, attend over the cache.
            slot = cache["index"] % max_len
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1
            )
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions, slot, axis=0
            )
            new_cache = {"k": ck, "v": cv, "pos": cpos, "index": cache["index"] + s}
            k = ck.astype(engine.policy.compute)
            v = cv.astype(engine.policy.compute)
            k_pos = cpos
    elif cross_kv is not None:
        k_pos = cross_pos
    else:
        k_pos = positions

    if kernel_ctx is not None:
        out = kernel_ctx
    else:
        out = _online_attention(
            q, k, v, positions, k_pos, cfg, engine,
            causal=causal and cross_kv is None, mesh_ctx=mesh_ctx,
        )
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = common.dense_apply(params["o"], out, engine)
    return out, new_cache


def init_cache(batch: int, max_len: int, cfg: AttnConfig, dtype) -> dict:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), dtype),
        "v": jnp.zeros((batch, max_len, hkv, hd), dtype),
        "pos": jnp.full((max_len,), POS_SENTINEL, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


def init_paged_pool(n_tokens: int, cfg: AttnConfig, dtype) -> dict:
    """One layer's flat KV token pool (n_pages * page_size slots), shared by
    every request through per-slot page tables (repro.serving)."""
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "kp": jnp.zeros((n_tokens, hkv, hd), dtype),
        "vp": jnp.zeros((n_tokens, hkv, hd), dtype),
    }
