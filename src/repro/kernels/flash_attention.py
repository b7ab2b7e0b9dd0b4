"""Fused flash-attention Pallas kernels (TPU target; paged decode runs on v5e).

This is the deployment path for the §Perf A.4 projection (EXPERIMENTS.md):
the XLA-lowered online-softmax scan materializes per-chunk score tensors in
HBM (~13 GB per layer pass on the 33B train cell); this kernel keeps the
(block_q, block_k) score tile in VMEM, so attention HBM traffic collapses to
q/k/v/o (+ per-row stats).

Same tiling discipline as ``redmule_gemm``: grid (BH, Sq/bq, Sk/bk) with the
KV dimension innermost, accumulating (acc, m, l) in VMEM scratch across KV
blocks — the Z-buffer/feedback pattern of the paper's datapath applied to
attention. Causal masking is positional per tile; fully-masked tiles are
skipped via ``pl.when`` (the leftover/clock-gating idea, in software).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            nk: int, block_q: int, block_k: int, scale: float,
            causal: bool, seq_q: int, seq_k: int, softcap: float | None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # A causal tile is dead when its lowest q position < its first k position.
    live = (not causal) or ((qi + 1) * block_q - 1 >= kj * block_k)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(kj == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel(pt_ref, len_ref, act_ref, q_ref, *refs,
                         ppb: int, nblk: int, page_size: int, scale: float,
                         window: int | None, softcap: float | None):
    """One (slot, kv-head block, page block) program of the paged decode grid.

    pt/len/act are scalar-prefetched (SMEM): the page table drives the K/V
    BlockSpec index maps, so each program's DMA fetches exactly the physical
    pages its slot owns — no host-side gather, no padded contiguous copy.
    refs unpacks to [k_0..k_{ppb-1}, v_0..v_{ppb-1}, o, acc, m, l]: the same
    pool array is bound ``ppb`` times with per-page index maps, which is how
    a "block" spans multiple non-contiguous physical pages.
    """
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    o_ref = refs[2 * ppb]
    acc_ref, m_ref, l_ref = refs[2 * ppb + 1:]

    slot = pl.program_id(0)
    blk = pl.program_id(2)
    _, hb, g, hd = q_ref.shape
    rows = hb * g

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # The decoding position: the fresh key was written at q_len, so the
    # attended window is positions [0, q_len] (gather path: lpos <= seq_len).
    q_len = len_ref[slot]
    slot_live = act_ref[slot] != 0
    q = q_ref[0].astype(jnp.float32)  # (hb, G, hd)

    for i in range(ppb):
        logical = blk * ppb + i
        base = logical * page_size
        # Dead pages never touch the softmax state: inactive slots (free /
        # mid chunked-prefill), NULL page-table entries (unallocated tails
        # AND pages recycled out of a sliding window), and pages entirely
        # past the decode position — the leftover/clock-gating idea applied
        # to the page walk.
        live = slot_live & (pt_ref[slot, logical] != 0) & (base <= q_len)
        if window is not None:
            live &= base + page_size - 1 > q_len - window

        @pl.when(live)
        def _compute(i=i, base=base):
            # In-tile dequant: pools may store fp8 E4M3 — the cast to f32
            # happens on the VMEM tile (the paper's fp8-storage /
            # 16-bit-compute split, done at the kernel boundary).
            k = k_refs[i][...].astype(jnp.float32)  # (page_size, hb, hd)
            v = v_refs[i][...].astype(jnp.float32)
            kt = jnp.transpose(k, (1, 0, 2))  # (hb, page_size, hd)
            s = jax.lax.dot_general(
                q, kt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # (hb, G, page_size)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            pos = base + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, page_size), 2
            )
            mask = pos <= q_len
            if window is not None:
                mask &= pos > q_len - window
            s = jnp.where(mask, s, NEG_INF).reshape(rows, page_size)

            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = m_new
            pv = jax.lax.dot_general(
                p.reshape(hb, g, page_size), jnp.transpose(v, (1, 0, 2)),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # (hb, G, hd)
            acc_ref[...] = acc_ref[...] * alpha + pv.reshape(rows, hd)

    @pl.when(blk == nblk - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.reshape(hb, g, hd).astype(o_ref.dtype)


def paged_flash_decode_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    active: jnp.ndarray,
    *,
    page_size: int,
    pages_per_block: int = 1,
    head_block: int = 1,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged flash-decode attention over the serving KV token pools.

    q: (S, Hkv, G, hd) grouped queries — one token per slot, GQA groups on
    their own axis (the same grouping rule ``_online_attention`` uses).
    k_pool/v_pool: (num_pages * page_size, Hkv, hd) flat token pools, any
    storage dtype (fp8 E4M3 pages dequantize in-tile). page_table: (S, P)
    physical page ids in position order, NULL (0) for unallocated or
    window-recycled entries. seq_lens: (S,) the decode position per slot.
    active: (S,) which slots actually decode this step.

    Grid: (slots, Hkv/head_block, P/pages_per_block) with the page axis
    innermost; (m, l, acc) online-softmax state lives in VMEM scratch and
    carries across page blocks, exactly like the prefill kernel carries it
    across KV blocks. Returns (S, Hkv, G, hd) in q's dtype; inactive slots
    return zeros (their logits are discarded by the server).
    """
    s, hkv, g, hd = q.shape
    n_pages_tbl = page_table.shape[1]
    ppb = max(1, min(pages_per_block, n_pages_tbl))
    hb = max(1, min(head_block, hkv))
    while hkv % hb:
        hb -= 1
    padded = -(-n_pages_tbl // ppb) * ppb
    if padded != n_pages_tbl:
        # NULL-pad the page-table tail: padded entries map to page 0 and are
        # pl.when-skipped, so they cost a deduped null-page DMA at most.
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - n_pages_tbl)))
    nblk = padded // ppb
    grid = (s, hkv // hb, nblk)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _paged_decode_kernel, ppb=ppb, nblk=nblk, page_size=page_size,
        scale=scale, window=window, softcap=softcap,
    )

    def kv_spec(i):
        # Block index along the pool's token axis IS the physical page id:
        # the index map reads it from the scalar-prefetched page table.
        return pl.BlockSpec(
            (page_size, hb, hd),
            lambda si, h, b, pt, lens, act, i=i: (pt[si, b * ppb + i], h, 0),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, g, hd),
                         lambda si, h, b, pt, lens, act: (si, h, 0, 0)),
            *[kv_spec(i) for i in range(ppb)],
            *[kv_spec(i) for i in range(ppb)],
        ],
        out_specs=pl.BlockSpec((1, hb, g, hd),
                               lambda si, h, b, pt, lens, act: (si, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb * g, hd), jnp.float32),
            pltpu.VMEM((hb * g, 1), jnp.float32),
            pltpu.VMEM((hb * g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, g, hd), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(
        page_table.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        active.astype(jnp.int32),
        q,
        *([k_pool] * ppb),
        *([v_pool] * ppb),
    )


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    softcap: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    true_seq_q: int | None = None,
    true_seq_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (BH, Sq, d); k/v: (BH, Sk, d) — GQA expansion happens in ops.py.

    Sq/Sk are padded to block multiples by the wrapper; ``true_seq_*``
    mask the padding inside the kernel.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(
            f"sequence ({sq}, {sk}) not divisible by blocks ({bq}, {bk}); "
            "pad the sequence and mask inside the kernel"
        )
    nk = sk // bk
    grid = (bh, sq // bq, nk)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _kernel, nk=nk, block_q=bq, block_k=bk, scale=scale,
        causal=causal, seq_q=true_seq_q or sq, seq_k=true_seq_k or sk,
        softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
