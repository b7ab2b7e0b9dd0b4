"""Block-size selection for the RedMulE Pallas kernel.

Replaces the hardcoded 128^3 tiles with a three-level policy:

  1. Explicit ``block_*`` arguments (or the ``REPRO_BLOCK_MNK`` env var,
     e.g. ``REPRO_BLOCK_MNK=64,128,256``) always win.
  2. With ``REPRO_AUTOTUNE=1`` and concrete (non-traced) operands, a
     timing-based autotune sweeps a candidate table and caches the winner to
     disk, keyed by (backend, policy, op, B, M, N, K). Cache location:
     ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/redmule_blocks.json``.
  3. Otherwise a heuristic table keyed on the storage dtype's byte width
     picks the tile: fp8 operands are 1 byte across HBM, so the K tile can
     double at the same VMEM budget (the software analogue of the paper's
     "FP8 doubles effective bandwidth").

All levels clamp tiles to the (padded) problem so small/ragged shapes never
allocate oversized VMEM tiles. The TPU tiling constraint (Mosaic refuses a
block whose last dim is not a multiple of the 128 lane, unless it spans
the whole array dim) binds N, which is the last dim of the w and output
blocks, and K, which is the last dim of the x block: every table K tile is
a multiple of 128 as well as of the sublane.
"""
from __future__ import annotations

import json
import os
import time
import warnings

import jax.numpy as jnp

LANE = 128
# Sublane granularity per storage byte-width (TPU min-tile second-to-last dim).
SUBLANE = {1: 32, 2: 16, 4: 8}
# Base (bm, bn, bk) per storage byte-width, before clamping to the problem.
_HEURISTIC = {
    1: (128, 128, 256),  # fp8: 1 B/elem across HBM -> double the K tile
    2: (128, 128, 128),  # fp16/bf16
    4: (128, 128, 128),  # fp32
}
# Serving decode GEMMs are skinny: M = #slots (often 1-8) x K = d_model.
# Padding such rows to a training-size M tile wastes the whole tile on
# garbage rows, so up to this M the tile clamps to M exactly and the freed
# VMEM goes into a deeper K tile (K is where decode's work actually is —
# the M=1 depthwise rows of paper Fig. 11, transplanted to serving).
_SKINNY_M = 8
# (bk, bn) per storage byte-width for the skinny-M decode table.
_SKINNY_HEURISTIC = {
    1: (1024, 128),
    2: (512, 128),
    4: (512, 128),
}
# Speculative-verify GEMMs live exactly at the seam between the skinny
# decode table and the chunk table: M = k+1 verify positions (2..16 for
# draft depths 1..15). Like decode rows they clamp block_m to M exactly —
# rounding M=9..16 up to an fp8 sublane (32) would spend most of the tile
# on padding — with a K tile between the skinny and chunk depths.
_VERIFY_M = 16
# (bk, bn) per storage byte-width for the verify-M table.
_VERIFY_HEURISTIC = {
    1: (768, 128),
    2: (384, 128),
    4: (384, 128),
}
# Chunked-prefill GEMMs sit between decode and training: M = chunk size
# (16/32/64 tokens). The M tile rounds the chunk up to the sublane grid
# (never a full 128 training tile) and, like the skinny table, spends the
# spare VMEM on a deeper K tile. (M <= _VERIFY_M is claimed by the verify
# table above, so in practice this covers (16, 64].)
_CHUNK_M = 64
# (bk, bn) per storage byte-width for the chunk-M prefill table.
_CHUNK_HEURISTIC = {
    1: (512, 128),
    2: (256, 128),
    4: (256, 128),
}
# Batched multi-slot prefill GEMMs: M = P x chunk for P prefilling slots
# packed into one (P, chunk) step (P bucketed to {1,2,4,8}, chunks 16-64),
# so M runs past the 64-row chunk ceiling up to 512. These are mid-size
# problems — big enough that a full 128-row M tile stops being padding
# waste, small enough that the training table's balanced tiles leave VMEM
# idle — so the M tile caps at 128 and the K tile sits between the chunk
# and training depths (for 2- and 4-byte storage those are 256 and 128, and
# no lane-aligned depth lies strictly between them).
_BATCH_PREFILL_M = 512
# (bk, bn) per storage byte-width for the batched-prefill table.
_BATCH_PREFILL_HEURISTIC = {
    1: (384, 128),
    2: (256, 128),
    4: (256, 128),
}
# VMEM budget for one grid step's working set (x, w, y/out, acc tiles).
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# Paged flash-decode attention: (pages_per_block, head_block) per storage
# byte-width. pages_per_block is how many physical KV pages one grid step
# walks (more pages per step = fewer grid steps but a bigger VMEM working
# set); head_block tiles the KV-head axis. fp8 pages are 1 B/elem, so twice
# the pages fit the same VMEM budget — the same rule as the GEMM K tile.
# The head block is the second-to-last dim of the (page_size, head_block,
# head_dim) pool block, so Mosaic takes only the whole KV-head axis or a
# multiple of 8 (see clamp_decode_attn_blocks).
DECODE_HEAD_TILE = 8
_DECODE_ATTN_HEURISTIC = {
    1: (8, 8),
    2: (4, 8),
    4: (4, 8),
}
# Candidate (pages_per_block, head_block) pairs swept by the decode-attn
# autotuner (clamped/deduped per problem like the GEMM candidates).
DECODE_ATTN_CANDIDATES = (
    (1, 8),
    (2, 8),
    (4, 8),
    (8, 8),
    (16, 8),
    (4, 16),
)
# VMEM budget for one decode-attn grid step (k+v pages, q, acc tiles).
_DECODE_ATTN_VMEM_BYTES = 4 * 1024 * 1024

# Candidate tilings swept by the autotuner (clamped/deduped per problem).
AUTOTUNE_CANDIDATES = (
    (128, 128, 128),
    (128, 128, 256),
    (128, 256, 128),
    (256, 128, 128),
    (64, 128, 128),
    (64, 128, 256),
    (32, 128, 512),
    # Skinny decode rows (M in {1, 2, 4, 8}); clamping dedupes these for
    # training-size problems so the sweep cost stays bounded.
    (1, 128, 512),
    (2, 128, 512),
    (4, 128, 512),
    (8, 128, 256),
    # Chunk-sized prefill rows (M = prefill chunk, 16/32/64); clamping
    # dedupes these for training-size problems just like the skinny set.
    (16, 128, 512),
    (32, 128, 256),
    (64, 128, 256),
    # Speculative-verify rows (M = k+1 for draft depth k): exact-M tiles at
    # the skinny/chunk seam, swept at the verify table's K depths.
    (3, 128, 512),
    (5, 128, 512),
    (9, 128, 384),
    (12, 128, 384),
    (16, 128, 384),
    # Batched multi-slot prefill (M = P x chunk, 64 < M <= 512): 128-cap M
    # tiles at the batched table's K depths, plus the neighbours the
    # heuristic rejects (a sub-128 M split, a deeper fp8 K).
    (96, 128, 256),
    (128, 128, 384),
    (256, 128, 128),
)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def default_cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "redmule_blocks.json"),
    )


def _vmem_bytes(bm: int, bn: int, bk: int, itemsize: int, acc_itemsize: int = 4) -> int:
    operands = (bm * bk + bk * bn) * itemsize
    acc_and_out = 2 * bm * bn * acc_itemsize
    return operands + acc_and_out


def _fit_vmem(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """Shrink the K tile until the working set fits the VMEM budget,
    keeping it a multiple of the 128 lane."""
    while _vmem_bytes(bm, bn, bk, itemsize) > _VMEM_BUDGET_BYTES and bk > LANE:
        bk = max(LANE, bk // 2 // LANE * LANE)
    return bk


def _dividing_depth(bk: int, k: int, fits) -> int:
    """The K tile of lane multiples nearest to ``bk`` that divides K rounded
    up to the lane (``bk`` itself where it does; ties go to the deeper),
    among the depths ``fits`` admits. A decode or verify row reads each
    weight once per step, so a tile that leaves no K remainder spares the
    step a padded copy of the weight (``down``'s K 12800 against a
    1024-deep tile)."""
    lanes = _ceil_to(k, LANE) // LANE
    depths = [d * LANE for d in range(1, lanes + 1)
              if lanes % d == 0 and (d == 1 or fits(d * LANE))]
    return min(depths, key=lambda d: (abs(d - bk), -d))


def clamp_blocks(
    bm: int, bn: int, bk: int, m: int, n: int, k: int, itemsize: int = 4
) -> tuple[int, int, int]:
    """Clamp a tiling to the problem: no tile larger than the padded dim.

    The cap rounds each dim up to the dtype's sublane granularity (SUBLANE)
    / the 128 lane so a clamped tile still evenly divides the padded
    problem. Explicit sub-sublane requests are honored as given (interpret
    mode accepts them; real-TPU callers own that choice).
    """
    sub = SUBLANE.get(itemsize, 8)
    bm = max(1, min(bm, _ceil_to(m, sub)))
    bn = max(1, min(bn, _ceil_to(n, LANE)))
    bk = max(1, min(bk, _ceil_to(k, sub)))
    return bm, bn, bk


def heuristic_block_sizes(
    m: int, n: int, k: int, storage_dtype
) -> tuple[int, int, int]:
    """Table-driven tile choice keyed on storage byte width, problem-clamped.

    Auto-selected tiles respect the dtype's TPU min-tile granularity: the
    M tile is a multiple of SUBLANE[itemsize], the N and K tiles multiples
    of the 128 lane unless clamped to span the whole (padded) dim —
    except skinny decode rows (m <= _SKINNY_M), where block_m clamps to m
    exactly so one-token decode GEMMs don't pad to training tiles.
    """
    itemsize = jnp.dtype(storage_dtype).itemsize
    sub = SUBLANE.get(itemsize, 8)
    if m <= _SKINNY_M:
        # Decode-shape table: block_m == M exactly (no sublane round-up —
        # a training tile would spend its whole M on padding; interpret
        # mode accepts sub-sublane tiles, real-TPU re-tunes override this
        # via the autotune cache). K tile deepens into the freed VMEM.
        bk, bn = _SKINNY_HEURISTIC.get(itemsize, (512, 128))
        return _exact_m_blocks(m, n, k, bk, bn, itemsize)
    if m <= _VERIFY_M:
        # Speculative-verify table: block_m == M exactly (same sub-sublane
        # rationale as the skinny table — a verify row is k+1 real tokens,
        # and a 32-row fp8 tile would be half padding at k=15), with a K
        # tile between the skinny and chunk depths.
        bk, bn = _VERIFY_HEURISTIC.get(itemsize, (384, 128))
        return _exact_m_blocks(m, n, k, bk, bn, itemsize)
    if m <= _CHUNK_M:
        # Chunk-prefill table: M tile = the chunk rounded to the sublane
        # grid, K tile deepened into the VMEM a 128-row tile would waste.
        bk, bn = _CHUNK_HEURISTIC.get(itemsize, (256, 128))
        bm = _ceil_to(m, sub)
        bk = _fit_vmem(bm, bn, bk, itemsize)
        bm, bn, bk = clamp_blocks(bm, bn, bk, m, n, k, itemsize)
        return bm, _ceil_to(bn, LANE), _ceil_to(bk, sub)
    if m <= _BATCH_PREFILL_M:
        # Batched-prefill table: M tile = min(sublane-rounded M, 128) —
        # a (P, chunk) step of, say, 4x48 rows tiles as 2 grid steps of
        # 96 rows rather than padding to 128x2 or falling into the
        # training table's shallower K. The K tile sits between the chunk
        # and training depths (bk_training <= bk_batched <= bk_chunk).
        bk, bn = _BATCH_PREFILL_HEURISTIC.get(itemsize, (256, 128))
        bm = min(_ceil_to(m, sub), 128)
        bk = _fit_vmem(bm, bn, bk, itemsize)
        bm, bn, bk = clamp_blocks(bm, bn, bk, m, n, k, itemsize)
        return bm, _ceil_to(bn, LANE), _ceil_to(bk, sub)
    bm, bn, bk = _HEURISTIC.get(itemsize, (128, 128, 128))
    bk = _fit_vmem(bm, bn, bk, itemsize)
    bm, bn, bk = clamp_blocks(bm, bn, bk, m, n, k, itemsize)
    # Round auto tiles up to the sublane/lane grid (still <= the caps above,
    # which are sublane/lane multiples themselves).
    return _ceil_to(bm, sub), _ceil_to(bn, LANE), _ceil_to(bk, sub)


def _exact_m_blocks(m: int, n: int, k: int, bk: int, bn: int,
                    itemsize: int) -> tuple[int, int, int]:
    """Tiles of the decode and verify tables: block_m == M, and the K tile
    nearest the table's depth that divides the lane-rounded K and fits the
    VMEM budget."""
    sub = SUBLANE.get(itemsize, 8)
    bk = _dividing_depth(
        _fit_vmem(m, bn, bk, itemsize), k,
        lambda d: _vmem_bytes(m, bn, d, itemsize) <= _VMEM_BUDGET_BYTES,
    )
    _, bn, bk = clamp_blocks(m, bn, bk, m, n, k, itemsize)
    return m, _ceil_to(bn, LANE), _ceil_to(bk, sub)


def _env_blocks() -> tuple[int | None, int | None, int | None]:
    raw = os.environ.get("REPRO_BLOCK_MNK", "")
    if not raw:
        return (None, None, None)
    try:
        parts = [int(p) for p in raw.split(",")]
        if len(parts) != 3:
            raise ValueError(raw)
    except ValueError:
        warnings.warn(
            f"ignoring malformed REPRO_BLOCK_MNK={raw!r} "
            "(expected 'bm,bn,bk', e.g. '64,128,256'); using heuristic tiles",
            stacklevel=3,
        )
        return (None, None, None)
    return tuple(parts)  # type: ignore[return-value]


def _load_cache(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_cache(path: str, cache: dict) -> None:
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is best-effort; never fail the GEMM over it


def autotune_block_sizes(
    x,
    w,
    y,
    *,
    gop,
    policy,
    backend: str,
    cache_path: str | None = None,
    candidates=AUTOTUNE_CANDIDATES,
    repeats: int = 3,
) -> tuple[int, int, int]:
    """Time each candidate tiling on the real operands; cache the winner.

    Requires concrete arrays (call it outside jit). The cache survives across
    processes so the sweep runs once per (backend, policy, op, shape).
    """
    import jax

    from repro.kernels import ops as kernel_ops  # local: avoid import cycle

    batch, m = kernel_ops.kernel_rows(x, w, y)
    k, n = x.shape[-1], w.shape[-1]
    key = f"{backend}/{policy.name}/{gop.name}/{batch or 1}x{m}x{n}x{k}"
    path = cache_path or default_cache_path()
    cache = _load_cache(path)
    if key in cache:
        return tuple(cache[key])

    itemsize = jnp.dtype(policy.storage_fwd).itemsize
    seen = set()
    best, best_t = None, float("inf")
    for cand in candidates:
        bm, bn, bk = clamp_blocks(*cand, m, n, k, itemsize)
        if (bm, bn, bk) in seen:
            continue
        seen.add((bm, bn, bk))

        def run():
            return kernel_ops.gemm_op(
                x, w, y, gop=gop, policy=policy, backend=backend,
                block_m=bm, block_n=bn, block_k=bk,
            )

        try:
            jax.block_until_ready(run())  # compile + correctness smoke
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run())
                ts.append(time.perf_counter() - t0)
            t = min(ts)
        except Exception:  # noqa: BLE001 — an invalid tiling just loses
            continue
        if t < best_t:
            best, best_t = (bm, bn, bk), t

    if best is None:
        best = heuristic_block_sizes(m, n, k, policy.storage_fwd)
    cache[key] = list(best)
    _save_cache(path, cache)
    return best


def resolve_block_sizes(
    m: int,
    n: int,
    k: int,
    *,
    policy,
    requested: tuple[int | None, int | None, int | None] = (None, None, None),
) -> tuple[int, int, int]:
    """Static (trace-safe) resolution: explicit args > env override > table."""
    itemsize = jnp.dtype(policy.storage_fwd).itemsize
    env = _env_blocks()
    heur = heuristic_block_sizes(m, n, k, policy.storage_fwd)
    bm, bn, bk = (
        req if req is not None else (ev if ev is not None else hv)
        for req, ev, hv in zip(requested, env, heur)
    )
    return clamp_blocks(bm, bn, bk, m, n, k, itemsize)


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "") == "1"


# ---------------------------------------------------------------------------
# Paged flash-decode attention blocks
# ---------------------------------------------------------------------------


def _env_decode_attn() -> tuple[int | None, int | None]:
    raw = os.environ.get("REPRO_DECODE_ATTN_BLOCKS", "")
    if not raw:
        return (None, None)
    try:
        parts = [int(p) for p in raw.split(",")]
        if len(parts) != 2:
            raise ValueError(raw)
    except ValueError:
        warnings.warn(
            f"ignoring malformed REPRO_DECODE_ATTN_BLOCKS={raw!r} "
            "(expected 'pages_per_block,head_block', e.g. '4,8'); "
            "using the heuristic table",
            stacklevel=3,
        )
        return (None, None)
    return tuple(parts)  # type: ignore[return-value]


def clamp_decode_attn_blocks(
    ppb: int, hb: int, *, pages_per_slot: int, n_kv_heads: int,
    page_size: int, head_dim: int, itemsize: int,
) -> tuple[int, int]:
    """Clamp a (pages_per_block, head_block) pair to the problem: head_block
    divides the KV-head count and is either the whole count or a multiple of
    DECODE_HEAD_TILE (Mosaic's rule for the pool block's second-to-last
    dim), pages_per_block never exceeds the page table width, and the k+v
    working set stays inside the VMEM budget."""
    ppb = max(1, min(ppb, pages_per_slot))
    hb = max(1, min(hb, n_kv_heads))
    if hb < n_kv_heads:
        hb -= hb % DECODE_HEAD_TILE
        while hb and n_kv_heads % hb:
            hb -= DECODE_HEAD_TILE
        hb = hb or n_kv_heads
    while (
        2 * ppb * page_size * hb * head_dim * itemsize > _DECODE_ATTN_VMEM_BYTES
        and ppb > 1
    ):
        ppb //= 2
    return ppb, hb


def decode_attn_blocks(
    *,
    pages_per_slot: int,
    n_kv_heads: int,
    page_size: int,
    head_dim: int,
    storage_dtype,
    requested: tuple[int | None, int | None] = (None, None),
) -> tuple[int, int]:
    """(pages_per_block, head_block) for the paged flash-decode kernel:
    explicit args > ``REPRO_DECODE_ATTN_BLOCKS`` env override > byte-width
    heuristic table, all problem-clamped (see the GEMM tables above —
    same three-level policy)."""
    itemsize = jnp.dtype(storage_dtype).itemsize
    env = _env_decode_attn()
    heur = _DECODE_ATTN_HEURISTIC.get(itemsize, (4, DECODE_HEAD_TILE))
    ppb, hb = (
        req if req is not None else (ev if ev is not None else hv)
        for req, ev, hv in zip(requested, env, heur)
    )
    return clamp_decode_attn_blocks(
        ppb, hb, pages_per_slot=pages_per_slot, n_kv_heads=n_kv_heads,
        page_size=page_size, head_dim=head_dim, itemsize=itemsize,
    )


def autotune_decode_attn(
    q,
    k_pool,
    v_pool,
    page_table,
    seq_lens,
    active,
    *,
    page_size: int,
    window: int | None,
    softcap: float | None,
    backend: str,
    cache_path: str | None = None,
    candidates=DECODE_ATTN_CANDIDATES,
    repeats: int = 3,
) -> tuple[int, int]:
    """Time each (pages_per_block, head_block) candidate on the real decode
    operands; cache the winner to the same disk cache as the GEMM tiles.
    Requires concrete arrays (call it outside jit)."""
    import jax

    from repro.kernels import ops as kernel_ops  # local: avoid import cycle

    s, hq, hd = q.shape
    hkv = k_pool.shape[1]
    key = (
        f"decode_attn/{backend}/{s}x{hq}x{hkv}x{hd}/"
        f"ps{page_size}xP{page_table.shape[1]}/"
        f"{jnp.dtype(k_pool.dtype).name}/w{window or 0}"
    )
    path = cache_path or default_cache_path()
    cache = _load_cache(path)
    if key in cache:
        return tuple(cache[key])

    itemsize = jnp.dtype(k_pool.dtype).itemsize
    seen = set()
    best, best_t = None, float("inf")
    for cand in candidates:
        ppb, hb = clamp_decode_attn_blocks(
            *cand, pages_per_slot=page_table.shape[1], n_kv_heads=hkv,
            page_size=page_size, head_dim=hd, itemsize=itemsize,
        )
        if (ppb, hb) in seen:
            continue
        seen.add((ppb, hb))

        def run():
            return kernel_ops.paged_decode_attention(
                q, k_pool, v_pool, page_table, seq_lens, active,
                page_size=page_size, window=window, softcap=softcap,
                pages_per_block=ppb, head_block=hb, backend=backend,
            )

        try:
            jax.block_until_ready(run())  # compile + correctness smoke
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run())
                ts.append(time.perf_counter() - t0)
            t = min(ts)
        except Exception:  # noqa: BLE001 — an invalid tiling just loses
            continue
        if t < best_t:
            best, best_t = (ppb, hb), t

    if best is None:
        best = decode_attn_blocks(
            pages_per_slot=page_table.shape[1], n_kv_heads=hkv,
            page_size=page_size, head_dim=hd, storage_dtype=k_pool.dtype,
        )
    cache[key] = list(best)
    _save_cache(path, cache)
    return best
