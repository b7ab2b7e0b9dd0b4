"""Static validation of every tile table in ``kernels/tuning.py``.

RedMulE's utilization claim rests on tiles that evenly feed the CE array;
the software mirror is that every band of the tuning layer must produce
tiles the TPU compiler accepts — the K tile is the last dim of the x block,
so it is lane- as well as sublane-aligned; the decode head block is the
whole KV-head axis or a multiple of 8 — inside the VMEM budget for every
storage byte width, with the documented cross-band monotonicity (the K
tile deepens as M thins). This module checks those properties
table-by-table and by sweeping representative problems through the real
selection functions — no kernel ever runs.

Coverage is enforced structurally: :func:`discover_tables` introspects the
tuning module for anything table-shaped (a module-level dict keyed by
byte-width), and :func:`validate_tuning_tables` fails if a table exists
that the validator does not know — adding a band without teaching the
validator about it is itself a finding.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.kernels import tuning

# Representative serving/training shapes per geometry knob: N spans one
# lane to many, K spans sub-sublane to model-width.
_SWEEP_N = (64, 128, 384, 4096)
_SWEEP_K = (48, 256, 4096)
_SWEEP_DTYPES = (jnp.float8_e4m3fn, jnp.bfloat16, jnp.float32)
# Band-boundary M values: every band interior + both sides of every seam.
_SWEEP_M = (1, 2, 7, 8, 9, 12, 16, 17, 31, 64, 65, 96, 512, 513, 2048)
# KV-head counts of the zoo (and a ragged 12) for the decode head block.
_SWEEP_HKV = (1, 2, 4, 8, 12, 16, 32)

# GEMM band tables: name -> (largest M the band serves, entry layout).
# Layout "bmnk" = (bm, bn, bk) triples; "kn" = (bk, bn) pairs with bm
# derived from M by the band rule.
GEMM_TABLES = {
    "_HEURISTIC": (None, "bmnk"),
    "_SKINNY_HEURISTIC": ("_SKINNY_M", "kn"),
    "_VERIFY_HEURISTIC": ("_VERIFY_M", "kn"),
    "_CHUNK_HEURISTIC": ("_CHUNK_M", "kn"),
    "_BATCH_PREFILL_HEURISTIC": ("_BATCH_PREFILL_M", "kn"),
}
ATTN_TABLES = ("_DECODE_ATTN_HEURISTIC",)
# The K tile must deepen (weakly) as the M band thins: training ->
# batched-prefill -> chunk -> verify -> skinny.
_BK_ORDER = (
    "_HEURISTIC", "_BATCH_PREFILL_HEURISTIC", "_CHUNK_HEURISTIC",
    "_VERIFY_HEURISTIC", "_SKINNY_HEURISTIC",
)
_ITEMSIZES = (1, 2, 4)
# Int-keyed module dicts that are constants, not tuning tables.
_NON_TABLES = frozenset({"SUBLANE"})


@dataclasses.dataclass(frozen=True)
class TileFinding:
    table: str
    entry: str
    detail: str

    def __str__(self) -> str:
        return f"kernels/tuning.py::{self.table}[{self.entry}]: {self.detail}"


def discover_tables(module=tuning) -> list[str]:
    """Module-level dicts keyed entirely by ints (byte widths) — the shape
    every tuning table here has."""
    out = []
    for name, val in vars(module).items():
        if (
            name not in _NON_TABLES
            and isinstance(val, dict)
            and val
            and all(isinstance(k, int) for k in val)
        ):
            out.append(name)
    return sorted(out)


def _band_max_m(mod, ceiling_name: str | None) -> int:
    if ceiling_name is None:
        return 4096  # training band: any large M behaves alike
    return getattr(mod, ceiling_name)


def _bm_for_band(table: str, m: int, sub: int) -> int:
    """The M tile each band's rule produces for a problem of M rows."""
    if table in ("_SKINNY_HEURISTIC", "_VERIFY_HEURISTIC"):
        return m  # exact-M bands
    ceil = -(-m // sub) * sub
    if table == "_CHUNK_HEURISTIC":
        return ceil
    if table == "_BATCH_PREFILL_HEURISTIC":
        return min(ceil, 128)
    return ceil


def validate_tuning_tables(module=tuning) -> list[TileFinding]:
    """Every table entry + the cross-band invariants; empty list = clean."""
    findings: list[TileFinding] = []
    mod = module

    def bad(table, entry, detail):
        findings.append(TileFinding(table, str(entry), detail))

    # -- coverage: no unknown tables ------------------------------------
    known = set(GEMM_TABLES) | set(ATTN_TABLES)
    for name in discover_tables(mod):
        if name not in known:
            bad(name, "*",
                "table not covered by repro.analysis.tiles — register it "
                "in GEMM_TABLES/ATTN_TABLES with its band rule")

    lane = mod.LANE
    budget = mod._VMEM_BUDGET_BYTES

    # -- band ceilings strictly ascending -------------------------------
    ceilings = [
        ("_SKINNY_M", mod._SKINNY_M), ("_VERIFY_M", mod._VERIFY_M),
        ("_CHUNK_M", mod._CHUNK_M), ("_BATCH_PREFILL_M", mod._BATCH_PREFILL_M),
    ]
    for (na, a), (nb, b) in zip(ceilings, ceilings[1:]):
        if not a < b:
            bad(nb, "*", f"band ceiling {nb}={b} must exceed {na}={a}")

    # -- per-entry checks ------------------------------------------------
    for table, (ceiling_name, layout) in GEMM_TABLES.items():
        entries = getattr(mod, table, None)
        if entries is None:
            bad(table, "*", "table missing from kernels/tuning.py")
            continue
        for itemsize in _ITEMSIZES:
            if itemsize not in entries:
                bad(table, itemsize,
                    f"no entry for storage byte-width {itemsize}")
        max_m = _band_max_m(mod, ceiling_name)
        for itemsize, entry in entries.items():
            sub = mod.SUBLANE.get(itemsize, 8)
            if layout == "bmnk":
                bm, bn, bk = entry
                if bm % sub:
                    bad(table, itemsize,
                        f"bm={bm} not a multiple of sublane {sub}")
            else:
                bk, bn = entry
                bm = _bm_for_band(table, max_m, sub)
            if bn % lane:
                bad(table, itemsize,
                    f"bn={bn} not a multiple of the {lane} lane")
            # A 128-lane multiple is a multiple of every sublane too.
            if bk % lane:
                bad(table, itemsize,
                    f"bk={bk} not a multiple of the {lane} lane (bk is the "
                    "last dim of the x block)")
            used = mod._vmem_bytes(bm, bn, bk, itemsize)
            if used > budget:
                bad(table, itemsize,
                    f"worst-case tile ({bm},{bn},{bk}) uses "
                    f"{used / 2**20:.2f} MiB > "
                    f"{budget / 2**20:.0f} MiB VMEM budget before the "
                    "halving loop — the band would always run degraded")

    # -- cross-band K-depth monotonicity --------------------------------
    for itemsize in _ITEMSIZES:
        bks = []
        for table in _BK_ORDER:
            entries = getattr(mod, table, {})
            if itemsize not in entries:
                continue
            entry = entries[itemsize]
            bks.append((table, entry[2] if len(entry) == 3 else entry[0]))
        for (ta, a), (tb, b) in zip(bks, bks[1:]):
            if a > b:
                bad(tb, itemsize,
                    f"K tile {b} shallower than wider band {ta}'s {a}: "
                    "the freed VMEM of a thinner M tile must go into K")

    # -- decode-attn table ----------------------------------------------
    for name in ATTN_TABLES:
        entries = getattr(mod, name, None)
        if entries is None:
            bad(name, "*", "table missing from kernels/tuning.py")
            continue
        for itemsize in _ITEMSIZES:
            if itemsize not in entries:
                bad(name, itemsize,
                    f"no entry for storage byte-width {itemsize}")
        for itemsize, (ppb, hb) in entries.items():
            if ppb < 1 or hb < 1:
                bad(name, itemsize, f"degenerate blocks ({ppb},{hb})")
            if hb % mod.DECODE_HEAD_TILE:
                bad(name, itemsize,
                    f"head_block={hb} not a multiple of "
                    f"{mod.DECODE_HEAD_TILE}: below the whole KV-head axis "
                    "the TPU compiler refuses it, so the clamp replaces it")
            # the kernel binds the pool once per page of the block
            used = 2 * ppb * 16 * hb * 128 * itemsize  # page=16, hd=128
            if used > mod._DECODE_ATTN_VMEM_BYTES:
                bad(name, itemsize,
                    f"({ppb},{hb}) blows the decode-attn VMEM budget at "
                    "page_size=16, head_dim=128")
        if 1 in entries and 2 in entries and entries[1][0] != 2 * entries[2][0]:
            bad(name, 1,
                f"fp8 pages_per_block {entries[1][0]} != 2x bf16's "
                f"{entries[2][0]} — fp8 halves page bytes, the table is "
                "documented to double the walk")

    # -- candidate sets are safe at any byte width ----------------------
    for i, (bm, bn, bk) in enumerate(mod.AUTOTUNE_CANDIDATES):
        if bn % lane:
            bad("AUTOTUNE_CANDIDATES", i, f"bn={bn} not lane-aligned")
        if bk % lane:
            bad("AUTOTUNE_CANDIDATES", i, f"bk={bk} not lane-aligned")
        for itemsize in _ITEMSIZES:
            if mod._vmem_bytes(bm, bn, bk, itemsize) > budget:
                bad("AUTOTUNE_CANDIDATES", i,
                    f"({bm},{bn},{bk}) exceeds the VMEM budget at "
                    f"itemsize {itemsize} — the sweep would always skip it")
    for i, cand in enumerate(mod.DECODE_ATTN_CANDIDATES):
        ppb, hb = mod.clamp_decode_attn_blocks(
            *cand, pages_per_slot=64, n_kv_heads=8, page_size=16,
            head_dim=128, itemsize=2,
        )
        if 2 * ppb * 16 * hb * 128 * 2 > mod._DECODE_ATTN_VMEM_BYTES:
            bad("DECODE_ATTN_CANDIDATES", i,
                f"{cand} still over the VMEM budget after clamping")

    # -- decode head block as the kernel sees it ------------------------
    for hkv in _SWEEP_HKV:
        for dtype in _SWEEP_DTYPES:
            _, hb = mod.decode_attn_blocks(
                pages_per_slot=64, n_kv_heads=hkv, page_size=16,
                head_dim=128, storage_dtype=dtype,
            )
            if hkv % hb or (hb != hkv and hb % mod.DECODE_HEAD_TILE):
                bad("decode_attn_blocks", f"Hkv={hkv},{jnp.dtype(dtype).name}",
                    f"head_block={hb} is neither the whole KV-head axis nor "
                    f"a multiple of {mod.DECODE_HEAD_TILE} dividing it")

    # -- sweep the real selection functions -----------------------------
    for dtype in _SWEEP_DTYPES:
        itemsize = jnp.dtype(dtype).itemsize
        sub = mod.SUBLANE.get(itemsize, 8)
        for m in _SWEEP_M:
            for n in _SWEEP_N:
                for k in _SWEEP_K:
                    entry = f"M={m},N={n},K={k},{jnp.dtype(dtype).name}"
                    bm, bn, bk = mod.heuristic_block_sizes(m, n, k, dtype)
                    if bn % lane:
                        bad("heuristic_block_sizes", entry,
                            f"bn={bn} not lane-aligned")
                        continue
                    if bk % lane and bk < k:
                        bad("heuristic_block_sizes", entry,
                            f"bk={bk} neither lane-aligned nor the whole K")
                    if m <= mod._VERIFY_M and bm != m:
                        bad("heuristic_block_sizes", entry,
                            f"exact-M band returned bm={bm} != M={m} "
                            "(decode/verify rows must not pad)")
                    if m > mod._VERIFY_M and bm % sub:
                        bad("heuristic_block_sizes", entry,
                            f"bm={bm} not sublane({sub})-aligned outside "
                            "the exact-M bands")
                    pad_m = -(-m // bm) * bm if bm else 0
                    if pad_m >= m + bm:
                        bad("heuristic_block_sizes", entry,
                            f"bm={bm} over-pads M={m} to {pad_m}")
                    if mod._vmem_bytes(bm, bn, bk, itemsize) > budget:
                        bad("heuristic_block_sizes", entry,
                            f"({bm},{bn},{bk}) over the VMEM budget")
                    # clamping the chosen tile must be a fixpoint
                    again = mod.clamp_blocks(bm, bn, bk, m, n, k, itemsize)
                    if again != (bm, bn, bk):
                        bad("heuristic_block_sizes", entry,
                            f"chosen tile {(bm, bn, bk)} not clamp-stable "
                            f"(re-clamps to {again})")
    return findings
