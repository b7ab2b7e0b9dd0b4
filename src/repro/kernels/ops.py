"""Jit'd wrappers around the RedMulE kernel: padding, dispatch, XLA fallback.

The Pallas kernel requires block-multiple shapes; this module implements the
paper's "leftover" handling in software: ragged dims are padded to the tile
grid with values that are absorbed by the (circ, star) pair, computed, and
sliced back. See ``semiring.pad_value_for`` discussion + docs/DESIGN.md Sec. 3 (clock
gating has no TPU analogue; padding-waste is the software observable).

Batching: ``gemm_op`` accepts arbitrary leading batch dims on x (and
optionally on w / y, broadcast-compatible). On the Pallas path a weight
shared by the whole batch (2D, or batch dims all 1) folds the batch rows of
x (and of y) into M: B products of M rows become one product of B*M rows,
so the kernel fetches and widens each weight tile once per call, not once
per batch element. The fold is exact for every GEMM-Op: an output row
depends only on its own rows of x and y and keeps its own accumulation, in
the same K order for a given tile. Only a batched w (attention products,
MoE experts, xLSTM) keeps the flattened batch as the kernel's outer grid
axis. A ``layer`` index makes w a stack (L, K, N) of shared weights, one per
layer, of which the product reads one in place (a serving step's scan over
layers); where the tile would pad it, the layer is sliced out first.
Block sizes default to the selection layer in
``repro.kernels.tuning`` (heuristic table, env override, optional
disk-cached autotune), resolved from the rows each kernel call sees
(``kernel_rows``), split over the data axes of an ambient mesh.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import semiring
from repro.core.precision import FP32_REF, PrecisionPolicy
from repro.core.semiring import GemmOp, Op
from repro.kernels import tuning
from repro.kernels.redmule_gemm import redmule_gemm_pallas


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# Star identity clamped to the dtype's finite range (e4m3fn has no inf);
# the rule lives in one place: repro.core.semiring.finite_identity.
_finite_identity = semiring.finite_identity


def _pad_last2(a, rows: int, cols: int, fill):
    """Pad the trailing (rows, cols) of an nd array, batch dims untouched."""
    if rows == a.shape[-2] and cols == a.shape[-1]:
        return a
    cfg = [(0, 0)] * (a.ndim - 2) + [
        (0, rows - a.shape[-2]),
        (0, cols - a.shape[-1]),
    ]
    return jnp.pad(a, cfg, constant_values=fill)


def _pad_operands(x, w, y, gop: GemmOp, bm: int, bn: int, bk: int):
    """Pad (x, w, y) so padded K-lanes contribute the star identity.

    Padding rules per circ (docs/DESIGN.md Sec. 3):
      mul: pad x-lanes with 0 (GEMM) or +/-"inf" and w-lanes with 1 (semiring)
      add: pad both with +/-"inf"/2 (sum hits the identity)
      min/max: pad both with the star identity
    Padded M/N rows/cols are sliced away by the caller. x/w/y may carry
    leading batch dims; only the trailing two are padded.
    """
    m, k = x.shape[-2:]
    n = w.shape[-1]
    mp, np_, kp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(k, bk)
    if (mp, np_, kp) == (m, n, k):
        return x, w, y, (m, n)
    if gop.is_gemm:
        x_fill = w_fill = 0.0
    elif gop.circ is Op.MUL:
        x_fill = _finite_identity(gop.star, x.dtype)
        w_fill = 1.0
    elif gop.circ is Op.ADD:
        ident = _finite_identity(gop.star, x.dtype)
        x_fill, w_fill = ident / 2, ident / 2
    else:  # circ in {MIN, MAX}: identity is absorbing for the map too
        x_fill = _finite_identity(gop.star, x.dtype)
        w_fill = _finite_identity(gop.star, w.dtype)

    x = _pad_last2(x, mp, kp, x_fill)
    w = _pad_last2(w, kp, np_, w_fill)
    if y is not None:
        y_fill = _finite_identity(gop.star, y.dtype) if not gop.is_gemm else 0.0
        y = _pad_last2(y, mp, np_, y_fill)
    return x, w, y, (m, n)


# ---------------------------------------------------------------------------
# XLA fallback
# ---------------------------------------------------------------------------


def _xla_semiring_2d(xc, wc, gop: GemmOp, policy: PrecisionPolicy, k_chunk: int):
    """Scalable 2D semiring path: scan over K-chunks, never (M, K, N)."""
    m, k = xc.shape
    _, n = wc.shape
    circ = semiring.op_fn(gop.circ)
    star = semiring.op_fn(gop.star)
    kc = min(k_chunk, k)
    kp = _ceil_to(k, kc)
    if kp != k:
        ident = _finite_identity(gop.star, policy.compute)
        if gop.circ is Op.MUL:
            xpad, wpad = ident, 1.0
        elif gop.circ is Op.ADD:
            xpad = wpad = ident / 2
        else:
            xpad = wpad = ident
        xc = jnp.pad(xc, ((0, 0), (0, kp - k)), constant_values=xpad)
        wc = jnp.pad(wc, ((0, kp - k), (0, 0)), constant_values=wpad)
    xs = xc.reshape(m, kp // kc, kc).transpose(1, 0, 2)  # (S, M, kc)
    ws = wc.reshape(kp // kc, kc, n)  # (S, kc, N)

    ident = semiring.reduce_identity(gop.star)
    init = jnp.full((m, n), ident, policy.acc)

    def step(acc, xw):
        xi, wi = xw
        prod = circ(xi[:, :, None], wi[None, :, :]).astype(policy.acc)
        red = _reduce(gop.star, prod)
        return star(acc, red), None

    z, _ = jax.lax.scan(step, init, (xs, ws))
    return z


def _xla_gemm_op(
    x, w, y, gop: GemmOp, policy: PrecisionPolicy, out_dtype, operand_quant: bool,
    k_chunk: int = 512,
):
    """XLA path; batch dims broadcast jnp.matmul-style."""
    if operand_quant:
        xc, wc = policy.cast_in_fwd(x), policy.cast_in_fwd(w)
    else:
        xc, wc = x.astype(policy.compute), w.astype(policy.compute)
    if gop.is_gemm:
        z = jnp.matmul(xc, wc, preferred_element_type=policy.acc)
        if y is not None:
            z = z + y.astype(policy.acc)
        return z.astype(out_dtype)

    batch = np.broadcast_shapes(
        xc.shape[:-2], wc.shape[:-2], () if y is None else y.shape[:-2]
    )
    run2d = functools.partial(
        _xla_semiring_2d, gop=gop, policy=policy, k_chunk=k_chunk
    )
    if not batch:
        z = run2d(xc, wc)
    else:
        xb = jnp.broadcast_to(xc, batch + xc.shape[-2:])
        xb = xb.reshape((-1,) + xc.shape[-2:])
        if wc.ndim == 2:
            z = jax.vmap(lambda xi: run2d(xi, wc))(xb)
        else:
            wb = jnp.broadcast_to(wc, batch + wc.shape[-2:])
            wb = wb.reshape((-1,) + wc.shape[-2:])
            z = jax.vmap(run2d)(xb, wb)
        z = z.reshape(batch + z.shape[-2:])
    if y is not None:
        z = semiring.op_fn(gop.star)(y.astype(policy.acc), z)
    return z.astype(out_dtype)


def _reduce(op: Op, prod):
    if op is Op.ADD:
        return jnp.sum(prod, axis=1)
    if op is Op.MIN:
        return jnp.min(prod, axis=1)
    return jnp.max(prod, axis=1)


# ---------------------------------------------------------------------------
# Pallas path
# ---------------------------------------------------------------------------


def _pallas_gemm_op(
    x, w, y, gop: GemmOp, policy: PrecisionPolicy,
    bm: int, bn: int, bk: int, out_dtype, operand_quant: bool, interpret: bool,
    layer=None,
):
    m, kdim = x.shape[-2:]
    n = w.shape[-1]
    if layer is not None and (
        operand_quant or kdim % bk or n % bn or _ambient_mesh() is not None
    ):
        # Read in place only what the kernel takes as it is stored.
        w, layer = w[layer], None
    # The layer's weight as a shape: what it is without slicing it out.
    shared = w if layer is None else jax.ShapeDtypeStruct(w.shape[1:], w.dtype)
    out_batch = _out_batch(x, shared, y)

    # Quantize operands to the storage grid before padding so pad values are
    # exactly representable and the kernel sees true storage dtypes. Callers
    # that pre-quantize (the VJP's mixed E5M2/E4M3 backward GEMMs) pass
    # operand_quant=False and their dtypes are forwarded untouched.
    if operand_quant:
        x = x.astype(policy.storage_fwd)
        w = w.astype(policy.storage_fwd)
    if y is not None:
        # Y folds into the accumulator init: carry it at accumulator
        # precision so Z = star(Y, ...) rounds once at the output cast
        # (matches the XLA path and the oracle — no pre-round of Y).
        y = y.astype(policy.acc)

    if layer is not None or _shares_weight(w):
        # One (B*M, K) @ (K, N) call: the batch rows fold into M. A layer
        # read in place from its stack is shared by the batch too.
        w3 = w if layer is not None else w.reshape(kdim, n)
        x3 = jnp.broadcast_to(x, out_batch + (m, kdim)).reshape(-1, kdim)
        y3 = None
        if y is not None:
            y3 = jnp.broadcast_to(y, out_batch + (m, n)).reshape(-1, n)
    else:
        w3 = jnp.broadcast_to(w, out_batch + (kdim, n)).reshape(-1, kdim, n)
        x3 = jnp.broadcast_to(x, out_batch + (m, kdim)).reshape(-1, m, kdim)
        y3 = y
        if y is not None and any(d != 1 for d in y.shape[:-2]):
            y3 = jnp.broadcast_to(y, out_batch + (m, n)).reshape(-1, m, n)
        elif y is not None:
            y3 = y.reshape(m, n)  # broadcast over the batch by the kernel

    def run(x3, w3, y3=None):
        if layer is not None:
            # Only the rows of x (and y) can need padding: K and N divide.
            x3, _, y3, (mo, no) = _pad_operands(x3, shared, y3, gop, bm, bn, bk)
        else:
            x3, w3, y3, (mo, no) = _pad_operands(x3, w3, y3, gop, bm, bn, bk)
        z = redmule_gemm_pallas(
            x3, w3, y3,
            gop=gop, policy=policy,
            block_m=bm, block_n=bn, block_k=bk,
            out_dtype=out_dtype, interpret=interpret,
            w_layer=None if layer is None else jnp.reshape(layer, (1,)),
        )
        return z[..., :mo, :no]

    operands = (x3, w3) if y3 is None else (x3, w3, y3)
    mesh = _ambient_mesh()
    if mesh is not None:
        run = _shard_over_mesh(run, mesh, operands)
    z = run(*operands)
    return z.reshape(out_batch + (m, n))


def _ambient_mesh():
    """The mesh a GEMM is traced under, where the kernel must be sharded
    by hand (see ``_shard_over_mesh``); None on one device or inside a
    ``shard_map`` body."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size > 1 and not mesh.are_all_axes_manual:
        return mesh
    return None


def _row_split(mesh, m: int, batch: int | None):
    """(batch axes, M axes, data-parallel size): the data axes (every axis
    but ``model``) split the kernel's batch axis where it divides evenly,
    else its M rows where they do. ``batch`` is None for a 2D x."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    n_dp = math.prod(mesh.shape[a] for a in dp)
    if dp and batch is not None and batch % n_dp == 0:
        return dp, None, n_dp
    if dp and m % n_dp == 0:
        return None, dp, n_dp
    return None, None, n_dp


def _shard_over_mesh(run, mesh, operands):
    """Run one kernel call per device of the ambient mesh.

    Mosaic kernels cannot be partitioned by the compiler, so a GEMM traced
    under a mesh (``jax.set_mesh`` or ``jax.sharding.use_abstract_mesh``)
    is wrapped in ``shard_map``: rows (the batch axis, else M) split over
    the data axes and N over ``model`` wherever they divide evenly, and K
    is never split, so every output element is one whole kernel
    accumulation, exactly as on one device.
    """
    x, w = operands[:2]
    n_ax = None
    if "model" in mesh.axis_names and w.shape[-1] % mesh.shape["model"] == 0:
        n_ax = "model"
    b_ax, m_ax, _ = _row_split(
        mesh, x.shape[-2], x.shape[0] if x.ndim == 3 else None
    )
    batched = (b_ax,) if x.ndim == 3 else ()
    x_spec = P(*batched, m_ax, None)
    w_spec = P(b_ax, None, n_ax) if w.ndim == 3 else P(None, n_ax)
    out_spec = P(*batched, m_ax, n_ax)
    specs = [x_spec, w_spec]
    if len(operands) == 3:
        y = operands[2]
        specs.append(P(b_ax, m_ax, n_ax) if y.ndim == 3 else P(m_ax, n_ax))
    return jax.shard_map(run, mesh=mesh, in_specs=tuple(specs),
                         out_specs=out_spec, check_vma=False)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _out_batch(x, w, y) -> tuple[int, ...]:
    return np.broadcast_shapes(
        x.shape[:-2], w.shape[:-2], () if y is None else y.shape[:-2]
    )


def _shares_weight(w) -> bool:
    """Whether one weight serves the whole batch (2D, or batch dims all 1)."""
    return all(d == 1 for d in w.shape[:-2])


def kernel_rows(x, w, y=None) -> tuple[int | None, int]:
    """(batch, M) of the Pallas kernel call for these operands: a shared
    weight folds the batch rows into M and leaves no batch axis (None)."""
    out_batch = _out_batch(x, w, y)
    m = x.shape[-2]
    if _shares_weight(w):
        return None, math.prod(out_batch) * m
    return math.prod(out_batch), m


def weight_prep_bytes(x, w, *, policy: PrecisionPolicy, backend: str,
                      blocks=(None, None, None), relaid: bool = False) -> int:
    """Bytes a product of x with the weight ``w`` moves to bring w into the
    form the kernel reads: a cast to the policy's forward storage format,
    and on the Pallas backends a pad of K or N to the tile; ``relaid``
    marks a transpose or relayout its caller made. Counted as w's bytes
    read plus the prepared operand's bytes written; 0 where w is stored as
    the kernel takes it."""
    k, n = w.shape[-2:]
    kp, np_ = k, n
    if backend != "xla":
        shared = jax.ShapeDtypeStruct((k, n), w.dtype)
        _, bn, bk = tuning.resolve_block_sizes(
            _rows_per_device(x, shared, None), n, k,
            policy=policy, requested=tuple(blocks),
        )
        kp, np_ = _ceil_to(k, bk), _ceil_to(n, bn)
    cast = jnp.dtype(w.dtype) != jnp.dtype(policy.storage_fwd)
    if not (cast or relaid or (kp, np_) != (k, n)):
        return 0
    batch = math.prod(w.shape[:-2])
    written = batch * kp * np_ * jnp.dtype(policy.storage_fwd).itemsize
    return batch * k * n * jnp.dtype(w.dtype).itemsize + written


def _rows_per_device(x, w, y) -> int:
    """M of each kernel call: the folded rows, or one device's share of
    them where an ambient mesh splits M (``_shard_over_mesh``)."""
    batch, m = kernel_rows(x, w, y)
    mesh = _ambient_mesh()
    if mesh is None:
        return m
    _, m_ax, n_dp = _row_split(mesh, m, batch)
    return m // n_dp if m_ax else m


@functools.partial(
    jax.jit,
    static_argnames=(
        "gop",
        "policy",
        "block_m",
        "block_n",
        "block_k",
        "backend",
        "out_dtype",
        "operand_quant",
    ),
)
def _gemm_op_impl(
    x, w, y, layer, *,
    gop: GemmOp, policy: PrecisionPolicy,
    block_m: int, block_n: int, block_k: int,
    backend: str, out_dtype, operand_quant: bool,
):
    out_dtype = policy.out if out_dtype is None else out_dtype
    if backend == "xla":
        if layer is not None:
            w = w[layer]
        return _xla_gemm_op(x, w, y, gop, policy, out_dtype, operand_quant)
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"unknown backend {backend!r}; expected xla|pallas|pallas_interpret"
        )
    return _pallas_gemm_op(
        x, w, y, gop, policy, block_m, block_n, block_k, out_dtype,
        operand_quant, interpret=backend == "pallas_interpret", layer=layer,
    )


def gemm_op(
    x: jnp.ndarray,
    w: jnp.ndarray,
    y: jnp.ndarray | None = None,
    *,
    gop: GemmOp = semiring.MATMUL,
    policy: PrecisionPolicy = FP32_REF,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    backend: str = "xla",  # xla | pallas | pallas_interpret
    out_dtype=None,
    operand_quant: bool = True,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Public GEMM-Op entry point: Z = star(Y, star_k(circ(X, W))).

    x: (..., M, K); w: (K, N) or (..., K, N); y: optional (M, N) / (..., M, N)
    — leading dims broadcast. With ``layer`` (a () int32), w is a stack
    (L, K, N) and the product reads w[layer], shared by x's batch (see the
    module docstring). ``block_* = None`` defers to the tuning layer.
    """
    kdim, n = x.shape[-1], w.shape[-1]
    shared = w if layer is None else jax.ShapeDtypeStruct(w.shape[1:], w.dtype)
    requested = (block_m, block_n, block_k)
    if backend != "xla":
        concrete = not isinstance(x, jax.core.Tracer)
        if (
            concrete
            and layer is None
            and tuning.autotune_enabled()
            and all(b is None for b in requested)
        ):
            block_m, block_n, block_k = tuning.autotune_block_sizes(
                x, w, y, gop=gop, policy=policy, backend=backend
            )
        else:
            block_m, block_n, block_k = tuning.resolve_block_sizes(
                _rows_per_device(x, shared, y), n, kdim,
                policy=policy, requested=requested,
            )
    else:
        block_m, block_n, block_k = 0, 0, 0  # unused on the XLA path
    return _gemm_op_impl(
        x, w, y, layer,
        gop=gop, policy=policy,
        block_m=block_m, block_n=block_n, block_k=block_k,
        backend=backend, out_dtype=out_dtype, operand_quant=operand_quant,
    )


def matmul(x, w, y=None, *, policy=FP32_REF, backend="xla", **kw):
    return gemm_op(x, w, y, gop=semiring.MATMUL, policy=policy, backend=backend, **kw)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    active: jnp.ndarray,
    *,
    page_size: int,
    window: int | None = None,
    softcap: float | None = None,
    pages_per_block: int | None = None,
    head_block: int | None = None,
    backend: str = "pallas_interpret",
) -> jnp.ndarray:
    """Fused paged flash-decode attention over the StateStore's flat KV pool.

    q: (S, Hq, hd) — one fresh query token per slot; k_pool/v_pool:
    (n_pages * page_size, Hkv, hd) physical pools (possibly fp8 storage,
    dequantized in-tile); page_table: (S, pages_per_slot) int32 physical page
    ids (0 = NULL); seq_lens: (S,) int32 position of the fresh token (keys at
    positions <= seq_lens attend — the fresh key is written before attention
    reads); active: (S,) slot-live mask. Returns (S, Hq, hd) in q.dtype;
    inactive slots return zeros.

    GQA reuses the grouping rule of `_online_attention`: q is reshaped to
    (S, Hkv, G, hd) so KV pages are never materially repeated per q-head.
    ``pages_per_block`` / ``head_block = None`` defers to the tuning layer.
    """
    from repro.kernels.flash_attention import paged_flash_decode_pallas

    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"paged_decode_attention is a Pallas kernel; backend={backend!r}"
            " has no paged path (the XLA gather reference lives in"
            " models.attention)"
        )
    s, hq, hd = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    requested = (pages_per_block, head_block)
    concrete = not isinstance(q, jax.core.Tracer)
    if (
        concrete
        and tuning.autotune_enabled()
        and all(b is None for b in requested)
    ):
        ppb, hb = tuning.autotune_decode_attn(
            q, k_pool, v_pool, page_table, seq_lens, active,
            page_size=page_size, window=window, softcap=softcap,
            backend=backend,
        )
    else:
        ppb, hb = tuning.decode_attn_blocks(
            pages_per_slot=page_table.shape[1], n_kv_heads=hkv,
            page_size=page_size, head_dim=hd, storage_dtype=k_pool.dtype,
            requested=requested,
        )
    qg = q.reshape(s, hkv, g, hd)
    out = paged_flash_decode_pallas(
        qg, k_pool, v_pool, page_table, seq_lens, active,
        page_size=page_size, pages_per_block=ppb, head_block=hb,
        window=window, softcap=softcap,
        interpret=backend == "pallas_interpret",
    )
    return out.reshape(s, hq, hd)


def flash_attention(q, k, v, *, causal=True, softcap=None, block_q=128,
                    block_k=128, backend="pallas_interpret"):
    """Fused attention entry point. q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd).

    GQA is expanded here (KV heads repeated per group); ragged Sq/Sk are
    padded to block multiples and masked inside the kernel.
    """
    from repro.kernels.flash_attention import flash_attention_pallas

    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, sk, hd)
    bq, bk = min(block_q, _ceil_to(sq, 8)), min(block_k, _ceil_to(sk, 8))
    sqp, skp = _ceil_to(sq, bq), _ceil_to(sk, bk)
    if sqp != sq:
        qf = jnp.pad(qf, ((0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        kf = jnp.pad(kf, ((0, 0), (0, skp - sk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, skp - sk), (0, 0)))
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, softcap=softcap, block_q=bq, block_k=bk,
        true_seq_q=sq, true_seq_k=sk,
        interpret=backend == "pallas_interpret",
    )
    out = out[:, :sq].reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)
    return out
