"""RedMulE GEMM-Op Pallas kernel (TPU target; compiled and run on the v5e).

TPU mapping of the paper's datapath (docs/DESIGN.md Sec. 2):

  - The L x H CE array with P pipeline registers becomes a (block_m, block_n)
    VMEM output tile; the Z-buffer feedback/accumulate loop becomes the K grid
    dimension accumulating into a VMEM scratch buffer.
  - The Streamer's cast units become in-kernel ``astype`` on load/store, so
    fp8 operands cross HBM at 1 byte/elem and are widened only inside VMEM.
  - The (mul, add) GEMM path issues ``dot_general`` (MXU). The semiring
    GEMM-Ops have no MXU mapping (the MXU is a hard-wired multiply-add
    systolic array) and lower to VPU ops: chunked outer-product broadcasts
    combined with the star operator. This is the honest TPU analogue of the
    paper's FNCOMP CE stage.

Grid: (B, M/bm, N/bn, K/bk) with K innermost and batch as the *outermost*
grid axis (one launch covers the whole batch, not ``vmap``-of-
``pallas_call``). ``w`` and ``y`` may each be unbatched (2D, broadcast over
B) or batched (3D, leading dim B). The weight's block index (kk, j) changes
on every grid step, so an unbatched ``w`` is fetched from HBM, and widened
in VMEM, once per batch step: B products of M rows cost B passes over the
weight. ``ops.gemm_op`` therefore folds the batch into M whenever the weight
is shared, so there the batch axis carries batched weights only (attention
products, MoE experts, xLSTM). The accumulator initializes from Y (the
GEMM-Op bias matrix) when present — valid because ``star`` is associative
and commutative, so folding Y in first equals combining it last.

A serving step may hand the kernel one layer's weight as the whole stack of
every layer's (L, K, N) plus the layer's index, scalar-prefetched: the
weight's block index becomes (layer, kk, j), so the kernel reads the
layer's tiles in place, where a slice taken outside it would be copied
out of the stack on every call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring
from repro.core.precision import PrecisionPolicy
from repro.core.semiring import GemmOp

# VPU-path chunk of the K dimension materialized per broadcast step:
# (block_m, _K_CHUNK, block_n) must fit VMEM alongside the operands.
_K_CHUNK = 8


def _star_reduce(op: semiring.Op, x, axis):
    if op is semiring.Op.ADD:
        return jnp.sum(x, axis=axis)
    if op is semiring.Op.MIN:
        return jnp.min(x, axis=axis)
    if op is semiring.Op.MAX:
        return jnp.max(x, axis=axis)
    raise ValueError(op)


def _read_tile(ref):
    """Load a (bm, bn)-shaped tile from a 2D (shared) or 3D (batched) ref."""
    return ref[0] if len(ref.shape) == 3 else ref[...]


def _kernel(
    x_ref,
    w_ref,
    y_ref,  # may be None (compile-time)
    o_ref,
    acc_ref,
    *,
    gop: GemmOp,
    nk: int,
    compute_dtype,
    acc_dtype,
):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        if y_ref is not None:
            acc_ref[...] = _read_tile(y_ref).astype(acc_dtype)
        else:
            ident = semiring.reduce_identity(gop.star)
            acc_ref[...] = jnp.full(acc_ref.shape, ident, acc_dtype)

    # Input cast unit: storage (possibly fp8) -> CE datapath format.
    x = x_ref[0].astype(compute_dtype)
    w = _read_tile(w_ref).astype(compute_dtype)

    if gop.is_gemm:
        acc_ref[...] += jax.lax.dot_general(
            x,
            w,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
        )
    else:
        circ = semiring.op_fn(gop.circ)
        star = functools.partial(_star_reduce, gop.star)
        star2 = semiring.op_fn(gop.star)
        acc = acc_ref[...]
        bk = x.shape[1]
        for i in range(0, bk, _K_CHUNK):
            xs = x[:, i : i + _K_CHUNK]  # (bm, c)
            ws = w[i : i + _K_CHUNK, :]  # (c, bn)
            prod = circ(xs[:, :, None], ws[None, :, :]).astype(acc_dtype)
            acc = star2(acc, star(prod, axis=1))
        acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _flush():
        # Output cast unit: accumulator -> storage format.
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def redmule_gemm_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    y: jnp.ndarray | None,
    *,
    gop: GemmOp,
    policy: PrecisionPolicy,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=None,
    interpret: bool = False,
    w_layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Tiled GEMM-Op. Shapes must already be padded to block multiples.

    x: (M, K) or (B, M, K); w: (K, N) or (B, K, N); y: optional (M, N) or
    (B, M, N) — all in a storage dtype (fp8/fp16/bf16/fp32). Unbatched w/y
    broadcast over B. With ``w_layer`` (a (1,) int32 array), w is a stack
    (L, K, N) of which the product reads layer ``w_layer[0]``, unbatched.
    Returns x's rank with trailing (M, N), in ``out_dtype`` (default
    ``policy.out``).
    """
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    b, m, k = x.shape
    k2, n = w.shape[-2:]
    if k != k2:
        raise ValueError(f"inner dims disagree: x {x.shape} @ w {w.shape}")
    if w_layer is not None and w.ndim != 3:
        raise ValueError(f"a layer index needs a stacked w (L, K, N), got {w.shape}")
    if w.ndim != 2 and w_layer is None and w.shape[0] != b:
        raise ValueError(f"batched w leading dim mismatch: x {x.shape} @ w {w.shape}")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"problem ({m}, {n}, {k}) not divisible by tile "
            f"({block_m}, {block_n}, {block_k}); pad or clamp the blocks first"
        )
    nk = k // block_k
    grid = (b, m // block_m, n // block_n, nk)
    out_dtype = policy.out if out_dtype is None else out_dtype

    kernel = functools.partial(
        _kernel,
        gop=gop,
        nk=nk,
        compute_dtype=policy.compute,
        acc_dtype=policy.acc,
    )
    # Index maps take ``*_``: with a layer index it is prefetched and passed
    # to every map after the grid indices.
    in_specs = [
        pl.BlockSpec((1, block_m, block_k), lambda bb, i, j, kk, *_: (bb, i, kk)),
    ]
    if w_layer is not None:
        in_specs.append(pl.BlockSpec(
            (None, block_k, block_n), lambda bb, i, j, kk, layer: (layer[0], kk, j)
        ))
    elif w.ndim == 3:
        in_specs.append(
            pl.BlockSpec((1, block_k, block_n), lambda bb, i, j, kk, *_: (bb, kk, j))
        )
    else:
        in_specs.append(
            pl.BlockSpec((block_k, block_n), lambda bb, i, j, kk, *_: (kk, j))
        )
    operands = [x, w]
    if y is not None:
        if y.ndim == 3:
            in_specs.append(pl.BlockSpec(
                (1, block_m, block_n), lambda bb, i, j, kk, *_: (bb, i, j)
            ))
        else:
            in_specs.append(
                pl.BlockSpec((block_m, block_n), lambda bb, i, j, kk, *_: (i, j))
            )
        operands.append(y)
        body = kernel
    else:
        body = lambda x_ref, w_ref, o_ref, acc_ref: kernel(  # noqa: E731
            x_ref, w_ref, None, o_ref, acc_ref
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0 if w_layer is None else 1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, block_m, block_n), lambda bb, i, j, kk, *_: (bb, i, j)
        ),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), policy.acc)],
    )
    if w_layer is not None:
        operands.insert(0, w_layer)
        in_place = body
        body = lambda layer_ref, *refs: in_place(*refs)  # noqa: E731
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, n), out_dtype),
        interpret=interpret,
        name="redmule_gemm",
    )(*operands)
    return out[0] if squeeze else out
