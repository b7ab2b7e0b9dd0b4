"""The batched Pallas engine path (tentpole surface of the backend unification).

Covers, all in interpret mode on ragged (non-tile-multiple) shapes:
  - batched ``gemm_op`` parity vs the XLA backend for every Table 1 GEMM-Op,
    with shared (2D) and batched (3D) w;
  - differentiability of ``mp_matmul(..., backend='pallas_interpret')``:
    forward parity vs the XLA backend, and ``jax.grad`` vs the fp32 reference
    within each policy's tolerance (fp16 and hybrid-fp8);
  - the block-size selection layer (heuristic table, clamping, env override,
    autotune disk cache).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import redmule, semiring
from repro.core.precision import (
    FP32_REF,
    REDMULE_FP16,
    REDMULE_HFP8,
    TPU_HFP8,
)
from repro.kernels import ops, tuning

BLOCKS = dict(block_m=8, block_n=128, block_k=8)

# Ragged on every dim: nothing is a multiple of the 8/128 tile grid.
BATCHED_SHAPES = [
    (3, 13, 21, 19),   # (B, M, K, N)
    (2, 1, 33, 5),     # M=1 rows (paper Fig. 11 depthwise case)
    (4, 17, 7, 29),
]


def _arrs(rng, b, m, k, n, batched_w=False):
    x = jnp.asarray(rng.standard_normal((b, m, k)).astype(np.float32))
    wshape = (b, k, n) if batched_w else (k, n)
    w = jnp.asarray(rng.standard_normal(wshape).astype(np.float32))
    return x, w


@pytest.mark.parametrize("gop", semiring.TABLE1, ids=lambda g: g.name)
@pytest.mark.parametrize("batched_w", [False, True], ids=["shared_w", "batched_w"])
def test_batched_gemm_op_matches_xla(gop, batched_w, rng):
    b, m, k, n = 3, 13, 21, 19
    x, w = _arrs(rng, b, m, k, n, batched_w)
    y = jnp.asarray(rng.standard_normal((b, m, n)).astype(np.float32))
    want = ops.gemm_op(x, w, y, gop=gop, policy=FP32_REF, backend="xla")
    got = ops.gemm_op(
        x, w, y, gop=gop, policy=FP32_REF, backend="pallas_interpret", **BLOCKS
    )
    assert got.shape == (b, m, n)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("shape", BATCHED_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_batched_matmul_ragged_shapes(shape, rng):
    b, m, k, n = shape
    x, w = _arrs(rng, b, m, k, n)
    want = jnp.matmul(x, w)
    got = ops.gemm_op(
        x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret", **BLOCKS,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


def test_batched_y_with_unbatched_xw(rng):
    """y may carry batch dims x/w lack; both backends must broadcast it."""
    x = jnp.asarray(rng.standard_normal((13, 21)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((21, 19)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((3, 13, 19)).astype(np.float32))
    want = ops.gemm_op(x, w, y, gop=semiring.MATMUL, policy=FP32_REF, backend="xla")
    assert want.shape == (3, 13, 19)
    got = ops.gemm_op(
        x, w, y, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret", **BLOCKS,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )
    # Semiring op on both backends too (xla takes the vmap path here).
    for backend in ("xla", "pallas_interpret"):
        z = ops.gemm_op(
            x, w, y, gop=semiring.ALL_PAIRS_SHORTEST_PATH, policy=FP32_REF,
            backend=backend,
        )
        assert z.shape == (3, 13, 19)


def test_gemm_op_honors_ambient_backend(monkeypatch):
    """redmule.gemm_op inside use_backend() must dispatch to that backend."""
    from repro.core import redmule as rm

    seen = {}
    real = rm.kernel_ops.gemm_op

    def spy(*args, **kwargs):
        seen["backend"] = kwargs.get("backend")
        return real(*args, **kwargs)

    monkeypatch.setattr(rm.kernel_ops, "gemm_op", spy)
    x = jnp.ones((4, 4), jnp.float32)
    with rm.use_backend("pallas_interpret"):
        rm.gemm_op(x, x, op="matmul", policy=FP32_REF)
    assert seen["backend"] == "pallas_interpret"
    rm.gemm_op(x, x, op="matmul", policy=FP32_REF)
    assert seen["backend"] == "xla"  # config default once the scope closes


def test_multi_batch_dims_and_broadcast(rng):
    """(2, 3, M, K) @ (1, 3, K, N): broadcasting batch dims, batched w."""
    x = jnp.asarray(rng.standard_normal((2, 3, 6, 11)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((1, 3, 11, 9)).astype(np.float32))
    want = jnp.matmul(x, w)
    got = ops.gemm_op(
        x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret", **BLOCKS,
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


# -- differentiable mp_matmul through the kernel -----------------------------


def _grad_check(got, ref, policy):
    """Policy-tolerance gradient check against the fp32 reference.

    fp16: elementwise. fp8: the E5M2 cotangent grid is ~12% relative, so a
    single grid step on a small element breaks any elementwise relative
    bound; assert a relative-RMSE budget (the Fig. 10 'negligible loss'
    criterion) plus a loose elementwise ceiling instead.
    """
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if policy.fp8_storage:
        rmse = float(np.sqrt(np.mean((got - ref) ** 2)))
        scale = float(np.sqrt(np.mean(ref**2))) + 1e-12
        assert rmse / scale < 0.15, (rmse, scale)
        # Elementwise ceiling scaled to the gradient's RMS: cancellation can
        # make any fixed per-element bound arbitrarily tight relative to ref.
        np.testing.assert_allclose(got, ref, rtol=0.5, atol=0.5 * scale)
    else:
        np.testing.assert_allclose(got, ref, rtol=3e-2, atol=8e-2)


@pytest.mark.parametrize(
    "policy", [REDMULE_FP16, REDMULE_HFP8, TPU_HFP8], ids=lambda p: p.name
)
@pytest.mark.parametrize("shape", BATCHED_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_mp_matmul_pallas_forward_matches_xla(policy, shape, rng):
    b, m, k, n = shape
    x, w = _arrs(rng, b, m, k, n)
    zx = redmule.mp_matmul(x, w, policy, backend="xla")
    zp = redmule.mp_matmul(x, w, policy, backend="pallas_interpret")
    assert zp.dtype == zx.dtype
    # Same storage quantization and fp32 accumulation; only the reduction
    # blocking differs, so outputs agree to one ulp of the 16-bit out dtype
    # (accumulator rounding ties can resolve differently across blockings).
    np.testing.assert_allclose(
        np.asarray(zp, np.float32), np.asarray(zx, np.float32),
        rtol=1e-3, atol=1e-3,
    )


@pytest.mark.parametrize(
    "policy", [REDMULE_FP16, REDMULE_HFP8, TPU_HFP8], ids=lambda p: p.name
)
@pytest.mark.parametrize("shape", BATCHED_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_mp_matmul_pallas_grad_matches_fp32_ref(policy, shape, rng):
    b, m, k, n = shape
    x, w = _arrs(rng, b, m, k, n)
    cot = jnp.asarray(rng.standard_normal((b, m, n)).astype(np.float32))

    def loss(backend):
        return lambda x_, w_: jnp.sum(
            redmule.mp_matmul(x_, w_, policy, backend=backend).astype(jnp.float32)
            * cot
        )

    dx, dw = jax.grad(loss("pallas_interpret"), argnums=(0, 1))(x, w)
    # fp32 reference gradients of sum(x @ w * cot).
    dx_ref = jnp.matmul(cot, jnp.swapaxes(w, -1, -2) if w.ndim > 2 else w.T)
    dw_ref = jnp.einsum("bmk,bmn->kn", x, cot)
    assert dx.shape == x.shape and dw.shape == w.shape
    _grad_check(dx, dx_ref, policy)
    _grad_check(dw, dw_ref, policy)
    # And the engine's own xla backend agrees with its pallas backend
    # bit-for-role: same quantization points, same accumulation dtype; only
    # 16-bit rounding ties differ between reduction blockings.
    dx2, dw2 = jax.grad(loss("xla"), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(dx2, np.float32),
        rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        np.asarray(dw, np.float32), np.asarray(dw2, np.float32),
        rtol=2e-3, atol=2e-3,
    )


def test_mp_matmul_batched_w_grads(rng):
    """xLSTM-style fully batched b: grads flow and match fp32 reference."""
    x = jnp.asarray(rng.standard_normal((3, 7, 11)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 11, 5)).astype(np.float32))
    dw = jax.grad(
        lambda w_: jnp.sum(redmule.mp_matmul(x, w_, FP32_REF,
                                             backend="pallas_interpret"))
    )(w)
    dw_ref = jax.grad(lambda w_: jnp.sum(jnp.matmul(x, w_)))(w)
    np.testing.assert_allclose(
        np.asarray(dw), np.asarray(dw_ref), rtol=1e-4, atol=1e-4
    )


def test_linear_backend_knob(rng):
    x = jnp.asarray(rng.standard_normal((4, 9, 6)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((6, 8)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal((8,)).astype(np.float32))
    yx = redmule.linear(x, w, bias, REDMULE_FP16, backend="xla")
    yp = redmule.linear(x, w, bias, REDMULE_FP16, backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(yx, np.float32), np.asarray(yp, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_ambient_backend_context():
    assert redmule.default_backend() == "xla"
    with redmule.use_backend("pallas_interpret"):
        assert redmule.default_backend() == "pallas_interpret"
        with redmule.use_backend("xla"):
            assert redmule.default_backend() == "xla"
        assert redmule.default_backend() == "pallas_interpret"
    assert redmule.default_backend() == "xla"
    with pytest.raises(ValueError):
        redmule.set_default_backend("tpu")


# -- block-size selection ----------------------------------------------------


@pytest.mark.parametrize("m", [1, 2, 4, 8, 9, 12, 16])
def test_decode_and_verify_k_tile_divides_the_weight(m):
    """For decode and verify rows (M <= 16) the K tile divides K rounded up
    to the lane, so a weight whose K is a lane multiple is never padded:
    ``down``'s K 12800 no longer pads to the 1024-deep tile (13312), and a
    depth that divides, 1024 for K 4096 under E4M3, stays."""
    for dt in (jnp.float8_e4m3fn, jnp.bfloat16, jnp.float32):
        for k in (4096, 12800):
            _, _, bk = tuning.heuristic_block_sizes(m, 4096, k, dt)
            assert bk % 128 == 0 and k % bk == 0, (m, dt, k, bk)
    if m <= 8:
        assert tuning.heuristic_block_sizes(m, 4096, 4096, jnp.float8_e4m3fn)[2] == 1024
        assert tuning.heuristic_block_sizes(m, 4096, 12800, jnp.float8_e4m3fn)[2] == 1280


def test_decode_down_gemm_pads_nothing():
    """An (8, 12800) @ (12800, 4096) decode GEMM on an E4M3 weight (``down``
    at granite-3-8b widths) traces no pad at all: not of w, not of x."""
    x = jax.ShapeDtypeStruct((8, 12800), jnp.float8_e4m3fn)
    w = jax.ShapeDtypeStruct((12800, 4096), jnp.float8_e4m3fn)
    jaxpr = jax.make_jaxpr(lambda x, w: ops.gemm_op(
        x, w, policy=TPU_HFP8, backend="pallas_interpret", operand_quant=False))(x, w)
    text = str(jaxpr)
    assert "redmule_gemm" in text and "pad[" not in text


# (name, x shape, K, N): K and N divide the decode tiles (read in place), or
# K is ragged (the layer is sliced out and padded first).
LAYER_CASES = [
    ("in_place", (2, 1, 256), 256, 256),
    ("in_place_rows", (3, 256), 256, 384),
    ("ragged_k", (2, 1, 200), 200, 256),
]


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("case", LAYER_CASES, ids=[c[0] for c in LAYER_CASES])
def test_layer_indexed_gemm_matches_its_slice(case, backend, rng):
    """A product read from one layer of a weight stack (``layer``) equals
    the product with that layer sliced out, bit for bit; on the Pallas path
    a stack that needs no pad is handed to the kernel whole, its index
    prefetched, so no slice of it is taken."""
    _, x_shape, k, n = case
    x = jnp.asarray(rng.standard_normal(x_shape).astype(np.float32)).astype(jnp.float8_e4m3fn)
    w = jnp.asarray(rng.standard_normal((3, k, n)).astype(np.float32)).astype(jnp.float8_e4m3fn)

    def gemm(x_, w_, layer=None):
        return ops.gemm_op(x_, w_, policy=TPU_HFP8, backend=backend,
                           operand_quant=False, layer=layer)

    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(gemm(x, w, jnp.int32(i)), np.float32),
            np.asarray(gemm(x, w[i]), np.float32), err_msg=f"layer {i}")
    if backend == "pallas_interpret":
        jaxpr = jax.make_jaxpr(gemm)(x, w, jnp.int32(1))
        [(w_seen, grid)] = _redmule_calls(jaxpr)
        in_place = case[0] != "ragged_k"
        assert grid[0] == 1 and len(w_seen) == 2 and w_seen[1] == n
        assert (w_seen[0] == k) == in_place  # the sliced layer is padded
        assert ("dynamic_slice" not in str(jaxpr)) == in_place


def test_heuristic_blocks_clamp_to_problem():
    bm, bn, bk = tuning.heuristic_block_sizes(13, 21, 19, jnp.float32)
    assert bm <= 16 and bn == 128 and bk <= 24
    # Training-size M (past the batched-prefill band's 512 ceiling).
    bm, bn, bk = tuning.heuristic_block_sizes(1024, 512, 512, jnp.float32)
    assert (bm, bn, bk) == (128, 128, 128)
    # fp8 storage: 1 B/elem doubles the K tile at the same VMEM budget.
    bm, bn, bk = tuning.heuristic_block_sizes(1024, 512, 512, jnp.float8_e4m3fn)
    assert bk == 256


def test_skinny_decode_blocks_clamp_block_m_to_m():
    """Decode-time GEMMs (M in {1,2,4,8}) must not pad the M tile to a
    training-size block: block_m == M exactly, with a deeper K tile."""
    for m in (1, 2, 4, 8):
        for dt in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn):
            bm, bn, bk = tuning.heuristic_block_sizes(m, 4096, 4096, dt)
            assert bm == m, (m, dt)
            assert bn % 128 == 0
            assert bk >= 256  # freed VMEM goes into the K tile
    # resolve path preserves the skinny tile end to end
    assert tuning.resolve_block_sizes(1, 256, 512, policy=FP32_REF)[0] == 1
    # just above the skinny table, the verify table keeps block_m == M
    assert tuning.heuristic_block_sizes(9, 4096, 4096, jnp.float32)[0] == 9


def test_verify_blocks_exact_m_at_the_seam():
    """Speculative-verify GEMMs (M = k+1 in 2..16) straddle the old
    skinny/chunk seam at M=8: the verify table keeps block_m == M exactly
    through 16 (an fp8 sublane round-up to 32 would be mostly padding)
    with a K tile between the skinny and chunk depths."""
    for m in (2, 3, 5, 9, 12, 16):
        for dt in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn):
            bm, bn, bk = tuning.heuristic_block_sizes(m, 4096, 4096, dt)
            assert bm == m, (m, dt)
            assert bn % 128 == 0
            assert bk >= 256, (m, dt)
    # Verify K depth sits between the skinny and chunk tables' depths.
    _, _, bk_skinny = tuning.heuristic_block_sizes(8, 4096, 4096, jnp.float32)
    _, _, bk_verify = tuning.heuristic_block_sizes(16, 4096, 4096, jnp.float32)
    _, _, bk_chunk = tuning.heuristic_block_sizes(32, 4096, 4096, jnp.float32)
    assert bk_chunk <= bk_verify <= bk_skinny
    # Just above the verify table, sublane rounding resumes.
    bm, _, _ = tuning.heuristic_block_sizes(17, 4096, 4096, jnp.float32)
    assert bm == 24  # ceil(17, sublane 8)
    # The autotune candidate list sweeps the verify seam.
    assert {(3, 128, 512), (5, 128, 512), (9, 128, 384), (12, 128, 384),
            (16, 128, 384)} <= set(tuning.AUTOTUNE_CANDIDATES)


def test_chunk_prefill_blocks_round_m_to_chunk():
    """Chunked-prefill GEMMs (M = chunk size, 32/64 — 16 now belongs to the
    exact-M verify table) get a sublane-sized M tile — never a padded
    128-row training tile — with a deeper K tile than the training
    default."""
    for m in (32, 64):
        for dt in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn):
            bm, bn, bk = tuning.heuristic_block_sizes(m, 4096, 4096, dt)
            sub = tuning.SUBLANE[jnp.dtype(dt).itemsize]
            assert bm == -(-m // sub) * sub, (m, dt)
            assert bm <= 64 < 128
            assert bn % 128 == 0
            assert bk >= 256, (m, dt)  # spare VMEM goes into the K tile
    # Above the chunk table, the batched-prefill band caps M at 128.
    assert tuning.heuristic_block_sizes(256, 4096, 4096, jnp.float32)[0] == 128
    # The autotune candidate list sweeps the chunk Ms.
    assert {(16, 128, 512), (32, 128, 256), (64, 128, 256)} <= set(
        tuning.AUTOTUNE_CANDIDATES
    )


def test_batched_prefill_blocks_between_chunk_and_training():
    """Batched multi-slot prefill GEMMs (M = P x chunk, 64 < M <= 512) cap
    the M tile at 128 (sublane-rounded below that) and take a K tile
    between the chunk and training depths — a (4, 48)-row step must not
    pad to a 128x2 grid nor fall into the training table's shallow K."""
    for m in (65, 96, 128, 192, 256, 512):
        for dt in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn):
            bm, bn, bk = tuning.heuristic_block_sizes(m, 4096, 4096, dt)
            sub = tuning.SUBLANE[jnp.dtype(dt).itemsize]
            assert bm == min(-(-m // sub) * sub, 128), (m, dt)
            assert bn % 128 == 0
            _, _, bk_chunk = tuning.heuristic_block_sizes(64, 4096, 4096, dt)
            _, _, bk_train = tuning.heuristic_block_sizes(1024, 4096, 4096, dt)
            assert bk_train <= bk <= bk_chunk, (m, dt)
    # Seam boundaries: 64 is still the chunk table, 65 enters the batched
    # band, 512 is its ceiling (P=8 x chunk 64), 513 falls to training.
    assert tuning.heuristic_block_sizes(64, 4096, 4096, jnp.float32)[0] == 64
    assert tuning.heuristic_block_sizes(65, 4096, 4096, jnp.float32)[0] == 72
    assert tuning.heuristic_block_sizes(512, 4096, 4096, jnp.float32)[2] == 256
    assert tuning.heuristic_block_sizes(513, 4096, 4096, jnp.float32)[2] == 128
    # The candidate list sweeps the batched band.
    assert {(96, 128, 256), (128, 128, 256), (128, 128, 384),
            (256, 128, 128)} <= set(tuning.AUTOTUNE_CANDIDATES)


def test_batched_prefill_gemm_matches_ref(rng):
    """A batched-prefill-sized (M=96 = 2 slots x 48-token chunk) GEMM
    through the Pallas path with the auto-selected batched tile still
    computes the right thing."""
    x = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((64, 20)).astype(np.float32))
    z = ops.gemm_op(x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
                    backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(z), np.asarray(x) @ np.asarray(w), rtol=1e-4, atol=1e-4
    )


def test_chunk_prefill_gemm_matches_ref(rng):
    """A chunk-sized (M=16) GEMM through the Pallas path with the
    auto-selected chunk tile still computes the right thing."""
    x = jnp.asarray(rng.standard_normal((16, 48)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((48, 20)).astype(np.float32))
    z = ops.gemm_op(x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
                    backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(z), np.asarray(x) @ np.asarray(w), rtol=1e-5, atol=1e-5
    )


def test_skinny_decode_gemm_matches_ref(rng):
    """A one-row decode GEMM through the Pallas path with the auto-selected
    bm=1 tile still computes the right thing."""
    x = jnp.asarray(rng.standard_normal((1, 48)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((48, 20)).astype(np.float32))
    z = ops.gemm_op(x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
                    backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(z), np.asarray(x) @ np.asarray(w), rtol=1e-5, atol=1e-5
    )


def test_env_block_override(monkeypatch):
    monkeypatch.setenv("REPRO_BLOCK_MNK", "16,128,32")
    blocks = tuning.resolve_block_sizes(256, 256, 256, policy=FP32_REF)
    assert blocks == (16, 128, 32)
    # Explicit arguments still beat the env var.
    blocks = tuning.resolve_block_sizes(
        256, 256, 256, policy=FP32_REF, requested=(64, None, None)
    )
    assert blocks == (64, 128, 32)


def test_autotune_caches_to_disk(tmp_path, monkeypatch, rng):
    cache = tmp_path / "blocks.json"
    x = jnp.asarray(rng.standard_normal((9, 12)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((12, 10)).astype(np.float32))
    blocks = tuning.autotune_block_sizes(
        x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret", cache_path=str(cache),
        candidates=((8, 128, 8), (16, 128, 16)), repeats=1,
    )
    assert cache.exists()
    stored = json.loads(cache.read_text())
    [(key, val)] = stored.items()
    assert key == "pallas_interpret/fp32/matmul/1x9x10x12"
    assert tuple(val) == blocks
    # Second call is a pure cache hit (poison the candidates to prove it).
    again = tuning.autotune_block_sizes(
        x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret", cache_path=str(cache),
        candidates=(), repeats=1,
    )
    assert again == blocks


def test_default_blocks_used_when_unspecified(rng):
    """gemm_op with block_*=None must route through the tuning layer."""
    x = jnp.asarray(rng.standard_normal((9, 12)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((12, 10)).astype(np.float32))
    got = ops.gemm_op(
        x, w, None, gop=semiring.MATMUL, policy=FP32_REF,
        backend="pallas_interpret",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x) @ np.asarray(w), rtol=1e-5, atol=1e-5
    )


# -- shared-weight batch rows fold into M ------------------------------------


def _redmule_calls(jaxpr):
    """(weight shape, grid) of every ``redmule_gemm`` kernel call in a
    (Closed)Jaxpr, nested programs (scans, custom VJPs, pjit) included. A
    weight read in place from a stack of layers (its index prefetched, the
    first operand) reports its layer's (K, N)."""
    from jax.extend import core as jcore

    out = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "redmule_gemm":
                gm = eqn.params["grid_mapping"]
                w = eqn.invars[1 + gm.num_index_operands].aval.shape
                out.append((w[1:] if gm.num_index_operands else w, gm.grid))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return out


# (name, x batch, M rows per element, K, N, w kind, y kind). Decode rows are
# (slots, 1, K); K=1200 is no multiple of the lane, so the decode table's
# dividing K tile still pads it; N=300 is ragged like the 49155-token vocab.
FOLD_CASES = [
    ("decode", 8, 1, 512, 256, "shared", None),
    ("decode_y", 8, 1, 512, 256, "shared", "batched"),
    ("ragged_k", 8, 1, 1200, 256, "shared", None),
    ("ragged_k_y", 8, 1, 1200, 256, "shared", "batched"),
    ("ragged_n", 8, 1, 256, 300, "shared", None),
    ("ragged_n_y", 8, 1, 256, 300, "shared", "batched"),
    ("verify_rows", 4, 3, 256, 200, "shared", None),
    ("w_batch_ones", 8, 1, 256, 200, "ones", "batched"),
    ("y_2d_broadcast", 4, 1, 256, 200, "shared", "2d"),
    ("y_row_broadcast", 4, 3, 256, 200, "shared", "row"),
    # Only a batched weight keeps the batch as the kernel's grid axis.
    ("batched_w", 3, 5, 256, 200, "batched", None),
]


@pytest.mark.parametrize("gop", [semiring.MATMUL, semiring.ALL_PAIRS_SHORTEST_PATH],
                         ids=lambda g: g.name)
@pytest.mark.parametrize("case", FOLD_CASES, ids=[c[0] for c in FOLD_CASES])
def test_shared_weight_fold_matches_per_element_calls(case, gop, rng):
    """A shared-weight GEMM over a batch of x is one kernel call of B*M
    rows, bitwise equal to B separate M-row calls (each row keeps its own
    fp32 accumulation in the same K order), whatever the batch shape of y;
    a batched w keeps the batch as the kernel's outer grid axis, also equal
    per element."""
    _, b, m, k, n, w_kind, y_kind = case
    x = jnp.asarray(rng.standard_normal((b, m, k)).astype(np.float32))
    w_shape = {"shared": (k, n), "ones": (1, k, n), "batched": (b, k, n)}[w_kind]
    w = jnp.asarray(rng.standard_normal(w_shape).astype(np.float32))
    y_shape = {None: None, "batched": (b, m, n), "2d": (m, n), "row": (b, 1, n)}[y_kind]
    y = None if y_shape is None else jnp.asarray(
        rng.standard_normal(y_shape).astype(np.float32))

    def gemm(x_, w_, y_):
        return ops.gemm_op(x_, w_, y_, gop=gop, policy=TPU_HFP8,
                           backend="pallas_interpret")

    got = gemm(x, w, y)
    assert got.shape == (b, m, n)
    for i in range(b):
        w_i = w[i] if w_kind == "batched" else w.reshape(k, n)
        y_i = None if y is None else (y if y_kind == "2d" else y[i])
        np.testing.assert_array_equal(
            np.asarray(got[i], np.float32), np.asarray(gemm(x[i], w_i, y_i), np.float32),
            err_msg=f"batch element {i}",
        )
    [(w_seen, grid)] = _redmule_calls(jax.make_jaxpr(gemm)(x, w, y))
    if w_kind == "batched":
        assert grid[0] == b
    else:  # one M tile, chosen for the folded rows, covers them all
        assert len(w_seen) == 2 and grid[:2] == (1, 1)


@pytest.mark.parametrize("step", ["decode", "verify", "prefill_batch"])
def test_serving_steps_fetch_each_shared_weight_once(step):
    """Engagement counter: in the decode, verify and batched-prefill steps
    of a small decoder on the Pallas backend, no ``redmule_gemm`` call with
    a shared (2D) weight carries a batch grid axis (each one used to run
    the weight once per slot: q, k, v, o, gate, up, down and the lm_head)."""
    from repro.analysis import contracts

    cfg, model = contracts._build_model(
        "granite-3-8b", backend="pallas_interpret", fp8_kv=False, smoke=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: model.init_state_store(
        contracts.NUM_SLOTS, contracts.NUM_PAGES, contracts.PAGE_SIZE))
    fn, args, _ = contracts._step_inputs(model, params, pools, cfg.vocab_size)[step]
    calls = _redmule_calls(jax.make_jaxpr(fn)(*args))
    shared = [grid for w_shape, grid in calls if len(w_shape) == 2]
    assert len(shared) >= 8, calls  # 7 in the layer body, 1 in the lm_head
    assert [g for g in shared if g[0] > 1] == []
