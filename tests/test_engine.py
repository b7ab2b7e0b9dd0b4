"""The Engine API (tentpole of the repro.engine redesign).

Covers:
  - parity of ``Engine.gemm_op`` over all 7 Table 1 ops x ragged shapes x
    batch dims x backends (xla vs pallas_interpret) against the pure-jnp
    oracle in ``repro.kernels.ref``;
  - the ``_pad_operands`` fill rules at ragged sizes for the previously
    untested (circ=mul, star=min/max) case, under fp16 and hybrid-fp8
    storage (finite-identity clamp: e4m3fn has no inf);
  - gradients of the new semiring VJPs (tropical subgradients) against
    ``jax.grad`` of fp32 references — including tie-splitting, the Y
    combination, batched/shared operands, and both backends;
  - ``Engine.closure`` vs Floyd-Warshall (and the Group 2 semirings);
  - Engine ergonomics: pytree/static behavior, ``engine_scope``
    (contextvars), ``as_engine`` coercion;
  - the deprecated ``repro.core.redmule`` shims: warn, and agree with the
    Engine results.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import semiring
from repro.core.precision import FP32_REF, REDMULE_FP16, REDMULE_HFP8
from repro.engine import (
    Engine,
    ambient_engine,
    as_engine,
    current_engine,
    engine_scope,
)
from repro.kernels import ref

BLOCKS = dict(block_m=8, block_n=128, block_k=8)
BACKENDS = ("xla", "pallas_interpret")

# Ragged on every dim (nothing is a tile multiple), plus the M=1 row case.
SHAPES_2D = [(5, 7, 9), (1, 33, 5), (13, 21, 19)]
# (batch..., M, K, N) with shared and broadcast-batched weights.
BATCH_CASES = [
    ((3,), (13, 7, 9), False),   # batched x, shared 2D w
    ((3,), (5, 11, 6), True),    # batched x and w
    ((2, 3), (4, 9, 5), False),  # two batch dims, shared w
]


def _ref_batched(x, w, y, gop, policy):
    """Oracle over leading batch dims via the 2D reference."""
    if x.ndim == 2 and (w.ndim == 2) and (y is None or y.ndim == 2):
        return ref.gemm_op_ref(x, w, y, gop, policy)
    batch = np.broadcast_shapes(
        x.shape[:-2], w.shape[:-2], () if y is None else y.shape[:-2]
    )
    xb = jnp.broadcast_to(x, batch + x.shape[-2:]).reshape((-1,) + x.shape[-2:])
    wb = (
        [w] * int(np.prod(batch))
        if w.ndim == 2
        else list(jnp.broadcast_to(w, batch + w.shape[-2:]).reshape((-1,) + w.shape[-2:]))
    )
    if y is None:
        yb = [None] * int(np.prod(batch))
    else:
        yb = list(jnp.broadcast_to(y, batch + y.shape[-2:]).reshape((-1,) + y.shape[-2:]))
    outs = [
        ref.gemm_op_ref(xb[i], wb[i], yb[i], gop, policy)
        for i in range(xb.shape[0])
    ]
    out = jnp.stack(outs)
    return out.reshape(batch + out.shape[-2:])


# ---------------------------------------------------------------------------
# Parity: 7 ops x shapes x backends vs the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("gop", semiring.TABLE1, ids=lambda g: g.name)
@pytest.mark.parametrize("shape", SHAPES_2D, ids=lambda s: "x".join(map(str, s)))
def test_gemm_op_parity_2d(gop, shape, backend, rng):
    m, k, n = shape
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    eng = Engine(policy=FP32_REF, backend=backend, **BLOCKS)
    want = ref.gemm_op_ref(x, w, y, gop, FP32_REF)
    got = eng.gemm_op(x, w, y, op=gop)
    assert got.shape == (m, n)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("gop", semiring.TABLE1, ids=lambda g: g.name)
@pytest.mark.parametrize(
    "case", BATCH_CASES,
    ids=lambda c: f"b{'x'.join(map(str, c[0]))}-{'bw' if c[2] else 'sw'}",
)
def test_gemm_op_parity_batched(gop, case, backend, rng):
    batch, (m, k, n), batched_w = case
    x = jnp.asarray(rng.standard_normal(batch + (m, k)).astype(np.float32))
    wshape = batch + (k, n) if batched_w else (k, n)
    w = jnp.asarray(rng.standard_normal(wshape).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(batch + (m, n)).astype(np.float32))
    eng = Engine(policy=FP32_REF, backend=backend, **BLOCKS)
    want = _ref_batched(x, w, y, gop, FP32_REF)
    got = eng.gemm_op(x, w, y, op=gop)
    assert got.shape == batch + (m, n)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("policy", [REDMULE_FP16, REDMULE_HFP8],
                         ids=lambda p: p.name)
@pytest.mark.parametrize(
    "gop", [semiring.MAX_RELIABILITY_PATH, semiring.MIN_RELIABILITY_PATH],
    ids=lambda g: g.name,
)
def test_mul_circ_minmax_star_padding(gop, policy, rng):
    """Pins the _pad_operands fill rule for circ=mul with star=min/max at
    ragged sizes (x-lanes filled with the clamped star identity, w-lanes
    with 1), previously untested. e4m3fn has no inf: fills must stay within
    the finite grid and the result must match the oracle on the same
    quantized operands."""
    m, k, n = 5, 7, 9  # ragged vs the 8/128/8 tile grid on every dim
    x = jnp.asarray(rng.random((m, k)).astype(np.float32))
    w = jnp.asarray(rng.random((k, n)).astype(np.float32))
    eng = Engine(policy=policy, backend="pallas_interpret", **BLOCKS)
    got = eng.gemm_op(x, w, op=gop)
    want = ref.gemm_op_ref(
        x.astype(policy.storage_fwd), w.astype(policy.storage_fwd), None,
        gop, policy,
    )
    assert np.isfinite(np.asarray(got, np.float32)).all()
    tol = dict(rtol=0.13, atol=0.3) if policy.fp8_storage else dict(rtol=2e-2, atol=5e-2)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


# ---------------------------------------------------------------------------
# Gradients: tropical subgradients vs jax.grad of fp32 references
# ---------------------------------------------------------------------------

_REFS = {
    "apsp": lambda x, w: jnp.min(x[..., :, :, None] + w[..., None, :, :], axis=-2),
    "max_critical_path": lambda x, w: jnp.max(
        x[..., :, :, None] + w[..., None, :, :], axis=-2),
    "max_reliability_path": lambda x, w: jnp.max(
        x[..., :, :, None] * w[..., None, :, :], axis=-2),
    "min_reliability_path": lambda x, w: jnp.min(
        x[..., :, :, None] * w[..., None, :, :], axis=-2),
    "min_spanning_tree": lambda x, w: jnp.min(
        jnp.maximum(x[..., :, :, None], w[..., None, :, :]), axis=-2),
    "max_capacity_path": lambda x, w: jnp.max(
        jnp.minimum(x[..., :, :, None], w[..., None, :, :]), axis=-2),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", sorted(_REFS))
def test_semiring_grads_match_fp32_reference(op, backend, rng):
    """The acceptance-criterion check: gemm_op is differentiable and its
    tropical VJP matches autodiff of the jnp reference, x/w/y, both
    backends."""
    m, k, n = 6, 11, 5
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    cot = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    eng = Engine(policy=FP32_REF, backend=backend, **BLOCKS)
    star = semiring.op_fn(semiring.get(op).star)

    got = jax.grad(
        lambda x_, w_, y_: jnp.sum(eng.gemm_op(x_, w_, y_, op=op) * cot),
        argnums=(0, 1, 2),
    )(x, w, y)
    want = jax.grad(
        lambda x_, w_, y_: jnp.sum(star(y_, _REFS[op](x_, w_)) * cot),
        argnums=(0, 1, 2),
    )(x, w, y)
    for g, r, name in zip(got, want, "xwy"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-6,
            err_msg=f"{op}/{backend}/d{name}",
        )


@pytest.mark.parametrize("op", ["apsp", "max_capacity_path"])
def test_semiring_grads_split_ties_like_jax(op, rng):
    """Integer-valued data forces ties on both the reduction and (for
    Group 2) the circ map; routing must match JAX's balanced conventions."""
    x = jnp.asarray(rng.integers(0, 3, (4, 6)).astype(np.float32))
    w = jnp.asarray(rng.integers(0, 3, (6, 5)).astype(np.float32))
    eng = Engine(policy=FP32_REF)
    got = jax.grad(lambda a, b: jnp.sum(eng.gemm_op(a, b, op=op)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda a, b: jnp.sum(_REFS[op](a, b)), argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_semiring_grads_batched_shared_w(backend, rng):
    """Batched x against a shared 2D w: dW must sum over the batch, through
    the chunked-K backward (K > one chunk)."""
    x = jnp.asarray(rng.standard_normal((3, 5, 70)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((70, 4)).astype(np.float32))
    eng = Engine(policy=FP32_REF, backend=backend, **BLOCKS)
    got = jax.grad(lambda w_: jnp.sum(eng.gemm_op(x, w_, op="apsp")))(w)
    want = jax.grad(lambda w_: jnp.sum(_REFS["apsp"](x, w_)))(w)
    assert got.shape == w.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_semiring_grads_quantized_policy(rng):
    """fp16 semiring VJP: the subgradient routes along the quantized
    forward's argmin lanes; compare against autodiff of the reference built
    from the same quantized operands."""
    pol = REDMULE_FP16
    x = jnp.asarray(rng.standard_normal((6, 9)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((9, 5)).astype(np.float32))
    eng = Engine(policy=pol, backend="pallas_interpret", **BLOCKS)
    got = jax.grad(
        lambda x_: jnp.sum(eng.gemm_op(x_, w, op="apsp").astype(jnp.float32))
    )(x)
    xq = x.astype(pol.storage_fwd).astype(jnp.float32)
    wq = w.astype(pol.storage_fwd).astype(jnp.float32)
    want = jax.grad(lambda x_: jnp.sum(_REFS["apsp"](x_, wq)))(xq)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=3e-2, atol=5e-2)


def test_gemm_with_y_rounds_once(rng):
    """GEMM + Y must accumulate Y in the acc dtype and round once (the
    kernel's fused Y init), not round z to the fp8 output first."""
    from repro.core.precision import REDMULE_HFP8_OUT8

    pol = REDMULE_HFP8_OUT8  # E4M3 output: double rounding is visible
    x = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    for backend in BACKENDS:
        eng = Engine(policy=pol, backend=backend, **BLOCKS)
        got = eng.gemm_op(x, w, y, op="matmul")
        want = ref.gemm_op_ref(
            x.astype(pol.storage_fwd), w.astype(pol.storage_fwd), y,
            semiring.MATMUL, pol,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-3, atol=1e-3, err_msg=backend,
        )
    # And it stays differentiable in y, including broadcast batch dims.
    xb = jnp.asarray(rng.standard_normal((3, 5, 7)).astype(np.float32))
    wb = jnp.asarray(rng.standard_normal((7, 4)).astype(np.float32))
    y2 = jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32))
    eng = Engine(policy=FP32_REF)
    dy = jax.grad(lambda y_: jnp.sum(eng.gemm_op(xb, wb, y_, op="matmul")))(y2)
    np.testing.assert_allclose(np.asarray(dy), np.full((5, 4), 3.0), rtol=1e-6)


def test_matmul_gemm_op_consistency(rng):
    """op='matmul' goes through the mixed-precision GEMM VJP: same result
    as Engine.matmul (+ y), and differentiable in y."""
    x = jnp.asarray(rng.standard_normal((5, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, 3)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((5, 3)).astype(np.float32))
    eng = Engine(policy=FP32_REF)
    np.testing.assert_allclose(
        np.asarray(eng.gemm_op(x, w, y)), np.asarray(eng.matmul(x, w) + y),
        rtol=1e-6,
    )
    dy = jax.grad(lambda y_: jnp.sum(eng.gemm_op(x, w, y_)))(y)
    np.testing.assert_allclose(np.asarray(dy), np.ones((5, 3)), rtol=1e-6)


# ---------------------------------------------------------------------------
# Closure
# ---------------------------------------------------------------------------


def _floyd_warshall(dist):
    fw = dist.copy()
    for k in range(dist.shape[0]):
        fw = np.minimum(fw, fw[:, k:k + 1] + fw[k:k + 1, :])
    return fw


def _random_graph(rng, v=16, p=0.25, inf=3e4):
    adj = rng.random((v, v)).astype(np.float32) * 10
    dist = np.where(rng.random((v, v)) < p, adj, np.float32(inf))
    np.fill_diagonal(dist, 0.0)
    return dist


def test_closure_matches_floyd_warshall(rng):
    dist = _random_graph(rng)
    got = Engine(policy=FP32_REF).closure(jnp.asarray(dist), op="apsp")
    np.testing.assert_allclose(
        np.asarray(got), _floyd_warshall(dist), rtol=1e-5, atol=1e-3
    )


def test_closure_pallas_backend(rng):
    dist = _random_graph(rng, v=12)
    eng = Engine(policy=FP32_REF, backend="pallas_interpret", **BLOCKS)
    got = eng.closure(jnp.asarray(dist), op="apsp")
    np.testing.assert_allclose(
        np.asarray(got), _floyd_warshall(dist), rtol=1e-5, atol=1e-3
    )


def test_closure_early_exit_is_fixpoint(rng):
    """Extra iterations past convergence must not change the result."""
    dist = _random_graph(rng, v=10)
    eng = Engine(policy=FP32_REF)
    a = eng.closure(jnp.asarray(dist), op="apsp")
    b = eng.closure(jnp.asarray(dist), op="apsp", max_steps=40)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_closure_batched_and_jitted(rng):
    dists = np.stack([_random_graph(rng, v=9) for _ in range(3)])
    eng = Engine(policy=FP32_REF)
    got = jax.jit(lambda a: eng.closure(a, op="apsp"))(jnp.asarray(dists))
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(got[i]), _floyd_warshall(dists[i]), rtol=1e-5, atol=1e-3
        )


def test_closure_max_capacity(rng):
    """(min, max) closure: capacities only improve, diagonal is the +inf-like
    circ identity, and one more squaring step is a no-op (fixpoint)."""
    v = 10
    cap = np.where(rng.random((v, v)) < 0.3,
                   rng.random((v, v)).astype(np.float32) * 9 + 1,
                   np.float32(0.0))
    eng = Engine(policy=FP32_REF)
    c = eng.closure(jnp.asarray(cap), op="max_capacity_path")
    assert (np.asarray(c) >= cap - 1e-6).all()
    again = eng.gemm_op(c, c, c, op="max_capacity_path")
    np.testing.assert_array_equal(np.asarray(again), np.asarray(c))


def test_closure_rejects_non_square():
    with pytest.raises(ValueError):
        Engine().closure(jnp.zeros((3, 4)))


# ---------------------------------------------------------------------------
# Engine ergonomics: pytree, scope, coercion
# ---------------------------------------------------------------------------


def test_engine_is_static_pytree(rng):
    eng = Engine(policy=FP32_REF)
    assert jax.tree_util.tree_leaves(eng) == []
    x = jnp.asarray(rng.standard_normal((4, 4)).astype(np.float32))
    out = jax.jit(lambda e, a: e.matmul(a, a))(eng, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ x), rtol=1e-5)
    # Hashable + equality: usable as custom_vjp nondiff / static argument.
    assert hash(eng) == hash(Engine(policy=FP32_REF))
    assert eng == Engine(policy=FP32_REF)
    assert eng != eng.with_backend("pallas_interpret")


def test_engine_scope_contextvar():
    assert ambient_engine() is None
    base = current_engine()
    assert base.backend == "xla"
    with engine_scope(Engine(backend="pallas_interpret")):
        assert current_engine().backend == "pallas_interpret"
        with engine_scope(Engine(backend="xla", policy="fp32")):
            assert current_engine().policy.name == "fp32"
        assert current_engine().backend == "pallas_interpret"
    assert ambient_engine() is None


def test_engine_scope_is_per_thread():
    """contextvars isolate scopes across threads (the race the old module
    global had under concurrent tracing)."""
    import threading

    seen = {}

    def worker():
        seen["inner"] = current_engine().backend

    with engine_scope(Engine(backend="pallas_interpret")):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert current_engine().backend == "pallas_interpret"
    # A fresh thread starts from the default context: no leakage.
    assert seen["inner"] == "xla"


def test_forward_engine_override_reaches_embed(rng):
    """A per-call engine override must govern the whole residual stream,
    including the embedding cast — no silent dtype mixing."""
    from repro.configs import get_config
    from repro.models import build

    model = build(get_config("granite-3-8b", smoke=True))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    override = model.engine.with_policy("redmule_fp16")
    h, _ = model.forward(params, batch, engine=override)
    assert h.dtype == jnp.float16  # not bf16 (config) and not a f32 promote


def test_engine_validation_and_coercion():
    with pytest.raises(ValueError):
        Engine(backend="tpu")
    with pytest.raises(KeyError):
        Engine(policy="nope")
    eng = as_engine(REDMULE_FP16)
    assert isinstance(eng, Engine) and eng.policy is REDMULE_FP16
    assert as_engine("fp32").policy.name == "fp32"
    assert as_engine(eng) is eng
    with pytest.raises(TypeError):
        as_engine(42)
    # String policies resolve at construction.
    assert Engine(policy="redmule_hfp8").policy is REDMULE_HFP8
    assert Engine().tile_cols == 16  # H*(P+1) default geometry


@pytest.mark.parametrize("policy",
                         ["redmule_fp16", "redmule_hfp8", "redmule_hfp8_out8"])
def test_fp16_compute_refused_on_the_tpu_kernel(policy):
    """Mosaic cannot lower the fp16 datapath: the Engine says so when it is
    built, naming the policy, instead of failing inside a compile."""
    with pytest.raises(ValueError, match=policy):
        Engine(policy=policy, backend="pallas")
    with pytest.raises(ValueError, match=policy):
        Engine(policy="tpu_hfp8", backend="pallas").with_policy(policy)
    for backend in ("xla", "pallas_interpret"):
        assert Engine(policy=policy, backend=backend).backend == backend


# ---------------------------------------------------------------------------
# Deprecated shims
# ---------------------------------------------------------------------------


def test_redmule_shims_warn_and_agree(rng):
    from repro.core import redmule

    x = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((6, 3)).astype(np.float32))
    eng = Engine(policy=FP32_REF)
    with pytest.warns(DeprecationWarning):
        z = redmule.mp_matmul(x, w, FP32_REF)
    np.testing.assert_allclose(np.asarray(z), np.asarray(eng.matmul(x, w)))
    with pytest.warns(DeprecationWarning):
        z = redmule.gemm_op(x, w, op="apsp", policy=FP32_REF)
    np.testing.assert_allclose(
        np.asarray(z), np.asarray(eng.gemm_op(x, w, op="apsp"))
    )
    with pytest.warns(DeprecationWarning):
        z = redmule.linear(x, w, None, FP32_REF)
    np.testing.assert_allclose(np.asarray(z), np.asarray(eng.linear(x, w)))


def test_redmule_shim_gemm_op_now_differentiable(rng):
    """The old surface stopped gradients on semiring ops; the shim inherits
    the engine's tropical VJP."""
    from repro.core import redmule

    x = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        dx = jax.grad(
            lambda a: jnp.sum(redmule.gemm_op(a, x.T, op="apsp", policy=FP32_REF))
        )(x)
    assert float(jnp.sum(jnp.abs(dx))) > 0.0


def test_set_default_backend_is_process_wide():
    """The deprecated setter keeps the old module-global semantics: visible
    from threads spawned afterwards (engine_scope stays per-context)."""
    import threading

    from repro.core import redmule
    from repro.engine import set_ambient_engine

    prev_engine = ambient_engine()
    prev_default = redmule._process_default_backend
    try:
        redmule.set_default_backend("pallas_interpret")
        seen = {}
        t = threading.Thread(
            target=lambda: seen.setdefault("b", redmule.default_backend())
        )
        t.start()
        t.join()
        assert seen["b"] == "pallas_interpret"
        assert redmule.default_backend() == "pallas_interpret"

        # The gemm_op shim consults the same process default from a thread
        # with no ambient scope (spy on the kernel layer to see the backend
        # it actually dispatched).
        def shim_call():
            from repro.kernels import ops as kernel_ops

            real = kernel_ops.gemm_op

            def spy(*a, **k):
                seen["dispatched"] = k.get("backend")
                return real(*a, **k)

            kernel_ops.gemm_op = spy
            try:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    redmule.gemm_op(
                        jnp.ones((2, 3)), jnp.ones((3, 2)), op="apsp",
                        policy="fp32",
                    )
            finally:
                kernel_ops.gemm_op = real

        t2 = threading.Thread(target=shim_call)
        t2.start()
        t2.join()
        assert seen["dispatched"] == "pallas_interpret"
    finally:
        set_ambient_engine(prev_engine)
        redmule._process_default_backend = prev_default


def test_lazy_core_reexports():
    """repro.core serves the deprecated names lazily (PEP 562)."""
    import repro.core as core

    assert core.get_policy("fp32").name == "fp32"  # non-deprecated path
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert callable(core.mp_matmul)
        assert core.BACKENDS == ("xla", "pallas", "pallas_interpret")
    with pytest.raises(AttributeError):
        core.not_a_name
