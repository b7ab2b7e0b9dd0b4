"""Multi-device coverage via subprocesses (XLA_FLAGS host-device override
must be set before jax initializes, so these cannot run in-process)."""
import os
import subprocess
import sys
import textwrap

import pytest

# Every test spawns a fresh interpreter (XLA_FLAGS host-device override) and
# compiles a sharded cell — minutes of work on a CPU runner.
pytestmark = pytest.mark.slow

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, n_dev: int = 8, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = _SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """(pod=2, data=2, model=2) sharded loss == unsharded loss on the same
    global batch, and params stay in sync."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.data import for_model
    from repro.distrib import sharding as shd
    from repro.models import build
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import MeshCtx
    from repro.optim import AdamW
    from repro.training import TrainState, make_train_step

    cfg = get_config("granite-3-8b", smoke=True)
    data = for_model(cfg, 16, 8)
    batch = data.batch(0)
    opt = AdamW(lr=1e-3)

    def make_state(model):
        p = model.init(jax.random.PRNGKey(0))
        return TrainState(jnp.zeros((), jnp.int32), p, opt.init(p),
                          jnp.zeros((), jnp.int32))

    # single device reference
    model1 = build(cfg)
    s1 = make_state(model1)
    step1 = jax.jit(make_train_step(model1, opt))
    s1, m1 = step1(s1, batch)

    # sharded
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    ctx = MeshCtx(mesh=mesh, dp_axes=("pod", "data"), ep_axis="model")
    model2 = build(cfg, ctx)
    s2 = make_state(model2)
    pspecs = shd.param_specs(jax.eval_shape(lambda: s2.params), cfg, 2)
    pshard = shd.tree_shardings(pspecs, mesh)
    scalar = NamedSharding(mesh, P())
    st_shard = TrainState(scalar, pshard, {"mu": pshard, "nu": pshard}, scalar)
    bshard = shd.tree_shardings(
        shd.batch_specs(jax.eval_shape(lambda: batch), ("pod", "data")), mesh)
    step2 = jax.jit(make_train_step(model2, opt),
                    in_shardings=(st_shard, bshard),
                    out_shardings=(st_shard, None))
    s2, m2 = step2(s2, batch)

    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2, (m1["loss"], m2["loss"])
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), s1.params, s2.params)
    assert max(jax.tree.leaves(d)) < 3e-2
    print("OK")
    """)


def test_pallas_gemm_under_mesh_runs_per_device():
    """Under an ambient 2x2 mesh the kernel GEMM (and its VJP) runs in
    shard_map, one whole-K call per device; its gradients equal the
    one-device ones exactly."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.engine import Engine
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    eng = Engine(policy="tpu_hfp8", backend="pallas_interpret")
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (2, 48, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 384), jnp.float32)

    def loss(x, w):
        return jnp.sum(eng.matmul(x, w).astype(jnp.float32) ** 2)

    def sharded(x, w):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    assert "shard_map" in str(jax.make_jaxpr(sharded)(x, w))
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
    got = jax.jit(sharded)(xs, ws)
    # The loss sums in another order across devices; the GEMM outputs and
    # the elementwise cotangents they feed do not.
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    print("OK")
    """, n_dev=4)


def test_folded_decode_gemm_under_mesh_tiles_per_shard():
    """A shared-weight decode GEMM, (8 slots, 1, K) @ (K, N), folds its
    slots into M; under a data mesh those rows split over the devices as
    M, and each device's kernel takes its tile from its own rows (2 of 8
    on a 4-way data axis, 4 of 8 under 2x2), not from all 8. The numbers
    equal the one-device call exactly."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.extend import core as jcore
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.precision import TPU_HFP8
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (8, 1, 1280), jnp.float32)
    w = jax.random.normal(kw, (1280, 384), jnp.float32)
    gemm = lambda x, w: ops.gemm_op(x, w, policy=TPU_HFP8,
                                    backend="pallas_interpret")
    want = np.asarray(jax.jit(gemm)(x, w), np.float32)

    def kernel_x_rows(jaxpr):
        rows = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                rows.append(eqn.invars[0].aval.shape[-2])
            for v in eqn.params.values():
                if isinstance(v, jcore.ClosedJaxpr):
                    rows += kernel_x_rows(v.jaxpr)
                elif isinstance(v, jcore.Jaxpr):
                    rows += kernel_x_rows(v)
        return rows

    for shape, names, rows in [((4,), ("data",), 2),
                               ((2, 2), ("data", "model"), 4)]:
        mesh = make_mesh(shape, names)

        def sharded(x, w):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return gemm(x, w)

        jaxpr = jax.make_jaxpr(sharded)(x, w)
        assert "shard_map" in str(jaxpr)
        assert kernel_x_rows(jaxpr.jaxpr) == [rows], (names, kernel_x_rows(jaxpr.jaxpr))
        on_mesh = NamedSharding(mesh, P())
        got = jax.jit(sharded)(jax.device_put(x, on_mesh), jax.device_put(w, on_mesh))
        got = np.asarray(got, np.float32)
        np.testing.assert_array_equal(got, want)
    print("OK")
    """, n_dev=4)


def test_compressed_psum_error_feedback():
    """fp8-compressed gradient all-reduce converges to the true mean via
    error feedback (bias shrinks across repeated reductions)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distrib.collectives import compressed_psum
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024), jnp.float32)
    true_mean = jnp.mean(x, axis=0)

    def body(xs, err):
        out, new_err = compressed_psum(xs, "data", err)
        return out, new_err

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(jax.sharding.PartitionSpec("data"),
                          jax.sharding.PartitionSpec("data")),
                out_specs=(jax.sharding.PartitionSpec("data"),
                           jax.sharding.PartitionSpec("data")),
                check_vma=False))
    err = jnp.zeros((8, 1024), jnp.bfloat16)
    T = 8
    cum = jnp.zeros_like(true_mean)
    single = None
    for t in range(T):
        out, err = f(x, err)
        if single is None:
            single = float(jnp.max(jnp.abs(out[0] - true_mean)))
        cum = cum + out[0]
    # E5M2 has a 2-bit mantissa: ~12% single-shot error is expected. The
    # error-feedback guarantee is that the CUMULATIVE applied update
    # telescopes to the truth (bias bounded by one step's residual), instead
    # of growing linearly (T * single) as naive quantization would.
    cum_bias = float(jnp.max(jnp.abs(cum - T * true_mean)))
    assert single < 0.3, single
    assert cum_bias < 2.5 * single, (cum_bias, single)
    assert cum_bias < 0.25 * T * single, (cum_bias, T * single)
    print("OK", single, cum_bias)
    """)


def test_moe_ep_on_real_mesh():
    """EP with experts sharded over model=4: matches dense oracle."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.precision import FP32_REF
    from repro.launch.mesh import make_mesh
    from repro.models import moe

    cfg = moe.MoEConfig(n_experts=8, top_k=2, d_model=16, d_ff=32,
                        capacity_factor=8.0, impl="ep")
    params = moe.init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
    want, _ = moe.apply_dense(params, x, cfg, FP32_REF)

    mesh = make_mesh((2, 4), ("data", "model"))
    got, _ = jax.jit(lambda p, x_: moe.apply_ep(
        p, x_, cfg, FP32_REF, mesh, ("data",), "model"))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("OK")
    """)


def test_zero1_specs_shard_moments():
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distrib import sharding as shd
    from repro.launch.mesh import make_mesh

    params = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32),
              "v": jax.ShapeDtypeStruct((7, 3), jnp.float32)}
    specs = {"w": P(None, "model"), "v": P(None, None)}
    z = shd.zero1_specs(specs, params, ("data",), 8)
    assert z["w"] == P(("data",), "model"), z["w"]
    assert z["v"] == P(None, None), z["v"]  # 7x3 not divisible by 8
    print("OK")
    """)


def test_dryrun_smoke_cell_small_mesh():
    """A full dry-run cell (reduced mesh 2x4) end to end: lower, compile,
    roofline extraction. Uses the real (non-smoke) xlstm-125m config."""
    _run("""
    import jax
    from repro.launch import dryrun
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    lowered, meta = dryrun.lower_cell("xlstm-125m", "decode_32k", mesh)
    compiled = lowered.compile()
    from repro.roofline import analysis as ra
    roof = ra.roofline_from_artifacts({}, compiled.as_text(), 8)
    assert roof.hlo_flops > 0
    print("OK", roof.bottleneck)
    """, timeout=560)
