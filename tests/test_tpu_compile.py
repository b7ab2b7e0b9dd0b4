"""Compile the main-path kernels for a described TPU v5e (nothing runs).

The TPU compiler is installed even where no chip is attached: these tests
lower each kernel at granite-3-8b widths (K = d_model = 4096; N in {4096,
12800, 49155}) for one chip of a described ``v5e:2x2`` and assert that
Mosaic accepts it and that the compiled program holds the kernel. They
catch what interpret mode cannot: tiles the chip's tiling rules refuse,
and kernels the compiler cannot partition.

The topology is described inside the module fixtures only: the TPU
library admits one process at a time, and a description made while
pytest collects would leave the other workers without these tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.precision import get_policy
from repro.engine import Engine
from repro.kernels import ops, tuning

D_MODEL, D_FF, VOCAB = 4096, 12800, 49155
N_HEADS, N_KV_HEADS, HEAD_DIM = 32, 8, 128
SLOTS, PAGE_SIZE, PAGES_PER_SLOT = 8, 16, 128
# (band, M, K, N): one GEMM per band of kernels/tuning.py, shaped as the
# serving and training steps call it. Decode's `down` takes the 1280-deep
# E4M3 K tile, which divides its K.
GEMM_BANDS = [
    ("decode", 8, D_MODEL, VOCAB),
    ("verify", 5, D_MODEL, D_MODEL),
    ("chunk", 32, D_MODEL, D_FF),
    ("batched", 256, D_MODEL, D_MODEL),
    ("training", 2048, D_MODEL, D_FF),
    ("decode_down", 8, D_FF, D_MODEL),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled, name: str) -> int:
    return sum(
        name in ln for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    )


@pytest.mark.parametrize("policy", ["tpu_bf16", "tpu_hfp8"])
@pytest.mark.parametrize("band,m,k,n", GEMM_BANDS, ids=[b[0] for b in GEMM_BANDS])
def test_gemm_band_compiles(one_chip, band, m, k, n, policy):
    pol = get_policy(policy)
    x = _spec((m, k), jnp.bfloat16, one_chip)
    w = _spec((k, n), jnp.bfloat16, one_chip)
    f = jax.jit(lambda x, w: ops.gemm_op(x, w, policy=pol, backend="pallas"))
    compiled = f.lower(x, w).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 1


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)], ids=["up", "down"])
def test_layer_indexed_decode_gemm_compiles(one_chip, k, n):
    """A decode GEMM reading one layer of a 20-layer E4M3 weight stack in
    place (its index scalar-prefetched): one kernel, and no temporary
    buffer of the layer's size, so the layer is not copied out."""
    pol = get_policy("tpu_hfp8")
    x = _spec((SLOTS, k), jnp.float8_e4m3fn, one_chip)
    w = _spec((20, k, n), jnp.float8_e4m3fn, one_chip)
    i = _spec((), jnp.int32, one_chip)
    f = jax.jit(lambda x, w, i: ops.gemm_op(
        x, w, policy=pol, backend="pallas", operand_quant=False, layer=i))
    compiled = f.lower(x, w, i).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < k * n


def test_hfp8_gemm_vjp_compiles(one_chip):
    """E4M3 forward, E5M2 cotangents: forward plus both backward GEMMs."""
    eng = Engine(policy="tpu_hfp8", backend="pallas")
    x = _spec((2048, D_MODEL), jnp.bfloat16, one_chip)
    w = _spec((D_MODEL, D_FF), jnp.bfloat16, one_chip)
    loss = lambda x, w: jnp.sum(eng.matmul(x, w).astype(jnp.float32))  # noqa: E731
    step = jax.value_and_grad(loss, argnums=(0, 1))
    compiled = jax.jit(step).lower(x, w).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 3


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "e4m3"])
def test_paged_decode_compiles(one_chip, kv_dtype):
    n_tok = (SLOTS * PAGES_PER_SLOT + 1) * PAGE_SIZE
    q = _spec((SLOTS, N_HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    pool = _spec((n_tok, N_KV_HEADS, HEAD_DIM), kv_dtype, one_chip)
    table = _spec((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    f = jax.jit(lambda q, k, v, t, s, a: ops.paged_decode_attention(
        q, k, v, t, s, a, page_size=PAGE_SIZE, backend="pallas"))
    compiled = f.lower(q, pool, pool, table, lens, lens).compile()
    assert _kernel_calls(compiled, "paged_flash_decode") == 1
    _, hb = tuning.decode_attn_blocks(
        pages_per_slot=PAGES_PER_SLOT, n_kv_heads=N_KV_HEADS,
        page_size=PAGE_SIZE, head_dim=HEAD_DIM, storage_dtype=kv_dtype)
    assert hb == N_KV_HEADS


def test_semiring_op_compiles(one_chip):
    """A Table-1 op on the VPU path, in the batched band (M = 256)."""
    eng = Engine(policy="fp32", backend="pallas")
    x = _spec((256, 256), jnp.float32, one_chip)
    f = jax.jit(lambda a, b: eng.gemm_op(a, b, op="apsp"))
    compiled = f.lower(x, x).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 1


def test_gemm_under_mesh_compiles(topo):
    """Mosaic kernels cannot be partitioned by the compiler; under a 2x2
    (data, model) mesh the GEMM runs per device in shard_map."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pol = get_policy("tpu_hfp8")
    x = _spec((2, 1024, D_MODEL), jnp.bfloat16,
              NamedSharding(mesh, P("data", "model", None)))
    w = _spec((D_MODEL, D_FF), jnp.bfloat16,
              NamedSharding(mesh, P(None, "model")))

    def f(x, w):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.gemm_op(x, w, policy=pol, backend="pallas")

    compiled = jax.jit(f).lower(x, w).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 1
    # x split four ways, w two ways over "model": bf16 bytes per device.
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == (2 * 1024 * D_MODEL // 4 + D_MODEL * D_FF // 2) * 2


def test_folded_decode_under_mesh_compiles(topo):
    """Under a 2x2 (data, model) mesh decode's folded rows split over
    "data" as M: each device's kernel takes a 4-row tile of E4M3 rows."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pol = get_policy("tpu_hfp8")
    x = _spec((SLOTS, 1, D_MODEL), jnp.bfloat16, NamedSharding(mesh, P()))
    w = _spec((D_MODEL, D_FF), jnp.bfloat16, NamedSharding(mesh, P(None, "model")))

    def f(x, w):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.gemm_op(x, w, policy=pol, backend="pallas")

    compiled = jax.jit(f).lower(x, w).compile()
    assert _kernel_calls(compiled, "redmule_gemm") == 1
