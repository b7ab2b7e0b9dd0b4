"""``reduce.py`` on a trace recorded on a TPU v5e (granite-3-8b at 20
layers, 8 slots: twelve ``Server.step`` calls, gzipped), and its interval
union on hand-made intervals."""
from __future__ import annotations

import gzip
import os

import pytest

import reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "serve20.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with gzip.open(TRACE) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return reduce.reduce(profile, 1, ("Server.step", "$server.py:500 step"),
                         window="Server.step")


def test_window_and_busy_time(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_programs_and_kernels_are_named(reduced):
    assert {"jit_prefill_chunk", "jit_decode_step"} <= set(reduced["programs"])
    assert len(reduced["programs"]["jit_decode_step"]) == 11  # counted by hand
    assert sum(reduced["programs"]["jit_prefill_chunk"]) == pytest.approx(
        16 * 0.3044, rel=0.01)
    assert reduced["ops"]["redmule_gemm"] > 0
    assert reduced["ops"]["paged_flash_decode"] > 0
    assert "while" not in reduced["ops"]  # control flow spans its ops


def test_per_op_time_fits_in_busy_time(reduced):
    assert sum(reduced["ops"].values()) <= reduced["busy_s"] * (1 + 1e-9)


def test_idle_gaps_are_named_by_host_spans(reduced):
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(reduced["idle_by_host"].values()) == pytest.approx(idle, rel=1e-6)
    assert "Server.step" in reduced["idle_by_host"]
    b = reduce.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_base_names():
    assert reduce.op_base("%redmule_gemm.48 = bf16[8,1,4096] custom-call(...)") == "redmule_gemm"
    assert reduce.op_base("%fusion.3.clone.1 = f32[2]") == "fusion"
    assert reduce.op_base("%all-reduce-start.2 = ...") == "all-reduce-start"


def test_union():
    assert reduce._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
