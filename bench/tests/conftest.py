"""Shared set-up of the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU at smoke sizes: the harness's modules and the program
are put on the path, and each test builds a small checkout of its own with
``make_root``: a ``BENCHMARK.json``, a configuration file, a traffic mix,
the limits and the metric readers, all found by name as on the chip.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256)
SERVER = {"num_slots": 4, "page_size": 16, "max_seq_len": 256, "prefill_chunk": 32}
CHAT = {"kind": "open_loop", "rate_per_s": 6.0, "lead_s": 1.0,
        "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8, "max": 100},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 4, "max": 16},
        "min_token_id": 1}


@pytest.fixture(autouse=True, scope="session")
def _compile_cache(tmp_path_factory):
    """Tests write no compile cache into the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax-cache"))


def tiny_config(base: str, **program) -> dict:
    conf = json.load(open(os.path.join(BENCH, "configs", base + ".json")))
    conf.update(TINY)
    conf["program"].update(program)
    return conf


def fp32_config(**program) -> dict:
    """The tiny configuration with the program in float32 throughout: the
    policy keeps its weights in float32, and so does the reference."""
    conf = tiny_config("granite-3-8b-serve", backend="xla", policy="fp32",
                       kv_cache_dtype="fp32", **program)
    conf["weight_dtype"] = "float32"
    return conf


def make_root(tmp, name: str, conf: dict, mix: dict, limits: dict, e2e: list,
              per_layer: list = (), extra_metrics: dict | None = None) -> str:
    """A checkout under ``tmp`` holding one cell ``name``."""
    root = str(tmp)
    for d in ("configs", "traffic", "checks"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    for metric, source in (extra_metrics or {}).items():
        with open(os.path.join(root, "bench", "metrics", metric + ".py"), "w") as f:
            f.write(source)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "bench", "traffic", "mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "checks", name + ".json"), "w") as f:
        json.dump({"limits": limits}, f)
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 3,
            "configs": [{"name": "tiny", "source": "smoke", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "smoke size"}],
            "workloads": [{"name": name, "config": "tiny", "traffic": "mix", "chips": 1,
                           "why": "smoke size"}],
            "end_to_end": [dict(m, workloads=[name]) for m in e2e],
            "per_layer": [dict(m, workloads=[name]) for m in per_layer]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def real_limits(workload: str) -> dict:
    """The limits the cell holds on the chip."""
    return json.load(open(os.path.join(BENCH, "checks", workload + ".json")))["limits"]


def run_cell(root: str, name: str, seconds: float = 3.0, seed: int = 2**31 + 77, trace=0):
    import run

    args = run.parse(["--workload", name, "--seed", str(seed), "--seconds", str(seconds),
                      "--trace", str(trace)])
    return run.run(args, require_chip=False, root=root)
