"""What every cell of the benchmark shares: the cell's files found by name,
the device check, the compile log, the table of peaks and the result line."""
from __future__ import annotations

import collections
import importlib.util
import json
import logging
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """A run that cannot give a result: it exits non-zero and prints none."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str, root: str = ROOT) -> dict:
    """The workload entry, its configuration file, its traffic file and the
    checkout ``root`` they were found in. A configuration that names no
    reference file, or a program field ``ModelConfig`` lacks, is refused
    here, before set-up."""
    import program

    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_json(os.path.join(root, entry["file"]))
    reference_path(conf, root)
    program.model_config(conf)
    return {
        "workload": w,
        "config": conf,
        "traffic": load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")),
        "metrics": metrics_for(bench, name),
        "root": root,
    }


def metrics_for(bench: dict, name: str) -> dict:
    """The cell's end-to-end and per-layer metric entries, by name."""
    def mine(m):
        return name in m.get("workloads", [name])
    return {
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def reader(metric: str, root: str = ROOT):
    """The ``read(record)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    if not os.path.exists(path):
        raise BenchError(f"per-layer metric {metric!r} has no reader {path}")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def reference_path(conf: dict, root: str = ROOT) -> str:
    """The file of the configuration's plain reference:
    ``bench/reference/<name>.py`` for its ``reference`` key, ``decoder``
    where it has none."""
    name = conf.get("reference", "decoder")
    path = os.path.join(root, "bench", "reference", f"{name}.py")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) or not os.path.exists(path):
        raise BenchError(f"configuration {conf.get('name')!r} names reference {name!r}, "
                         f"and there is no {path}")
    return path


def reference(conf: dict, root: str = ROOT):
    """The configuration's reference module (see ``check.py``), loaded from
    its file once per process."""
    path = reference_path(conf, root)
    name = f"bench_reference:{path}"
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        # Registered before it runs, as an import does: a dataclass looks
        # its module up by name.
        sys.modules[name] = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def accelerators(n_chips: int):
    """The first ``n_chips`` TPU devices; anything less is an error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n_chips:
        raise BenchError(f"the cell needs {n_chips} chips, JAX found {len(devs)}")
    return devs[:n_chips]


def enable_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout (the path is part of
    what a later run looks up, so it never moves). Returns the directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    # Every program goes to the cache, however quickly it compiled: a run
    # that finds all of them compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compile seconds and persistent-cache hits and writes, from
    JAX's own monitoring events, and the programs whose cache lookup missed,
    from its compiler log's debug records (which are then dropped, so the
    log prints what it printed before)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.missed = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        compiler_log = logging.getLogger("jax._src.compiler")
        self._level = compiler_log.getEffectiveLevel()
        compiler_log.setLevel(logging.DEBUG)
        compiler_log.addFilter(self._record)

    def _record(self, record) -> bool:
        if str(record.msg).startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.missed[record.args[0]] += 1
        return record.levelno >= self._level

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.seconds:.1f} s backend compile in {self.compiles} "
                f"compiles, persistent cache {self.hits} hits / {self.misses} "
                f"misses written; lookups that missed: {dict(self.missed)}")


def log(*args):
    print(*args, file=sys.stderr, flush=True)
