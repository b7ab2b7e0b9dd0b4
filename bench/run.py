#!/usr/bin/env python3
"""Run one cell of the benchmark once, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration file, its traffic mix (``bench/traffic/<mix>.json``), its
metric readers (``bench/metrics/<metric>.py``), its limits
(``bench/checks/<cell>.json``) and the reference its configuration names
(``bench/reference/<module>.py``, see ``check.py``) are found by name.
Set-up builds the program and warms every shape the window uses; the window
then runs for
``--seconds``; after it, the program's state is freed and the plain
reference checks what the window produced.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import check  # noqa: E402
import common  # noqa: E402
from common import BenchError, log  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def driver(cell: dict, seed: int, devices, compiles):
    kind = cell["traffic"]["kind"]
    if kind in ("open_loop", "offline"):
        from serve import ServeCell
        return ServeCell(cell, seed, devices, compiles)
    raise BenchError(f"traffic kind {kind!r} has no driver")


def run(args, *, require_chip: bool = True, root: str = common.ROOT) -> dict:
    """One run of one cell. ``root`` is the checkout that holds
    ``BENCHMARK.json`` and ``bench/``; ``require_chip=False`` skips the look
    for a TPU (the tests drive the rest of a run on the CPU)."""
    bench = common.spec(root)
    cell = common.cell(bench, args.workload, root)
    n_chips = cell["workload"]["chips"]
    import jax

    if require_chip:
        devices = common.accelerators(n_chips)
        peak = common.peaks(devices[0].device_kind)
    else:
        devices = jax.devices()[:n_chips]
        peak = common.load_json(os.path.join(common.BENCH, "peaks.json"))["devices"]["TPU v5 lite"]
    cache = common.enable_cache()
    compiles = common.CompileLog()
    log(f"{args.workload}: seed {args.seed}, {args.seconds} s, trace {args.trace}; "
        f"{n_chips} x {devices[0].device_kind}; compile cache {cache}")

    cell_run = driver(cell, args.seed, devices, compiles)
    cell_run.setup(args.seconds)
    setup_s = cell_run.t0 - T_START
    before = compiles.compiles
    log(f"set-up: {setup_s:.2f} s; compile log: {compiles}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        rec = cell_run.window(args.seconds, trace_dir)
        in_window = compiles.compiles - before
        log(f"window: {rec['window_s']:.2f} s, {in_window} compiles inside it")
        rec.update(setup_s=setup_s, peak=peak, config=cell["config"],
                   traffic=cell["traffic"], chips=n_chips, compiles_in_window=in_window)
        if trace_dir:
            import reduce
            import scopes
            from jax.profiler import ProfileData

            files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                     for f in fs if f.endswith(".xplane.pb")]
            if not files:
                raise BenchError("the traced run wrote no trace")
            with open(files[0], "rb") as f:
                data = f.read()
            profile = ProfileData.from_serialized_xspace(data)
            rec["trace"] = reduce.reduce(profile, n_chips, cell_run.host_spans)
            # The program's own names: device time per layer scope, host
            # time per server step.
            rec["trace"].update(scopes.reduce_trace(data, profile, n_chips))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    cell_run.free()
    t = time.perf_counter()
    readings = cell_run.readings()
    log(f"readings: {readings}")
    lim = check.limits(args.workload, root)
    correct, rows = check.judge(readings, lim)
    log(f"check: {time.perf_counter() - t:.1f} s")

    which = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell["metrics"][which]:
        read = common.reader(m["name"], root)
        value = read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_chips,
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        import reduce

        device.update(busy_s=rec["trace"]["busy_s"], window_s=rec["trace"]["window_s"])
        result["breakdown"] = reduce.breakdown(rec["trace"])
    result["checks"] = {name: {"value": v, "limit": limit} for name, v, limit in rows}
    for name, v, limit in rows:
        log(f"check {name}: {v!r} (limit {limit!r}) {'ok' if v <= limit else 'FAILED'}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
