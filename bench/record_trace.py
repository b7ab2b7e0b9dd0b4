#!/usr/bin/env python3
"""Record a short profiler trace of a serving cell's program, for the
harness's own tests (``bench/tests/data``), from the root of a checkout:

    python3 bench/record_trace.py --workload granite-3-8b.chat --seed 11 \\
        --seconds 6 --out chat6s.xplane.pb.gz

Set-up is ``bench/run.py``'s, with a shorter lead (``LEAD_S`` seconds of
the mix's arrivals, at ``RATE`` requests a second, so that five or six
requests run when the windows open). Three windows of
``--seconds`` follow, each with the server's step loop as the harness
drives it:

1. untraced: the token gaps as a run without the profiler sees them;
2. untraced, the program's ``JsonTracer`` on: host time per
   ``server.step`` less its ``harvest.wait``, on the host clock;
3. traced by ``jax.profiler`` (the program's tracer off again): the trace
   written gzipped to ``--out``.

Beside the trace, ``<out>.json`` holds the harness's record of each step
dispatched in the traced window (``Recorder.steps``: kind, seconds after
the window opened, work arguments), the traced window's span, and the
three windows' token-gap percentiles and host step times. Without a TPU it
exits non-zero and writes nothing.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import common  # noqa: E402
from common import BenchError, log  # noqa: E402

LEAD_S, RATE = 20.0, 0.3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="granite-3-8b.chat")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", required=True)
    return ap.parse_args(argv)


def _gaps_ms(cell_run, lo: float, hi: float) -> dict:
    gaps = [b - a for ts in cell_run.tok_times.values() for a, b in zip(ts, ts[1:])
            if lo <= a and b <= hi]
    if not gaps:
        return {}
    return {f"p{q}": float(np.percentile(gaps, q)) * 1e3 for q in (50, 95)} | {"n": len(gaps)}


def _json_host_steps(events) -> list[float]:
    """Milliseconds of each ``server.step`` less the ``harvest.wait`` inside
    it, from a ``JsonTracer``'s B/E events (microseconds)."""
    def spans(name):
        out, start = [], None
        for ev in events:
            if ev["name"] == name and ev["ph"] == "B":
                start = ev["ts"]
            elif ev["name"] == name and ev["ph"] == "E":
                out.append((start, ev["ts"]))
        return out

    waits = spans("harvest.wait")
    return [(e - s - sum(b - a for a, b in waits if s <= a and b <= e)) * 1e-3
            for s, e in spans("server.step")]


def record(cell: dict, args, devices, compiles, out: str) -> dict:
    """Set up the cell, serve the three windows, write the trace to ``out``
    and return the sidecar record."""
    from repro.obs import JsonTracer, NullTracer
    from serve import ServeCell

    cell = dict(cell, traffic=dict(cell["traffic"], lead_s=LEAD_S, rate_per_s=RATE))
    run = ServeCell(cell, args.seed, devices, compiles)
    # Requests for all three windows are drawn as one window's worth.
    run.setup(3 * args.seconds)
    log(f"set-up: {compiles}")
    before = compiles.compiles
    server, t0, s = run.server, run.t0, args.seconds

    def tracer(t):
        server.tracer = server.engine.tracer = t

    run._serve(t0 + s)
    tracer(JsonTracer())
    run._serve(t0 + 2 * s)
    json_steps = _json_host_steps(server.tracer.events)
    tracer(NullTracer())
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        lo, hi = run._serve(t0 + 3 * s, t0 + 2 * s, trace_dir)
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        with open(path, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "workload": cell["workload"]["name"], "seed": args.seed, "seconds": s,
        "trace_window": [lo, hi],
        "steps": [(k, t - t0, a) for k, t, a in run.recorder.steps if lo <= t - t0 <= hi],
        "gaps_ms": {"untraced": _gaps_ms(run, 0, s), "json_tracer": _gaps_ms(run, s, 2 * s),
                    "traced": _gaps_ms(run, lo, hi)},
        "json_tracer_host_step_ms": json_steps,
        "compiles_in_windows": compiles.compiles - before,
    }


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = common.spec()
        cell = common.cell(bench, args.workload)
        devices = common.accelerators(cell["workload"]["chips"])
        common.enable_cache()
        compiles = common.CompileLog()
        side = record(cell, args, devices, compiles, args.out)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 2
    with open(args.out.removesuffix(".gz").removesuffix(".xplane.pb") + ".json", "w") as f:
        json.dump(side, f)
    log(f"trace: {os.path.getsize(args.out)} bytes gzipped -> {args.out}")
    print(json.dumps({k: side[k] for k in ("gaps_ms", "trace_window")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
