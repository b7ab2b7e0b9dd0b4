#!/usr/bin/env python3
"""The one-time sweep that finds an open-loop cell's knee: the highest
arrival rate the server sustains without a backlog that grows.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.1 0.2 0.3

Runs the cell's set-up once, then its traffic mix at each rate, from an
empty server each time: the mix's lead, then a window of ``--seconds``.
Prints one JSON line per rate: requests due in the window, the queue and
the running requests when it opened and when it closed, the mean of the
running requests over its steps, tokens a second, the token gaps' median
and 95th percentile, and the time to first token of the requests due in
it. The cell's traffic file then fixes its rate at about four fifths of
the knee; no run of the benchmark searches.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

import common  # noqa: E402
from serve import ServeCell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = common.cell(common.spec(), args.workload)
    devices = common.accelerators(cell["workload"]["chips"])
    common.enable_cache()
    run = ServeCell(dict(cell, traffic=dict(cell["traffic"], lead_s=0.0)), args.seed, devices,
                    common.CompileLog())
    run.setup(0.0)
    for rate in args.rates:
        run.server.reset()  # a new scheduler: read it after
        sched = run.server.scheduler
        run.start(dict(cell["traffic"], rate_per_s=rate), args.seconds)
        at_open = (len(sched.queue), len(sched.running))
        running = []
        step = run.server.step

        def counted_step():
            running.append(len(sched.running))
            return step()

        run.server.step = counted_step
        rec = run.window(args.seconds, None)
        del run.server.step
        reqs = rec["requests"]
        due = [r for r in reqs if r["due"] >= 0]
        waits = [(r["tokens"][0] if r["tokens"] else rec["window_s"]) - r["due"] for r in due]
        gaps = [b - a for r in reqs for a, b in zip(r["tokens"], r["tokens"][1:])]
        print(json.dumps({
            "rate_per_s": rate, "due": rec["attempted"],
            "queued_at_open": at_open[0], "running_at_open": at_open[1],
            "queued_at_close": len(sched.queue), "running_at_close": len(sched.running),
            "running_mean": float(np.mean(running)) if running else 0.0,
            "tok_s": sum(len(r["tokens"]) for r in reqs) / rec["window_s"],
            "itl_p50_ms": float(np.percentile(gaps, 50)) * 1e3 if gaps else None,
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3 if gaps else None,
            "ttft_p50_s": float(np.percentile(waits, 50)) if waits else None,
            "ttft_p90_s": float(np.percentile(waits, 90)) if waits else None,
            "generator_late_max_s": max(r["submit"] - r["due"] for r in reqs),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
