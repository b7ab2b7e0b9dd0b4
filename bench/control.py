#!/usr/bin/env python3
"""Readings for a cell's limits: the program's numbers, the control's and
the planted faults', on several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Each seed runs the cell's set-up and window as ``run.py`` does and frees
the program's state. A served cell then reads, on the same finished
requests, the gap of the served tokens (the program's reading) and the gap
of the tokens that the reference computed in the precision below the
configuration's puts first (the control's reading). ``run.py`` never runs
the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import check  # noqa: E402
import common  # noqa: E402
from common import log  # noqa: E402
from serve import ServeCell  # noqa: E402

CONTROL = "int4"  # the precision below the configuration's E4M3 operands


def readings(cell: dict, seed: int, seconds: float, devices, compiles) -> dict:
    run = ServeCell(cell, seed, devices, compiles)
    run.setup(seconds)
    run.window(seconds, None)
    run.free()
    sample = run.sample()
    prog = check.serve_gaps(cell["config"], seed, sample)
    ctrl = check.serve_gaps(cell["config"], seed, sample, control=CONTROL)
    return {"seed": seed, "tokens": int(len(prog)), "requests": len(sample),
            "program": float(prog.max()), "control": float(ctrl.max()),
            "program_mean": float(prog.mean()), "control_mean": float(ctrl.mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = common.cell(common.spec(), args.workload)
    devices = common.accelerators(cell["workload"]["chips"])
    common.enable_cache()
    compiles = common.CompileLog()
    for seed in args.seeds:
        r = readings(cell, seed, args.seconds, devices, compiles)
        log(f"control: {r}")
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
