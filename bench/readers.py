"""What the metric readers share: the steps a traced run dispatched inside
its trace, and their required work from ``work.py``."""
from __future__ import annotations

import numpy as np

import work

PROGRAMS = {"decode": "jit_decode_step", "prefill": "jit_prefill_chunk"}


def traced_steps(rec: dict, kind: str) -> list:
    """Host records of the ``kind`` steps dispatched inside the trace."""
    if not rec.get("trace_window"):
        return []
    lo, hi = rec["trace_window"]
    return [a for k, t, a in rec["steps"] if k == kind and lo <= t <= hi]


def device_calls(rec: dict, kind: str) -> list[float]:
    """Device seconds of each execution of the ``kind`` step's program."""
    return rec.get("trace", {}).get("programs", {}).get(PROGRAMS[kind], [])


def step_work(rec: dict, kind: str, args) -> dict:
    w = work.Widths.of(rec["config"])
    if kind == "decode":
        return work.decode_step(w, args)
    return work.prefill_chunk(w, *args)


def mean_required(rec: dict, kind: str, fn) -> float | None:
    """Mean over the traced ``kind`` steps of ``fn(step work)``."""
    steps = traced_steps(rec, kind)
    if not steps:
        return None
    return float(np.mean([fn(step_work(rec, kind, a)) for a in steps]))


def kernel_share(rec: dict, kernel: str, fn, kinds=("decode", "prefill")) -> float | None:
    """Required roofline seconds of ``kernel``'s work in the traced steps,
    scaled to the device's count of step executions, over the kernel's
    device seconds; in percent."""
    busy = rec.get("trace", {}).get("ops", {}).get(kernel)
    if not busy:
        return None
    need = 0.0
    for kind in kinds:
        per_step = mean_required(rec, kind, fn)
        if per_step is not None:
            need += per_step * len(device_calls(rec, kind))
    return 100.0 * need / busy if need else None

