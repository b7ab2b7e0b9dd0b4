"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) with a
line of program executions (``XLA Modules``: ``jit_decode_step(<hash>)``)
and a line of operations (``XLA Ops``: each event named after its HLO
instruction, ``%redmule_gemm.48 = bf16[...] custom-call(...)``). Pallas
kernels are custom calls whose instruction takes the kernel's name, so a
kernel's events are the ops whose instruction name starts with it. Control
flow ops (``while``, ``conditional``, ``call``) span the ops they run and are
left out of per-op sums. Host spans (``jax.profiler.TraceAnnotation``) lie in
the ``/host:CPU`` plane on the same clock.

The traced window is the host span named ``WINDOW``; everything is clipped
to it.
"""
from __future__ import annotations

import collections
import re

WINDOW = "bench.window"
_CONTAINERS = ("while", "conditional", "call")


def op_base(name: str) -> str:
    """``%redmule_gemm.48 = ...`` -> ``redmule_gemm``; ``%fusion.3.clone`` -> ``fusion``."""
    inst = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d.*$", "", inst)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    return []


def host_spans(profile, names) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the host annotations named in ``names``."""
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events if ev.name in names)
    return out


def reduce_device(plane, lo, hi) -> dict:
    """One chip's busy time, per-op and per-program device time in [lo, hi]."""
    ops = [(n, *_clip(s, e, lo, hi)) for n, s, e in _events(plane, "XLA Ops")]
    ops = [(n, s, e) for n, s, e in ops if e > s]
    busy = _union((s, e) for _, s, e in ops)
    per_op = collections.defaultdict(float)
    for n, s, e in ops:
        base = op_base(n)
        if base not in _CONTAINERS:
            per_op[base] += (e - s) * 1e-9
    programs = collections.defaultdict(list)
    for n, s, e in _events(plane, "XLA Modules"):
        if lo <= s and e <= hi:
            programs[n.split("(")[0]].append((e - s) * 1e-9)
    return {
        "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "busy": busy,
        "ops": dict(per_op),
        "programs": dict(programs),
    }


def reduce(profile, n_chips: int, host_names=(), window=WINDOW) -> dict:
    """The traced window (from the first host span named ``window`` to the
    end of the last), averaged over the first ``n_chips`` chips."""
    win = host_spans(profile, {window})
    if not win:
        raise ValueError(f"the trace holds no host span {window!r}")
    lo, hi = min(w[1] for w in win), max(w[2] for w in win)
    planes = {p.name: p for p in profile.planes}
    chips = [reduce_device(planes[f"/device:TPU:{i}"], lo, hi) for i in range(n_chips)]
    ops = collections.defaultdict(float)
    programs = collections.defaultdict(list)
    for c in chips:
        for k, v in c["ops"].items():
            ops[k] += v / n_chips
        for k, v in c["programs"].items():
            programs[k].extend(v)
    spans = host_spans(profile, set(host_names))
    gaps = collections.defaultdict(float)
    busy = chips[0]["busy"]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps[_host_at(spans, s, e)] += (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(c["busy_s"] for c in chips) / n_chips,
        "ops": dict(ops),
        "programs": dict(programs),
        "idle_by_host": dict(gaps),
    }


def _host_at(spans, s, e) -> str:
    """The innermost host span that covers most of the device gap [s, e]."""
    best, best_key = "other host work", (0.0, 0.0)
    for name, a, b in spans:
        cover = max(0.0, min(b, e) - max(a, s))
        if cover <= 0:
            continue
        key = (cover, -(b - a))  # most overlap, then the shortest (innermost)
        if key > best_key:
            best, best_key = name, key
    return best


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_by_host"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
