"""Wall-clock profiling of jitted device steps.

:class:`StepProfiler` keys every measurement by ``(kind, shape_bucket)`` —
the same key a jit cache entry has — and attributes the **first** call per
key to compile (tracing + lowering dominate it) and every later call to
steady state. That separation is why ``Server.reset()`` deliberately does
NOT clear the profiler: warmup compiles, the timed run after the reset
reuses the cache, and the profiler's first-call memory is what keeps the
attribution honest across the reset. Reported serving tok/s therefore
never includes tracing time, and the summary shows exactly where compile
time went when it does happen (e.g. an unexpected new shape mid-run —
the usual cause of a mysterious latency spike).

:func:`device_capture` is the opt-in escalation: a context manager around
``jax.profiler`` that records a full device trace (XLA ops, transfers)
into a TensorBoard/Perfetto-loadable logdir for the wrapped window only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional


@dataclasses.dataclass
class StepRecord:
    """Aggregate timing of one (kind, shape_bucket) jitted step."""

    kind: str
    bucket: str
    calls: int = 0
    compile_s: float = 0.0  # first call: tracing + lowering + run
    steady_s: float = 0.0  # every later call, summed
    steady_max_s: float = 0.0

    @property
    def steady_calls(self) -> int:
        return max(0, self.calls - 1)

    @property
    def steady_mean_s(self) -> float:
        n = self.steady_calls
        return self.steady_s / n if n else 0.0


class StepProfiler:
    """Per-(kind, shape-bucket) wall-clock accounting of jitted steps."""

    def __init__(self):
        self.records: dict[tuple[str, str], StepRecord] = {}

    def record(self, kind: str, bucket, seconds: float) -> None:
        key = (kind, str(bucket))
        rec = self.records.get(key)
        if rec is None:
            rec = self.records[key] = StepRecord(kind=kind, bucket=str(bucket))
        rec.calls += 1
        if rec.calls == 1:
            rec.compile_s = seconds
        else:
            rec.steady_s += seconds
            rec.steady_max_s = max(rec.steady_max_s, seconds)

    @contextlib.contextmanager
    def step(self, kind: str, bucket):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(kind, bucket, time.perf_counter() - t0)

    def summary(self) -> dict[str, dict]:
        """JSON-able view keyed ``kind[bucket]``, compile and steady split."""
        out = {}
        for (kind, bucket), r in sorted(self.records.items()):
            out[f"{kind}[{bucket}]"] = {
                "calls": r.calls,
                "compile_s": r.compile_s,
                "steady_calls": r.steady_calls,
                "steady_s": r.steady_s,
                "steady_mean_s": r.steady_mean_s,
                "steady_max_s": r.steady_max_s,
            }
        return out

    def format_summary(self) -> str:
        lines = ["step profile (first call = compile):"]
        for key, s in self.summary().items():
            lines.append(
                f"  {key}: compile {s['compile_s'] * 1e3:.1f} ms, "
                f"steady {s['steady_mean_s'] * 1e6:.0f} us/call "
                f"x {s['steady_calls']}"
            )
        return "\n".join(lines)

    def reset(self) -> None:
        self.records = {}


@contextlib.contextmanager
def device_capture(logdir: Optional[str]):
    """Opt-in ``jax.profiler`` capture window. ``logdir=None`` is a no-op
    passthrough, so call sites can wrap unconditionally. A profiler that
    fails to start (e.g. an already-active trace) raises: a run asked to
    trace must not finish untraced."""
    if not logdir:
        yield
        return
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
