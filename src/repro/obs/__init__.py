"""repro.obs: observability for the serving stack.

RedMulE's headline claim is *measured* — 99.4% CE-array utilization at
specific operating points — and the serving analogue
(``ServerStats.utilization``) needs the same evidentiary chain: this
package provides request-lifecycle tracing (``trace``: Chrome
trace-event / JSONL export, Perfetto-loadable), a process-local metrics
registry with log-bucket latency histograms and Prometheus/JSON export
(``metrics``), per-jitted-step wall-clock profiling that separates
compile from steady state (``profiler``), a trace-time count of the
weight casts, transposes and pads each jitted step does (``weight_prep``),
and the flush plumbing (``export``). The server is instrumented against the ``Tracer`` protocol
with a zero-overhead ``NullTracer`` default — tracing off costs nothing
and changes nothing (bitwise, a tested invariant).

    from repro.obs import JsonTracer
    tracer = JsonTracer()
    server = Server(model, params, cfg, tracer=tracer)
    ...
    tracer.write_chrome("trace.json")   # open in https://ui.perfetto.dev
    print(server.metrics.to_prometheus())
"""
from repro.obs.export import metrics_doc, write_metrics, write_trace
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_bounds,
)
from repro.obs.profiler import StepProfiler, device_capture
from repro.obs.trace import (
    DEVICE_INFLIGHT_TID,
    DEVICE_TID,
    PID_DEVICE,
    PID_REQUESTS,
    JsonTracer,
    NullTracer,
    Tracer,
)
from repro.obs.weight_prep import (
    WeightPrep,
    count_weight_prep,
    weight_prep_tally,
)

__all__ = [
    "Counter",
    "DEVICE_INFLIGHT_TID",
    "DEVICE_TID",
    "Gauge",
    "Histogram",
    "JsonTracer",
    "MetricsRegistry",
    "NullTracer",
    "PID_DEVICE",
    "PID_REQUESTS",
    "StepProfiler",
    "Tracer",
    "WeightPrep",
    "count_weight_prep",
    "device_capture",
    "log_bounds",
    "metrics_doc",
    "weight_prep_tally",
    "write_metrics",
    "write_trace",
]
