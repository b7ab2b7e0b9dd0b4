"""Observability tests (repro.obs + the instrumented serving stack).

The load-bearing invariants:

- the default NullTracer is *behaviourally free*: a JsonTracer-instrumented
  server produces bitwise-identical greedy outputs to an uninstrumented one
  (tracing never touches the RNG, the device arrays, or the scheduler);
- the JsonTracer's Chrome export passes ``scripts/validate_trace.py`` with
  a complete span chain per finished request (the same validator CI runs
  on the serving-smoke artifact);
- histogram-derived percentiles agree with the exact percentiles over the
  same samples to within one log bucket (``Server.ttft_percentiles`` vs
  the ``serving_ttft_seconds`` snapshot);
- ``Server.reset()`` zeroes *every* metric — including the spec counters —
  and drops trace events, so warmup/compile activity never leaks into a
  timed run's report.
"""
import bisect
import dataclasses
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.obs import (
    DEVICE_TID,
    PID_DEVICE,
    PID_REQUESTS,
    Histogram,
    JsonTracer,
    MetricsRegistry,
    NullTracer,
    StepProfiler,
    log_bounds,
    metrics_doc,
    write_metrics,
    write_trace,
)
from repro.serving import Server, ServerConfig, SpecConfig

_REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_trace", _REPO / "scripts" / "validate_trace.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fp32(cfg):
    return dataclasses.replace(cfg, policy="fp32", kv_cache_dtype="fp32")


@pytest.fixture(scope="module")
def served_model():
    cfg = _fp32(get_config("granite-3-8b", smoke=True))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, size=n)) for n in lens]


# -- histograms ---------------------------------------------------------------

def test_histogram_bucket_boundaries_le_semantics():
    """Inclusive upper edges (Prometheus le): a value equal to an edge
    lands in that edge's bucket; above the last edge -> overflow."""
    h = Histogram("h", bounds=(1.0, 2.0, 4.0))
    h.observe(1.0)   # == first edge -> bucket 0
    h.observe(1.5)   # bucket 1 (le 2.0)
    h.observe(2.0)   # == second edge -> bucket 1
    h.observe(4.0)   # == last edge -> bucket 2
    h.observe(4.001)  # overflow
    h.observe(0.0)   # bucket 0
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.min == 0.0 and h.max == 4.001
    # Cumulative series ends at +Inf with the total count.
    cum = h.cumulative()
    assert cum[-1] == ("+Inf", 6)
    assert [c for _, c in cum] == [2, 4, 5, 6]


def test_histogram_bounds_must_increase():
    with pytest.raises(ValueError):
        Histogram("h", bounds=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram("h", bounds=(2.0, 1.0))


def test_histogram_percentile_within_one_bucket_of_exact():
    """The bucket-edge estimate brackets the exact percentile: it is >= the
    exact value and <= the upper edge of the exact value's bucket."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=2.0, size=500)  # latency-ish
    h = Histogram("h")  # default log_bounds
    for s in samples:
        h.observe(float(s))
    bounds = list(h.bounds)
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q, method="inverted_cdf"))
        est = h.percentile(q)
        assert est >= exact - 1e-12
        i = bisect.bisect_left(bounds, exact)
        upper = bounds[i] if i < len(bounds) else float(np.max(samples))
        assert est <= min(upper, float(np.max(samples))) + 1e-12


def test_histogram_percentile_empty_and_clamped():
    h = Histogram("h", bounds=(1.0, 1000.0))
    assert h.percentile(50) is None
    h.observe(1.5)  # lands in the (1, 1000] bucket
    # Clamped to the observed max, not the absurdly wide bucket edge.
    assert h.percentile(99) == 1.5


def test_log_bounds_shape():
    b = log_bounds()
    assert len(b) == 26 and b[0] == pytest.approx(1e-5)
    assert all(y == pytest.approx(2 * x) for x, y in zip(b, b[1:]))


# -- registry -----------------------------------------------------------------

def test_registry_get_or_create_and_kind_conflicts():
    m = MetricsRegistry()
    c = m.counter("x_total", "help")
    assert m.counter("x_total") is c
    with pytest.raises(TypeError):
        m.gauge("x_total")
    h = m.histogram("lat", bounds=(1.0, 2.0))
    with pytest.raises(ValueError):
        m.histogram("lat", bounds=(1.0, 3.0))
    assert "lat" in m and "nope" not in m
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_reset_zeroes_in_place_handles_survive():
    m = MetricsRegistry()
    c = m.counter("c_total")
    g = m.gauge("g")
    h = m.histogram("h", bounds=(1.0, 2.0))
    c.inc(3)
    g.set(7)
    h.observe(1.5)
    m.reset()
    snap = m.snapshot()
    assert snap["counters"]["c_total"] == 0.0
    assert snap["gauges"]["g"] == 0.0
    assert snap["histograms"]["h"]["count"] == 0
    assert snap["histograms"]["h"]["p50"] is None
    # The same handles keep working after the reset.
    c.inc()
    h.observe(1.0)
    assert m.counter("c_total") is c and c.value == 1.0
    assert h.count == 1


def test_prometheus_exposition_format():
    m = MetricsRegistry()
    m.counter("req_total", "requests").inc(2)
    m.gauge("depth").set(3)
    h = m.histogram("lat_seconds", bounds=(0.5, 1.0), help="latency")
    h.observe(0.2)
    h.observe(0.7)
    h.observe(9.0)
    text = m.to_prometheus()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 2" in text
    assert "depth 3" in text
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


# -- profiler -----------------------------------------------------------------

def test_step_profiler_compile_vs_steady_split():
    p = StepProfiler()
    p.record("decode", 4, 1.0)   # first call per key -> compile
    p.record("decode", 4, 0.1)
    p.record("decode", 4, 0.3)
    p.record("decode", 8, 0.5)   # different bucket: its own compile
    s = p.summary()
    d4 = s["decode[4]"]
    assert d4["calls"] == 3 and d4["compile_s"] == 1.0
    assert d4["steady_calls"] == 2
    assert d4["steady_mean_s"] == pytest.approx(0.2)
    assert d4["steady_max_s"] == 0.3
    assert s["decode[8]"]["compile_s"] == 0.5
    assert s["decode[8]"]["steady_calls"] == 0
    assert "decode[4]" in p.format_summary()
    p.reset()
    assert p.summary() == {}


# -- tracer schema ------------------------------------------------------------

def test_json_tracer_chrome_schema_golden(tmp_path):
    """A hand-driven request lifecycle exports a Chrome document the repo
    validator accepts, with named tracks and a complete span chain."""
    t = JsonTracer()
    t.begin(PID_REQUESTS, 0, "request", rid=0, prompt_len=4)
    t.begin(PID_REQUESTS, 0, "queued")
    t.end(PID_REQUESTS, 0, "queued")
    t.instant(PID_REQUESTS, 0, "admitted", slot=1)
    t.begin(PID_REQUESTS, 0, "prefill_chunk", start=0, tokens=4)
    t.begin(PID_DEVICE, DEVICE_TID, "prefill_full", tokens=4)
    t.end(PID_DEVICE, DEVICE_TID, "prefill_full")
    t.end(PID_REQUESTS, 0, "prefill_chunk")
    t.begin(PID_REQUESTS, 0, "decode")
    t.instant(PID_REQUESTS, 0, "finished", finish_reason="length")
    t.end(PID_REQUESTS, 0, "decode")
    t.end(PID_REQUESTS, 0, "request")
    path = tmp_path / "trace.json"
    assert write_trace(t, str(path), meta={"k": 1}) == "chrome"
    doc = json.loads(path.read_text())
    assert doc["metadata"] == {"k": 1}
    assert doc["displayTimeUnit"] == "ms"
    names = {(e["pid"], e["tid"], e["args"]["name"])
             for e in doc["traceEvents"] if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert (PID_REQUESTS, 0, "req 0") in names
    assert (PID_DEVICE, DEVICE_TID, "steps") in names

    vt = _load_validator()
    assert vt.validate(str(path)) == []

    # JSONL export round-trips the same events one-per-line.
    jl = tmp_path / "trace.jsonl"
    assert write_trace(t, str(jl), meta=None) == "jsonl"
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert lines == doc["traceEvents"]


def test_validator_rejects_malformed_traces(tmp_path):
    vt = _load_validator()

    def check(events):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"traceEvents": events}))
        return vt.validate(str(p))

    base = {"pid": 1, "tid": 0}
    # Unclosed span.
    assert check([dict(base, name="request", ph="B", ts=1.0)])
    # Mismatched E.
    assert check([dict(base, name="a", ph="B", ts=1.0),
                  dict(base, name="b", ph="E", ts=2.0)])
    # ts goes backwards on one track.
    assert check([dict(base, name="a", ph="B", ts=5.0),
                  dict(base, name="a", ph="E", ts=1.0)])
    # Unknown phase / missing keys.
    assert check([dict(base, name="a", ph="Z", ts=1.0)])
    assert check([{"name": "a", "ph": "B"}])
    # finished instant without the full chain.
    assert check([dict(base, name="request", ph="B", ts=1.0),
                  dict(base, name="finished", ph="i", ts=2.0, s="t"),
                  dict(base, name="request", ph="E", ts=3.0)])
    # Not a trace document at all.
    p = tmp_path / "notdoc.json"
    p.write_text("[1, 2]")
    assert vt.validate(str(p))


def test_null_tracer_is_inert():
    t = NullTracer()
    assert not t.enabled
    t.begin(1, 0, "x", a=1)
    t.end(1, 0, "x")
    t.instant(1, 0, "y")
    t.reset()  # no state to clear, no error


@pytest.mark.parametrize("tracer", [NullTracer, JsonTracer])
def test_span_annotates_the_profiler_and_records_when_enabled(monkeypatch,
                                                               tracer):
    """``span`` always enters a ``TraceAnnotation`` with its name and args
    (the profiler's sink); only an enabled tracer also records B/E."""
    entered = []

    class Annotation:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            entered.append(("enter", self.name, self.args))

        def __exit__(self, *exc):
            entered.append(("exit", self.name, {}))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    t = tracer()
    with t.span(PID_DEVICE, DEVICE_TID, "decode.dispatch", decoding=2, ctx=9):
        with t.span(PID_DEVICE, DEVICE_TID, "harvest.wait", kind="decode"):
            pass
    assert entered == [
        ("enter", "decode.dispatch", {"decoding": 2, "ctx": 9}),
        ("enter", "harvest.wait", {"kind": "decode"}),
        ("exit", "harvest.wait", {}),
        ("exit", "decode.dispatch", {}),
    ]
    if tracer is NullTracer:
        return
    assert [(e["ph"], e["name"], e.get("args")) for e in t.events
            if e["ph"] in "BE"] == [
        ("B", "decode.dispatch", {"decoding": 2, "ctx": 9}),
        ("B", "harvest.wait", {"kind": "decode"}),
        ("E", "harvest.wait", None),
        ("E", "decode.dispatch", None),
    ]


# -- instrumented server ------------------------------------------------------

_LENS = (5, 11, 7, 9)
_GENS = (6, 3, 8, 5)


def _run_server(model, params, prompts, *, tracer=None, spec=None,
                prefill_chunk=4):
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=24, prefill_bucket=8,
        prefill_chunk=prefill_chunk,
    ), tracer=tracer, spec=spec)
    reqs = [server.submit(p, max_new_tokens=g)
            for p, g in zip(prompts, _GENS)]
    server.run()
    outs = [server.results[r.rid].out_tokens for r in reqs]
    return server, outs


def test_json_tracer_does_not_change_greedy_outputs(served_model):
    """Bitwise parity: tracing on vs off (the NullTracer default) yields
    identical greedy tokens — observability is read-only."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    _, plain_outs = _run_server(model, params, prompts)
    traced, traced_outs = _run_server(model, params, prompts,
                                      tracer=JsonTracer())
    assert traced_outs == plain_outs
    assert len(traced.tracer.events) > 0


def test_server_trace_passes_validator_with_full_chains(served_model, tmp_path):
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    server, _ = _run_server(model, params, prompts, tracer=JsonTracer())
    path = tmp_path / "trace.json"
    write_trace(server.tracer, str(path))
    vt = _load_validator()
    assert vt.validate(str(path)) == []
    events = server.tracer.events
    finished = [e for e in events
                if e["ph"] == "i" and e["name"] == "finished"]
    assert len(finished) == len(_LENS)
    # Device "steps" track records dispatch spans for both step kinds; the
    # "in flight" track records the matching dispatch->harvest X events.
    dev = {e["name"] for e in events
           if e["pid"] == PID_DEVICE and e["ph"] == "B"}
    assert {"prefill_chunk.dispatch", "decode.dispatch"} <= dev
    inflight = {e["name"] for e in events
                if e["pid"] == PID_DEVICE and e["ph"] == "X"}
    assert {"prefill_chunk.complete", "decode.complete"} <= inflight


def test_profiler_trace_holds_the_step_spans(served_model, tmp_path):
    """Under ``jax.profiler`` (the default NullTracer), a server's steps show
    in the profiler's own trace: ``server.step`` holding ``server.admit``,
    the dispatches, ``harvest.wait`` and ``server.commit``. Each
    ``decode.dispatch`` carries the rows it decodes and their contexts
    summed, new tokens included, as read from the engine's page mirrors
    at dispatch (the number a benchmark computes from the same mirrors)."""
    from jax.profiler import ProfileData

    cfg, model, params = served_model
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=24, prefill_bucket=8,
        prefill_chunk=4))
    eng, seen = server.engine, []
    dispatch_decode = eng.dispatch_decode

    def decode(*, active, **kw):
        seen.append((int(active.sum()),
                     int((server.cache.seq_lens[active] + 1).sum())))
        return dispatch_decode(active=active, **kw)

    eng.dispatch_decode = decode
    prompts = _prompts(cfg, _LENS)
    with jax.profiler.trace(str(tmp_path)):
        for p, g in zip(prompts, _GENS):
            server.submit(p, max_new_tokens=g)
        server.run()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = [ev for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events]
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev.name, []).append(ev)
    assert {"server.step", "server.admit", "prefill_chunk.dispatch",
            "decode.dispatch", "harvest.wait", "server.commit"} <= set(by_name)
    decodes = sorted(by_name["decode.dispatch"], key=lambda ev: ev.start_ns)
    assert [(dict(ev.stats)["decoding"], dict(ev.stats)["ctx"])
            for ev in decodes] == seen
    assert {dict(ev.stats)["kind"] for ev in by_name["harvest.wait"]} \
        == {"prefill_chunk", "decode"}

    def inside(ev):
        return any(s.start_ns <= ev.start_ns and ev.end_ns <= s.end_ns
                   for s in by_name["server.step"])

    assert all(inside(ev) for ev in decodes + by_name["harvest.wait"])


def test_step_programs_name_their_layers(served_model):
    """The compiled decode step names each layer's ops with a named scope
    (in op metadata only: the program keeps its name), and so does the
    sampler."""
    import re

    _, model, params = served_model
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=24))
    eng, cache = server.engine, server.cache
    hlo = eng._decode.lower(
        params, eng._last_tok, cache.pools, cache.page_table,
        cache.seq_lens, np.ones(2, bool)).compile().as_text()
    assert hlo.startswith("HloModule jit_decode_step")
    stacks = re.findall(r'op_name="jit\(decode_step\)/([^"]*)"', hlo)
    layers = {p for s in stacks for p in s.split("/")}
    assert {"embed", "attn", "ffn", "lm_head"} <= layers
    logits = np.zeros((2, model.cfg.vocab_size), np.float32)
    hlo = eng._sample.lower(logits, jax.random.PRNGKey(0), temperature=np.zeros(2, np.float32),
                            top_k=np.zeros(2, np.int32), top_p=np.ones(2, np.float32)
                            ).compile().as_text()
    assert hlo.startswith("HloModule jit_sample_logits")
    assert 'op_name="jit(sample_logits)/sample/' in hlo


def test_metrics_ttft_percentiles_within_one_bucket(served_model):
    """The histogram-derived TTFT p50/p95 agree with the exact
    ``Server.ttft_percentiles()`` to within one log bucket."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    server, _ = _run_server(model, params, prompts)
    exact = server.ttft_percentiles()
    h = server.metrics.snapshot()["histograms"]["serving_ttft_seconds"]
    assert h["count"] == len(_LENS)
    bounds = h["bounds"]
    for exact_q, est_q in zip(exact, (h["p50"], h["p95"])):
        assert est_q >= exact_q - 1e-12  # upper-edge estimate
        i = bisect.bisect_left(bounds, exact_q)
        upper = bounds[i] if i < len(bounds) else h["max"]
        assert est_q <= min(upper, h["max"]) + 1e-12


def test_server_stats_reads_from_registry(served_model):
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    server, _ = _run_server(model, params, prompts)
    s = server.stats
    snap = server.metrics.snapshot()["counters"]
    assert s.decode_steps == snap["serving_decode_steps_total"] > 0
    assert s.prefill_tokens == snap["serving_prefill_tokens_total"] \
        == sum(_LENS)
    # Each request's first token comes out of its final prefill chunk, so
    # decode_tokens counts the rest.
    assert s.decode_tokens == snap["serving_decode_tokens_total"] \
        == sum(_GENS) - len(_GENS)
    assert snap["serving_requests_submitted_total"] == len(_LENS)
    assert snap["serving_requests_finished_total"] == len(_LENS)


def test_reset_clears_spec_counters_and_metrics(served_model):
    """Satellite regression: ``Server.reset()`` must zero the speculative
    counters (spec_steps/spec_drafted/spec_accepted) and tracer/metric
    state exactly like the pre-existing fields — reported acceptance must
    exclude warmup/compile activity."""
    cfg, model, params = served_model
    # Repeated-motif prompts so the n-gram drafter actually accepts.
    rng = np.random.default_rng(3)
    prompts = []
    for i in range(3):
        motif = list(rng.integers(0, cfg.vocab_size, size=3 + i))
        prompts.append(motif * 3)
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=48, prefill_bucket=16,
    ), spec=SpecConfig(k=3), tracer=JsonTracer())
    for p in prompts:
        server.submit(p, max_new_tokens=8)
    server.run()
    s = server.stats
    assert s.spec_steps > 0 and s.spec_drafted > 0
    assert len(server.tracer.events) > 0
    pre_profile = dict(server.profiler.summary())
    assert pre_profile  # warmupless run: compile recorded per step kind

    server.reset()
    s = server.stats
    assert s.spec_steps == 0 and s.spec_drafted == 0 and s.spec_accepted == 0
    assert s.decode_steps == 0 and s.prefill_calls == 0
    assert s.acceptance_rate == 0.0
    assert server.tracer.events == []
    snap = server.metrics.snapshot()
    assert all(v == 0.0 for v in snap["counters"].values())
    assert all(v == 0.0 for v in snap["gauges"].values())
    assert all(h["count"] == 0 for h in snap["histograms"].values())
    # The step profiler deliberately survives: its first-call-per-key
    # memory is what keeps compile attributed to warmup after the reset.
    assert server.profiler.summary() == pre_profile

    # The same server still works (and re-accumulates) after the reset.
    for p in prompts:
        server.submit(p, max_new_tokens=4)
    server.run()
    assert server.stats.spec_steps > 0


def test_export_metrics_doc_and_files(tmp_path):
    m = MetricsRegistry()
    m.counter("c_total").inc(5)
    m.histogram("h_seconds", bounds=(1.0, 2.0)).observe(1.5)
    prof = StepProfiler()
    prof.record("decode", 2, 0.5)
    doc = metrics_doc(m, profiler=prof, meta={"arch": "x"})
    assert doc["arch"] == "x"
    assert doc["counters"]["c_total"] == 5.0
    assert doc["step_profile"]["decode[2]"]["compile_s"] == 0.5
    jp = tmp_path / "m.json"
    assert write_metrics(m, str(jp), profiler=prof) == "json"
    assert json.loads(jp.read_text())["counters"]["c_total"] == 5.0
    pp = tmp_path / "m.prom"
    assert write_metrics(m, str(pp)) == "prometheus"
    assert "c_total 5" in pp.read_text()


def test_scheduler_queue_gauges(served_model):
    cfg, model, params = served_model
    prompts = _prompts(cfg, (5, 6, 7))
    server = Server(model, params, ServerConfig(
        num_slots=1, page_size=4, max_seq_len=16, prefill_bucket=8,
    ))
    for p in prompts:
        server.submit(p, max_new_tokens=6)
    g = server.metrics.snapshot()["gauges"]
    assert g["serving_queue_depth"] == 3.0
    server.step()
    g = server.metrics.snapshot()["gauges"]
    assert g["serving_queue_depth"] == 2.0
    assert g["serving_running_requests"] == 1.0
    server.run()
    g = server.metrics.snapshot()["gauges"]
    assert g["serving_queue_depth"] == 0.0
    assert g["serving_running_requests"] == 0.0


def test_queue_wait_and_itl_histograms_populated(served_model):
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    server, _ = _run_server(model, params, prompts)
    h = server.metrics.snapshot()["histograms"]
    assert h["serving_queue_wait_seconds"]["count"] == len(_LENS)
    # Every generated token after a request's first contributes one ITL gap.
    assert h["serving_inter_token_seconds"]["count"] == \
        sum(_GENS) - len(_GENS)
    assert h["serving_prefill_chunk_seconds"]["count"] > 0
    assert h["serving_decode_step_seconds"]["count"] > 0


def test_device_capture_raises_when_the_profiler_cannot_start(monkeypatch,
                                                              tmp_path):
    """A run asked for a device trace must not finish without one."""
    from repro.obs.profiler import device_capture

    with device_capture(None):
        pass  # no logdir: a passthrough that never touches the profiler

    def refuse(logdir):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    ran = []
    with pytest.raises(RuntimeError, match="already active"):
        with device_capture(str(tmp_path)):
            ran.append(True)
    assert not ran
