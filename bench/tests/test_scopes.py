"""``scopes.py`` and the per-layer readers that read it, on two traces
recorded on a TPU v5e at the chat cell's widths (granite-3-8b, 20 layers, 8
slots, gzipped):

- ``serve20``: the program before it named its layers and phases (twelve
  ``Server.step`` calls), so every op is outside any layer scope;
- ``chat6s``: six seconds of the chat cell, traced whole, recorded by
  ``bench/record_trace.py`` with the program's named scopes and step
  spans; ``chat6s.json`` beside it holds the harness's record of the steps
  dispatched in it.
"""
from __future__ import annotations

import gzip
import json
import os

import pytest

import common
import readers
import reduce
import scopes
import serve

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT = "granite-3-8b.chat"
NEW = ("decode_ffn_ms.chat", "decode_attn_ms.chat", "decode_lm_head_ms.chat",
       "host_step_ms.chat")
KERNELS = ("redmule_gemm", "paged_flash_decode")
PROGRAM_SPANS = ("server.step", "server.admit", "decode.dispatch", "prefill_chunk.dispatch",
                 "harvest.wait", "server.commit")


def _load(name):
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f:
        data = f.read()
    return data, ProfileData.from_serialized_xspace(data), scopes.DevicePlane(data)


@pytest.fixture(scope="module")
def serve20():
    return _load("serve20")


@pytest.fixture(scope="module")
def chat():
    data, profile, dev = _load("chat6s")
    with open(os.path.join(DATA, "chat6s.json")) as f:
        side = json.load(f)
    return data, profile, dev, side


def _op_times(dev, program):
    """(op name, tf_op stack or None, seconds) of each non-container op of
    every execution of ``program``."""
    ops = [(m, s, e) for m, s, e in dev.events("XLA Ops")
           if reduce.op_base(dev.names[m]) not in reduce._CONTAINERS]
    out = []
    for m, s, e in dev.events("XLA Modules"):
        if dev.names[m].split("(")[0] == program:
            out += [(dev.names[o], dev.stacks.get(o), (min(b, e) - a) * 1e-9)
                    for o, a, b in ops if s <= a <= e]
    return out


def _record(data, profile, side, host_names=serve.HOST_SPANS, with_scopes=True):
    """A traced run's record as ``bench/run.py`` builds it from this trace;
    ``with_scopes`` adds ``scopes.reduce_trace``'s keys."""
    rec = {"config": common.cell(common.spec(), CHAT)["config"],
           "peak": common.peaks("TPU v5 lite"), "steps": side["steps"],
           "trace_window": side["trace_window"], "requests": [],
           "setup_s": 60.0, "trace": reduce.reduce(profile, 1, host_names)}
    if with_scopes:
        rec["trace"].update(scopes.reduce_trace(data, profile))
    return rec


# -- the wire reader, on the trace without scopes ------------------------------

def test_ops_resolve_to_a_name_stack(serve20):
    """Every op of the step programs carries its JAX name stack, but for the
    compiler's own copies and layout changes: under 0.01% of their time."""
    _, _, dev = serve20
    for program in readers.PROGRAMS.values():
        ops = _op_times(dev, program)
        bare = [(n, t) for n, stack, t in ops if stack is None]
        assert ops and sum(t for _, t in bare) < 1e-4 * sum(t for _, _, t in ops)
        assert all(stack.startswith(f"jit({program[4:]})/")
                   for _, stack, _ in ops if stack is not None)
        assert not any(reduce.op_base(n) in KERNELS for n, _ in bare)


def test_program_sums_match_the_reduction(serve20):
    _, profile, dev = serve20
    red = reduce.reduce(profile, 1, (), window="Server.step")
    for program in readers.PROGRAMS.values():
        calls = scopes.program_scopes(dev, program)
        assert len(calls) == len(red["programs"][program])
        for split, device_s in zip(calls, red["programs"][program]):
            assert sum(split.values()) == pytest.approx(device_s, rel=0.01)


def test_unnamed_program_is_all_unscoped(serve20):
    data, profile, dev = serve20
    calls = scopes.program_scopes(dev, "jit_decode_step")
    assert len(calls) == 11 and all(set(c) == {scopes.UNSCOPED} for c in calls)
    rec = {"trace": scopes.reduce_trace(data, profile, window="Server.step")}
    assert rec["trace"]["host_steps"] == []  # no program spans yet
    for metric in NEW:
        assert common.reader(metric)(rec) is None


def test_scope_of():
    stack = ("jit(decode_step)/while/body/closed_call/checkpoint/ffn/"
             "jit(_gemm_op_impl)/redmule_gemm/pallas_call:")
    assert scopes.scope_of(stack) == "ffn"
    assert scopes.scope_of("jit(sample_logits)/sample/argmax:") == "sample"
    assert scopes.scope_of("jit(decode_step)/while/body/dynamic_slice:") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


# -- the trace with named scopes and step spans --------------------------------

def test_layer_scopes_cover_the_decode_step(chat):
    _, _, dev, _ = chat
    calls = scopes.program_scopes(dev, "jit_decode_step")
    assert len(calls) >= 5
    for split in calls:
        named = sum(split.get(s, 0.0) for s in ("ffn", "attn", "lm_head", "embed"))
        assert named >= 0.95 * sum(split.values())
    ops = _op_times(dev, "jit_decode_step") + _op_times(dev, "jit_prefill_chunk")
    gemms = [stack for name, stack, _ in ops if reduce.op_base(name) == "redmule_gemm"]
    assert gemms and all(scopes.scope_of(s or "") != scopes.UNSCOPED for s in gemms)


def test_program_and_kernel_names_are_unchanged(chat):
    data, profile, _, side = chat
    red = _record(data, profile, side)["trace"]
    assert {"jit_decode_step", "jit_prefill_chunk", "jit_sample_logits"} <= set(red["programs"])
    assert all(red["ops"].get(k, 0) > 0 for k in KERNELS)


def test_step_spans_nest_dispatch_and_wait(chat):
    _, profile, _, _ = chat
    spans = reduce.host_spans(profile, set(PROGRAM_SPANS))
    assert {n for n, _, _ in spans} == set(PROGRAM_SPANS)
    steps = [(s, e) for n, s, e in spans if n == "server.step"]
    for n, s, e in spans:
        if n in ("decode.dispatch", "harvest.wait", "server.admit", "server.commit"):
            assert any(a <= s and e <= b for a, b in steps), n


def test_decode_dispatch_args_equal_the_harness_record(chat):
    """Each ``decode.dispatch`` span's ``decoding`` and ``ctx`` equal what
    the harness's ``Recorder`` computed for the same step."""
    _, profile, _, side = chat
    lo, hi = [(s, e) for n, s, e in reduce.host_spans(profile, {reduce.WINDOW})][0]
    spans = sorted((ev.start_ns, dict(ev.stats)) for plane in profile.planes
                   if plane.name == "/host:CPU" for line in plane.lines
                   for ev in line.events
                   if ev.name == "decode.dispatch" and lo <= ev.start_ns <= hi)
    recorded = [ctx for kind, _, ctx in side["steps"] if kind == "decode"]
    assert recorded and [(a["decoding"], a["ctx"]) for _, a in spans] == [
        (len(ctx), sum(ctx)) for ctx in recorded]


def test_new_readers_return_numbers(chat):
    data, profile, _, side = chat
    rec = _record(data, profile, side)
    values = {m: common.reader(m)(rec) for m in NEW}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), values
    step_ms = common.reader("decode_step_ms.chat")(rec)
    layers = sum(values[m] for m in NEW[:3])
    assert 0.95 * step_ms <= layers <= step_ms * (1 + 1e-9)


@pytest.mark.parametrize("metric", [m["name"] for m in common.spec()["per_layer"]]
                         + ["setup_s"])
def test_existing_metrics_read_the_same(chat, metric):
    """The per-layer split and the program's spans added to a record move
    none of the metrics the benchmark reads without them, and the
    program's spans among the host names move none of those that read the
    split."""
    data, profile, _, side = chat
    plain = _record(data, profile, side, with_scopes=metric in NEW)
    wired = _record(data, profile, side, serve.HOST_SPANS + PROGRAM_SPANS)
    read = common.reader(metric)
    assert read(wired) == read(plain)
    if metric != "setup_s":
        assert read(plain) is not None and read(plain) > 0


def test_idle_gaps_name_program_spans(chat):
    data, profile, _, side = chat
    gaps = _record(data, profile, side, serve.HOST_SPANS + PROGRAM_SPANS)["trace"]
    assert set(gaps["idle_by_host"]) & set(PROGRAM_SPANS)


def test_record_trace_at_smoke_size(tmp_path, monkeypatch):
    """``record_trace.py``'s three windows on the CPU at smoke size: a
    gzipped trace holding the program's spans, and the harness's record of
    the traced window's steps beside it."""
    import jax
    from jax.profiler import ProfileData

    import record_trace
    from conftest import CHAT, tiny_config

    monkeypatch.setattr(record_trace, "LEAD_S", 1.0)
    monkeypatch.setattr(record_trace, "RATE", 6.0)
    cell = {"workload": {"name": "tiny.chat", "chips": 1}, "traffic": dict(CHAT),
            "config": tiny_config("granite-3-8b-serve", backend="xla"), "root": common.ROOT}
    out = str(tmp_path / "t.xplane.pb.gz")
    args = record_trace.parse(["--seconds", "1", "--out", out])
    side = record_trace.record(cell, args, jax.devices()[:1], common.CompileLog(), out)
    assert side["steps"] and side["json_tracer_host_step_ms"]
    assert side["compiles_in_windows"] == 0
    with gzip.open(out) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    assert {n for n, _, _ in reduce.host_spans(profile, set(PROGRAM_SPANS))} >= {
        "server.step", "decode.dispatch", "harvest.wait"}


def test_traced_run_records_the_programs_names(chat, tmp_path, monkeypatch):
    """A traced ``run.run`` on the CPU puts ``scopes.reduce_trace``'s
    ``scopes`` and ``host_steps`` into its record, and the four readers of
    the program's names report from them. The CPU's profiler writes no
    device plane, so the trace the run reads is ``chat6s``, written where
    the profiler writes its own."""
    import jax

    from conftest import CHAT as MIX, make_root, run_cell, tiny_config

    data, profile, _, side = chat
    traced = {}

    def stop_trace():
        out = os.path.join(traced["dir"], "plugins", "profile", "chip")
        os.makedirs(out)
        with open(os.path.join(out, "chip.xplane.pb"), "wb") as f:
            f.write(data)

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: traced.update(dir=d))
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    per_layer = [m for m in common.spec()["per_layer"] if m["name"] in NEW]
    e2e = [{"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
            "source": "host_clock"}]
    root = make_root(tmp_path, "tiny.chat", tiny_config("granite-3-8b-serve", backend="xla"),
                     MIX, {"logit_gap": {"limit": 10.0}}, e2e, per_layer)
    result = run_cell(root, "tiny.chat", trace=1)
    assert traced and set(result["metrics"]) == set(NEW)
    want = _record(data, profile, side)
    for metric in NEW:
        assert result["metrics"][metric]["value"] == common.reader(metric)(want)
