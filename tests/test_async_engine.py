"""Async serving-engine tests (repro.serving.engine + the Server facade's
dispatch-ahead loop and batched multi-slot prefill).

The load-bearing guarantees:

- greedy outputs are bitwise identical at every ``async_depth`` and with
  ``prefill_batch`` on or off — the dispatch window and P-bucketed
  prefill packing change wall-clock overlap, never results;
- P-bucketing is a fixed, small shape set, so batched prefill compiles a
  bounded number of programs;
- latency marks (TTFT / t_last_token) are stamped when tokens are
  harvested at the stream boundary, not when the step was dispatched.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.serving import Server, ServerConfig
from repro.serving.engine import P_BUCKETS


def _fp32(cfg):
    return dataclasses.replace(cfg, policy="fp32", kv_cache_dtype="fp32")


@pytest.fixture(scope="module")
def served_model():
    cfg = _fp32(get_config("granite-3-8b", smoke=True))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def recurrent_model():
    cfg = _fp32(get_config("recurrentgemma-2b", smoke=True))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, size=n)) for n in lens]


_LENS = (5, 11, 7, 9)
_GENS = (6, 3, 8, 5)


def _run(model, params, prompts, gens, tracer=None, **cfg_kw):
    kw = dict(num_slots=2, page_size=4, max_seq_len=24, prefill_bucket=8)
    kw.update(cfg_kw)
    server = Server(model, params, ServerConfig(**kw), tracer=tracer)
    reqs = [server.submit(p, max_new_tokens=g)
            for p, g in zip(prompts, gens)]
    results = server.run()
    outs = [results[r.rid].out_tokens for r in reqs]
    assert server.cache.allocator.num_held == 0
    assert server.engine.num_inflight == 0
    return server, outs


# -- config validation --------------------------------------------------------

def test_config_validation(served_model):
    _, model, params = served_model
    with pytest.raises(ValueError, match="async_depth"):
        Server(model, params, ServerConfig(
            num_slots=2, page_size=4, max_seq_len=24, async_depth=-1))
    with pytest.raises(ValueError, match="prefill_chunk"):
        Server(model, params, ServerConfig(
            num_slots=2, page_size=4, max_seq_len=24, prefill_batch=True))


# -- P-bucketing policy -------------------------------------------------------

def test_bucket_policy(served_model):
    """Buckets are the fixed P_BUCKETS ladder clamped to num_slots, and
    bucket_for picks the smallest bucket covering the group."""
    _, model, params = served_model
    server = Server(model, params, ServerConfig(
        num_slots=6, page_size=4, max_seq_len=24, prefill_bucket=8,
        prefill_chunk=4, prefill_batch=True))
    eng = server.engine
    assert P_BUCKETS == (1, 2, 4, 8)
    assert eng.allowed_buckets() == (1, 2, 4)   # 8 > num_slots=6
    assert [eng.bucket_for(n) for n in (1, 2, 3, 4)] == [1, 2, 4, 4]

    server1 = Server(model, params, ServerConfig(
        num_slots=1, page_size=4, max_seq_len=24, prefill_bucket=8,
        prefill_chunk=4, prefill_batch=True))
    assert server1.engine.allowed_buckets() == (1,)


# -- greedy parity ------------------------------------------------------------

def test_async_depth_greedy_parity(served_model):
    """Bitwise-identical greedy outputs at every dispatch depth: the
    window only overlaps host work with device compute."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS)
    _, base = _run(model, params, prompts, _GENS, async_depth=0)
    for depth in (1, 2, 3):
        _, outs = _run(model, params, prompts, _GENS, async_depth=depth)
        assert outs == base, f"depth {depth}"


def test_async_depth_parity_sliding_window():
    """Same parity on a sliding-window arch (gemma2), where decode-side
    page recycling races the dispatch window if snapshots are skipped."""
    cfg = _fp32(get_config("gemma2-2b", smoke=True))  # window 16
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompts = _prompts(cfg, (14, 10), seed=9)
    _, base = _run(model, params, prompts, (8, 8), async_depth=0)
    _, outs = _run(model, params, prompts, (8, 8), async_depth=2)
    assert outs == base


def test_batched_prefill_greedy_parity(served_model):
    """(P, chunk) multi-slot prefill == serial (1, chunk) prefill, with
    and without the dispatch window."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS, seed=4)
    _, base = _run(model, params, prompts, _GENS, prefill_chunk=4)
    for depth in (0, 2):
        _, outs = _run(model, params, prompts, _GENS, prefill_chunk=4,
                       prefill_batch=True, async_depth=depth)
        assert outs == base, f"depth {depth}"


def test_batched_prefill_greedy_parity_recurrent(recurrent_model):
    """Same parity on a recurrent/hybrid arch: batched prefill touches
    per-slot state rows, where a pad row aliasing an active slot would
    corrupt state via duplicate-index scatter."""
    cfg, model, params = recurrent_model
    prompts = _prompts(cfg, (6, 9, 5, 7), seed=11)
    gens = (4, 4, 4, 4)
    _, base = _run(model, params, prompts, gens, prefill_chunk=4)
    _, outs = _run(model, params, prompts, gens, prefill_chunk=4,
                   prefill_batch=True, async_depth=1)
    assert outs == base


# -- EOS overshoot ------------------------------------------------------------

def test_eos_overshoot_discarded(served_model):
    """With depth >= 1, up to ``depth`` decode steps may already be in
    flight when EOS is harvested; their tokens must be discarded, leaving
    exactly the depth-0 output."""
    cfg, model, params = served_model
    (prompt,) = _prompts(cfg, (6,), seed=5)
    server = Server(model, params, ServerConfig(
        num_slots=1, page_size=4, max_seq_len=16, prefill_bucket=8))
    req = server.submit(prompt, max_new_tokens=5)
    first = server.run()[req.rid].out_tokens
    eos = first[1]
    for depth in (1, 3):
        server = Server(model, params, ServerConfig(
            num_slots=1, page_size=4, max_seq_len=16, prefill_bucket=8,
            async_depth=depth))
        req = server.submit(prompt, max_new_tokens=5, eos_id=eos)
        out = server.run()[req.rid].out_tokens
        assert out == first[: first.index(eos) + 1], f"depth {depth}"
        assert server.engine.num_inflight == 0
        assert server.cache.allocator.num_held == 0


# -- latency marks at the stream boundary -------------------------------------

def test_latency_marks_stamped_at_harvest(served_model):
    """Each TokenEvent's t_first_token / t_last_token falls inside the
    wall-clock window of the step() call that returned it. At depth >= 1
    a token's step is dispatched one or more steps before it is
    harvested, so dispatch-time stamping would land in an earlier
    window."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, _LENS, seed=6)
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=4, max_seq_len=24, prefill_bucket=8,
        async_depth=2))
    reqs = {}
    for p, g in zip(prompts, _GENS):
        r = server.submit(p, max_new_tokens=g)
        reqs[r.rid] = r
    n_events = 0
    while server.scheduler.has_work():
        t0 = time.perf_counter()
        events = server.step()
        t1 = time.perf_counter()
        for ev in events:
            req = reqs[ev.rid]
            assert t0 <= req.t_last_token <= t1
            if ev.index == 0:
                assert t0 <= req.t_first_token <= t1
            n_events += 1
    assert n_events == sum(_GENS)
    server.run()  # drain; EOS-free run leaves nothing in flight
    assert server.engine.num_inflight == 0


# -- compile count ------------------------------------------------------------

def test_batched_prefill_compile_count_bounded(served_model):
    """prefill_batch compiles at most one program per allowed P bucket —
    the StepProfiler's first-call-per-key memory counts compiles."""
    cfg, model, params = served_model
    prompts = _prompts(cfg, (3, 5, 6, 7, 9, 11, 4, 8), seed=7)
    server = Server(model, params, ServerConfig(
        num_slots=4, page_size=4, max_seq_len=24, prefill_bucket=8,
        prefill_chunk=4, prefill_batch=True, async_depth=1))
    for p in prompts:
        server.submit(p, max_new_tokens=3)
    server.run()
    keys = [k for k in server.profiler.summary()
            if k.startswith("prefill_batch[")]
    assert keys  # the batched path actually ran
    assert len(keys) <= len(server.engine.allowed_buckets())


# -- engine observability -----------------------------------------------------

def test_engine_metrics(served_model):
    """engine_inflight settles to 0 and every harvested step records one
    ``harvest.wait`` span (the host's wait at the stream boundary), of the
    step's kind."""
    from repro.obs import JsonTracer

    cfg, model, params = served_model
    prompts = _prompts(cfg, (5, 7), seed=8)
    server, _ = _run(model, params, prompts, (4, 4), tracer=JsonTracer(),
                     async_depth=2)
    snap = server.metrics.snapshot()
    assert snap["gauges"]["engine_inflight"] == 0
    assert "engine_idle_seconds" not in snap["histograms"]
    events = server.tracer.events
    harvested = [e["name"].removesuffix(".complete") for e in events
                 if e["ph"] == "X"]
    waits = [e["args"]["kind"] for e in events
             if e["ph"] == "B" and e["name"] == "harvest.wait"]
    assert harvested and waits == harvested


# -- spec interaction ---------------------------------------------------------

def test_async_depth_inert_under_spec(served_model):
    """Speculative rounds are host-synchronous; --async-depth must not
    change spec outputs (prefills are drained before each round)."""
    from repro.serving import SpecConfig
    cfg, model, params = served_model
    rng = np.random.default_rng(12)
    motif = list(rng.integers(0, cfg.vocab_size, size=4))
    prompt = motif * 3

    def run(depth):
        server = Server(model, params, ServerConfig(
            num_slots=2, page_size=4, max_seq_len=48, prefill_bucket=16,
            async_depth=depth), spec=SpecConfig(k=3, ngram_n=3))
        req = server.submit(prompt, max_new_tokens=8)
        return server.run()[req.rid].out_tokens

    assert run(2) == run(0)
