"""Serving throughput: continuous batching (StateStore: paged KV pools +
per-slot recurrent state rows) vs static batching on mixed long/short
synthetic workloads, per architecture family.

Static batching pads every prompt in a batch and decodes until the batch's
longest request finishes — short requests hold their lane idle. Continuous
batching recycles a finished slot into the next queued request, so the
decode GEMM stays fed (the utilization discipline the paper applies to its
CE array via double-buffering, transplanted to serving). Long prompts
prefill in fixed-size chunks interleaved with decode steps, bounding how
long running requests stall (TTFT jitter) behind a long admission.

Both paths report steady-state decode tok/s with compile excluded: the
continuous server warms up every jitted shape first; the static path
extrapolates its measured per-step cost over all steps. The continuous
path additionally reports TTFT p50/p95 (submit -> first token, queueing
included — the latency continuous batching + chunked prefill actually
improve).

The shared-system-prompt section runs the dominant real-traffic shape —
every request opens with the same system/few-shot prefix — twice, with
prefix caching off then on, and reports the TTFT p50/p95 drop, the prefix
hit-rate, and a preemption count from a priority burst; greedy outputs are
asserted identical between the two runs (caching must never change
results). Gate: the hit-rate must clear 50% (CI fails otherwise).

  PYTHONPATH=src:. python benchmarks/serving.py --smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Rows
from repro.configs import get_config
from repro.models import build
from repro.serving import Server, ServerConfig, SpecConfig, generate_static

# One benchmarked arch per serving family; hybrid exercises the recurrent
# state rows + windowed page recycling, attention the pure paged-KV path.
ARCHS = (
    ("granite-3-8b", "attention"),
    ("recurrentgemma-2b", "hybrid"),
)

# Mixed long/short workload: short interactive prompts interleaved with
# long ones that the continuous path chunk-prefills. Generation lengths are
# deliberately spread — static batching pays for the spread by idling every
# short request's lane until the group's longest finishes.
_SHORT_PROMPTS = (6, 9, 12, 8)
_LONG_PROMPTS = (32,)
_GEN_CYCLE = (4, 24, 6, 18)
_PREFILL_CHUNK = 16


def _workload(n_requests: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        if i % 3 == 2:
            plen = _LONG_PROMPTS[i % len(_LONG_PROMPTS)]
        else:
            plen = _SHORT_PROMPTS[i % len(_SHORT_PROMPTS)]
        gen = _GEN_CYCLE[i % len(_GEN_CYCLE)]
        reqs.append((list(rng.integers(0, vocab, size=plen)), gen))
    return reqs


def _bench_arch(rows: Rows, arch: str, family: str, smoke: bool) -> dict:
    n_slots = 3 if smoke else 4
    n_requests = 6 if smoke else 16
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = _workload(n_requests, cfg.vocab_size)
    max_seq = max(len(p) + g for p, g in workload)

    # -- continuous batching over the StateStore (chunked prefill) ---------
    server = Server(model, params, ServerConfig(
        num_slots=n_slots, page_size=8, max_seq_len=max_seq,
        prefill_chunk=_PREFILL_CHUNK,
    ))
    server.warmup([len(p) for p, _ in workload])
    for prompt, gen in workload:
        server.submit(prompt, max_new_tokens=gen)
    server.run()
    s = server.stats
    cb_tok_s = s.decode_tok_s
    cb_util = s.utilization
    ttft_p50, ttft_p95 = server.ttft_percentiles() or (0.0, 0.0)
    # Histogram-derived latencies from the metrics registry (warmup resets
    # it, so the snapshot covers exactly the timed run). The exact TTFT
    # percentiles above and the bucketed ones below must agree to within
    # one log bucket — a tested invariant.
    hists = server.metrics.snapshot()["histograms"]
    itl = hists.get("serving_inter_token_seconds", {})
    itl_p50 = (itl.get("p50") or 0.0) * 1e3
    itl_p95 = (itl.get("p95") or 0.0) * 1e3
    ttft_hist = hists.get("serving_ttft_seconds", {})

    # -- static batching baseline (arrival-order groups, padded prompts) ---
    static_steps = 0
    static_lane_steps = 0
    static_s = 0.0
    useful_decode = 0
    for i in range(0, n_requests, n_slots):
        group = workload[i : i + n_slots]
        t = max(len(p) for p, _ in group)
        gen = max(g for _, g in group)
        toks = np.zeros((len(group), t), np.int32)
        for j, (p, _) in enumerate(group):
            toks[j, : len(p)] = p
        _, st = generate_static(
            model, params, {"tokens": jnp.asarray(toks)}, max_new_tokens=gen
        )
        per_step = st.steady_s / max(st.steady_steps, 1)
        static_steps += gen - 1
        static_lane_steps += (gen - 1) * len(group)
        static_s += per_step * (gen - 1)
        useful_decode += sum(g - 1 for _, g in group)
    static_tok_s = useful_decode / static_s if static_s else 0.0
    static_util = useful_decode / static_lane_steps if static_lane_steps else 0.0

    speedup = cb_tok_s / static_tok_s if static_tok_s else 0.0
    pre = f"serving/{family}"
    rows.add(f"{pre}/continuous/decode_tok_s", None, f"{cb_tok_s:.1f}",
             tok_s=cb_tok_s, decode_steps=s.decode_steps, arch=arch,
             arch_family=family)
    rows.add(f"{pre}/continuous/utilization", None, f"{cb_util:.3f}",
             utilization=cb_util, arch=arch, arch_family=family)
    rows.add(f"{pre}/continuous/ttft_ms", None,
             f"p50 {ttft_p50 * 1e3:.1f} / p95 {ttft_p95 * 1e3:.1f}",
             ttft_p50_ms=ttft_p50 * 1e3, ttft_p95_ms=ttft_p95 * 1e3,
             ttft_hist_p50_ms=(ttft_hist.get("p50") or 0.0) * 1e3,
             ttft_hist_p95_ms=(ttft_hist.get("p95") or 0.0) * 1e3,
             prefill_chunk=_PREFILL_CHUNK, arch=arch, arch_family=family)
    rows.add(f"{pre}/continuous/itl_ms", None,
             f"p50 {itl_p50:.1f} / p95 {itl_p95:.1f}",
             itl_p50_ms=itl_p50, itl_p95_ms=itl_p95,
             itl_samples=itl.get("count", 0), arch=arch, arch_family=family)
    rows.add(f"{pre}/static/decode_tok_s", None, f"{static_tok_s:.1f}",
             tok_s=static_tok_s, decode_steps=static_steps, arch=arch,
             arch_family=family)
    rows.add(f"{pre}/static/utilization", None, f"{static_util:.3f}",
             utilization=static_util, arch=arch, arch_family=family)
    rows.add(f"{pre}/continuous_vs_static_speedup", None, f"{speedup:.2f}",
             speedup=speedup, arch=arch, arch_family=family)
    return {
        "arch": arch, "family": family,
        "cb_tok_s": cb_tok_s, "static_tok_s": static_tok_s,
        "cb_util": cb_util, "static_util": static_util, "speedup": speedup,
        "ttft_p50_ms": ttft_p50 * 1e3, "ttft_p95_ms": ttft_p95 * 1e3,
        "itl_p50_ms": itl_p50, "itl_p95_ms": itl_p95,
    }


# Shared-system-prompt workload: every request opens with the same SYS_LEN
# tokens (system prompt / few-shot template) followed by a short unique
# user tail — the traffic shape prefix caching exists for. The prompt
# dominates the per-request work (long prefix, short answers), as it does
# in classification/extraction traffic.
_SYS_LEN = 96
_TAILS = (4, 6, 5, 7)
_PREFIX_GEN = 4
_PREFIX_PAGE = 8


def _prefix_workload(n_requests: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    sys_prompt = list(rng.integers(0, vocab, size=_SYS_LEN))
    return [
        sys_prompt + list(rng.integers(0, vocab, size=_TAILS[i % len(_TAILS)]))
        for i in range(n_requests)
    ]


def _bench_prefix(rows: Rows, smoke: bool) -> dict:
    arch = "granite-3-8b"
    n_requests = 8 if smoke else 16
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = _prefix_workload(n_requests, cfg.vocab_size)
    max_seq = max(len(p) for p in workload) + _PREFIX_GEN

    def run(prefix_cache: bool):
        # One slot: the queue drains serially, so TTFT differences come
        # from prefill work skipped, not from admission-order jitter.
        server = Server(model, params, ServerConfig(
            num_slots=1, page_size=_PREFIX_PAGE, max_seq_len=max_seq,
            prefill_chunk=_PREFILL_CHUNK, prefix_cache=prefix_cache,
        ))
        server.warmup([len(p) for p in workload])
        reqs = [server.submit(p, max_new_tokens=_PREFIX_GEN) for p in workload]
        server.run()
        outs = [server.results[r.rid].out_tokens for r in reqs]
        p50, p95 = server.ttft_percentiles() or (0.0, 0.0)
        return server, outs, p50, p95

    _, cold_outs, cold_p50, cold_p95 = run(prefix_cache=False)
    hot, hot_outs, hot_p50, hot_p95 = run(prefix_cache=True)
    if hot_outs != cold_outs:
        raise SystemExit(
            "prefix caching changed greedy outputs — parity violated"
        )
    hit_rate = hot.stats.prefix_hit_rate
    ttft_speedup = cold_p50 / hot_p50 if hot_p50 else 0.0

    # Priority burst: a low-priority long prompt starts prefilling, then
    # high-priority interactive requests preempt it mid-chunking.
    rng = np.random.default_rng(7)
    pre = Server(model, params, ServerConfig(
        num_slots=1, page_size=_PREFIX_PAGE, max_seq_len=64,
        prefill_chunk=8, prefix_cache=True, preemption=True,
    ))
    pre.submit(list(rng.integers(0, cfg.vocab_size, size=40)),
               max_new_tokens=4, priority=0)
    pre.step()
    for _ in range(2):
        pre.submit(list(rng.integers(0, cfg.vocab_size, size=6)),
                   max_new_tokens=4, priority=5)
    pre.run()
    preemptions = pre.stats.preemptions

    name = "serving/prefix"
    rows.add(f"{name}/hit_rate", None, f"{hit_rate:.2f}",
             prefix_hit_rate=hit_rate, arch=arch,
             cow_copies=hot.stats.cow_copies)
    rows.add(f"{name}/ttft_ms_cold", None,
             f"p50 {cold_p50 * 1e3:.1f} / p95 {cold_p95 * 1e3:.1f}",
             ttft_p50_ms=cold_p50 * 1e3, ttft_p95_ms=cold_p95 * 1e3, arch=arch)
    rows.add(f"{name}/ttft_ms_cached", None,
             f"p50 {hot_p50 * 1e3:.1f} / p95 {hot_p95 * 1e3:.1f}",
             ttft_p50_ms=hot_p50 * 1e3, ttft_p95_ms=hot_p95 * 1e3,
             ttft_p50_speedup=ttft_speedup, arch=arch)
    rows.add(f"{name}/preemptions", None, f"{preemptions}",
             preemptions=preemptions, arch=arch)
    return {
        "hit_rate": hit_rate, "ttft_speedup": ttft_speedup,
        "cold_p50_ms": cold_p50 * 1e3, "hot_p50_ms": hot_p50 * 1e3,
        "preemptions": preemptions,
    }


def _bench_kernel_decode(rows: Rows, smoke: bool) -> dict:
    """Continuous batching with the paged flash-decode kernel enabled.

    On CPU CI the kernel runs in interpret mode, so the absolute tok/s is an
    emulation number — the row anchors the *trajectory* (and the utilization
    field, which is scheduling-determined and machine-independent); on a TPU
    host the same section runs the compiled kernel via backend="pallas".
    """
    arch = "granite-3-8b"
    n_requests = 4 if smoke else 8
    backend = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = _workload(n_requests, cfg.vocab_size)
    max_seq = max(len(p) + g for p, g in workload)
    server = Server(model, params, ServerConfig(
        num_slots=2, page_size=8, max_seq_len=max_seq,
        prefill_chunk=_PREFILL_CHUNK,
    ), backend=backend)
    server.warmup([len(p) for p, _ in workload])
    for prompt, gen in workload:
        server.submit(prompt, max_new_tokens=gen)
    server.run()
    s = server.stats
    name = "serving/attention/kernel_decode"
    rows.add(f"{name}/decode_tok_s", None, f"{s.decode_tok_s:.1f}",
             tok_s=s.decode_tok_s, decode_steps=s.decode_steps, arch=arch,
             backend=backend)
    rows.add(f"{name}/utilization", None, f"{s.utilization:.3f}",
             utilization=s.utilization, arch=arch, backend=backend)
    return {
        "arch": arch, "family": "kernel_decode", "backend": backend,
        "cb_tok_s": s.decode_tok_s, "cb_util": s.utilization,
    }


# Speculative decoding workload: prompts built from a repeated motif. An
# untrained greedy target collapses into a token loop, which is exactly the
# traffic shape prompt-lookup (n-gram) self-drafting feeds on — acceptance
# is structural, not luck, so it can be gated in CI. num_slots=1 isolates
# the metric speculation actually improves: decode tok/s *per request*
# (batch-level tok/s is already saturated by continuous batching).
_SPEC_K = 4
_SPEC_GEN = 24


def _spec_workload(n_requests: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        motif = list(rng.integers(0, vocab, size=3 + i % 3))
        reqs.append((motif * 3, _SPEC_GEN))
    return reqs


def _bench_spec(rows: Rows, smoke: bool) -> dict:
    arch = "granite-3-8b"
    n_requests = 4 if smoke else 8
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = _spec_workload(n_requests, cfg.vocab_size)
    max_seq = max(len(p) + g for p, g in workload)

    def run(spec: bool):
        kw = {"spec": SpecConfig(k=_SPEC_K)} if spec else {}
        server = Server(model, params, ServerConfig(
            num_slots=1, page_size=8, max_seq_len=max_seq, prefill_chunk=8,
        ), **kw)
        # First pass compiles every shape (the k+1-wide verify step has no
        # warmup() coverage); the second, timed pass starts warm.
        for _ in range(2):
            server.reset()
            reqs = [server.submit(p, max_new_tokens=g) for p, g in workload]
            server.run()
        outs = [server.results[r.rid].out_tokens for r in reqs]
        return server.stats, outs

    spec_stats, spec_outs = run(spec=True)
    base_stats, base_outs = run(spec=False)
    if spec_outs != base_outs:
        raise SystemExit(
            "speculative decoding changed greedy outputs — parity violated"
        )
    spec_tok_s = spec_stats.decode_tok_s
    base_tok_s = base_stats.decode_tok_s
    acc = spec_stats.acceptance_rate
    aps = spec_stats.accepted_per_step
    speedup = spec_tok_s / base_tok_s if base_tok_s else 0.0

    name = "serving/spec"
    rows.add(f"{name}/acceptance_rate", None, f"{acc:.3f}",
             acceptance_rate=acc, spec_k=_SPEC_K, drafter="ngram", arch=arch)
    rows.add(f"{name}/accepted_per_step", None, f"{aps:.2f}",
             accepted_per_step=aps, spec_steps=spec_stats.spec_steps,
             arch=arch)
    rows.add(f"{name}/decode_tok_s_per_req", None, f"{spec_tok_s:.1f}",
             tok_s=spec_tok_s, arch=arch, drafter="ngram")
    rows.add(f"{name}/baseline_tok_s_per_req", None, f"{base_tok_s:.1f}",
             tok_s=base_tok_s, arch=arch)
    rows.add(f"{name}/tok_s_per_req_speedup", None, f"{speedup:.2f}",
             speedup=speedup, arch=arch)
    return {
        "arch": arch, "family": "spec", "acceptance_rate": acc,
        "accepted_per_step": aps, "spec_tok_s": spec_tok_s,
        "base_tok_s": base_tok_s, "speedup": speedup,
    }


# Batched multi-slot prefill workload: P equal-length prompts queued at
# once, so the server can pack P rows into one (P, chunk) prefill step
# instead of P serial (1, chunk) steps. max_new_tokens=1 keeps decode out
# of the measurement — the section isolates prefill throughput vs queue
# depth. Lengths are chunk multiples so serial and batched run the same
# token count through the same chunk grid.
_BATCH_PROMPT_LEN = 32
_BATCH_CHUNK = 8
_BATCH_DEPTHS = (1, 2, 4, 8)


def _bench_batched_prefill(rows: Rows, smoke: bool) -> dict:
    arch = "granite-3-8b"
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    out = {"arch": arch, "family": "batched_prefill", "depths": {}}
    for depth in _BATCH_DEPTHS:
        prompts = [
            list(rng.integers(0, cfg.vocab_size, size=_BATCH_PROMPT_LEN))
            for _ in range(depth)
        ]

        def run(batched: bool) -> float:
            server = Server(model, params, ServerConfig(
                num_slots=max(_BATCH_DEPTHS), page_size=8,
                max_seq_len=_BATCH_PROMPT_LEN + 2,
                prefill_chunk=_BATCH_CHUNK, prefill_batch=batched,
            ))
            # Pass 1 compiles the (P, chunk) shapes; pass 2 starts warm
            # (reset() clears the metrics registry, so the snapshot covers
            # exactly the timed pass).
            for _ in range(2):
                server.reset()
                for p in prompts:
                    server.submit(p, max_new_tokens=1)
                server.run()
            snap = server.metrics.snapshot()["counters"]
            sec = snap["serving_prefill_seconds_total"]
            toks = snap["serving_prefill_tokens_total"]
            return toks / sec if sec else 0.0

        serial_tok_s = run(False)
        batched_tok_s = run(True)
        speedup = batched_tok_s / serial_tok_s if serial_tok_s else 0.0
        name = f"serving/batched_prefill/p{depth}"
        rows.add(f"{name}/serial_tok_s", None, f"{serial_tok_s:.0f}",
                 tok_s=serial_tok_s, queue_depth=depth,
                 prefill_chunk=_BATCH_CHUNK, arch=arch)
        rows.add(f"{name}/batched_tok_s", None, f"{batched_tok_s:.0f}",
                 tok_s=batched_tok_s, queue_depth=depth,
                 prefill_chunk=_BATCH_CHUNK, arch=arch)
        rows.add(f"{name}/speedup", None, f"{speedup:.2f}",
                 speedup=speedup, queue_depth=depth, arch=arch)
        out["depths"][depth] = {
            "serial_tok_s": serial_tok_s, "batched_tok_s": batched_tok_s,
            "speedup": speedup,
        }
    return out


# Async dispatch-ahead decode: a decode-dominated workload (long
# generations, so slot-refill lag at finishes is amortized) at
# async_depth=0 (block every step) vs async_depth=2 (host scheduling
# overlaps device compute). Greedy outputs are depth-invariant (tested in
# tests/test_async_engine.py), so only throughput is compared here.
_ASYNC_DEPTH = 2
_ASYNC_GEN = 64
_ASYNC_PASSES = 3


def _bench_async_decode(rows: Rows, smoke: bool) -> dict:
    arch = "granite-3-8b"
    n_requests = 6 if smoke else 12
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, cfg.vocab_size, size=_SHORT_PROMPTS[i % 4]))
        for i in range(n_requests)
    ]
    max_seq = max(len(p) for p in prompts) + _ASYNC_GEN

    # Wall-clock end-to-end tok/s, not stats.decode_tok_s: the per-step
    # metric attributes overlapped host time to the engine once steps tile
    # the timeline (depth >= 1), so it is not comparable across depths —
    # wall clock is what the dispatch window actually improves. Best of
    # several warm passes, because a smoke-sized run is noise-dominated.
    n_tokens = n_requests * _ASYNC_GEN

    def run(depth: int) -> float:
        server = Server(model, params, ServerConfig(
            num_slots=3, page_size=8, max_seq_len=max_seq,
            prefill_chunk=_PREFILL_CHUNK, async_depth=depth,
        ))
        server.warmup([len(p) for p in prompts])
        best = 0.0
        for _ in range(_ASYNC_PASSES):
            server.reset()
            t0 = time.perf_counter()
            for prompt in prompts:
                server.submit(prompt, max_new_tokens=_ASYNC_GEN)
            server.run()
            best = max(best, n_tokens / (time.perf_counter() - t0))
        return best

    sync_tok_s = run(0)
    async_tok_s = run(_ASYNC_DEPTH)
    speedup = async_tok_s / sync_tok_s if sync_tok_s else 0.0
    name = "serving/async_decode"
    rows.add(f"{name}/sync_tok_s", None, f"{sync_tok_s:.0f}",
             tok_s=sync_tok_s, async_depth=0, arch=arch)
    rows.add(f"{name}/async_tok_s", None, f"{async_tok_s:.0f}",
             tok_s=async_tok_s, async_depth=_ASYNC_DEPTH, arch=arch)
    rows.add(f"{name}/speedup", None, f"{speedup:.2f}",
             speedup=speedup, async_depth=_ASYNC_DEPTH, arch=arch)
    return {
        "arch": arch, "family": "async_decode", "sync_tok_s": sync_tok_s,
        "async_tok_s": async_tok_s, "speedup": speedup,
    }


def bench_serving(rows: Rows, smoke: bool = True) -> list[dict]:
    results = [_bench_arch(rows, arch, family, smoke) for arch, family in ARCHS]
    results.append(_bench_kernel_decode(rows, smoke))
    prefix = _bench_prefix(rows, smoke)
    # CI gate: the shared-prefix workload must actually hit the cache (and
    # well past the break-even 50%) without perturbing results — parity is
    # asserted inside _bench_prefix.
    if prefix["hit_rate"] <= 0.5:
        raise SystemExit(
            f"prefix hit-rate {prefix['hit_rate']:.2f} <= 0.5 on the "
            "shared-system-prompt workload"
        )
    results.append(dict(prefix, arch="granite-3-8b", family="prefix"))
    spec = _bench_spec(rows, smoke)
    # CI gate: self-drafting must accept real tokens on the loop-shaped
    # workload (greedy parity is asserted inside _bench_spec).
    if spec["acceptance_rate"] <= 0.0:
        raise SystemExit(
            "speculative acceptance rate is 0 on the repeated-motif workload"
        )
    results.append(spec)
    batched = _bench_batched_prefill(rows, smoke)
    # CI gate: packing P rows into one (P, chunk) step must actually beat
    # P serial steps once the queue is deep enough to fill a bucket.
    for depth, d in batched["depths"].items():
        if depth >= 4 and d["speedup"] < 1.3:
            raise SystemExit(
                f"batched prefill speedup {d['speedup']:.2f} < 1.3 at "
                f"queue depth {depth}"
            )
    results.append(batched)
    adec = _bench_async_decode(rows, smoke)
    # CI gate: the dispatch window must not cost decode throughput (the
    # generous floor absorbs shared-runner timing noise).
    if adec["speedup"] < 0.8:
        raise SystemExit(
            f"async decode tok/s {adec['async_tok_s']:.1f} < 0.8x the "
            f"synchronous path's {adec['sync_tok_s']:.1f}"
        )
    results.append(adec)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable rows")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="fail on >15%% tok/s or utilization regression vs "
                    "a committed baseline JSON")
    args = ap.parse_args(argv)
    rows = Rows()
    results = bench_serving(rows, smoke=args.smoke)
    print("name,us_per_call,derived")
    rows.emit()
    for res in results:
        if res["family"] == "kernel_decode":
            print(f"# [kernel_decode] paged flash-decode over "
                  f"backend={res['backend']}: {res['cb_tok_s']:.1f} tok/s, "
                  f"utilization {res['cb_util']:.0%}")
            continue
        if res["family"] == "spec":
            verdict = ("accepting" if res["acceptance_rate"] > 0
                       else "NOT accepting")
            print(f"# [spec] n-gram self-drafting k={_SPEC_K}: {verdict} "
                  f"(acceptance {res['acceptance_rate']:.0%}, "
                  f"{res['accepted_per_step']:.2f} accepted/step, "
                  f"per-request {res['base_tok_s']:.1f} -> "
                  f"{res['spec_tok_s']:.1f} tok/s)")
            continue
        if res["family"] == "batched_prefill":
            parts = ", ".join(
                f"P={d}: {v['speedup']:.2f}x"
                for d, v in res["depths"].items()
            )
            print(f"# [batched_prefill] (P, chunk) packing vs serial "
                  f"prefill: {parts}")
            continue
        if res["family"] == "async_decode":
            verdict = ("confirmed" if res["speedup"] >= 1.0
                       else "NOT met (timing noise?)")
            print(f"# [async_decode] dispatch-ahead >= sync: {verdict} "
                  f"({res['sync_tok_s']:.1f} -> {res['async_tok_s']:.1f} "
                  f"tok/s at depth {_ASYNC_DEPTH})")
            continue
        if res["family"] == "prefix":
            verdict = "confirmed" if res["ttft_speedup"] >= 1.0 else "NOT met"
            print(f"# [prefix] caching cuts TTFT: {verdict} "
                  f"(p50 {res['cold_p50_ms']:.1f} -> {res['hot_p50_ms']:.1f} "
                  f"ms, hit-rate {res['hit_rate']:.0%}, "
                  f"{res['preemptions']} preemption(s) in the priority burst)")
            continue
        verdict = ("confirmed" if res["speedup"] >= 1.0
                   else "NOT met (timing noise?)")
        print(f"# [{res['family']}] continuous >= static: {verdict} "
              f"({res['cb_tok_s']:.1f} vs {res['static_tok_s']:.1f} tok/s, "
              f"utilization {res['cb_util']:.0%} vs {res['static_util']:.0%}, "
              f"ttft p50 {res['ttft_p50_ms']:.1f} ms / "
              f"p95 {res['ttft_p95_ms']:.1f} ms)")

    if args.json:
        rows.write_json(args.json, meta={
            "smoke": args.smoke, "platform": jax.default_backend(),
        })
        print(f"# wrote {args.json}")
    if args.compare:
        from benchmarks.common import compare_rows, load_rows_json

        failures = compare_rows(rows.to_json(), load_rows_json(args.compare),
                                label=args.compare)
        if failures:
            for f in failures:
                print(f"# REGRESSION {f}")
            raise SystemExit(
                f"{len(failures)} bench regression(s) vs {args.compare}"
            )
        print(f"# bench gate passed vs {args.compare}")


if __name__ == "__main__":
    main()
