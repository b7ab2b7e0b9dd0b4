"""Serving launcher on the ``repro.serving`` subsystem.

Default mode is continuous batching over the serving StateStore (paged KV
pools + per-slot recurrent state rows — every decoder-only family,
including recurrent/hybrid); ``--mode static`` runs the ring-buffer
static-batch path for comparison, and is the automatic fallback only for
enc-dec/VLM. Both report steady-state tok/s (compile excluded — the
continuous path warms up every jitted shape first, the static path times
its first decode separately).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b --smoke \\
      --chunked-prefill 16
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \\
      --prefix-cache --chunked-prefill 8   # shared-system-prompt workload
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.obs import JsonTracer, device_capture, write_metrics, write_trace
from repro.serving import (
    SamplingParams,
    Server,
    ServerConfig,
    SpecConfig,
    generate_static,
)


def mixed_prompt_lens(base: int, n: int) -> list[int]:
    """Deterministic mixed-length workload around ``base`` (>=2 tokens)."""
    cycle = [base, max(2, base // 2), base + base // 2, max(2, base - 2)]
    return [cycle[i % len(cycle)] for i in range(n)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=("continuous", "static"), default="continuous")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--fp8-kv", action="store_true",
                    help="store the KV pages in E4M3 (paper fp8 storage)")
    ap.add_argument("--chunked-prefill", type=int, default=0, metavar="N",
                    help="split prompts into N-token chunks interleaved "
                         "with decode steps (0 = whole-prompt prefill)")
    ap.add_argument("--async-depth", type=int, default=0, metavar="D",
                    help="dispatch up to D device steps ahead before the "
                         "host blocks at the stream boundary (0 = "
                         "synchronous; greedy outputs are identical at "
                         "every depth)")
    ap.add_argument("--prefill-batch", action="store_true",
                    help="pack all prefilling slots into one (P, chunk) "
                         "jitted step, P bucketed to {1,2,4,8}; requires "
                         "--chunked-prefill")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share published prompt pages across requests "
                         "(refcounted, copy-on-write); the workload then "
                         "opens every prompt with one shared system prefix "
                         "so the cache has something to hit")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority for the submitted requests (higher runs "
                         "first; enables TTFT-aware ordering)")
    ap.add_argument("--preempt", action="store_true",
                    help="allow higher-priority requests to evict "
                         "lower-priority ones that are still prefilling; "
                         "the workload then submits the second half of the "
                         "requests at priority+5 after the first half has "
                         "started prefilling, so preemption actually fires")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per decode "
                         "round and verify them in one target pass "
                         "(0 = off). Without --draft-model the drafter is "
                         "n-gram prompt-lookup (no extra model)")
    ap.add_argument("--draft-model", choices=ARCH_IDS, default=None,
                    help="decoder-only zoo config to run as the draft "
                         "model (own StateStore; vocab must match the "
                         "target). Implies --spec-k 4 if unset")
    ap.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                    help="max n-gram order for prompt-lookup self-drafting")
    ap.add_argument("--spec-gate", action="store_true",
                    help="CI gate: assert greedy speculative output matches "
                         "a non-speculative run token-for-token, and that "
                         "the acceptance rate is > 0 (for a model drafter "
                         "under greedy the acceptance check runs a "
                         "temperature-1.0 pass — two random-init models "
                         "share no greedy attractor, so greedy acceptance "
                         "is structurally ~0 there)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument(
        "--backend", choices=("", "xla", "pallas", "pallas_interpret"),
        default="", help="GEMM engine backend override (default: config)",
    )
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a request-lifecycle trace of the timed run: "
                         "Chrome trace-event JSON (open in ui.perfetto.dev) "
                         "or JSONL when PATH ends in .jsonl")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot (counters, gauges, "
                         "latency histograms, step profile): JSON, or "
                         "Prometheus text when PATH ends in .prom/.txt")
    ap.add_argument("--profile", default=None, metavar="LOGDIR",
                    help="capture a jax.profiler device trace of the timed "
                         "run into LOGDIR (TensorBoard/Perfetto-loadable)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.fp8_kv:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="e4m3")
    model = build(cfg)
    # Jitted init draws each weight on the device in its storage dtype
    # (eagerly, every fp32 draw would be materialised before its cast).
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    eng = model.engine.with_backend(args.backend) if args.backend else model.engine
    print(f"engine: policy={eng.policy.name} backend={eng.backend} "
          f"kv_dtype={cfg.kv_cache_dtype}")

    rng = np.random.default_rng(args.seed)
    sampling = SamplingParams(args.temperature, args.top_k, args.top_p)

    mode = args.mode
    if mode == "continuous" and not model.supports_cb():
        print(f"note: {cfg.name} ({cfg.family}) is not decoder-only; "
              "falling back to static-batch serving")
        mode = "static"

    spec = None
    draft_model = draft_params = None
    if args.draft_model is not None or args.spec_k > 0:
        if mode == "static":
            print("note: speculative decoding rides the continuous server; "
                  "--spec-k/--draft-model are inert under static mode")
        else:
            spec = SpecConfig(k=args.spec_k or 4, ngram_n=args.spec_ngram)
            if args.draft_model is not None:
                dcfg = get_config(args.draft_model, smoke=args.smoke)
                draft_model = build(dcfg)
                draft_params = jax.jit(draft_model.init)(
                    jax.random.PRNGKey(args.seed + 1)
                )

    if mode == "static":
        if args.trace_out or args.metrics_out or args.profile:
            print("note: --trace-out/--metrics-out/--profile instrument the "
                  "continuous server; they are inert under static mode")
        tokens = rng.integers(
            0, cfg.vocab_size, size=(args.requests, args.prompt_len)
        ).astype(np.int32)
        seqs, stats = generate_static(
            model, params, {"tokens": jnp.asarray(tokens)},
            max_new_tokens=args.max_new, engine=eng, sampling=sampling,
            seed=args.seed,
        )
        print(f"static: {args.requests} seqs x {args.max_new} tokens "
              f"(prefill {stats.prefill_s:.2f}s, first decode "
              f"{stats.first_decode_s:.2f}s incl. compile)")
        print(f"steady-state decode: {stats.decode_tok_s:.1f} tok/s "
              f"over {stats.steady_steps} steps")
        print(seqs)
        return

    lens = mixed_prompt_lens(args.prompt_len, args.requests)
    if args.prefix_cache:
        # Shared-system-prompt shape: one common prefix + unique tails.
        sys_prompt = list(rng.integers(0, cfg.vocab_size, size=args.prompt_len))
        prompts = [sys_prompt + list(rng.integers(0, cfg.vocab_size, size=ln))
                   for ln in lens]
    elif spec is not None and args.draft_model is None:
        # Repeated-motif prompts: the traffic shape prompt-lookup
        # self-drafting feeds on (a purely random prompt has no repeated
        # n-gram until the greedy chain falls into a loop).
        prompts = []
        for ln in lens:
            motif = list(rng.integers(0, cfg.vocab_size,
                                      size=max(2, ln // 3)))
            prompts.append((motif * 3)[: max(ln, 6)])
    else:
        prompts = [list(rng.integers(0, cfg.vocab_size, size=ln))
                   for ln in lens]
    max_seq = max(len(p) for p in prompts) + args.max_new
    tracer = JsonTracer() if args.trace_out else None
    server = Server(
        model, params,
        ServerConfig(
            num_slots=args.num_slots, page_size=args.page_size,
            max_seq_len=max_seq,
            prefill_bucket=min(32, max(8, args.prompt_len)),
            prefill_chunk=args.chunked_prefill or None,
            prefix_cache=args.prefix_cache, preemption=args.preempt,
            async_depth=args.async_depth, prefill_batch=args.prefill_batch,
        ),
        engine=eng, seed=args.seed, spec=spec,
        draft_model=draft_model, draft_params=draft_params,
        tracer=tracer,
    )
    prof = server.profile
    print(f"state store: {server.cache.allocator.num_pages} pages x "
          f"{args.page_size} tokens ({server.cache.kv_bytes() / 1e6:.2f} MB kv, "
          f"{server.cache.state_bytes() / 1e6:.2f} MB recurrent rows; "
          f"kv_window={prof.kv_window})")
    if args.prefix_cache and not server.prefix_cache:
        print(f"note: prefix cache disabled — {cfg.name} keeps recurrent "
              "state rows (cached pages cannot replace their updates)")
    if spec is not None and args.async_depth:
        print("note: --async-depth is inert under speculative decoding — "
              "spec rounds are host-synchronous, the dispatch window "
              "collapses to 0")
    if args.preempt and not args.chunked_prefill:
        print("note: --preempt is inert without --chunked-prefill — "
              "whole-prompt mode fully prefills a request in the step it "
              "is admitted, so there is never a prefilling victim")
    server.warmup([len(p) for p in prompts])

    def submit(p, priority):
        server.submit(p, max_new_tokens=args.max_new, sampling=sampling,
                      priority=priority)

    with device_capture(args.profile):
        if args.preempt:
            # Priority burst: the first half starts prefilling at the base
            # priority, then the second half arrives above it — a uniform
            # priority could never trigger a preemption.
            half = max(1, len(prompts) // 2)
            for p in prompts[:half]:
                submit(p, args.priority)
            server.step()
            for p in prompts[half:]:
                submit(p, args.priority + 5)
        else:
            for p in prompts:
                submit(p, args.priority)
        results = server.run()
    s = server.stats
    print(f"continuous: {len(results)} requests, {s.decode_tokens} decode "
          f"tokens in {s.decode_steps} steps over {args.num_slots} slots"
          + (f", prefill chunk {args.chunked_prefill}"
             if args.chunked_prefill else ""))
    print(f"steady-state decode: {s.decode_tok_s:.1f} tok/s, "
          f"engine utilization {s.utilization:.0%}")
    ttft = server.ttft_percentiles()
    if ttft is not None:
        print(f"ttft: p50 {ttft[0] * 1e3:.1f} ms, p95 {ttft[1] * 1e3:.1f} ms")
    if server.prefix_cache:
        print(f"prefix cache: hit-rate {s.prefix_hit_rate:.0%} "
              f"({s.prefix_hit_tokens}/{s.prefix_prompt_tokens} prompt "
              f"tokens), {s.cow_copies} cow copies")
    if args.preempt:
        print(f"preemptions: {s.preemptions}")
    if spec is not None:
        drafter = (f"model:{args.draft_model}" if args.draft_model
                   else f"ngram(n={spec.ngram_n})")
        print(f"speculative: k={spec.k} drafter={drafter} "
              f"acceptance {s.acceptance_rate:.0%} "
              f"({s.spec_accepted}/{s.spec_drafted} drafts), "
              f"{s.accepted_per_step:.2f} accepted/step "
              f"over {s.spec_steps} rounds")
    for rid in sorted(results):
        r = results[rid]
        print(f"  req {rid}: prompt {r.prompt_len:>3} -> "
              f"{r.num_generated} tokens ({r.finish_reason}): "
              f"{r.out_tokens}")

    # Flush observability artifacts BEFORE the spec gate: its reference run
    # and reset() would wipe the timed run's metrics and trace.
    run_meta = {"arch": args.arch, "mode": mode, "requests": args.requests,
                "seed": args.seed}
    if args.trace_out:
        fmt = write_trace(tracer, args.trace_out, meta=run_meta)
        print(f"trace: {len(tracer.events)} events -> {args.trace_out} "
              f"({fmt}; chrome format opens in ui.perfetto.dev)")
    if args.metrics_out:
        fmt = write_metrics(server.metrics, args.metrics_out,
                            profiler=server.profiler, meta=run_meta)
        print(f"metrics: snapshot -> {args.metrics_out} ({fmt})")
    if args.trace_out or args.metrics_out or args.profile:
        print(server.profiler.format_summary())

    if spec is not None and args.spec_gate:
        failures = []
        if args.temperature <= 0:
            ref = Server(model, params, server.config, engine=eng,
                         seed=args.seed)
            for p in prompts:
                ref.submit(p, max_new_tokens=args.max_new, sampling=sampling,
                           priority=args.priority)
            ref_results = ref.run()
            spec_outs = [results[rid].out_tokens for rid in sorted(results)]
            ref_outs = [ref_results[rid].out_tokens
                        for rid in sorted(ref_results)]
            if spec_outs != ref_outs:
                failures.append("greedy speculative output diverges from "
                                "the non-speculative run")
            else:
                print("spec gate: greedy parity vs non-speculative decode "
                      "confirmed")
        acc = s.acceptance_rate
        if args.draft_model is not None and args.temperature <= 0:
            # Two random-init models share no greedy attractor, so greedy
            # model-drafter acceptance is structurally ~0; the meaningful
            # acceptance check for this pairing is a sampled pass (the
            # near-uniform logits of target and drafter overlap heavily).
            server.reset()
            sampled = SamplingParams(1.0, 0, 1.0)
            for p in prompts:
                server.submit(p, max_new_tokens=args.max_new,
                              sampling=sampled)
            server.run()
            acc = server.stats.acceptance_rate
            print(f"spec gate: temperature-1.0 acceptance {acc:.0%} "
                  f"({server.stats.spec_accepted}/"
                  f"{server.stats.spec_drafted} drafts)")
        if acc <= 0.0:
            failures.append("speculative acceptance rate is 0")
        if failures:
            raise SystemExit("spec gate FAILED: " + "; ".join(failures))
        print("spec gate passed")


if __name__ == "__main__":
    main()
