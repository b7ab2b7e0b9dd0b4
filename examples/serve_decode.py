"""Continuous-batching serving demo: mixed-length requests stream through
the serving StateStore (``repro.serving``) — paged KV pools for attention
layers, per-slot state rows for recurrent layers — each with its own
sampling settings, while the decode batch stays one fixed jitted shape.

  PYTHONPATH=src python examples/serve_decode.py --arch granite-3-8b
  PYTHONPATH=src python examples/serve_decode.py --arch recurrentgemma-2b \\
      --chunked-prefill 8                        # hybrid, chunked prompts
  PYTHONPATH=src python examples/serve_decode.py --fp8-kv   # E4M3 KV pages
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import build, make_batch
from repro.serving import SamplingParams, Server, ServerConfig, generate_static


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-8b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--num-slots", type=int, default=2)
    ap.add_argument("--fp8-kv", action="store_true",
                    help="store the KV pages in E4M3 (paper fp8 storage)")
    ap.add_argument("--chunked-prefill", type=int,
                    default=ServerConfig.prefill_chunk, metavar="N",
                    help="N-token prefill chunks interleaved with decode")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if args.fp8_kv:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="e4m3")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)

    if not model.supports_cb():
        # Only enc-dec / VLM still serve on the static-batch path.
        print(f"{cfg.name}: not decoder-only; static-batch decode")
        batch = make_batch(cfg, args.requests, args.prompt_len,
                           jax.random.PRNGKey(1))
        seqs, stats = generate_static(model, params, batch,
                                      max_new_tokens=args.gen)
        print(seqs)
        print(f"{stats.decode_tok_s:.1f} tok/s steady-state decode "
              "(compile excluded)")
        return

    server = Server(model, params, ServerConfig(
        num_slots=args.num_slots, page_size=8,
        max_seq_len=args.prompt_len + args.gen,
        prefill_chunk=args.chunked_prefill,
    ))
    print(f"arch={cfg.name} kv_dtype={cfg.kv_cache_dtype} "
          f"kv pool={server.cache.kv_bytes() / 1e6:.2f} MB "
          f"({server.cache.allocator.num_pages} pages x 8 tokens), "
          f"state rows={server.cache.state_bytes() / 1e6:.2f} MB")

    # Mixed lengths, mixed sampling: even requests greedy, odd ones sampled.
    lens = [max(2, args.prompt_len - 3 * (i % 3)) for i in range(args.requests)]
    server.warmup(lens)  # compile the chunk and decode shapes before timing
    for i, plen in enumerate(lens):
        sampling = (SamplingParams() if i % 2 == 0
                    else SamplingParams(temperature=0.8, top_k=40, top_p=0.95))
        server.submit(rng.integers(0, cfg.vocab_size, size=plen),
                      max_new_tokens=args.gen, sampling=sampling)
    # Tokens stream out as soon as each decode step samples them, in arrival
    # order interleaved across requests — that's continuous batching.
    for ev in server.stream():
        tag = f" <- {ev.finish_reason}" if ev.finished else ""
        print(f"req {ev.rid} token[{ev.index}] = {ev.token}{tag}")

    s = server.stats
    print(f"\n{len(server.results)} requests done: "
          f"{s.decode_tok_s:.1f} tok/s steady-state decode "
          f"(compile excluded), utilization {s.utilization:.0%}")


if __name__ == "__main__":
    main()
