#!/usr/bin/env python3
"""Bring-up check: the serving and hybrid-FP8 training paths on a TPU.

Drives the repo's own entry points (``build``, a jitted ``model.init``,
``Server``/``ServerConfig``, and ``make_sharded_train`` from
``repro.launch.train``) at granite-3-8b's published widths, with random
weights drawn from a seed. Run it from the root of a checkout:

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the 2x2 training mesh only

On one chip the phases run in order, and any failure exits non-zero:

1. device check: the first device must be a TPU;
2. serve 8 seeded requests on the Pallas kernels (``tpu_hfp8``, E4M3 KV
   pages), 20 of the 40 layers: one stage of a two-stage pipeline;
3. the compiled prefill and decode steps must hold the kernels;
4. prefill and decode logits of the Pallas path must agree with the XLA
   path on the same requests;
5. three hybrid-FP8 training steps (E4M3 forward, E5M2 cotangents) at 2 of
   the 40 layers, which leaves weights, AdamW state and activations room
   on one chip.

``--four-chips`` runs only the training step, on a 2x2 (data, model) mesh
over all four devices, against the same step on the first device.

The last line of standard output is one JSON object naming the device,
printed only when every phase passed. The compile cache is JAX's
persistent cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.precision import get_policy  # noqa: E402
from repro.data import for_model  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.train import make_sharded_train  # noqa: E402
from repro.models import build  # noqa: E402
from repro.models.transformer import MeshCtx  # noqa: E402
from repro.optim import AdamW, cosine_schedule  # noqa: E402
from repro.serving import Server, ServerConfig  # noqa: E402
from repro.training import make_paged_serve_steps  # noqa: E402

SEED = 0
ARCH = "granite-3-8b"
POLICY = "tpu_hfp8"  # E4M3 storage forward, E5M2 backward, bf16 MXU datapath
SERVE_LAYERS = 20  # of 40: one stage of a two-stage pipeline
TRAIN_LAYERS = 2  # sized from the train step's memory_analysis()
SERVER = ServerConfig(num_slots=8, page_size=16, max_seq_len=2048,
                      prefill_chunk=256)
N_REQUESTS = 8
PROMPT_LENS = (128, 1024)  # inclusive range of the seeded prompt lengths
NEW_TOKENS = 32
AGREE_REQUESTS = 2
AGREE_DECODE_STEPS = 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 3
GEMM_KERNEL, DECODE_KERNEL = "redmule_gemm", "paged_flash_decode"

# Each kernel against a plain fp32 reference on the same operands, as
# ||a - b|| / ||b||. The GEMM's E4M3 operands multiply exactly and sum in
# fp32, so only the summation order over K = 4096 differs (~1e-6).
GEMM_RTOL = 1e-4
# The decode kernel's fp32 dots take one bf16 pass on the MXU, so the
# query and the softmax weights round to bf16 (2**-9 relative, 2e-3 on a
# v5e); the reference runs at full fp32 precision. One skipped page of 16
# among ~1000 tokens of random values moves the output by ~10%.
DECODE_RTOL = 1e-2
# Logits, pallas vs xla, as ||a - b|| / ||b|| per row. Both paths quantize
# every GEMM operand to E4M3 the same way and accumulate in fp32; they
# differ in the fp32 summation order, in the decode attention (fp32 in the
# kernel, bf16 operands on the XLA path) and so in which way a bf16 output
# rounds. The next GEMM requantizes to E4M3, and a value on the other side
# of an E4M3 rounding boundary moves by a whole E4M3 step (12.5%): each
# quantizing GEMM turns a relative difference e into about sqrt(e / 8).
# So the paths differ by about the policy's own E4M3 noise, at any depth:
# 0.12-0.13 at one, four and twenty layers in a CPU rehearsal at d_model
# 256. Logit rows that share nothing differ by sqrt(2) = 1.41.
LOGIT_RTOL = 0.25
# Loss, 2x2 mesh vs one chip, same seed and batch. Every kernel call does
# whole K accumulations on either layout, so only the order of the XLA
# reductions (norms, softmax, the loss sum, the gradient norm) differs:
# a loss near ln(49155) = 10.8 agrees to well within 1e-2.
LOSS_ATOL = 1e-2


class CompileLog:
    """Backend compile seconds and persistent-cache hits and writes, from
    JAX's own monitoring events, and the programs whose cache lookup missed,
    from its compiler log's debug records (which are then dropped, so the
    log prints what it printed before)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.missed = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        compiler_log = logging.getLogger("jax._src.compiler")
        self._level = compiler_log.getEffectiveLevel()
        compiler_log.setLevel(logging.DEBUG)
        compiler_log.addFilter(self._record)

    def _record(self, record) -> bool:
        if record.msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.missed[record.args[0]] += 1
        return record.levelno >= self._level

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.seconds:.1f} s backend compile, persistent cache "
                f"{self.hits} hits / {self.misses} misses written; lookups "
                f"that missed: {dict(self.missed)}")


def fail(msg: str):
    raise SystemExit(f"FAIL: {msg}")


def granite(n_layers: int):
    cfg = get_config(ARCH)
    return dataclasses.replace(cfg, n_layers=n_layers, policy=POLICY,
                               backend="pallas", kv_cache_dtype="e4m3")


def describe(cfg, why: str) -> str:
    return (f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} q / "
            f"{cfg.n_kv_heads} kv heads x {cfg.head_dim}, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab_size}, {cfg.n_layers} of "
            f"{get_config(ARCH).n_layers} layers ({why}); policy "
            f"{cfg.policy}, backend {cfg.backend}, kv {cfg.kv_cache_dtype}")


def memory(dev, stat: str = "peak_bytes_in_use") -> int:
    return dev.memory_stats()[stat]


def kernel_counts(compiled) -> dict[str, int]:
    """Pallas kernels in a compiled program: the ``tpu_custom_call``
    instructions named after each kernel."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {k: sum(k in ln for ln in calls) for k in (GEMM_KERNEL, DECODE_KERNEL)}


def seeded_prompts(vocab: int, n: int) -> list[list[int]]:
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    return [rng.integers(0, vocab, size=int(ln)).tolist() for ln in lens]


# -- serving -------------------------------------------------------------------


def serve(model, params, prompts, log: CompileLog):
    server = Server(model, params, SERVER, seed=SEED)
    t0, c0 = time.perf_counter(), log.seconds
    server.warmup([len(p) for p in prompts])
    print(f"serve warm-up: {time.perf_counter() - t0:.1f} s wall, "
          f"{log.seconds - c0:.1f} s of it backend compile")
    for p in prompts:
        server.submit(p, max_new_tokens=NEW_TOKENS)
    t0 = time.perf_counter()
    results = server.run()
    done = [r for r in results.values() if r.num_generated == NEW_TOKENS]
    print(f"serve: {len(done)} of {len(prompts)} requests finished with "
          f"{NEW_TOKENS} tokens each (prompts {min(map(len, prompts))}-"
          f"{max(map(len, prompts))} tokens, {time.perf_counter() - t0:.1f} s "
          "wall, not a benchmark)")
    if len(done) != len(prompts):
        fail(f"{len(prompts) - len(done)} requests did not finish")
    return server.engine.resolved_num_pages()


def step_args(model, prompt, num_pages):
    """Arguments of the first prefill chunk and of one decode step, shaped
    as the Server passes them."""
    c = SERVER.prefill_chunk
    pools = model.init_state_store(SERVER.num_slots, num_pages, SERVER.page_size)
    pps = SERVER.pages_per_slot
    table = np.zeros((SERVER.num_slots, pps), np.int32)
    for slot in range(SERVER.num_slots):
        table[slot] = 1 + slot * pps + np.arange(pps)
    tokens = np.zeros((1, c), np.int32)
    tokens[0, :min(c, len(prompt))] = prompt[:c]
    prefill = (jnp.asarray(tokens), pools, jnp.asarray(table[0]),
               jnp.int32(0), jnp.int32(0), jnp.int32(min(c, len(prompt))))
    decode = (jnp.zeros((SERVER.num_slots, 1), jnp.int32), pools,
              jnp.asarray(table), jnp.zeros((SERVER.num_slots,), jnp.int32),
              jnp.ones((SERVER.num_slots,), bool))
    return table, prefill, decode


def run_requests(steps, params, pools, table, prompts, feed=None):
    """Chunked prefill of ``prompts`` into slots 0.., then decode steps.
    Returns (prefill logits per prompt, decode logits per step, fed tokens).
    ``feed`` gives the decode input tokens; by default each step feeds the
    argmax of the previous logits."""
    prefill_chunk, decode = steps
    c = SERVER.prefill_chunk
    pre = []
    for slot, prompt in enumerate(prompts):
        for start in range(0, len(prompt), c):
            chunk = prompt[start:start + c]
            tokens = np.zeros((1, c), np.int32)
            tokens[0, :len(chunk)] = chunk
            logits, pools = prefill_chunk(
                params, jnp.asarray(tokens), pools, jnp.asarray(table[slot]),
                jnp.int32(slot), jnp.int32(start), jnp.int32(len(chunk)))
        pre.append(np.asarray(logits[0], np.float32))
    n = len(prompts)
    active = np.zeros(SERVER.num_slots, bool)
    active[:n] = True
    seq_lens = np.zeros(SERVER.num_slots, np.int32)
    seq_lens[:n] = [len(p) for p in prompts]
    last = np.zeros((SERVER.num_slots, 1), np.int32)
    last[:n, 0] = [int(np.argmax(lg)) for lg in pre]
    dec, fed = [], []
    for i in range(AGREE_DECODE_STEPS):
        if feed is not None:
            last = feed[i]
        fed.append(last.copy())
        logits, pools = decode(params, jnp.asarray(last), pools,
                               jnp.asarray(table), jnp.asarray(seq_lens),
                               jnp.asarray(active))
        logits = np.asarray(logits[:n], np.float32)
        dec.append(logits)
        last = last.copy()
        last[:n, 0] = logits.argmax(-1)
        seq_lens[:n] += 1
    return pre, dec, fed


def rel_err(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def kernel_checks(cfg, table, num_pages):
    """Both kernels at the served shapes against plain fp32 references."""
    policy = get_policy(cfg.policy)
    backend = cfg.backend
    kx, kw, kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 5)
    x = jax.random.normal(kx, (SERVER.prefill_chunk, cfg.d_model), jnp.bfloat16)
    w = jax.random.normal(kw, (cfg.d_model, cfg.d_ff), jnp.bfloat16)
    got = ops.gemm_op(x, w, policy=policy, backend=backend,
                      out_dtype=jnp.float32)
    want = jnp.matmul(policy.cast_in_fwd(x), policy.cast_in_fwd(w),
                      preferred_element_type=jnp.float32)
    gemm_err = rel_err(np.asarray(got), np.asarray(want))

    n_tok = num_pages * SERVER.page_size
    q = jax.random.normal(kq, (SERVER.num_slots, cfg.n_heads, cfg.head_dim),
                          jnp.float32)
    pool_shape = (n_tok, cfg.n_kv_heads, cfg.head_dim)
    k_pool = jax.random.normal(kk, pool_shape).astype(jnp.float8_e4m3fn)
    v_pool = jax.random.normal(kv, pool_shape).astype(jnp.float8_e4m3fn)
    rng = np.random.default_rng(SEED)
    seq_lens = rng.integers(1, SERVER.max_seq_len, size=SERVER.num_slots)
    seq_lens = seq_lens.astype(np.int32)
    owned = np.arange(table.shape[1]) <= (seq_lens // SERVER.page_size)[:, None]
    table = np.where(owned, table, 0).astype(np.int32)
    got = ops.paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(seq_lens),
        jnp.ones((SERVER.num_slots,), jnp.int32),
        page_size=SERVER.page_size, backend=backend)
    # Dense reference: every slot's pages gathered in position order.
    read = (table[:, :, None] * SERVER.page_size
            + np.arange(SERVER.page_size)[None, None, :]).reshape(len(table), -1)
    k = k_pool[read].astype(jnp.float32)  # (S, T, Hkv, hd)
    v = v_pool[read].astype(jnp.float32)
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(SERVER.num_slots, cfg.n_kv_heads, g, cfg.head_dim)
    exact = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("shgd,sthd->shgt", qg, k, precision=exact)
    scores = scores / np.sqrt(cfg.head_dim)
    live = np.arange(read.shape[1])[None, :] <= seq_lens[:, None]
    scores = jnp.where(live[:, None, None, :], scores, -jnp.inf)
    want = jnp.einsum("shgt,sthd->shgd", jax.nn.softmax(scores, axis=-1), v,
                      precision=exact)
    dec_err = rel_err(np.asarray(got), np.asarray(want).reshape(got.shape))
    print(f"kernels vs fp32 references at the served shapes: {GEMM_KERNEL} "
          f"({SERVER.prefill_chunk}x{cfg.d_model} @ {cfg.d_model}x{cfg.d_ff}) "
          f"{gemm_err:.3e} (tolerance {GEMM_RTOL:.0e}), {DECODE_KERNEL} "
          f"({SERVER.num_slots} slots, up to {seq_lens.max() + 1} tokens, "
          f"E4M3 pages) {dec_err:.3e} (tolerance {DECODE_RTOL:.0e})")
    if not (gemm_err <= GEMM_RTOL and dec_err <= DECODE_RTOL):
        fail("a kernel disagrees with its reference")


def agreement(model, params, prompts, num_pages, log: CompileLog):
    """Kernel presence in the compiled steps, then pallas-vs-xla logits."""
    steps = {}
    for backend in (model.engine.backend, "xla"):
        _, chunk, _, decode = make_paged_serve_steps(
            model, page_size=SERVER.page_size,
            engine=model.engine.with_backend(backend))
        steps[backend] = (jax.jit(chunk), jax.jit(decode))
    kernel = steps[model.engine.backend]
    table, pre_args, dec_args = step_args(model, prompts[0], num_pages)
    pre_k = kernel_counts(kernel[0].lower(params, *pre_args).compile())
    dec_k = kernel_counts(kernel[1].lower(params, *dec_args).compile())
    print(f"kernels in compiled prefill chunk: {pre_k}; in compiled decode: "
          f"{dec_k}")
    if not (pre_k[GEMM_KERNEL] and dec_k[GEMM_KERNEL] and dec_k[DECODE_KERNEL]):
        fail("a compiled step lacks the GEMM or the paged-decode kernel")
    del pre_args, dec_args
    kernel_checks(model.cfg, table, num_pages)

    reqs = prompts[:AGREE_REQUESTS]
    fresh = lambda: model.init_state_store(  # noqa: E731
        SERVER.num_slots, num_pages, SERVER.page_size)
    t0, c0 = time.perf_counter(), log.seconds
    pre_p, dec_p, fed = run_requests(kernel, params, fresh(), table, reqs)
    pre_x, dec_x, _ = run_requests(steps["xla"], params, fresh(), table, reqs,
                                   feed=fed)
    pre_err = max(rel_err(a, b) for a, b in zip(pre_p, pre_x))
    dec_err = max(rel_err(a[i], b[i]) for a, b in zip(dec_p, dec_x)
                  for i in range(len(reqs)))
    print(f"agreement, pallas vs xla ({len(reqs)} requests, "
          f"{AGREE_DECODE_STEPS} decode steps; {time.perf_counter() - t0:.1f} s, "
          f"{log.seconds - c0:.1f} s compile): max relative L2 error of "
          f"logits {pre_err:.3e} after prefill, {dec_err:.3e} in decode "
          f"(tolerance {LOGIT_RTOL})")
    if not (pre_err <= LOGIT_RTOL and dec_err <= LOGIT_RTOL):
        fail("pallas and xla logits disagree beyond the tolerance")


def serve_phase(dev, log: CompileLog):
    cfg = granite(SERVE_LAYERS)
    print(describe(cfg, "one stage of a two-stage pipeline"))
    model = build(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(SEED)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"init: {n_bytes / 1e9:.2f} GB of weights in "
          f"{time.perf_counter() - t0:.1f} s (jitted, on the device); bytes "
          f"in use {memory(dev, 'bytes_in_use')}, peak {memory(dev)}")
    prompts = seeded_prompts(cfg.vocab_size, N_REQUESTS)
    num_pages = serve(model, params, prompts, log)
    print(f"serve: peak bytes in use {memory(dev)}")
    agreement(model, params, prompts, num_pages, log)


# -- training ------------------------------------------------------------------


def train(n_layers: int, mesh, log: CompileLog, tag: str):
    """TRAIN_STEPS steps of the launcher's sharded train step on ``mesh``.
    Returns (losses, bytes in use per device after init)."""
    cfg = granite(n_layers)
    ctx = MeshCtx(mesh=mesh, dp_axes=("data",), ep_axis="model")
    model = build(cfg, ctx)
    opt = AdamW(lr=cosine_schedule(3e-4, 20, TRAIN_STEPS))
    data = for_model(cfg, TRAIN_SEQ, TRAIN_BATCH, seed=SEED)
    batch_shape = jax.eval_shape(lambda: data.batch(0))
    init_fn, step_fn, _ = make_sharded_train(model, opt, mesh, batch_shape)
    key = jax.random.PRNGKey(SEED)
    t0, c0 = time.perf_counter(), log.seconds
    compiled = step_fn.lower(jax.eval_shape(init_fn, key), batch_shape).compile()
    mem = compiled.memory_analysis()
    n_kernel = kernel_counts(compiled)[GEMM_KERNEL]
    print(f"{tag}: train step compiled in {time.perf_counter() - t0:.1f} s "
          f"({log.seconds - c0:.1f} s backend); memory_analysis per device: "
          f"arguments {mem.argument_size_in_bytes}, outputs "
          f"{mem.output_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}; {n_kernel} {GEMM_KERNEL} call "
          "sites")
    if not n_kernel:
        fail(f"{tag}: the compiled train step lacks the {GEMM_KERNEL} kernel")
    state = jax.block_until_ready(init_fn(key))
    in_use = [memory(d, "bytes_in_use") for d in mesh.devices.flat]
    losses = []
    for i in range(TRAIN_STEPS):
        state, metrics = compiled(state, data.batch(i))
        loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
        print(f"{tag}: step {i + 1} loss {loss:.6f} grad norm {gnorm:.6f}")
        if not (np.isfinite(loss) and np.isfinite(gnorm)):
            fail(f"{tag}: non-finite loss or gradient norm at step {i + 1}")
        losses.append(loss)
    if int(state.skipped):
        fail(f"{tag}: the anomaly guard skipped {int(state.skipped)} steps")
    return losses, in_use


def train_phase(dev, log: CompileLog):
    cfg = granite(TRAIN_LAYERS)
    print(describe(cfg, "weights, AdamW state and activations of one chip"))
    print(f"train: batch {TRAIN_BATCH} x {TRAIN_SEQ} tokens, {TRAIN_STEPS} steps")
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    _, in_use = train(TRAIN_LAYERS, mesh, log, "train")
    # The device keeps one peak for the whole process: serving's, if higher.
    print(f"train: bytes in use after init {in_use[0]}; peak bytes in use "
          f"since the process started {memory(dev)}")


def four_chip_phase(log: CompileLog):
    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = granite(TRAIN_LAYERS)
    print(describe(cfg, "the one-chip training cut, now on a 2x2 mesh"))
    one = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    ref, ref_bytes = train(TRAIN_LAYERS, one, log, "one chip")
    gc.collect()
    four = make_mesh((2, 2), ("data", "model"))
    got, per_dev = train(TRAIN_LAYERS, four, log, "2x2 mesh")
    print(f"bytes in use after init: one chip {ref_bytes[0]}; 2x2 mesh "
          f"{per_dev}")
    if max(per_dev) > 0.6 * ref_bytes[0] or min(per_dev) < 0.2 * ref_bytes[0]:
        fail("the training state is not spread over the four devices")
    diff = max(abs(a - b) for a, b in zip(ref, got))
    print(f"loss, 2x2 mesh vs one chip: max |difference| {diff:.3e} over "
          f"{TRAIN_STEPS} steps (tolerance {LOSS_ATOL:.0e})")
    if diff > LOSS_ATOL:
        fail("the sharded training step disagrees with the one-chip step")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the training step on a 2x2 (data, model) "
                         "mesh and compare it with one chip")
    args = ap.parse_args(argv)
    stray = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if stray:
        fail(f"REPRO_* overrides are set ({', '.join(stray)}); the check "
             "compiles only what the repository holds")

    dev = jax.devices()[0]
    print(f"jax {jax.__version__}: {len(jax.devices())} x {dev.platform} "
          f"({dev.device_kind})")
    if dev.platform != "tpu":
        fail(f"no TPU: the first device is {dev.platform}")
    cache_dir = enable_compile_cache()
    log = CompileLog()
    print(f"compile cache: {cache_dir}")

    if args.four_chips:
        four_chip_phase(log)
    else:
        serve_phase(dev, log)
        gc.collect()
        train_phase(dev, log)
    print(f"compile: {log}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
