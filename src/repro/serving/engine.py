"""The serving engine core: device stepping with an in-flight dispatch
window, split out of the host-side :class:`~repro.serving.server.Server`.

The split mirrors the paper's double-buffering discipline at the serving
layer: RedMulE keeps its CE array busy by overlapping operand streaming
with computation, and the engine keeps the device busy by overlapping
host-side scheduling with device steps. ``EngineCore`` owns everything a
device step touches — the :class:`StateStore`, the jitted fixed-shape
steps, the RNG key stream, and a per-slot device **last-token array** —
while the ``Server`` facade owns everything a *request* touches
(scheduler, tokenised prompts, streaming, request bookkeeping).

Dispatch-ahead works because jitted JAX calls are asynchronous: a
``dispatch_*`` method enqueues device work and returns immediately with
futures; the only blocking point is :meth:`harvest_one`, where the oldest
in-flight step's sampled tokens are materialised (``np.asarray`` — the
stream boundary). The functionally-threaded ``pools`` pytree serialises
every dispatched step in dispatch order on the device, which is the whole
safety argument for committing host state optimistically at dispatch:

- a later step's writes always land *after* an earlier step's reads, so
  freeing a finished request's pages at harvest can never corrupt a
  still-in-flight reader — the new owner's writes are dispatched later;
- a stale in-flight write (a decode step dispatched past an EOS the host
  had not yet harvested) only ever targets the writer's own frontier
  page, never a published prefix page, and a reallocated page is fully
  rewritten by its new owner before any of its positions become valid.

Decode steps read their input tokens from the engine's device-resident
last-token array — updated by jitted scatters from each sample — so a
decode can be dispatched before the sample feeding it has been harvested.
The values are exactly the token ids the host would have passed, so
greedy outputs are bitwise identical to the synchronous path at every
dispatch depth.

**Batched multi-slot prefill** packs every currently-prefilling slot into
one ``(P, chunk)`` jitted step, with P bucketed to :data:`P_BUCKETS`
(clamped to the slot count) so the compile count stays bounded. Pad rows
are inactive: their K/V writes land in the null page, their keys are
masked, and they carry slot ids distinct from every active row so their
masked state write-back cannot race a real update.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import (
    DEVICE_INFLIGHT_TID,
    DEVICE_TID,
    PID_DEVICE,
    MetricsRegistry,
    NullTracer,
    StepProfiler,
    WeightPrep,
    weight_prep_tally,
)
from repro.serving.cache import StateStore, copy_kv_page
from repro.serving.sampling import GREEDY, sample_logits, stack_params
from repro.training import make_paged_serve_steps
from repro.training.loop import resolve_engine

# Allowed P values for batched multi-slot prefill. Bucketing the row count
# (instead of compiling one shape per prefilling-set size) bounds the
# number of compiled prefill_batch variants to |P_BUCKETS|.
P_BUCKETS = (1, 2, 4, 8)


@dataclasses.dataclass
class InflightStep:
    """One dispatched-but-not-yet-harvested device step."""

    kind: str  # prefill_full | prefill_chunk | prefill_batch | decode
    bucket: int  # profiler shape bucket (chunk size, P*chunk, or num_slots)
    t_dispatch: float  # perf_counter just before the jit call
    done: Any  # device array whose readiness marks step completion
    toks: Any  # sampled-token future ((1,)/(P,)/(S,) int32) or None
    payload: Any  # opaque server-side commit payload
    trace_args: dict


def serving_params(model, params, engine=None, backend: Optional[str] = None):
    """``params`` with every GEMM weight prepared once for the engine the
    serving steps run (``Transformer.serving_params``), and the bytes the
    preparation made."""
    prepared = model.serving_params(params, resolve_engine(model, engine, backend))
    kept = {id(a) for a in jax.tree.leaves(params)}
    made = sum(a.nbytes for a in jax.tree.leaves(prepared) if id(a) not in kept)
    return prepared, made


class EngineCore:
    """Device-stepping core of the continuous-batching server.

    ``depth`` in :meth:`harvest_due` is the dispatch window: how many
    device steps may be in flight before the host blocks. Depth 0 is the
    synchronous mode — every step is harvested in the same server
    iteration that dispatched it — and, because dispatch order does not
    depend on depth, greedy outputs are identical at every depth.

    ``params`` are prepared for the steps at construction
    (:func:`serving_params`); ``weight_prep`` holds, per step kind, the
    weight casts, transposes and pads the compiled step still does
    (``repro.obs.weight_prep``), recorded when the step is traced.
    """

    def __init__(self, model, params, config, profile, *, engine=None,
                 backend: Optional[str] = None, seed: int = 0,
                 tracer=None, metrics: Optional[MetricsRegistry] = None,
                 profiler: Optional[StepProfiler] = None):
        self.model = model
        self.params, self.prepared_bytes = serving_params(
            model, params, engine, backend)
        self.config = config
        self.profile = profile
        self.seed = seed
        self.tracer = tracer if tracer is not None else NullTracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.profiler = profiler if profiler is not None else StepProfiler()
        prefill_full, prefill_chunk, prefill_batch, decode_step = (
            make_paged_serve_steps(
                model, page_size=config.page_size, engine=engine,
                backend=backend,
            )
        )
        self.weight_prep: dict[str, WeightPrep] = {}
        self._prefill_full = self._jit("prefill_full", prefill_full)
        self._prefill_chunk = self._jit("prefill_chunk", prefill_chunk)
        self._prefill_batch = self._jit("prefill_batch", prefill_batch)
        self._decode = self._jit("decode", decode_step)
        self._sample = jax.jit(sample_logits)
        ps = config.page_size
        self._copy_page = jax.jit(
            lambda pools, src, dst: copy_kv_page(pools, src, dst, page_size=ps)
        )
        # Jitted last-token maintenance: the (S, 1) device array decode
        # steps read their inputs from (so decode never waits on a host
        # round-trip of the previous sample).
        self._last_set = jax.jit(
            lambda last, slot, tok: last.at[slot, 0].set(tok)
        )
        self._last_set_rows = jax.jit(
            lambda last, slots, toks, mask: last.at[slots, 0].set(
                jnp.where(mask, toks, last[slots, 0])
            )
        )
        self._last_merge = jax.jit(
            lambda last, toks, active: jnp.where(
                active[:, None], toks[:, None], last
            )
        )
        m = self.metrics
        self._g_inflight = m.gauge(
            "engine_inflight", "device steps dispatched but not yet harvested")
        self._c_prefill_s = m.counter(
            "serving_prefill_seconds_total", "wall seconds in prefill steps")
        self._c_decode_s = m.counter(
            "serving_decode_seconds_total", "wall seconds in decode rounds")
        self._h_chunk = m.histogram(
            "serving_prefill_chunk_seconds", help="one prefill step")
        self._h_decode_step = m.histogram(
            "serving_decode_step_seconds",
            help="one decode round over all slots (incl. sampling sync)")
        # NB: the engine is not usable until fresh() builds the StateStore —
        # the Server calls it from _fresh_state so pools are built exactly
        # once per (re)start.

    def _jit(self, kind: str, step):
        """jit ``step`` under its own name (the program's, which a trace's
        reader looks up), recording its weight preparation when traced."""
        @functools.wraps(step)
        def traced(*args):
            with weight_prep_tally() as prep:
                out = step(*args)
            self.weight_prep[kind] = prep
            return out
        return jax.jit(traced)

    # -- state lifecycle ---------------------------------------------------
    def fresh(self, pools=None) -> None:
        """(Re)build the StateStore and per-run device state. Must not be
        called with steps still in flight — drain first."""
        if getattr(self, "_inflight", None):
            raise RuntimeError(
                f"engine reset with {len(self._inflight)} steps in flight; "
                "harvest them first"
            )
        cfg = self.config
        self.cache = StateStore.build(
            self.model, num_slots=cfg.num_slots,
            num_pages=self.resolved_num_pages(), page_size=cfg.page_size,
            pages_per_slot=cfg.pages_per_slot, pools=pools,
        )
        self._key = jax.random.PRNGKey(self.seed)
        self._last_tok = jnp.zeros((cfg.num_slots, 1), jnp.int32)
        self._inflight: collections.deque[InflightStep] = collections.deque()
        self._t_last_harvest = 0.0
        self._g_inflight.set(0)

    # -- pool sizing (derived from the model's CBProfile) ------------------
    def reserve_tokens_cap(self) -> Optional[int]:
        """Tokens a request must keep page-resident at once, from the
        model's pool layout. None = the full sequence."""
        cfg, prof = self.config, self.profile
        if not prof.needs_kv_pages:
            return 0
        if prof.kv_window is not None and cfg.prefill_chunk is not None:
            # Window + one in-flight chunk + slack pages so lazy allocation
            # ahead of recycling never outruns the reservation. Only sound
            # under chunked prefill: whole-prompt prefill allocates every
            # prompt page at once (recycling runs after the jitted call),
            # so its peak demand is the full prompt, not a window.
            return min(cfg.max_seq_len,
                       prof.kv_window + cfg.prefill_chunk + 2 * cfg.page_size)
        return None

    def resolved_num_pages(self) -> int:
        cfg = self.config
        if cfg.num_pages is not None:
            return cfg.num_pages
        cap = self.reserve_tokens_cap()
        per_slot = -(-min(cfg.max_seq_len, cap if cap is not None
                          else cfg.max_seq_len) // cfg.page_size)
        return max(cfg.num_slots * per_slot + 1, 2)

    # -- P-bucketing -------------------------------------------------------
    def allowed_buckets(self) -> tuple[int, ...]:
        """P buckets usable on this engine: the standard set clamped to the
        slot count (pad rows need slot ids disjoint from the active rows,
        which a bucket wider than the slot count could not provide)."""
        allowed = tuple(b for b in P_BUCKETS if b <= self.config.num_slots)
        return allowed or (1,)

    def bucket_for(self, n_rows: int) -> int:
        """Smallest allowed bucket covering ``n_rows`` (callers cap group
        sizes at ``allowed_buckets()[-1]``)."""
        for b in self.allowed_buckets():
            if b >= n_rows:
                return b
        return self.allowed_buckets()[-1]

    # -- misc device helpers ----------------------------------------------
    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write page copy, threaded through the pools chain."""
        self.cache.pools = self._copy_page(
            self.cache.pools, jnp.int32(src), jnp.int32(dst)
        )

    @property
    def num_inflight(self) -> int:
        return len(self._inflight)

    # -- dispatch ----------------------------------------------------------
    def _record(self, step: InflightStep) -> None:
        self._inflight.append(step)
        self._g_inflight.set(len(self._inflight))

    def dispatch_prefill(self, *, kind: str, tokens: np.ndarray,
                         page_row: np.ndarray, slot: int, start: int, n: int,
                         bucket: int, sampling=None, payload=None,
                         rid: int = -1) -> None:
        """Enqueue one single-row prefill step (``prefill_full`` or
        ``prefill_chunk``). ``sampling`` non-None marks the final chunk:
        the first token is sampled on-device and scattered into the
        last-token array so decode can be dispatched against it."""
        targs = {"rid": rid, "slot": slot, "start": start, "tokens": n,
                 "bucket": bucket}
        t0 = time.perf_counter()
        with self.tracer.span(PID_DEVICE, DEVICE_TID, f"{kind}.dispatch",
                              **targs):
            fn = (self._prefill_full if kind == "prefill_full"
                  else self._prefill_chunk)
            logits, pools = fn(
                self.params, jnp.asarray(tokens), self.cache.pools,
                jnp.asarray(page_row), jnp.int32(slot), jnp.int32(start),
                jnp.int32(n),
            )
            self.cache.pools = pools
            toks = None
            if sampling is not None:
                toks = self._sample(logits, self.next_key(),
                                    **stack_params([sampling]))
                self._last_tok = self._last_set(
                    self._last_tok, jnp.int32(slot), toks[0]
                )
        self._record(InflightStep(
            kind=kind, bucket=bucket, t_dispatch=t0, done=logits, toks=toks,
            payload=payload, trace_args=targs,
        ))

    def dispatch_prefill_batch(self, *, tokens: np.ndarray,
                               page_rows: np.ndarray, slots: np.ndarray,
                               starts: np.ndarray, lengths: np.ndarray,
                               active: np.ndarray, final_mask: np.ndarray,
                               sampling_list, payload=None,
                               rids=None) -> None:
        """Enqueue one (P, chunk) multi-slot prefill step. Every row is
        sampled in one fixed-shape call (one key for the whole batch —
        greedy rows take their per-row argmax regardless); only rows whose
        ``final_mask`` is set (their chunk completes the prompt) scatter
        into the last-token array."""
        p, chunk = tokens.shape
        bucket = p * chunk  # effective GEMM M — the tuning band's key
        # repro: allow[RPR106] active is a host numpy array — no device sync
        targs = {"rows": int(active.sum()), "P": p, "chunk": chunk,
                 "bucket": bucket}
        if rids is not None:
            targs["rids"] = list(rids)
        t0 = time.perf_counter()
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "prefill_batch.dispatch",
                              **targs):
            logits, pools = self._prefill_batch(
                self.params, jnp.asarray(tokens), self.cache.pools,
                jnp.asarray(page_rows), jnp.asarray(slots),
                jnp.asarray(starts), jnp.asarray(lengths), jnp.asarray(active),
            )
            self.cache.pools = pools
            toks = self._sample(logits, self.next_key(),
                                **stack_params(sampling_list))
            self._last_tok = self._last_set_rows(
                self._last_tok, jnp.asarray(slots), toks,
                jnp.asarray(final_mask)
            )
        self._record(InflightStep(
            kind="prefill_batch", bucket=bucket, t_dispatch=t0, done=logits,
            toks=toks, payload=payload, trace_args=targs,
        ))

    def dispatch_decode(self, *, active: np.ndarray, params_list,
                        payload=None) -> None:
        """Enqueue one all-slots decode step. Input tokens come from the
        device last-token array (no host sync); the sampled tokens merge
        back into it for the next decode."""
        n = self.cache.num_slots
        # ``ctx``: the decoding rows' contexts summed, the new token
        # included — the KV positions the step's attention reads.
        # repro: allow[RPR106] active and seq_lens are host numpy arrays
        ctx = int((self.cache.seq_lens[active] + 1).sum())
        # repro: allow[RPR106] active is a host numpy array — no device sync
        targs = {"slots": n, "decoding": int(active.sum()), "ctx": ctx}
        t0 = time.perf_counter()
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "decode.dispatch",
                              **targs):
            active_dev = jnp.asarray(active)
            # .copy(): on CPU backends device_put of a numpy array may be
            # zero-copy, aliasing the live host mirror — which the server
            # mutates right after dispatch. The snapshot must be immutable.
            logits, pools = self._decode(
                self.params, self._last_tok, self.cache.pools,
                jnp.asarray(self.cache.page_table.copy()),
                jnp.asarray(self.cache.seq_lens.copy()), active_dev,
            )
            self.cache.pools = pools
            toks = self._sample(logits, self.next_key(),
                                **stack_params(params_list))
            self._last_tok = self._last_merge(self._last_tok, toks, active_dev)
        self._record(InflightStep(
            kind="decode", bucket=n, t_dispatch=t0, done=logits, toks=toks,
            payload=payload, trace_args=targs,
        ))

    # -- harvest -----------------------------------------------------------
    def harvest_one(self):
        """Block on the oldest in-flight step (the stream boundary) and
        return ``(step, sampled_tokens_or_None)``; None when nothing is in
        flight. Timing is attributed without double-counting overlap: each
        step charges the wall time from ``max(its dispatch, the previous
        harvest)`` to its own completion, so the per-step seconds sum to
        elapsed wall time when the device is saturated (and reduce to the
        synchronous dispatch->block measure at depth 0)."""
        if not self._inflight:
            return None
        rec = self._inflight.popleft()
        t_wait = time.perf_counter()
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "harvest.wait",
                              kind=rec.kind):
            jax.block_until_ready(rec.done)
            toks = np.asarray(rec.toks) if rec.toks is not None else None
        t_done = time.perf_counter()
        dt = t_done - max(rec.t_dispatch, self._t_last_harvest)
        self._t_last_harvest = t_done
        if rec.kind.startswith("prefill"):
            self._c_prefill_s.inc(dt)
            self._h_chunk.observe(dt)
        else:
            self._c_decode_s.inc(dt)
            self._h_decode_step.observe(dt)
        self.profiler.record(rec.kind, rec.bucket, dt)
        t = self.tracer
        if t.enabled:
            t.complete(
                PID_DEVICE, DEVICE_INFLIGHT_TID, f"{rec.kind}.complete",
                rec.t_dispatch, t_done - rec.t_dispatch,
                wait_s=round(t_done - t_wait, 6), **rec.trace_args,
            )
        self._g_inflight.set(len(self._inflight))
        return rec, toks
