"""The serving loop: jit-friendly fixed-shape steps driven by the
continuous-batching scheduler, for every decoder-only sequence family.

The stack splits in two (see ``repro.serving.engine``):

- :class:`~repro.serving.engine.EngineCore` owns the device: the
  StateStore, the jitted steps, the RNG key stream, the device-resident
  last-token array, and the FIFO window of dispatched-but-unharvested
  steps.
- :class:`Server` (this module) owns the requests: scheduler, admission,
  prompts, token commits and streaming. It *dispatches* work into the
  engine and *harvests* results out.

Layout of one ``Server.step()``:

  1. admit queued requests into free slots (pages + budget permitting);
  2. dispatch one prompt chunk per prefilling request — either one
     single-row step each, or (``prefill_batch``) every prefilling slot
     packed into one ``(P, chunk)`` step with P bucketed to {1,2,4,8}.
     The final chunk samples the request's first token on-device;
  3. dispatch ONE decode step over every slot; sampled tokens merge into
     the engine's last-token array so the *next* decode can dispatch
     without waiting for this one;
  4. harvest the oldest in-flight steps down to ``async_depth``: block
     at the stream boundary, commit tokens/prefix pages, stamp
     TTFT/inter-token marks, emit :class:`TokenEvent`s.

At ``async_depth=0`` every step is harvested in the iteration that
dispatched it — the synchronous mode — and because the dispatch sequence
(and therefore the RNG key stream) does not depend on the depth, greedy
outputs are bitwise identical at every depth. Host bookkeeping runs in
two phases: *optimistic* at dispatch (page growth, seq_lens mirrors,
per-request dispatch cursors) and *authoritative* at harvest (committed
tokens, prefix publishing, latency stamps, finishes). An EOS the host
only learns about at harvest may leave up to ``async_depth`` stale decode
steps in flight; their tokens are discarded at harvest and their writes
only ever touched the finished request's own frontier page.

Tokens stream out as :class:`TokenEvent`s at harvest; every request
records submit -> first-token wall time (TTFT) at the moment its first
token is *consumed*, not dispatched.

The static-batch path (:func:`generate_static`) lives here too: it is the
baseline the benchmarks compare against and the single implementation behind
``launch/serve.py`` / ``examples/serve_decode.py``. Both paths separate
compile time from steady-state time — reported tok/s never includes tracing.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import (
    DEVICE_TID,
    PID_DEVICE,
    PID_REQUESTS,
    MetricsRegistry,
    NullTracer,
    StepProfiler,
)
from repro.serving.engine import EngineCore
from repro.serving.sampling import (
    GREEDY,
    SamplingParams,
    sample_logits,
    stack_params,
)
from repro.serving.scheduler import RUNNING, Request, Scheduler
from repro.serving.spec import (
    ModelDrafter,
    NgramDrafter,
    SpecConfig,
    Verifier,
    effective_k,
)
from repro.serving.steps import make_serve_steps

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Sizing of the serving engine (all shapes derive from these)."""

    num_slots: int = 4  # concurrent decode lanes (the fixed batch)
    page_size: int = 16  # tokens per KV page
    max_seq_len: int = 256  # per-request prompt + generation cap
    # Total pages in the pool incl. the null page; default is computed from
    # the model's CBProfile (zero KV pages for attention-free archs, a
    # window's worth for all-sliding-window archs, worst case otherwise)
    # so admission is gated by slots, not pages.
    num_pages: Optional[int] = None
    token_budget: Optional[int] = None  # cap on sum(max_total) in flight
    # Prompts advance one fixed-size chunk per step, interleaved with
    # decode steps: one compiled prefill shape whatever the prompt length.
    prefill_chunk: int = 256
    # Prefix caching: published full prompt pages are shared (refcounted,
    # copy-on-write on a partial tail) into later requests with the same
    # prompt prefix. Auto-disabled for models with recurrent state rows —
    # skipping prefill positions would skip their state updates.
    prefix_cache: bool = False
    # Preemptive scheduling: a queued higher-priority request may evict a
    # strictly lower-priority request that is still prefilling (its
    # published pages make the resume mostly a cache hit).
    preemption: bool = False
    # Admission passes a queued request waits per effective-priority level
    # gained (anti-starvation aging).
    aging_steps: int = 32
    # Dispatch-ahead window: device steps that may be in flight before the
    # host blocks at the stream boundary. 0 = synchronous. Greedy outputs
    # are identical at every depth; forced to 0 while speculative decoding
    # is active (spec rounds are host-synchronous by construction).
    async_depth: int = 0
    # Batched multi-slot prefill: pack every prefilling slot into one
    # (P, prefill_chunk) jitted step, P bucketed to {1, 2, 4, 8} (clamped
    # to num_slots).
    prefill_batch: bool = False

    @property
    def pages_per_slot(self) -> int:
        # Page-table width: positions are page-indexed absolutely, so the
        # table always spans max_seq_len even when reservation is windowed
        # (recycled entries go back to NULL_PAGE).
        return -(-self.max_seq_len // self.page_size)


class TokenEvent(NamedTuple):
    """One streamed token: emitted by ``step()`` when it is harvested —
    the point its value is actually available on the host."""

    rid: int
    token: int
    index: int  # position within the generated sequence
    finished: bool
    finish_reason: Optional[str]


class ServerStats:
    """Read-only view over the server's :class:`MetricsRegistry` — the
    registry is the single source of truth (one set of counters feeds the
    launcher report, the benchmark rows, the Prometheus exposition and the
    JSON snapshot); this class keeps the pre-registry field names every
    caller already uses. Constructible standalone (fresh registry) for
    tests."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._m = registry if registry is not None else MetricsRegistry()

    def _c(self, name: str) -> float:
        return self._m.counter(name).value

    @property
    def prefill_calls(self) -> int:
        return int(self._c("serving_prefill_calls_total"))

    @property
    def prefill_tokens(self) -> int:
        """Valid prompt tokens prefilled."""
        return int(self._c("serving_prefill_tokens_total"))

    @property
    def decode_steps(self) -> int:
        return int(self._c("serving_decode_steps_total"))

    @property
    def decode_tokens(self) -> int:
        """Tokens sampled for *active* slots."""
        return int(self._c("serving_decode_tokens_total"))

    @property
    def slot_steps(self) -> int:
        """decode_steps * num_slots (capacity offered)."""
        return int(self._c("serving_slot_steps_total"))

    @property
    def prefill_s(self) -> float:
        return self._c("serving_prefill_seconds_total")

    @property
    def decode_s(self) -> float:
        return self._c("serving_decode_seconds_total")

    # Prefix cache: prompt tokens satisfied from published pages vs all
    # prompt tokens admitted (a preempted request's resume counts again).
    # The scheduler's counters are the authority; gauges mirror them.
    @property
    def prefix_hit_tokens(self) -> int:
        return int(self._m.gauge("serving_prefix_hit_tokens").value)

    @property
    def prefix_prompt_tokens(self) -> int:
        return int(self._m.gauge("serving_prefix_prompt_tokens").value)

    @property
    def cow_copies(self) -> int:
        """Copy-on-write page copies performed."""
        return int(self._c("serving_cow_copies_total"))

    @property
    def preemptions(self) -> int:
        """Prefilling requests evicted back to the queue."""
        return int(self._m.gauge("serving_preemptions").value)

    # Speculative decoding: verify rounds run, drafts fielded, drafts the
    # rejection sampler accepted.
    @property
    def spec_steps(self) -> int:
        return int(self._c("serving_spec_steps_total"))

    @property
    def spec_drafted(self) -> int:
        return int(self._c("serving_spec_drafted_total"))

    @property
    def spec_accepted(self) -> int:
        return int(self._c("serving_spec_accepted_total"))

    @property
    def utilization(self) -> float:
        """Fraction of offered decode-lane steps that produced a token —
        the serving analogue of the paper's CE-array utilization. Under
        speculative decoding one lane-step can emit several tokens, so
        this can exceed 1.0 — that surplus IS the speedup."""
        return self.decode_tokens / self.slot_steps if self.slot_steps else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of fielded draft tokens the target accepted."""
        if not self.spec_drafted:
            return 0.0
        return self.spec_accepted / self.spec_drafted

    @property
    def accepted_per_step(self) -> float:
        """Mean accepted draft tokens per speculative verify round (the
        emitted tokens per round are this + 1)."""
        if not self.spec_steps:
            return 0.0
        return self.spec_accepted / self.spec_steps

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the prefix cache."""
        if not self.prefix_prompt_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_prompt_tokens

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s


@dataclasses.dataclass
class _DispatchState:
    """Per-request host cursor for work *dispatched* (vs. committed —
    ``req.prefilled`` / ``req.out_tokens`` stay authoritative and only
    advance at harvest). ``epoch`` snapshots ``req.preemptions`` at
    install: a record dispatched before a preemption carries the old
    epoch, so its harvest is recognised as stale and skipped."""

    prefilled: int
    generated: int
    epoch: int


class Server:
    """Continuous-batching inference server over the engine's StateStore.

    ``backend`` selects the kernel backend for every GEMM *and* the
    decode attention path: with ``"pallas"`` / ``"pallas_interpret"``,
    one-token decode steps dispatch to the fused paged flash-decode kernel
    (page-table walk inside the kernel, in-tile fp8 dequant); the default
    XLA backend keeps the gather + online-softmax reference path, which is
    also the CPU fallback and the parity oracle the kernel is tested against.

    ``engine`` is the *compute* engine forwarded to the jitted steps;
    ``self.engine`` is the serving :class:`EngineCore` built around it.
    """

    def __init__(self, model, params, config: Optional[ServerConfig] = None, *,
                 engine=None, backend: Optional[str] = None, seed: int = 0,
                 spec: Optional[SpecConfig] = None, draft_model=None,
                 draft_params=None, tracer=None,
                 metrics: Optional[MetricsRegistry] = None,
                 profiler: Optional[StepProfiler] = None):
        # None sentinel, NOT a default instance: a module-level default
        # would be one shared object evaluated at import time, bleeding any
        # mutation between servers.
        if config is None:
            config = ServerConfig()
        if config.async_depth < 0:
            raise ValueError("async_depth must be >= 0")
        # Observability: tracer defaults to the zero-overhead NullTracer
        # (hot paths gate on tracer.enabled before building event args);
        # the metrics registry is always on — it IS the stats store.
        self.tracer = tracer if tracer is not None else NullTracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.profiler = profiler if profiler is not None else StepProfiler()
        self._bind_metrics()
        if not model.supports_cb():
            raise NotImplementedError(
                f"{model.cfg.name}: continuous batching covers decoder-only "
                "families; use generate_static for this family"
            )
        self.model = model
        self.config = config
        self.profile = model.cb_profile()
        # Prefix caching shares KV pages only; a model with recurrent state
        # rows cannot skip prefill positions (their state updates would be
        # skipped too), so the knob auto-disables there.
        self.prefix_cache = (
            config.prefix_cache
            and self.profile.needs_kv_pages
            and not self.profile.has_state_rows
        )
        self.engine = EngineCore(
            model, params, config, self.profile, engine=engine,
            backend=backend, seed=seed, tracer=self.tracer,
            metrics=self.metrics, profiler=self.profiler,
        )
        # The steps read the weights as the engine's kernel takes them,
        # prepared once here (the caller's params stay as they were).
        self.params = self.engine.params
        # Speculative decoding: a drafter (paired model with its own
        # StateStore, or n-gram self-drafting) + the target-side verifier.
        # Passing draft_model without spec enables it at the default k.
        # Spec rounds are host-synchronous (draft -> verify -> commit), so
        # the dispatch window collapses to depth 0 while spec is on.
        if draft_model is not None and spec is None:
            spec = SpecConfig()
        self.spec = spec
        self.drafter = None
        self.verifier = None
        if spec is not None:
            if draft_model is not None:
                if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                    raise ValueError(
                        "drafter and target must share a vocabulary: "
                        f"{draft_model.cfg.vocab_size} != {model.cfg.vocab_size}"
                    )
                self.drafter = ModelDrafter(
                    draft_model, draft_params, num_slots=config.num_slots,
                    page_size=config.page_size, max_seq_len=config.max_seq_len,
                    k=spec.k, draft_chunk=spec.draft_chunk, backend=backend,
                    metrics=self.metrics,
                )
            else:
                self.drafter = NgramDrafter(k=spec.k, ngram_n=spec.ngram_n,
                                            metrics=self.metrics)
            self.verifier = Verifier(
                model, page_size=config.page_size, engine=engine,
                backend=backend, metrics=self.metrics,
            )
        log.info(
            "%s: %d bytes of GEMM weights prepared for serving", model.cfg.name,
            self.engine.prepared_bytes
            + getattr(self.drafter, "prepared_bytes", 0),
        )
        self._fresh_state()

    def _bind_metrics(self) -> None:
        """Resolve the registry handles the step loop increments. Names
        are the public metric surface (DESIGN.md, Observability); handles
        survive ``metrics.reset()`` (metrics zero in place)."""
        m = self.metrics
        self._c_prefill_calls = m.counter(
            "serving_prefill_calls_total", "prefill chunk advances committed")
        self._c_prefill_tokens = m.counter(
            "serving_prefill_tokens_total", "valid prompt tokens prefilled")
        self._c_decode_steps = m.counter(
            "serving_decode_steps_total", "decode/spec rounds run")
        self._c_decode_tokens = m.counter(
            "serving_decode_tokens_total", "tokens sampled for active slots")
        self._c_decode_s = m.counter(
            "serving_decode_seconds_total", "wall seconds in decode rounds")
        self._c_slot_steps = m.counter(
            "serving_slot_steps_total", "decode lane-steps offered")
        self._c_cow = m.counter(
            "serving_cow_copies_total", "copy-on-write page copies")
        self._c_spec_steps = m.counter(
            "serving_spec_steps_total", "speculative verify rounds")
        self._c_spec_drafted = m.counter(
            "serving_spec_drafted_total", "draft tokens fielded")
        self._c_spec_accepted = m.counter(
            "serving_spec_accepted_total", "draft tokens accepted")
        self._g_prefix_hit = m.gauge(
            "serving_prefix_hit_tokens",
            "prompt tokens served from the prefix cache (scheduler mirror)")
        self._g_prefix_prompt = m.gauge(
            "serving_prefix_prompt_tokens",
            "prompt tokens admitted (scheduler mirror)")
        self._g_preemptions = m.gauge(
            "serving_preemptions", "preemptions (scheduler mirror)")
        self._h_ttft = m.histogram(
            "serving_ttft_seconds", help="submit -> first token, queue incl.")
        self._h_itl = m.histogram(
            "serving_inter_token_seconds",
            help="gap between a request's consecutive emitted tokens")
        self._h_queue_wait = m.histogram(
            "serving_queue_wait_seconds",
            help="enqueue (submit or preemption) -> admission")
        self._h_decode_step = m.histogram(
            "serving_decode_step_seconds",
            help="one decode round over all slots (incl. sampling sync)")
        self._h_acc_round = m.histogram(
            "serving_spec_accepted_per_round", bounds=list(range(33)),
            help="accepted drafts per decoding row per verify round")

    @property
    def cache(self):
        """The engine's StateStore (page tables, seq_lens, pools)."""
        return self.engine.cache

    @property
    def seed(self) -> int:
        """PRNG seed; lives on the engine (re-keyed on reset())."""
        return self.engine.seed

    @seed.setter
    def seed(self, value: int) -> None:
        self.engine.seed = value

    def _fresh_state(self, pools=None) -> None:
        cfg = self.config
        # Warmup accounting: metrics and trace state reset with the rest of
        # the serving state — counters from compile/warmup runs (including
        # the spec counters feeding acceptance_rate) must never leak into a
        # timed run's report. The profiler deliberately survives: its
        # first-call-per-shape memory is what keeps compile attributed to
        # warmup rather than to the first post-reset step.
        self.metrics.reset()
        self.tracer.reset()
        self.engine.fresh(pools=pools)
        self.scheduler = Scheduler(
            num_slots=cfg.num_slots, pool=self.cache.allocator,
            pages_per_slot=cfg.pages_per_slot, max_seq_len=cfg.max_seq_len,
            token_budget=cfg.token_budget,
            kv_reserve_tokens=self.engine.reserve_tokens_cap(),
            prefix_cache=self.prefix_cache, preemption=cfg.preemption,
            aging_steps=cfg.aging_steps, metrics=self.metrics,
        )
        self.stats = ServerStats(self.metrics)
        self.results: dict[int, Request] = {}
        # Slot -> running Request mirror (server-side: lets _on_preempt
        # attribute the evicted slot back to its request for tracing).
        self._slot_req: dict[int, Request] = {}
        # rid -> dispatch cursor (see _DispatchState).
        self._disp: dict[int, _DispatchState] = {}
        if getattr(self, "drafter", None) is not None:
            self.drafter.reset()

    def reset(self) -> None:
        """Drop all serving state (keeps compiled steps and the pools —
        stale K/V and state rows are never read back as valid). In-flight
        steps are harvested first (their events are discarded). Metrics
        and trace events reset too; the step profiler's compile/steady
        attribution survives (see ``_fresh_state``)."""
        self._drain([])
        self._fresh_state(pools=self.cache.pools)

    # -- request intake ----------------------------------------------------
    def submit(self, prompt: Iterable[int], *, max_new_tokens: int = 32,
               sampling: SamplingParams = GREEDY,
               eos_id: Optional[int] = None, priority: int = 0,
               spec_k: Optional[int] = None) -> Request:
        req = self.scheduler.submit(Request(
            prompt=[int(t) for t in prompt], max_new_tokens=max_new_tokens,
            sampling=sampling, eos_id=eos_id, priority=priority,
            spec_k=spec_k,
        ))
        req.t_submit = req.t_queued = time.perf_counter()
        t = self.tracer
        if t.enabled:
            t.begin(PID_REQUESTS, req.rid, "request",
                    rid=req.rid, prompt_len=req.prompt_len,
                    max_new_tokens=req.max_new_tokens, priority=priority)
            t.begin(PID_REQUESTS, req.rid, "queued")
        return req

    # -- the step loop -----------------------------------------------------
    def step(self) -> list[TokenEvent]:
        """One scheduler iteration: admit (mapping cached prefixes, possibly
        preempting), dispatch one prefill chunk per prefilling request
        (batched when ``prefill_batch``) and one decode over all slots,
        then harvest in-flight steps down to ``async_depth``. Returns the
        tokens harvested (possibly empty while work is still in flight)."""
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "server.step"):
            return self._step()

    def _step(self) -> list[TokenEvent]:
        events: list[TokenEvent] = []
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "server.admit"):
            for req in self.scheduler.admit(on_preempt=self._on_preempt):
                self._install(req)
            # The scheduler's counters are the single authority; the
            # registry gauges mirror them for reporting/exposition.
            self._g_prefix_hit.set(self.scheduler.prefix_hit_tokens)
            self._g_prefix_prompt.set(self.scheduler.prefix_prompt_tokens)
            self._g_preemptions.set(self.scheduler.preemptions)
        prefilling = [req for req in self.scheduler.running.values()
                      if self._dispatch_prefilling(req)]
        dispatched = 0
        if prefilling:
            if self.config.prefill_batch:
                dispatched += self._dispatch_prefill_batched(prefilling)
            else:
                for req in prefilling:
                    self._dispatch_prefill_serial(req)
                    dispatched += 1
        if self.spec is not None:
            # Spec rounds are host-synchronous: drain the prefill
            # dispatches (committing first tokens) so the round sees
            # exactly the state the synchronous server would.
            self._drain(events)
            if any(r.decoding for r in self.scheduler.running.values()):
                self._spec_decode_once(events)
            return events
        decoding = self._decode_candidates()
        if decoding:
            self._dispatch_decode(decoding)
            dispatched += 1
        while self.engine.num_inflight > self.config.async_depth:
            self._harvest_one(events)
        if not dispatched and self.engine.num_inflight:
            # Everything admissible is already in flight: consume one
            # result so the loop always makes progress toward drain.
            self._harvest_one(events)
        return events

    def run(self) -> dict[int, Request]:
        """Drain the queue; returns {rid: finished Request}."""
        while self.scheduler.has_work():
            self.step()
        self._drain([])  # EOS-overshoot leftovers; commits are all stale
        return dict(self.results)

    def stream(self):
        """Generator over TokenEvents until all submitted work finishes."""
        while self.scheduler.has_work():
            yield from self.step()
        tail: list[TokenEvent] = []
        self._drain(tail)
        yield from tail

    def ttft_percentiles(self, qs=(50, 95)) -> Optional[tuple[float, ...]]:
        """Submit -> first-token wall seconds at the given percentiles over
        finished requests (queueing included — the latency continuous
        batching + chunked prefill actually improve); None before any
        request finished."""
        ttft = [r.t_first_token - r.t_submit for r in self.results.values()
                if r.t_first_token is not None]
        if not ttft:
            return None
        return tuple(float(np.percentile(ttft, q)) for q in qs)

    def warmup(self, prompt_lens: Iterable[int], max_new_tokens: int = 2) -> None:
        """Compile the chunk, sampling and decode steps — one shape each,
        whatever the prompt length, so one prompt of the shortest given
        length does — then reset serving state, so a timed run right after
        measures steady state only."""
        self.submit([1] * min(prompt_lens), max_new_tokens=max_new_tokens)
        self.run()
        self.reset()

    # -- internals ---------------------------------------------------------
    def _next_key(self):
        return self.engine.next_key()

    def _gen_cap(self, req: Request) -> int:
        """Tokens this request may generate in total. Host-predictable, so
        length finishes never overshoot: dispatch stops exactly where
        ``scheduler.commit`` will declare FINISH_LENGTH."""
        return max(0, min(req.max_new_tokens,
                          req.max_total - req.prompt_len))

    def _dispatch_prefilling(self, req: Request) -> bool:
        ds = self._disp.get(req.rid)
        return ds is not None and ds.prefilled < req.prompt_len

    def _decode_candidates(self) -> list:
        out = []
        for slot, req in self.scheduler.running.items():
            ds = self._disp.get(req.rid)
            if ds is None or ds.prefilled < req.prompt_len:
                continue
            if ds.generated >= self._gen_cap(req):
                continue
            out.append((slot, req, ds))
        return out

    def _mirror_pages(self, req: Request, grown) -> None:
        for idx, page in grown:
            self.cache.set_page(req.slot, idx, page)

    def _on_preempt(self, slot: int) -> None:
        """Scheduler evicted this slot's request: NULL its device page-table
        row (its pages may now belong to someone else or sit free), drop
        its dispatch cursor (in-flight chunks carry the old epoch and are
        skipped at harvest), and re-open the victim's queued span."""
        self.cache.reset_slot(slot)
        req = self._slot_req.pop(slot, None)
        if req is not None:
            self._disp.pop(req.rid, None)
            req.t_queued = time.perf_counter()
            t = self.tracer
            if t.enabled:
                t.instant(PID_REQUESTS, req.rid, "preempted",
                          prefilled=req.prefilled, slot=slot)
                t.begin(PID_REQUESTS, req.rid, "queued")

    def _install(self, req: Request) -> None:
        """Wire a freshly admitted request into the device state: mirror its
        prefix-matched pages, run the copy-on-write page copies, and start
        its committed length at the cached prefix."""
        now = time.perf_counter()
        req.t_admit = now
        self._h_queue_wait.observe(now - req.t_queued)
        self._slot_req[req.slot] = req
        self._disp[req.rid] = _DispatchState(
            prefilled=req.prefilled, generated=len(req.out_tokens),
            epoch=req.preemptions,
        )
        t = self.tracer
        if t.enabled:
            t.end(PID_REQUESTS, req.rid, "queued")
            t.instant(PID_REQUESTS, req.rid, "admitted", slot=req.slot,
                      prefix_hit_tokens=req.cached_tokens,
                      cow_copies=len(req.pending_copies),
                      preemptions=req.preemptions)
        self._mirror_pages(req, list(enumerate(req.pages)))
        for src, dst in req.pending_copies:
            self.engine.copy_page(src, dst)
            self._c_cow.inc()
        req.pending_copies = []
        self.cache.seq_lens[req.slot] = req.prefilled

    def _recycle_window(self, req: Request) -> None:
        window = self.profile.kv_window
        if window is None:
            return
        freed = self.scheduler.release_out_of_window(
            req, int(self.cache.seq_lens[req.slot]), window
        )
        self.cache.clear_pages(req.slot, freed)

    # -- dispatch (optimistic host state) ----------------------------------
    def _dispatch_prefill_serial(self, req: Request) -> None:
        """Dispatch one prompt chunk for one slot, from its first position
        not yet prefilled (past a prefix-cache hit, the chunk reads the
        mapped pages' K/V back through the page table)."""
        chunk = self.config.prefill_chunk
        ds = self._disp[req.rid]
        start = ds.prefilled
        n = min(chunk, req.prompt_len - start)
        if self.profile.needs_kv_pages:
            self._mirror_pages(req, self.scheduler.ensure_pages(req, start + n))
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = req.prompt[start:start + n]
        final = start + n == req.prompt_len
        # The StateStore mirror is the single source of truth for the row
        # (kept in sync by _mirror_pages / clear_pages / reset_slot);
        # copied so later host mutations can't leak into the snapshot.
        self.engine.dispatch_prefill(
            tokens=toks, page_row=self.cache.page_table[req.slot].copy(),
            slot=req.slot, start=start, n=n,
            sampling=req.sampling if final else None,
            payload=[(req, ds.epoch, start, n, final)], rid=req.rid,
        )
        ds.prefilled = start + n
        if final:
            ds.generated += 1  # the final chunk samples the first token
        self.cache.seq_lens[req.slot] = ds.prefilled
        self._recycle_window(req)

    def _dispatch_prefill_batched(self, prefilling: list) -> int:
        """Dispatch every prefilling request's next chunk as (P, chunk)
        steps, P bucketed to the engine's allowed set. Pad rows are
        inactive and carry slot ids disjoint from the group's active slots:
        an inactive row's masked state write-back scatters its slot's OLD
        row, and XLA leaves duplicate-index scatter order unspecified — a
        pad sharing an active row's slot could clobber the real update.
        Buckets never exceed num_slots, so a distinct pad slot always
        exists. Returns the number of steps dispatched."""
        cfg = self.config
        chunk = cfg.prefill_chunk
        max_b = self.engine.allowed_buckets()[-1]
        dispatched = 0
        for i in range(0, len(prefilling), max_b):
            group = prefilling[i:i + max_b]
            if len(group) == 1:
                # A single prefilling request takes the (1, chunk) step:
                # the same model body as a one-row batch, under the program
                # the serial path compiles anyway.
                self._dispatch_prefill_serial(group[0])
                dispatched += 1
                continue
            p = self.engine.bucket_for(len(group))
            toks = np.zeros((p, chunk), np.int32)
            page_rows = np.zeros((p, cfg.pages_per_slot), np.int32)
            slots = np.zeros((p,), np.int32)
            starts = np.zeros((p,), np.int32)
            lengths = np.zeros((p,), np.int32)
            active = np.zeros((p,), bool)
            final_mask = np.zeros((p,), bool)
            sampling_list = [GREEDY] * p
            rows = []
            used = set()
            for r, req in enumerate(group):
                ds = self._disp[req.rid]
                start = ds.prefilled
                n = min(chunk, req.prompt_len - start)
                if self.profile.needs_kv_pages:
                    self._mirror_pages(
                        req, self.scheduler.ensure_pages(req, start + n))
                toks[r, :n] = req.prompt[start:start + n]
                page_rows[r] = self.cache.page_table[req.slot]
                slots[r] = req.slot
                starts[r] = start
                lengths[r] = n
                active[r] = True
                final = start + n == req.prompt_len
                final_mask[r] = final
                sampling_list[r] = req.sampling
                used.add(req.slot)
                rows.append((req, ds.epoch, start, n, final))
            pad_slots = [s for s in range(cfg.num_slots) if s not in used]
            for r in range(len(group), p):
                slots[r] = pad_slots[0]  # pads may share a slot between them
            self.engine.dispatch_prefill_batch(
                tokens=toks, page_rows=page_rows, slots=slots, starts=starts,
                lengths=lengths, active=active, final_mask=final_mask,
                sampling_list=sampling_list, payload=rows,
                rids=[req.rid for req in group],
            )
            dispatched += 1
            for req, _, start, n, final in rows:
                ds = self._disp[req.rid]
                ds.prefilled = start + n
                if final:
                    ds.generated += 1
                self.cache.seq_lens[req.slot] = ds.prefilled
                self._recycle_window(req)
        return dispatched

    def _dispatch_decode(self, decoding: list) -> None:
        n = self.config.num_slots
        active = np.zeros((n,), bool)
        params_list = [GREEDY] * n
        rows = []
        for slot, req, ds in decoding:
            if self.profile.needs_kv_pages:
                grown = self.scheduler.ensure_page(
                    req, int(self.cache.seq_lens[slot]))
                if grown is not None:
                    self._mirror_pages(req, [grown])
            active[slot] = True
            params_list[slot] = req.sampling
            rows.append((slot, req, ds.epoch))
        self.engine.dispatch_decode(active=active, params_list=params_list,
                                    payload=rows)
        for slot, req, ds in decoding:
            ds.generated += 1
            self.cache.seq_lens[slot] += 1
            self._recycle_window(req)

    # -- harvest (authoritative commits) -----------------------------------
    def _drain(self, events: list[TokenEvent]) -> None:
        while self._harvest_one(events):
            pass

    def _harvest_one(self, events: list[TokenEvent]) -> bool:
        """Consume the oldest in-flight step: commit its tokens/prefix
        state and emit TokenEvents. Rows whose request was preempted (old
        epoch) or already finished (EOS overshoot within the dispatch
        window) are discarded. Returns False when nothing was in flight."""
        res = self.engine.harvest_one()
        if res is None:
            return False
        with self.tracer.span(PID_DEVICE, DEVICE_TID, "server.commit"):
            self._commit_step(*res, events)
        return True

    def _commit_step(self, rec, toks, events: list[TokenEvent]) -> None:
        if rec.kind == "decode":
            committed = 0
            for slot, req, epoch in rec.payload:
                if (req.status != RUNNING or req.preemptions != epoch
                        or req.slot != slot):
                    continue
                self._commit(req, int(toks[slot]), events)
                committed += 1
            self._c_decode_steps.inc()
            self._c_slot_steps.inc(self.config.num_slots)
            self._c_decode_tokens.inc(committed)
        else:
            t = self.tracer
            for i, (req, epoch, start, n, final) in enumerate(rec.payload):
                if req.status != RUNNING or req.preemptions != epoch:
                    continue
                if t.enabled:
                    t.begin(PID_REQUESTS, req.rid, "prefill_chunk",
                            start=start, tokens=n)
                    t.end(PID_REQUESTS, req.rid, "prefill_chunk")
                req.prefilled = start + n
                self.scheduler.publish_prefix(req)
                self._c_prefill_calls.inc()
                self._c_prefill_tokens.inc(n)
                if final:
                    self._commit(req, int(toks[i]), events)

    def _spec_decode_once(self, events: list[TokenEvent]) -> None:
        """One speculative round over every decoding slot: draft k, verify
        all k+1 positions in one fixed-shape step, rejection-sample, then
        commit the accepted prefix + one target token per row.

        Rollback is asymmetric by design. Target K/V written past the
        accepted boundary needs no undo — ``seq_lens`` simply doesn't
        advance over it, so it is never read back and the next round
        overwrites it. Target recurrent state rows get a second
        ``commit_state`` pass clamped to accepted+1. The drafter rolls
        itself back internally (pool snapshot), so its next-round replay
        sees only tokens the target really emitted."""
        spec = self.spec
        decoding = [(slot, req) for slot, req in self.scheduler.running.items()
                    if req.decoding]
        n = self.cache.num_slots
        width = spec.k + 1
        want = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        contexts: dict[int, list[int]] = {}
        params_list = [GREEDY] * n
        for slot, req in decoding:
            committed = int(self.cache.seq_lens[slot])
            remaining = min(
                req.max_new_tokens - req.num_generated,
                req.max_total - req.prompt_len - req.num_generated,
            )
            want[slot] = effective_k(
                spec.k if req.spec_k is None else req.spec_k,
                spec.k, remaining, req.max_total - 1 - committed,
            )
            active[slot] = True
            contexts[slot] = req.prompt + req.out_tokens
            params_list[slot] = req.sampling
        t = self.tracer
        with t.span(PID_DEVICE, DEVICE_TID, "spec_round",
                    slots=n, decoding=len(decoding), k=spec.k):
            t0 = time.perf_counter()
            with t.span(PID_DEVICE, DEVICE_TID, "draft"):
                proposal = self.drafter.propose(
                    contexts, want, self._next_key(), params_list,
                )
            k_eff = np.minimum(want, proposal.counts)
            lengths = np.where(active, k_eff + 1, 0).astype(np.int32)
            tokens = np.zeros((n, width), np.int32)
            for slot, req in decoding:
                tokens[slot, 0] = req.out_tokens[-1]
                m = int(k_eff[slot])
                tokens[slot, 1:1 + m] = proposal.tokens[slot, :m]
            if self.profile.needs_kv_pages:
                for slot, req in decoding:
                    grown = self.scheduler.ensure_pages(
                        req,
                        int(self.cache.seq_lens[slot]) + int(lengths[slot]))
                    self._mirror_pages(req, grown)
            sp = stack_params(params_list)
            # repro: allow[RPR105] spec round is host-synchronous; no mirror write before commit reads it
            seq_lens_dev = jnp.asarray(self.cache.seq_lens)
            # repro: allow[RPR105] spec round is host-synchronous; no mirror write before commit reads it
            page_table_dev = jnp.asarray(self.cache.page_table)
            active_dev = jnp.asarray(active)
            with t.span(PID_DEVICE, DEVICE_TID, "verify",
                        width=width, rows=len(decoding)):
                logits, pools = self.verifier.verify(
                    self.params, jnp.asarray(tokens), self.cache.pools,
                    page_table_dev, seq_lens_dev, jnp.asarray(lengths),
                    active_dev,
                )
                out, acc = self.verifier.sample(
                    logits, jnp.asarray(tokens[:, 1:]), proposal.logits,
                    self._next_key(), sp, jnp.asarray(lengths), active_dev,
                )
                out = np.asarray(out)
                acc = np.asarray(acc)
            with t.span(PID_DEVICE, DEVICE_TID, "commit"):
                if self.verifier.needs_state_commit:
                    commit_lengths = np.where(active, acc + 1, 0).astype(np.int32)
                    pools = self.verifier.commit_state(
                        self.params, jnp.asarray(tokens), pools,
                        page_table_dev, seq_lens_dev,
                        jnp.asarray(commit_lengths), active_dev,
                    )
                jax.block_until_ready(pools)
            dt = time.perf_counter() - t0
        self._c_decode_s.inc(dt)
        self._h_decode_step.observe(dt)
        self.profiler.record("spec_round", n, dt)
        self.cache.pools = pools
        self._c_decode_steps.inc()
        self._c_slot_steps.inc(n)
        self._c_spec_steps.inc()
        for slot, req in decoding:
            a = int(acc[slot])
            self._c_spec_drafted.inc(int(k_eff[slot]))
            self._c_spec_accepted.inc(a)
            self._h_acc_round.observe(a)
            req.spec_accepted += a
            ds = self._disp.get(req.rid)
            emitted = 0
            for j in range(a + 1):
                self._commit(req, int(out[slot, j]), events)
                emitted += 1
                if req.finish_reason is not None:
                    break  # accepted tokens past EOS are discarded
            self._c_decode_tokens.inc(emitted)
            if req.finish_reason is None:
                if ds is not None:
                    ds.generated += emitted
                self.cache.seq_lens[slot] += a + 1
                self._recycle_window(req)

    def _commit(self, req: Request, token: int, events: list[TokenEvent]) -> None:
        """Authoritative commit of one harvested token: latency marks are
        stamped HERE, at the stream boundary where the value becomes
        available — never at dispatch time."""
        now = time.perf_counter()
        t = self.tracer
        if req.t_first_token is None:
            req.t_first_token = now
            self._h_ttft.observe(now - req.t_submit)
            if t.enabled:
                t.begin(PID_REQUESTS, req.rid, "decode")
        elif req.t_last_token is not None:
            self._h_itl.observe(now - req.t_last_token)
        req.t_last_token = now
        finished = self.scheduler.commit(req, token)
        events.append(TokenEvent(
            rid=req.rid, token=token, index=req.num_generated - 1,
            finished=finished, finish_reason=req.finish_reason,
        ))
        if finished:
            slot = req.slot
            req.t_finish = now
            self.scheduler.finish(req)
            self.cache.reset_slot(slot)
            if self.drafter is not None:
                self.drafter.release_slot(slot)
            self.results[req.rid] = req
            self._slot_req.pop(slot, None)
            self._disp.pop(req.rid, None)
            if t.enabled:
                t.instant(PID_REQUESTS, req.rid, "finished",
                          finish_reason=req.finish_reason,
                          generated=req.num_generated)
                t.end(PID_REQUESTS, req.rid, "decode")
                t.end(PID_REQUESTS, req.rid, "request",
                      prefix_hit_tokens=req.cached_tokens,
                      spec_accepted=req.spec_accepted,
                      generated=req.num_generated)


# -- static-batch reference path ---------------------------------------------

class StaticStats(NamedTuple):
    prefill_s: float
    first_decode_s: float  # includes compile; excluded from tok/s
    steady_s: float
    steady_steps: int
    batch: int

    @property
    def decode_tok_s(self) -> float:
        if not self.steady_steps or not self.steady_s:
            return 0.0
        return self.batch * self.steady_steps / self.steady_s


def generate_static(model, params, batch: dict, *, max_new_tokens: int,
                    engine=None, backend: Optional[str] = None,
                    sampling: SamplingParams = GREEDY, seed: int = 0):
    """Static-batch generation on the ring-buffer cache: every sequence
    shares one position, the batch runs until ``max_new_tokens`` regardless
    of per-sequence needs. Returns (generated (B, max_new) np.ndarray,
    :class:`StaticStats`); steady-state tok/s excludes the prefill and the
    first (compiling) decode call.
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    prefill_step, decode_step = make_serve_steps(model, engine=engine, backend=backend)
    max_len = t + max_new_tokens
    prefill = jax.jit(lambda p, bt: prefill_step(p, bt, max_len))
    decode = jax.jit(decode_step)
    sample = jax.jit(sample_logits)
    key = jax.random.PRNGKey(seed)
    sp = stack_params([sampling] * b)

    def pick(logits, key):
        return sample(logits, key, **sp)[:, None].astype(jnp.int32)

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    key, sub = jax.random.split(key)
    tok = pick(logits[:, -1], sub)
    jax.block_until_ready(tok)
    prefill_s = time.perf_counter() - t0
    out = [tok]

    first_decode_s = steady_s = 0.0
    steady_steps = 0
    for i in range(max_new_tokens - 1):
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        logits, cache = decode(params, tok, cache)
        tok = pick(logits[:, 0], sub)
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0
        if i == 0:
            first_decode_s = dt
        else:
            steady_s += dt
            steady_steps += 1
        out.append(tok)
    seqs = np.asarray(jnp.concatenate(out, axis=1))
    return seqs, StaticStats(prefill_s, first_decode_s, steady_s, steady_steps, b)
