"""Serving cells: the program's ``Server`` under an open loop or a backlog.

Set-up builds the model from the configuration file, draws its weights on
the device in one jitted ``model.init`` from the seed, builds the ``Server``,
warms the one prefill-chunk shape and the decode step, and serves the
arrivals of the mix's lead, so that the window opens on a server at its
steady load. Lead and window alike submit each request when it is due and
call ``Server.step()``, stamping every token as it leaves ``step()``.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

import loadgen
from common import BenchError, log
from program import model_config

TRACE_S = 8.0  # a traced run traces the last seconds of its window
SAMPLE_TOKENS = 400  # served tokens the reference checks, at least
SAMPLE_ROWS = 8  # requests the reference checks, at most
MIN_FINISHED = 2  # the window's close waits for this many finished requests
DRAIN_S = 120.0  # ... but no longer than this past the close


class Recorder:
    """Host spans around the calls into the server's layers, and what each
    device step carried, recorded from the benchmark's side: the engine's
    dispatch and harvest methods are wrapped on the instance."""

    def __init__(self, server):
        import jax

        self.steps = []  # (kind, host time, work args)
        ann = jax.profiler.TraceAnnotation
        eng = server.engine
        dispatch_decode, dispatch_prefill = eng.dispatch_decode, eng.dispatch_prefill
        harvest_one = eng.harvest_one

        def decode(*, active, **kw):
            ctx = (server.cache.seq_lens[active] + 1).tolist()
            self.steps.append(("decode", time.perf_counter(), ctx))
            with ann("dispatch_decode"):
                return dispatch_decode(active=active, **kw)

        def prefill(*, start, n, **kw):
            self.steps.append(("prefill", time.perf_counter(), (int(start), int(n))))
            with ann("dispatch_prefill"):
                return dispatch_prefill(start=start, n=n, **kw)

        def harvest():
            with ann("harvest"):
                return harvest_one()

        eng.dispatch_decode, eng.dispatch_prefill, eng.harvest_one = decode, prefill, harvest


HOST_SPANS = ("Server.step", "submit", "dispatch_decode", "dispatch_prefill",
              "harvest", "wait for arrivals")


class ServeCell:
    host_spans = HOST_SPANS

    def __init__(self, cell: dict, seed: int, devices, log_compiles):
        self.conf, self.mix, self.seed = cell["config"], cell["traffic"], seed
        self.devices = devices
        self.compiles = log_compiles

    # -- set-up -------------------------------------------------------------
    def setup(self, seconds: float) -> None:
        import jax

        from repro.models import build
        from repro.serving import Server, ServerConfig

        cfg = model_config(self.conf)
        self.model = build(cfg)
        t = time.perf_counter()
        self.params = jax.block_until_ready(
            jax.jit(self.model.init)(jax.random.PRNGKey(self.seed)))
        log(f"set-up: weights drawn on the device in {time.perf_counter() - t:.1f} s")
        self.server = Server(self.model, self.params,
                             ServerConfig(**self.conf["program"]["server"]), seed=self.seed)
        t, c = time.perf_counter(), self.compiles.seconds
        # One prompt of one chunk compiles the chunk step, sampling and the
        # decode step: every shape the window uses.
        self.server.warmup([self.server.config.prefill_chunk])
        log(f"set-up: warm-up {time.perf_counter() - t:.1f} s, "
            f"{self.compiles.seconds - c:.1f} s of it backend compile")
        self.recorder = Recorder(self.server)
        self.start(self.mix, seconds)

    def start(self, mix: dict, seconds: float) -> None:
        """Draw the run's requests and serve those due before the window
        opens: the mix's ``lead_s`` brings the server to its steady load,
        and the window opens at ``self.t0``."""
        self.requests = loadgen.requests(mix, self.seed, seconds, self.conf["vocab_size"])
        self.t0 = time.perf_counter() + mix.get("lead_s", 0.0)
        self.next, self.submitted = 0, []
        self.tok_times = collections.defaultdict(list)
        self.recorder.steps.clear()
        t = time.perf_counter()
        self._serve(self.t0)
        if mix.get("lead_s"):
            log(f"set-up: {time.perf_counter() - t:.1f} s of arrivals before the window, "
                f"{self.next} requests submitted, {len(self.server.results)} finished, "
                f"{len(self.server.scheduler.running)} running")

    def _serve(self, end: float, trace_at: float | None = None, trace_dir: str | None = None):
        """Submit each request when it is due and step the server until
        ``end``, stamping every token as it leaves ``step()``. With
        ``trace_at``, the profiler traces from then to ``end``; returns the
        traced span's (start, close) against the window's opening."""
        import jax

        from reduce import WINDOW

        ann = jax.profiler.TraceAnnotation
        server, reqs, t0 = self.server, self.requests, self.t0
        traced = trace_t = None
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if trace_at is not None and traced is None and now >= trace_at:
                jax.profiler.start_trace(trace_dir)
                traced = ann(WINDOW)
                traced.__enter__()
                trace_t = time.perf_counter() - t0
            while self.next < len(reqs) and t0 + reqs[self.next].due_s <= now:
                q = reqs[self.next]
                with ann("submit"):
                    r = server.submit(q.prompt, max_new_tokens=q.max_new_tokens)
                self.submitted.append((r, q, time.perf_counter() - t0))
                self.next += 1
            if server.scheduler.has_work():
                with ann("Server.step"):
                    events = server.step()
                t = time.perf_counter() - t0
                for ev in events:
                    self.tok_times[ev.rid].append(t)
            else:
                nxt = end
                if self.next < len(reqs):
                    nxt = min(nxt, t0 + reqs[self.next].due_s)
                if trace_at is not None and traced is None:
                    nxt = min(nxt, trace_at)
                with ann("wait for arrivals"):
                    time.sleep(max(0.0, nxt - time.perf_counter()))
        if traced is not None:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            return trace_t, time.perf_counter() - t0
        return None

    def _finished_since_open(self) -> list[tuple[list, list]]:
        """(prompt, served tokens) of the requests that finished after the
        window opened: in it, or in the drain after it."""
        done = self.server.results
        return [(list(r.prompt), list(r.out_tokens)) for r, _, _ in self.submitted
                if r.rid in done and self.tok_times[r.rid][-1] >= 0]

    # -- the window ---------------------------------------------------------
    def window(self, seconds: float, trace_dir: str | None) -> dict:
        server, t0 = self.server, self.t0
        end = t0 + seconds
        trace_at = end - min(TRACE_S, seconds) if trace_dir else None
        trace_t = self._serve(end, trace_at, trace_dir)
        close = time.perf_counter() - t0
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        counters = server.metrics.snapshot()["counters"]
        t_drain = time.perf_counter()
        while (len(self._finished_since_open()) < MIN_FINISHED and server.scheduler.has_work()
               and time.perf_counter() - t_drain < DRAIN_S):
            for ev in server.step():
                self.tok_times[ev.rid].append(time.perf_counter() - t0)
        reqs = self.requests
        unsent = [q.due_s for q in reqs[self.next:] if 0 <= q.due_s < close]
        requests = []
        for r, q, t_sub in self.submitted:
            times = [t for t in self.tok_times[r.rid] if 0 <= t <= close]
            requests.append({
                "due": q.due_s, "submit": t_sub,
                "admit": (r.t_admit - t0) if r.t_admit else None,
                "tokens": times, "prompt_len": r.prompt_len,
                "max_new_tokens": r.max_new_tokens,
                "finished": len(self.tok_times[r.rid]) == r.max_new_tokens
                and 0 <= self.tok_times[r.rid][-1] <= close,
            })
        self.finished = self._finished_since_open()
        gaps = [b - a for r in requests for a, b in zip(r["tokens"], r["tokens"][1:])]
        late = [r["submit"] - r["due"] for r in requests]
        in_window = [r for r in requests if r["due"] >= 0]
        log(f"window: token gaps p50/p90/p95/p99 "
            f"{'/'.join(f'{np.percentile(gaps or [0], q):.4f}' for q in (50, 90, 95, 99))} s "
            f"over {len(gaps)} gaps of "
            f"{sum(bool(r['tokens']) for r in requests)} requests; generator late by "
            f"{max(late):.4f} s at most")
        log(f"window: {len(in_window)} requests due and submitted in it, {len(unsent)} due "
            f"and not submitted; {sum(r['finished'] for r in requests)} finished in it, "
            f"{len(self.finished)} with {time.perf_counter() - t_drain:.1f} s of drain")
        return {
            "kind": "serve",
            "window_s": close,
            "requests": requests,
            "unsent_due": unsent,
            "counters": counters,
            "steps": [(k, t - t0, a) for k, t, a in self.recorder.steps if t0 <= t <= t0 + close],
            "trace_window": trace_t,
            "memory_peak_bytes": peak,
            "attempted": len(in_window) + len(unsent),
            "failed": 0,
            "num_slots": server.config.num_slots,
        }

    def free(self) -> None:
        """Drop every device array the program made (its peak is read)."""
        import jax

        del self.server, self.params, self.model, self.recorder
        gc.collect()
        for a in jax.live_arrays():
            a.delete()

    # -- what the reference checks ------------------------------------------
    def readings(self) -> dict:
        import check

        sample = self.sample()
        gaps = check.serve_gaps(self.conf, self.seed, sample)
        log(f"check: {len(gaps)} served tokens of {len(sample)} requests compared")
        return {"logit_gap": float(gaps.max()), "logit_gap_mean": float(gaps.mean())}

    def sample(self) -> list[tuple[list, list]]:
        """Finished requests drawn from the seed, the longest among them,
        until the sample holds ``SAMPLE_TOKENS`` served tokens."""
        done = sorted(self.finished, key=lambda pt: (-len(pt[1]), -len(pt[0])))
        if not done:
            raise BenchError("no request finished: nothing to compare")
        rest = done[1:]
        order = np.random.default_rng(np.random.SeedSequence([self.seed, 7])).permutation(len(rest))
        out = [done[0]]
        for j in order:
            if len(out) >= SAMPLE_ROWS or sum(len(s) for _, s in out) >= SAMPLE_TOKENS:
                break
            out.append(rest[j])
        return out
