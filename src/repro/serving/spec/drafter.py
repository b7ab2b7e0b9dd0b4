"""Draft-token proposers for speculative decoding.

Two drafters share one interface (``propose`` / ``release_slot`` /
``reset``):

:class:`NgramDrafter` — prompt-lookup self-drafting, the zero-extra-model
fallback: propose the continuation of the most recent earlier occurrence
of the row's current n-gram suffix. Proposals are deterministic, so the
rejection sampler treats q as onehot(d) (``DraftProposal.logits is None``).

:class:`ModelDrafter` — a second, smaller zoo model served from its OWN
``StateStore`` (its own page pool sized for its layer pattern — zero KV
pages for an attention-free drafter like xlstm — and its own state rows),
slot-paired 1:1 with the target server's slots. Per round it (1) catches
up on the committed tokens it has not consumed yet via batched
multi-token commit steps (the verify step doubling as a prefill), (2)
snapshots its pools — a free O(1) "copy" since jax arrays are immutable —
(3) runs k single-token decode steps sampling each draft from its own
filtered distribution, and (4) rolls back to the snapshot, discarding
every draft-time K/V write and state update. Rejected drafts therefore
never contaminate drafter state: the next round's catch-up replays
exactly the tokens the target actually committed.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.cache import StateStore
from repro.serving.engine import serving_params
from repro.serving.sampling import sample_logits, stack_params
from repro.training import make_paged_serve_steps, make_spec_verify_steps


def _draft_histogram(metrics):
    """Per-round draft wall-clock histogram, when a MetricsRegistry is
    wired in (duck-typed: no repro.obs import on the spec hot path)."""
    if metrics is None:
        return None
    return metrics.histogram(
        "serving_draft_seconds",
        help="Wall-clock of one drafter.propose round",
    )


class DraftProposal(NamedTuple):
    """One round of proposals for all slots (fixed shapes)."""

    tokens: np.ndarray  # (S, k) int32, right-padded
    counts: np.ndarray  # (S,) int32 proposals actually fielded per row
    logits: Optional[jnp.ndarray]  # (S, k, V) drafter logits, or None


class NgramDrafter:
    """Prompt-lookup self-drafting over each request's own token history.

    For a row whose history ends in suffix g (the longest n-gram with
    n <= ngram_n that also occurs earlier), propose the tokens that
    followed g's most recent earlier occurrence. No match at any n means
    no proposals — the row degrades to a plain one-token decode through
    the verify step.
    """

    def __init__(self, *, k: int, ngram_n: int = 3, metrics=None):
        self.k = k
        self.ngram_n = ngram_n
        self._h_draft = _draft_histogram(metrics)

    def propose(self, contexts, want, key, params_list) -> DraftProposal:
        t0 = time.perf_counter()
        n_slots = len(want)
        tokens = np.zeros((n_slots, self.k), np.int32)
        counts = np.zeros((n_slots,), np.int32)
        for slot, hist in contexts.items():
            m = int(want[slot])
            if m <= 0 or len(hist) < 2:
                continue
            cont = self._lookup(hist, m)
            counts[slot] = len(cont)
            tokens[slot, : len(cont)] = cont
        if self._h_draft is not None:
            self._h_draft.observe(time.perf_counter() - t0)
        return DraftProposal(tokens=tokens, counts=counts, logits=None)

    def _lookup(self, hist, m: int) -> list[int]:
        for n in range(min(self.ngram_n, len(hist) - 1), 0, -1):
            suffix = hist[-n:]
            # Most recent earlier occurrence: scan right to left, the match
            # must end strictly before the history's end so there is a
            # continuation to propose.
            for j in range(len(hist) - n - 1, -1, -1):
                if hist[j : j + n] == suffix:
                    cont = hist[j + n : j + n + m]
                    if cont:
                        return [int(t) for t in cont]
        return []

    def release_slot(self, slot: int) -> None:  # stateless
        pass

    def reset(self) -> None:
        pass


class ModelDrafter:
    """A small zoo model proposing drafts from its own StateStore.

    The drafter's pool is sized so a slot can hold ``max_seq_len + k``
    tokens (draft-time writes run up to k-1 past the committed boundary
    before the snapshot rollback discards them) and is never shared with
    the target's pool — the pairing is by slot index only.
    """

    def __init__(self, model, params, *, num_slots: int, page_size: int,
                 max_seq_len: int, k: int, draft_chunk: int = 16,
                 engine=None, backend: Optional[str] = None, metrics=None):
        self._h_draft = _draft_histogram(metrics)
        if not model.supports_cb():
            raise NotImplementedError(
                f"{model.cfg.name}: drafter must be a decoder-only family"
            )
        self.model = model
        self.params, self.prepared_bytes = serving_params(
            model, params, engine, backend)
        self.k = k
        # A steady-state round replays at most k+1 tokens (accepted prefix
        # + correction); never chunk below that or every round pays two
        # catch-up dispatches.
        self.chunk = max(int(draft_chunk), k + 1)
        self.profile = model.cb_profile()
        width = -(-(max_seq_len + k) // page_size)
        num_pages = (num_slots * width + 1) if self.profile.needs_kv_pages else 2
        self.store = StateStore.build(
            model, num_slots=num_slots, num_pages=num_pages,
            page_size=page_size, pages_per_slot=width,
        )
        _, commit_step = make_spec_verify_steps(
            model, page_size=page_size, engine=engine, backend=backend,
        )
        _, _, _, decode_step = make_paged_serve_steps(
            model, page_size=page_size, engine=engine, backend=backend,
        )
        self._catch_up = jax.jit(commit_step)
        self._decode = jax.jit(decode_step)
        self._sample = jax.jit(sample_logits)
        self._pages: dict[int, list[int]] = {s: [] for s in range(num_slots)}

    # -- slot lifecycle ----------------------------------------------------
    def release_slot(self, slot: int) -> None:
        """Target request left this slot: free the drafter's pages and zero
        its consumed length (state rows reset on the next tenant's start-0
        catch-up)."""
        if self._pages[slot]:
            self.store.allocator.decref(self._pages[slot])
            self._pages[slot] = []
        self.store.reset_slot(slot)

    def reset(self) -> None:
        for slot in range(self.store.num_slots):
            self.release_slot(slot)

    def _ensure_pages(self, slot: int, end_position: int) -> None:
        need = self.store.allocator.pages_for(end_position)
        pages = self._pages[slot]
        while len(pages) < need:
            (pg,) = self.store.allocator.alloc(1)
            self.store.set_page(slot, len(pages), pg)
            pages.append(pg)

    # -- proposing ---------------------------------------------------------
    def propose(self, contexts, want, key, params_list) -> DraftProposal:
        """contexts: {slot: full committed token history (prompt + emitted)};
        want: (S,) drafts requested per row; params_list: per-slot
        SamplingParams the drafts are drawn with (so q is the distribution
        the rejection sampler assumes). Returns a fixed-shape proposal."""
        t0 = time.perf_counter()
        store = self.store
        n_slots = store.num_slots
        k = self.k

        q0 = self._replay(contexts)

        # -- draft: k single-token decode steps, then roll back ------------
        snapshot = store.pools
        base = store.seq_lens.copy()
        drafting = np.zeros((n_slots,), bool)
        for slot in contexts:
            if int(want[slot]) > 0:
                drafting[slot] = True
                if self.profile.needs_kv_pages:
                    # Draft-time writes land at base .. base+k-2.
                    self._ensure_pages(slot, int(base[slot]) + k - 1)
        sp = stack_params(params_list)
        # repro: allow[RPR105] draft loop is host-synchronous; table is stable until verify commits
        page_table = jnp.asarray(store.page_table)
        active = jnp.asarray(drafting)
        tokens = np.zeros((n_slots, k), np.int32)
        logits_per_pos = [q0]
        key, sub = jax.random.split(key)
        cur = np.asarray(self._sample(q0, sub, **sp))
        tokens[:, 0] = cur
        pools = store.pools
        for i in range(1, k):
            logits, pools = self._decode(
                self.params, jnp.asarray(cur[:, None]), pools, page_table,
                jnp.asarray(base + (i - 1)), active,
            )
            logits_per_pos.append(logits)
            key, sub = jax.random.split(key)
            cur = np.asarray(self._sample(logits, sub, **sp))
            tokens[:, i] = cur
        store.pools = snapshot  # roll back every draft-time write
        counts = np.where(drafting, np.minimum(want, k), 0).astype(np.int32)
        logits_out = jnp.stack(logits_per_pos, axis=1)
        if self._h_draft is not None:
            jax.block_until_ready(logits_out)
            self._h_draft.observe(time.perf_counter() - t0)
        return DraftProposal(tokens=tokens, counts=counts, logits=logits_out)

    def _replay(self, contexts) -> jnp.ndarray:
        """Catch the drafter up on committed tokens it has not consumed yet
        (batched multi-token commit steps), returning each row's logits at
        its final position — the distribution the first draft samples from.
        """
        store = self.store
        n_slots = store.num_slots
        chunk = self.chunk
        targets = {slot: len(hist) for slot, hist in contexts.items()}
        q0 = jnp.zeros((n_slots, self.model.cfg.vocab_size), jnp.float32)
        while True:
            toks = np.zeros((n_slots, chunk), np.int32)
            lengths = np.zeros((n_slots,), np.int32)
            act = np.zeros((n_slots,), bool)
            done_rows = np.zeros((n_slots,), bool)
            for slot, hist in contexts.items():
                have = int(store.seq_lens[slot])
                todo = targets[slot] - have
                if todo <= 0:
                    continue
                m = min(todo, chunk)
                toks[slot, :m] = hist[have : have + m]
                lengths[slot] = m
                act[slot] = True
                done_rows[slot] = m == todo
                if self.profile.needs_kv_pages:
                    self._ensure_pages(slot, have + m)
            if not act.any():
                break
            logits, pools = self._catch_up(
                self.params, jnp.asarray(toks), store.pools,
                # repro: allow[RPR105] catch-up loop is host-synchronous; mirrors stable until it returns
                jnp.asarray(store.page_table), jnp.asarray(store.seq_lens),
                jnp.asarray(lengths), jnp.asarray(act),
            )
            store.pools = pools
            # Rows finishing their replay this iteration: keep the logits at
            # their last valid position (the next token's distribution).
            last = jnp.take_along_axis(
                logits,
                jnp.asarray(np.maximum(lengths - 1, 0))[:, None, None],
                axis=1,
            )[:, 0].astype(jnp.float32)
            q0 = jnp.where(jnp.asarray(done_rows)[:, None], last, q0)
            store.seq_lens += lengths
        return q0
