"""Target-side verification for speculative decoding.

:class:`Verifier` owns the jitted fixed-shape verify/commit steps
(``Transformer.verify_cb`` via ``make_spec_verify_steps``) and the jitted
rejection sampler, so the server's speculative loop stays a thin host
orchestration:

  1. ``verify`` runs all slots' [last token | drafts] rows through the
     target in ONE batched prefill-chunk-style step, returning logits at
     every position. Recurrent state rows do NOT commit here — the
     accepted prefix is unknown until the sampler runs. K/V for every
     fielded position is written through the page table; positions past
     what the host later commits are dead writes (never read back), which
     is the whole KV-rollback story.
  2. ``sample`` applies exact rejection sampling (see ``rejection.py``).
  3. For targets with recurrent state rows, ``commit_state`` re-runs the
     same step with lengths clamped to accepted+1, scanning state rows
     forward through exactly the accepted tokens (and rewriting the same
     accepted K/V bit-identically). Attention-only targets skip it.
"""
from __future__ import annotations

import time
from typing import Optional

import jax

from repro.serving.spec.rejection import speculative_sample
from repro.serving.steps import make_spec_verify_steps


class Verifier:
    def __init__(self, model, *, page_size: int, engine=None,
                 backend: Optional[str] = None, metrics=None):
        # Duck-typed MetricsRegistry (no repro.obs import on the hot path).
        self._h_verify = None if metrics is None else metrics.histogram(
            "serving_verify_seconds",
            help="Wall-clock of one fixed-shape verify step",
        )
        verify_step, commit_step = make_spec_verify_steps(
            model, page_size=page_size, engine=engine, backend=backend,
        )
        self._verify = jax.jit(verify_step)
        self._commit = jax.jit(commit_step)
        # Only targets holding recurrent state rows need the commit pass.
        self.needs_state_commit = model.cb_profile().has_state_rows
        self._sample_onehot = jax.jit(
            lambda tl, dt, key, t, k, p, lens, act: speculative_sample(
                tl, dt, key, t, k, p, lens, act, draft_logits=None,
            )
        )
        self._sample_model = jax.jit(
            lambda tl, dt, dl, key, t, k, p, lens, act: speculative_sample(
                tl, dt, key, t, k, p, lens, act, draft_logits=dl,
            )
        )

    def verify(self, params, tokens, pools, page_table, seq_lens, lengths,
               active):
        """One fixed-shape verify step; returns (logits (S, T, V), pools)."""
        t0 = time.perf_counter()
        logits, pools = self._verify(
            params, tokens, pools, page_table, seq_lens, lengths, active,
        )
        if self._h_verify is not None:
            # The sync costs nothing real: the caller's rejection sample
            # consumes these logits on the host within the same round.
            jax.block_until_ready(logits)
            self._h_verify.observe(time.perf_counter() - t0)
        return logits, pools

    def sample(self, target_logits, draft_tokens, draft_logits, key,
               sampling, lengths, active):
        """Rejection-sample one round. ``sampling`` is the dict from
        ``stack_params``; ``draft_logits`` None means onehot-q proposals.
        Returns (out_tokens (S, T), n_accepted (S,))."""
        args = (
            key, sampling["temperature"], sampling["top_k"],
            sampling["top_p"], lengths, active,
        )
        if draft_logits is None:
            return self._sample_onehot(target_logits, draft_tokens, *args)
        return self._sample_model(
            target_logits, draft_tokens, draft_logits, *args,
        )

    def commit_state(self, params, tokens, pools, page_table, seq_lens,
                     lengths, active):
        """Advance recurrent state rows through the tokens actually consumed
        this round: ``lengths = accepted + 1`` per active row (the verify
        row's token i — t_last then the drafts — is an *input* at position
        seq_lens + i; the round's final emitted token is fed next round).
        Returns the committed pools."""
        _, pools = self._commit(
            params, tokens, pools, page_table, seq_lens, lengths, active,
        )
        return pools
