"""Step factories of the serving stack: the functions its jitted programs
trace, each running the model under one resolved GEMM engine.

- :func:`make_serve_steps`: static-batch prefill/decode on the ring-buffer
  cache (``generate_static``).
- :func:`make_paged_serve_steps`: the fixed-shape StateStore steps the
  continuous-batching engine drives (``repro.serving.engine``).
- :func:`make_spec_verify_steps`: the speculative verify/commit pair
  (``repro.serving.spec``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.engine import Engine, engine_scope, resolve_engine


def make_serve_steps(model, *, engine: Engine | None = None,
                     backend: str | None = None):
    """(prefill_step, decode_step) pair for static-batch serving: every
    sequence in the batch shares one position and one ring-buffer cache."""
    eng = resolve_engine(model, engine, backend)

    def prefill_step(params, batch, max_len: int):
        cross = batch["frames"].shape[1] if "frames" in batch else 0
        cache = model.init_cache(batch["tokens"].shape[0], max_len, cross_len=cross)
        with engine_scope(eng):
            logits, cache = model.prefill(params, batch, cache, engine=eng)
        return logits, cache

    def decode_step(params, tokens, cache):
        with engine_scope(eng):
            return model.decode_step(params, tokens, cache, engine=eng)

    return prefill_step, decode_step


def make_paged_serve_steps(model, *, page_size: int,
                           engine: Engine | None = None,
                           backend: str | None = None):
    """Slot-aware (prefill_chunk, prefill_batch, decode_step) triple over
    the serving StateStore — the fixed-shape steps the continuous-batching
    scheduler drives for EVERY decoder-only family: attention layers page
    K/V, recurrent layers read/commit per-slot state rows.

    A prompt advances one fixed-width chunk per step
    (``Transformer.prefill_cb``). ``prefill_batch`` runs one chunk for
    each of P slots, inactive pad rows masked to the null page;
    ``prefill_chunk`` is its one-row case under its own program name. Each
    decode covers every slot at its own length, committing only ``active``
    rows.

    prefill_chunk(params, tokens (1, Tb), pools, page_row (Pps,),
              slot (), start (), length ()) -> (logits (1, V), pools)
    prefill_batch(params, tokens (P, Tb), pools, page_rows (P, Pps),
              slots (P,), starts (P,), lengths (P,), active (P,))
              -> (logits (P, V), pools)
    decode_step(params, tokens (S, 1), pools, page_table (S, Pps),
                seq_lens (S,), active (S,)) -> (logits (S, V), pools)
    """
    eng = resolve_engine(model, engine, backend)

    def prefill_chunk(params, tokens, pools, page_row, slot, start, length):
        with engine_scope(eng):
            return model.prefill_cb(
                params, tokens, pools, jnp.asarray(page_row)[None],
                jnp.atleast_1d(slot), jnp.atleast_1d(start),
                jnp.atleast_1d(length), page_size=page_size, engine=eng,
            )

    def prefill_batch(params, tokens, pools, page_rows, slots, starts,
                      lengths, active):
        with engine_scope(eng):
            return model.prefill_cb(
                params, tokens, pools, page_rows, slots, starts, lengths,
                active, page_size=page_size, engine=eng,
            )

    def decode_step(params, tokens, pools, page_table, seq_lens, active):
        with engine_scope(eng):
            return model.decode_cb(
                params, tokens, pools, page_table, seq_lens, active,
                page_size=page_size, engine=eng,
            )

    return prefill_chunk, prefill_batch, decode_step


def make_spec_verify_steps(model, *, page_size: int,
                           engine: Engine | None = None,
                           backend: str | None = None):
    """(verify_step, commit_step) pair for speculative decoding over the
    StateStore (``repro.serving.spec``). Both run the same slot-batched
    multi-token step (``Transformer.verify_cb`` — a prefill chunk over
    every slot, logits at every position) and differ only in whether
    recurrent state rows commit:

    ``verify_step`` leaves state rows untouched (the accepted prefix isn't
    known until rejection sampling runs); ``commit_step`` re-scans with
    ``lengths`` clamped to the accepted counts, advancing state rows exactly
    through the accepted tokens. Attention-only targets skip the commit
    pass — K/V written past the accepted boundary is never read back.
    ``commit_step`` also doubles as the drafter's batched catch-up prefill.

    verify/commit(params, tokens (S, T), pools, page_table (S, P),
                  seq_lens (S,), lengths (S,), active (S,))
        -> (logits (S, T, V), pools)
    """
    eng = resolve_engine(model, engine, backend)

    def verify_step(params, tokens, pools, page_table, seq_lens, lengths, active):
        with engine_scope(eng):
            return model.verify_cb(
                params, tokens, pools, page_table, seq_lens, lengths, active,
                page_size=page_size, commit=False, engine=eng,
            )

    def commit_step(params, tokens, pools, page_table, seq_lens, lengths, active):
        with engine_scope(eng):
            return model.verify_cb(
                params, tokens, pools, page_table, seq_lens, lengths, active,
                page_size=page_size, commit=True, engine=eng,
            )

    return verify_step, commit_step
