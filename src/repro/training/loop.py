"""Training step factories: loss, metrics, anomaly guard.

The cross-entropy is computed in sequence chunks with per-chunk
rematerialization so the (B, S, vocab) logits tensor is never materialized —
mandatory for the 256k-vocab archs at 4k sequence (67 GB/device otherwise).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.engine import Engine, engine_scope, resolve_engine
from repro.models.transformer import Transformer

AUX_LOSS_WEIGHT = 0.01
XENT_CHUNK = 512


def _shift_labels(tokens):
    """Next-token labels + mask (last position unsupervised)."""
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, -1:])], axis=1
    ).astype(jnp.float32)
    return labels, mask


def chunked_xent(model: Transformer, params, h, labels, mask, chunk=XENT_CHUNK,
                 engine: Engine | None = None):
    """sum CE over masked positions, computed chunk-by-chunk with remat."""
    b, s, d = h.shape
    c = min(chunk, s)
    n = -(-s // c)
    pad = n * c - s
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n, c).transpose(1, 0, 2)
    mc = mask.reshape(b, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(params, h_c, y_c, m_c):
        logits = model.logits(params, h_c, engine=engine)  # (B,c,V) fp32
        logz = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction instead of take_along_axis: reduces over the
        # (possibly TP-sharded) vocab dim, so under vocab-parallel sharding
        # only (B, c) partials are all-reduced — never the logits.
        onehot = jax.nn.one_hot(y_c, logits.shape[-1], dtype=logits.dtype)
        ll = jnp.sum(logits * onehot, axis=-1)
        return jnp.sum((logz - ll) * m_c)

    def body(acc, xs):
        h_c, y_c, m_c = xs
        return acc + chunk_loss(params, h_c, y_c, m_c), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, yc, mc))
    return total, jnp.sum(mask)


def make_loss_fn(model: Transformer, *, engine: Engine | None = None,
                 backend: str | None = None) -> Callable:
    """Loss factory. ``engine`` (or the ``backend`` override) selects the
    GEMM engine for every matmul in the traced step (forward *and* its
    VJP); default is the model's configured engine. The engine is passed
    explicitly through the model AND installed as the ambient scope, so
    stray shim-level calls inside custom models follow the same choice."""
    eng = resolve_engine(model, engine, backend)

    def loss_fn(params, batch):
        with engine_scope(eng):
            h, aux = model.forward(params, batch, engine=eng)
            labels, mask = _shift_labels(batch["tokens"])
            total, denom = chunked_xent(model, params, h, labels, mask, engine=eng)
        loss = total / jnp.maximum(denom, 1.0)
        return loss + AUX_LOSS_WEIGHT * aux, {"xent": loss, "aux": aux}

    return loss_fn


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    # Fault tolerance: count of steps skipped by the anomaly guard.
    skipped: jnp.ndarray


def make_train_step(model: Transformer, optimizer, *, anomaly_guard: bool = True,
                    grad_accum: int = 1, engine: Engine | None = None,
                    backend: str | None = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    anomaly_guard: skip the update (keep params) when the global grad norm is
    non-finite — a NaN/inf produced by a bad batch or a flaky worker must not
    poison the replicated state (fault-tolerance at step granularity).
    engine/backend: GEMM engine for the step; defaults to the model's
    configured engine (``backend`` alone swaps just the execution backend).
    """
    loss_fn = make_loss_fn(model, engine=engine, backend=backend)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch):
        if grad_accum > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:]),
                batch,
            )
            def body(carry, mb):
                (lv, m), g = grad_fn(state.params, mb)
                cl, cg = carry
                return (cl + lv, jax.tree.map(jnp.add, cg, g)), m
            zero_g = jax.tree.map(jnp.zeros_like, state.params)
            (loss, grads), metrics = jax.lax.scan(
                body, (jnp.zeros(()), zero_g), mbs
            )
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            metrics = jax.tree.map(lambda x: x[-1], metrics)
        else:
            (loss, metrics), grads = grad_fn(state.params, batch)

        gnorm = optimizer.global_norm(grads)
        new_params, new_opt = optimizer.update(
            state.params, grads, state.opt_state, state.step
        )
        if anomaly_guard:
            ok = jnp.isfinite(gnorm) & jnp.isfinite(loss)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, state.params
            )
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_opt, state.opt_state
            )
            skipped = state.skipped + jnp.where(ok, 0, 1).astype(jnp.int32)
        else:
            skipped = state.skipped
        new_state = TrainState(state.step + 1, new_params, new_opt, skipped)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, skipped=skipped)
        return new_state, metrics

    return train_step
