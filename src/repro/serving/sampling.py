"""Token sampling for the serving step: greedy + temperature/top-k/top-p.

One jit-friendly function over the whole decode batch: per-slot parameters
arrive as arrays so requests with different sampling settings share the one
fixed-shape step. Temperature 0 means greedy (argmax); top_k 0 and top_p 1.0
disable their filters.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = jnp.float32(-1e30)


class SamplingParams(NamedTuple):
    """Per-request sampling settings (host-side; stacked into arrays)."""

    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0  # 0 => no top-k filter
    top_p: float = 1.0  # 1.0 => no nucleus filter


GREEDY = SamplingParams()


def stack_params(params_list) -> dict[str, np.ndarray]:
    """Stack per-slot SamplingParams into the arrays sample_logits takes."""
    return {
        "temperature": np.asarray([p.temperature for p in params_list], np.float32),
        "top_k": np.asarray([p.top_k for p in params_list], np.int32),
        "top_p": np.asarray([p.top_p for p in params_list], np.float32),
    }


def filter_logits(logits, temperature, top_k, top_p):
    """The sampler's distribution transform, factored out so speculative
    rejection sampling (serving/spec) can build the *same* filtered
    target/drafter distributions the non-speculative sampler draws from.
    logits: (S, V); parameters: (S,) arrays. Returns temperature-scaled
    logits with filtered entries at NEG_INF; ``softmax`` of the result is
    the distribution ``sample_logits`` samples when temperature > 0.
    """
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k: drop everything below the k-th largest logit (ties survive).
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    # top-p: smallest prefix of the sorted distribution with mass >= top_p.
    # The first sorted column is forced to survive: `cum - p < top_p` alone
    # drops EVERY column at top_p=0.0 (the first column has cum - p == 0),
    # which masked all logits to NEG_INF and degenerated the draw to
    # uniform-random over the vocabulary.
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]
    keep = keep.at[:, 0].set(True)
    thresh = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(scaled < thresh, NEG_INF, scaled)


def sample_logits(logits, key, temperature, top_k, top_p):
    """Sample one token per row. logits: (S, V); parameters: (S,) arrays.

    Rows with temperature <= 0 take the argmax; the random draw still
    happens for every row (fixed shape) and is discarded there.
    """
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1)
        scaled = filter_logits(logits, temperature, top_k, top_p)
        sampled = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)
