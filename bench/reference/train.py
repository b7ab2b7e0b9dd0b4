"""Plain float32 reference of the first training steps of a dense decoder:
next-token cross-entropy over each row but its last position, its gradient
through every layer, and AdamW as the configuration states it (global-norm
clipping, bias-corrected moments, decoupled weight decay, a constant
learning rate). Parameters and moments stay in float32; the gradient is
taken on the chip, the optimizer's arithmetic runs on the host's CPU
device, so weights, gradients and both moments never share the chip.

``quant="int4"`` is the control: every matrix-product operand, forward and
backward, is rounded to symmetric int4 (see ``decoder.int4``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import decoder


def params(dims: decoder.Dims, key) -> dict:
    """Every trained leaf, float32, named as the checks name them."""
    out = {"embed": decoder.embed_table(dims, key),
           "final_norm": jnp.ones((dims.d_model,), jnp.float32)}
    for i in range(dims.layers):
        for name, w in decoder.layer_weights(dims, key, i).items():
            out[f"layer{i}/{name}"] = w
    return out


def loss(dims: decoder.Dims, p: dict, tokens, quant=None, keep: float = 1.0):
    """Mean next-token cross-entropy over every position but each row's
    last. ``keep < 1`` plants a fault for the readings of a limit: the mean
    is taken over the first ``keep`` of each row's positions only."""
    def layer(w, x):
        x = decoder.attn_block(dims, w, x, quant)
        h = decoder.rms_norm(x, w["norm2"], dims.eps)
        return x + decoder.swiglu(h, w["up"], w["gate"], w["down"], quant)

    x = p["embed"][tokens]
    for i in range(dims.layers):
        w = {k.split("/", 1)[1]: v for k, v in p.items() if k.startswith(f"layer{i}/")}
        x = jax.checkpoint(layer)(w, x)
    h = decoder.rms_norm(x, p["final_norm"], dims.eps)
    logits = decoder.mm(h, p["embed"].T, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    mask = mask.at[:, int(tokens.shape[1] * keep):].set(0.0)
    return jnp.sum((logz - ll) * mask) / jnp.sum(mask)


def norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def _adamw(opt: dict):
    """One AdamW step over the host-side state; returns it with the leaf
    norms of the clipped gradient the step used."""
    b1, b2, eps, wd, lr, clip = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                                 opt["lr"], opt["clip_norm"])

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, step):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        t = step + 1.0
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
                                          + wd * p_), p, m, v)
        return p, m, v, norms(g)

    return update


def steps(dims: decoder.Dims, key, batches, opt: dict, quant=None, keep: float = 1.0) -> dict:
    """Follow ``len(batches)`` steps from the seed's weights. Returns each
    step's loss, the leaf norms of the first (clipped) gradient, and the
    leaf norms of the parameters' change over all the steps."""
    cpu = jax.devices("cpu")[0]
    grad = jax.jit(jax.value_and_grad(functools.partial(loss, dims, quant=quant, keep=keep), argnums=0))
    p_dev = params(dims, key)
    p0 = jax.device_put(p_dev, cpu)
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    p, m, v = copy(p0), zeros(p0), zeros(p0)
    update = _adamw(opt)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        value, g = grad(p_dev, jnp.asarray(tokens))
        losses.append(float(value))
        g = jax.device_put(g, cpu)
        del p_dev
        p, m, v, g_norms = update(p, g, m, v, np.float32(i))
        del g
        if first is None:
            first = {k: float(x) for k, x in g_norms.items()}
        p_dev = jax.device_put(p, jax.devices()[0])
    del p_dev, m, v
    change = jax.jit(lambda a, b: norms(jax.tree.map(jnp.subtract, a, b)))(p, p0)
    return {"losses": losses, "grad_norms": first,
            "change_norms": {k: float(x) for k, x in change.items()}}
