"""The reference's training step for a model with a router correction bias.

As ``bench/check/train.py`` (whose readings, schedule and comparison it
uses), with DeepSeek-V3's auxiliary-loss-free balancing: the family's loss
also gives each MoE layer's assignments per expert, the bias takes no
gradient (the family reads it under ``stop_gradient``) and no weight decay,
and after each step it moves by ``gamma * sign(mean load - load)`` per
layer, the loads counted over every routed expert on the step's tokens.

The bias takes no gradient, so the four gaps of ``bench/check/train.py``
leave it out; ``bias_gap`` reads it: the share of the (MoE layer, expert)
bias entries that differ between the program and the reference after the
three steps by ``gamma / 2`` or more.  A step that never moves the bias
reads about 1; where rounding flips a routing choice, only experts whose
load lies within a few assignments of the mean can differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.check import train
from bench.check.train import leaf_norms, schedule  # noqa: F401


def reference(fam, m: dict, opt: dict, params_fn, batches, *, gamma: float,
              cast=None) -> dict:
    """Three AdamW steps of the reference with the bias update; returns the
    readings :func:`compare` takes."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                          for x in jax.tree_util.tree_leaves(tree)])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mu, nu, count, lr, tok, lab):
        (loss, load), g = jax.value_and_grad(
            lambda q: fam.loss(m, q, tok, lab, cast), has_aux=True)(p)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9)), g)
        mu = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
        bias = p["blocks"]["moe"]["bias"]
        p = jax.tree_util.tree_map(
            lambda w, a, v: w - lr * ((a / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * w),
            p, mu, nu)
        p["blocks"]["moe"]["bias"] = bias + gamma * jnp.sign(
            jnp.mean(load, -1, keepdims=True) - load)
        return p, mu, nu, loss, gn, norms(g)

    p = params_fn()
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses = []
    for i, b in enumerate(batches[:3]):
        p, mu, nu, loss, gn, gl = step(
            p, mu, nu, jnp.float32(i + 1), jnp.float32(schedule(opt, i)),
            jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i == 0:
            grad_norm, grad_leaf = float(gn), np.asarray(gl, np.float64)
    del mu, nu
    bias = np.asarray(p["blocks"]["moe"]["bias"], np.float64)
    delta = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(jnp.subtract, a, b)),
                    donate_argnums=(0,))(p, params_fn())
    return {"losses": losses, "grad_norm": grad_norm, "grad_leaf": grad_leaf,
            "update_leaf": np.asarray(delta, np.float64), "bias": bias}


def compare(prog: dict, ref: dict, gamma: float) -> dict:
    """``bench/check/train.compare``'s four gaps and ``bias_gap``."""
    out = train.compare(prog, ref)
    out["bias_gap"] = float(np.mean(np.abs(prog["bias"] - ref["bias"]) >= gamma / 2))
    return out
