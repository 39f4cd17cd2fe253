"""Does the timed training step compute what the plain reference does?

The program's first three steps (run in set-up, through the window's own
compiled step and feed) are compared with three steps of the reference
from the same weights and batches:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_norm_gap``: relative gap of the first step's global gradient norm
  before clipping;
* ``grad_leaf_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer gets it (after clipping), which the program's
  Adam state gives after one step (first moment / (1 - beta1));
* ``update_gap``: the worst leaf's gap between the norms of the parameters'
  change over the three steps.

A leaf's gap is the gap of the two norms over the reference's norm of that
leaf or of the median leaf, whichever is larger.  Leaves whose reference
gradient is under a thousandth of the median leaf's are left out of the
two leaf gaps: Adam moves them by round-off alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXCLUDE_BELOW = 1e-3


def leaf_norms(tree) -> np.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return np.asarray(jax.jit(
        lambda ls: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                              for x in ls])
    )(leaves), np.float64)


def schedule(opt: dict, step: int) -> float:
    """Cosine learning rate with linear warm-up, the job's stated schedule."""
    warm = min(step / max(opt["warmup"], 1), 1.0)
    prog = min(max((step - opt["warmup"]) / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_ratio"] + (1 - opt["min_ratio"]) * cos)


def reference(fam, m: dict, opt: dict, params_fn, batches, *, cast=None,
              rows: int | None = None) -> dict:
    """Three AdamW steps of the reference; returns its readings.

    ``params_fn()`` gives the initial weights (drawn again for the change
    over three steps, so that no second copy stays alive); ``rows`` keeps
    only the first rows of each batch (a planted fault); ``cast`` lowers the
    precision of every matrix product (the control).
    """
    def lossf(p, tok, lab):
        if rows is not None:
            tok, lab = tok[:rows], lab[:rows]
        return fam.loss(m, p, tok, lab, cast)

    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                          for x in jax.tree_util.tree_leaves(tree)])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mu, nu, count, lr, tok, lab):
        loss, g = jax.value_and_grad(lossf)(p, tok, lab)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9)), g)
        mu = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
        p = jax.tree_util.tree_map(
            lambda w, a, v: w - lr * ((a / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * w),
            p, mu, nu)
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
    delta = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(jnp.subtract, a, b)),
                    donate_argnums=(0,))(p, params_fn())
    return {"losses": losses, "grad_norm": grad_norm, "grad_leaf": grad_leaf,
            "update_leaf": np.asarray(delta, np.float64)}


def _leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    floor = np.maximum(ref, np.median(ref))
    gaps = np.abs(prog - ref) / floor
    return float(np.max(gaps[keep]))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, each a gap of the program from the reference."""
    keep = ref["grad_leaf"] >= EXCLUDE_BELOW * np.median(ref["grad_leaf"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    return {
        "loss_gap": float(loss_gap),
        "grad_norm_gap": abs(prog["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
        "grad_leaf_gap": _leaf_gap(prog["grad_leaf"], ref["grad_leaf"], keep),
        "update_gap": _leaf_gap(prog["update_leaf"], ref["update_leaf"], keep),
    }
