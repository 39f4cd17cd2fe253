"""Mixture-of-experts FFN: the routed experts this layer holds, dropless.

The router scores every one of ``num_experts`` experts (f32, full
precision) and picks ``top_k`` per token: on the scores (softmax) or, with
a correction bias, on scores plus bias (sigmoid, DeepSeek-V3 ``noaux_tc``);
the bias steers selection only, never the weights.  The layer holds the
weights of ``experts_held = (first, count)`` of them (all of them unless
the config says otherwise: under expert parallelism the others live on
other chips).  Every (token, choice) assignment to a held expert is
computed, none dropped:

* assignments are sorted by expert, held experts first, so each held
  expert's tokens are one contiguous run of rows;
* each projection is one grouped matrix product over those ragged runs
  (the Pallas ``megablox`` kernel, in 512-wide tiles: at the MoE cell's
  shapes on a TPU v5e it ran the three products, forward and backward, in
  9.2 ms against 12.3 ms for ``jax.lax.ragged_dot``); rows of absent
  experts are in no group, are not computed, and are masked on the way in
  and out, so that neither pass reads what the kernel leaves there;
* the rows are put back in (token, choice) order and summed with the
  routing weights.

Buffers are sized for the worst case (every choice of every token held),
so no shape depends on the routing and nothing is compiled per batch.  XLA
does not partition a Pallas call, so on a mesh of more than one device the
layer runs inside ``shard_map``: each device takes its own tokens and its
own share of the held experts (and of their width, where the rules split
it), and the parts are summed.  The capacity-dispatch layout survives only
where tokens cross chips by all-to-all, ``repro.dist.ep_a2a`` (``impl ==
"ep_a2a"``).

Returns ``(y, aux, load)``: ``aux`` is the balance loss (Switch for
softmax routers; DeepSeek-V3's sequence-wise loss for sigmoid routers) and
``load`` the number of assignments to each of the ``num_experts`` experts,
which the train step reads to move the correction bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import gmm
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.layers import _init_dense

HIGHEST = jax.lax.Precision.HIGHEST
# rows, contraction and output columns of one tile of the grouped product
TILE = 512
# The grouped product is compiled for the TPU.  Code that runs the layer
# where it cannot be (the CPU tests) sets this to run the kernel op by op.
INTERPRET = False


def init_moe(key, d_model: int, moe: MoEConfig, dtype):
    ks = jax.random.split(key, 4)
    E, F = moe.num_experts, moe.d_ff_expert
    n = moe.held[1]
    params = {
        "router": _init_dense(ks[0], (d_model, E), d_model, jnp.float32),
        "wg": _init_dense(ks[1], (n, d_model, F), d_model, dtype),
        "wu": _init_dense(ks[2], (n, d_model, F), d_model, dtype),
        "wd": _init_dense(ks[3], (n, F, d_model), F, dtype),
    }
    # expert weights get their own logical axes so §Perf rule overrides can
    # re-shard them without touching global "embed"/"ffn" activations
    if moe.impl == "ep_a2a":
        # explicit EP layout: experts over data, ffn width over model
        axes = {
            "router": ("embed", None),
            "wg": ("experts_ep", "expert_embed", "expert_ffn_ep"),
            "wu": ("experts_ep", "expert_embed", "expert_ffn_ep"),
            "wd": ("experts_ep", "expert_ffn_ep", "expert_embed"),
        }
    else:
        axes = {
            "router": ("embed", "experts"),
            "wg": ("experts", "expert_embed", "expert_ffn"),
            "wu": ("experts", "expert_embed", "expert_ffn"),
            "wd": ("experts", "expert_ffn", "expert_embed"),
        }
    if moe.bias_update_rate:
        params["bias"] = jnp.zeros((E,), jnp.float32)
        axes["bias"] = (None,)
    return params, axes


def route(p, x2, moe: MoEConfig):
    """x2: (T, D) -> (scores (T, E), choices (T, k), weights (T, k)), f32.

    ``scores`` are the router's softmax or sigmoid outputs; ``choices`` the
    top-k experts on the scores plus the correction bias, if any;
    ``weights`` the chosen scores, normalised to sum 1, times
    ``routed_scaling_factor``.
    """
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32), p["router"],
                        precision=HIGHEST)
    if moe.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    sel = scores
    if "bias" in p:
        sel = scores + jax.lax.stop_gradient(p["bias"])
    _, choices = jax.lax.top_k(sel, moe.top_k)
    w = jnp.take_along_axis(scores, choices, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return scores, choices, w * moe.routed_scaling_factor


def expert_load(choices, num_experts: int):
    """Assignments to each expert: (E,) f32."""
    return jnp.zeros((num_experts,), jnp.float32).at[choices.reshape(-1)].add(1.0)


def balance_loss(scores, choices, moe: MoEConfig, batch: int):
    """Switch loss for softmax routers: E * sum_i f_i P_i over the batch,
    f_i the share of tokens whose first choice is i.  DeepSeek-V3's
    sequence-wise loss for sigmoid routers: per sequence,
    f_i = E / (k T) * #(tokens choosing i), P_i = mean_t s_it / sum_j s_jt;
    sum_i f_i P_i, averaged over the sequences.  Times ``router_aux_loss``."""
    E, k = moe.num_experts, moe.top_k
    if moe.score_func != "sigmoid":
        me = jnp.mean(scores, axis=0)
        ce = jnp.mean(jax.nn.one_hot(choices[:, 0], E, dtype=jnp.float32), axis=0)
        return moe.router_aux_loss * E * jnp.sum(me * ce)
    T = scores.shape[0] // batch
    s = scores.reshape(batch, T, E)
    p_i = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    counts = jnp.sum(jax.nn.one_hot(choices.reshape(batch, T * k), E,
                                    dtype=jnp.float32), axis=1)
    f_i = counts * (E / (k * T))
    return moe.router_aux_loss * jnp.mean(jnp.sum(f_i * p_i, axis=-1))


def _grouped(lhs, w, sizes):
    """Rows ``[sum(sizes[:g]), sum(sizes[:g+1]))`` of ``lhs`` times ``w[g]``
    for the ``len(w)`` groups; ``sizes`` has one more entry, the rows after
    them, which are not computed.  ``lhs`` rows are a multiple of the row
    tile."""
    tiling = (min(TILE, lhs.shape[0]), min(TILE, w.shape[1]), min(TILE, w.shape[2]))
    return gmm(lhs, w, sizes, lhs.dtype, tiling, None, None, False, INTERPRET)


@jax.custom_vjp
def _permute(x, order, inv):
    """``x[order]`` for a permutation ``order`` whose inverse is ``inv``;
    its gradient is the gather ``g[inv]`` and not a scatter-add."""
    return x[order]


def _permute_fwd(x, order, inv):
    return x[order], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def held_experts(p, x2, choices, weights, first, compute_dtype):
    """The part of the layer for tokens ``x2`` (T, D) that the experts whose
    weights ``p`` holds give: ``len(p["wg"])`` experts, numbered from
    ``first`` (an int or a traced scalar)."""
    cdt = jnp.dtype(compute_dtype)
    T, D = x2.shape
    k = choices.shape[1]
    count = p["wg"].shape[0]
    n = T * k
    rows = n if n <= TILE else -(-n // TILE) * TILE    # whole row tiles
    local = choices.reshape(-1) - first
    held = (local >= 0) & (local < count)
    slot = jnp.where(held, local, count)            # absent experts sort last
    order = jnp.argsort(slot, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n, dtype=order.dtype))
    sizes = jnp.zeros((count + 1,), jnp.int32).at[slot].add(1)
    sizes = sizes.at[count].add(rows - n)           # padding rows: absent
    mask = jnp.pad(held[order], (0, rows - n))[:, None]
    # row j of the token-major copy is token j // k; sorted, by expert
    xs = _permute(jnp.repeat(x2.astype(cdt), k, axis=0), order, inv)
    xs = jnp.where(mask, jnp.pad(xs, ((0, rows - n), (0, 0))), 0)
    h = jax.nn.silu(_grouped(xs, p["wg"].astype(cdt), sizes)) * _grouped(
        xs, p["wu"].astype(cdt), sizes)
    out = jnp.where(mask, _grouped(h, p["wd"].astype(cdt), sizes), 0)[:n]
    out = _permute(out, inv, order).reshape(T, k, D)    # absent rows are 0
    return jnp.einsum("tkd,tk->td", out, weights.astype(cdt))


def _mesh_axes(entry) -> tuple:
    """The mesh axes of one entry of a PartitionSpec."""
    return () if entry is None else (entry,) if isinstance(entry, str) else tuple(entry)


def _held_on_mesh(p, x2, choices, weights, first, cdt, ctx):
    """``held_experts`` on a mesh of more than one device.  Tokens are split
    as the batch is, the held experts and their width as the rules place
    the weights (``init_moe``'s axes); each device computes its tokens'
    part from its experts, and the parts are summed over the axes that
    split the experts or their width."""
    def axes(logical, shape, name):
        spec = tuple(ctx.spec_for(logical, shape, name))
        return [_mesh_axes(e) for e in spec + (None,) * (len(shape) - len(spec))]

    e_ax, _, f_ax = axes(("experts", "expert_embed", "expert_ffn"), p["wg"].shape,
                         "moe.wg")
    summed = e_ax + f_ax
    t_ax = tuple(a for a in axes(("batch", None), x2.shape, "moe.tokens")[0]
                 if a not in summed)
    tok = P(t_ax or None, None)
    ew = P(e_ax or None, None, f_ax or None)

    def body(x2, choices, weights, wg, wu, wd):
        offset = jax.lax.axis_index(e_ax) * wg.shape[0] if e_ax else 0
        y = held_experts({"wg": wg, "wu": wu, "wd": wd}, x2, choices, weights,
                         first + offset, cdt)
        return y[None]

    # each device's part along a leading axis over ``summed``, added outside
    # (the Pallas call does not say how its output varies over the mesh)
    parts = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(tok, tok, tok, ew, ew, P(e_ax or None, f_ax or None, None)),
        out_specs=P(summed or None, t_ax or None, None), check_vma=False,
    )(x2, choices, weights, p["wg"], p["wu"], p["wd"])
    return jnp.sum(parts, axis=0)


def moe_ffn(p, x, moe: MoEConfig, compute_dtype):
    """x: (B, S, D) -> (y, aux, load)."""
    from repro.models.sharding import current_ctx

    B, S, D = x.shape
    ctx = current_ctx()
    if moe.impl == "ep_a2a" and ctx is not None:
        from repro.dist.ep_a2a import ep_a2a_feasible, moe_ffn_ep_a2a

        if ep_a2a_feasible(x.shape, moe, ctx.mesh):
            return moe_ffn_ep_a2a(p, x, moe, compute_dtype, ctx.mesh)
        # an EP-infeasible mesh: the dropless layer below equals it where it
        # drops nothing
    x2 = x.reshape(B * S, D)
    scores, choices, weights = route(p, x2, moe)
    first = moe.held[0]
    if ctx is not None and ctx.mesh.size > 1:
        y = _held_on_mesh(p, x2, choices, weights, first, compute_dtype, ctx)
    else:
        y = held_experts(p, x2, choices, weights, first, compute_dtype)
    aux = balance_loss(scores, choices, moe, B)
    return y.reshape(B, S, D), aux, expert_load(choices, moe.num_experts)
