"""Expert-parallel MoE FFN with explicit all-to-all dispatch (shard_map).

Experts are sharded over ``data`` (expert parallelism), routing happens per
data shard, and only the *routed* capacity slots move — two all-to-alls
(dispatch, return).  The all-to-all needs every shard to send the same
shape, so tokens are dispatched in GShard's capacity layout: per routing
group of ``group_size`` tokens, each expert gets ``capacity(...)`` slots
and assignments past them are dropped.  This is the one place the capacity
layout lives; on one chip ``repro.models.moe`` is dropless, and the two
agree where no assignment is dropped (softmax routers only).

``moe_a2a_bytes`` is the simulator-facing twin: the per-device payload of
one dispatch (or return) all-to-all, consumed by the comm-volume hooks in
``repro.core.estimator``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import MoEConfig


def capacity(moe: MoEConfig, group: int) -> int:
    """Expert slots per routing group: ``ceil(top_k * group / E * cf)``;
    assignments past them are dropped."""
    return max(
        1, int(math.ceil(moe.top_k * group / moe.num_experts * moe.capacity_factor))
    )


def _global_group(moe: MoEConfig, n_tok: int) -> int:
    """The routing-group size the einsum path uses for n_tok global tokens."""
    group = min(moe.group_size, n_tok)
    return group if n_tok % group == 0 else n_tok


def ep_a2a_feasible(
    x_shape, moe: MoEConfig, mesh: Mesh,
    data_axis: str = "data", model_axis: str = "model",
) -> bool:
    """Whether the explicit-EP layout divides evenly on this mesh.

    Requires: experts and batch divisible by the data-axis size, expert FFN
    width divisible by the model-axis size (when present), and each shard's
    local tokens forming whole *global-size* routing groups — the per-shard
    grouping must reproduce the einsum path's global grouping exactly, or
    the two paths would assign different capacities and drop different
    tokens.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get(data_axis, 0)
    if dp < 1:
        return False
    tp = sizes.get(model_axis, 1)
    B, S, _ = x_shape
    if moe.num_experts % dp or B % dp or moe.d_ff_expert % tp:
        return False
    group = _global_group(moe, B * S)
    n_loc = (B // dp) * S
    return n_loc % group == 0


def moe_ffn_ep_a2a(
    p, x, moe: MoEConfig, compute_dtype, mesh: Mesh,
    data_axis: str = "data", model_axis: str = "model",
):
    """x: (B, S, D) sharded ``P(data)`` on batch -> (y, aux_loss, load),
    ``load`` the assignments to each expert over every shard's tokens.

    Parameter layout (the ``impl == "ep_a2a"`` axes of ``init_moe``):
    router replicated; wg/wu ``P(data, None, model)``; wd
    ``P(data, model, None)`` — experts over ``data``, FFN width over
    ``model`` (Megatron column/row split, one psum over ``model``).
    """
    if (moe.score_func, moe.experts_held, moe.routed_scaling_factor,
            moe.bias_update_rate) != ("softmax", None, 1.0, 0.0):
        raise ValueError(
            "the all-to-all layer routes by softmax over every expert, "
            "unscaled and without a correction bias"
        )
    cdt = jnp.dtype(compute_dtype)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes[data_axis]
    tp = sizes.get(model_axis, 1)
    B, S, D = x.shape
    E, k = moe.num_experts, moe.top_k
    n_loc = (B // dp) * S
    # the einsum path's GLOBAL group size — shards must tile it exactly
    # (guaranteed by ep_a2a_feasible) so capacities match across impls
    group = _global_group(moe, B * S)
    assert n_loc % group == 0, (
        f"local tokens {n_loc} not a multiple of global group {group}; "
        "gate on ep_a2a_feasible before dispatching here"
    )
    g = n_loc // group
    C = capacity(moe, group)
    e_loc = E // dp

    def body(router, wg, wu, wd, x_loc):
        bl = x_loc.shape[0]
        xg = x_loc.reshape(g, group, D)

        # -- routing + capacity: identical math to moe_ffn ------------------
        logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
        oh_e = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        oh_flat = oh_e.reshape(g, group * k, E)
        pos = jnp.cumsum(oh_flat, axis=1) - oh_flat
        pos = pos.reshape(g, group, k, E)
        pos_tok = jnp.sum(pos * oh_e, axis=-1)
        keep = pos_tok < C
        oh_c = jax.nn.one_hot(
            jnp.where(keep, pos_tok, C).astype(jnp.int32), C, dtype=jnp.float32
        )
        dispatch = jnp.einsum("gske,gskc->gsec", oh_e, oh_c).astype(cdt)
        combine = jnp.einsum(
            "gske,gskc,gsk->gsec", oh_e, oh_c, gate_vals
        ).astype(cdt)

        # -- dispatch a2a: route capacity slots to their expert's shard -----
        expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg.astype(cdt))
        expert_in = expert_in.reshape(dp, e_loc, g, C, D)
        if dp > 1:
            expert_in = jax.lax.all_to_all(
                expert_in, data_axis, split_axis=0, concat_axis=0
            )
        # dim 0 now indexes the source data shard; fold into the group dim
        expert_in = expert_in.transpose(1, 0, 2, 3, 4).reshape(e_loc, dp * g, C, D)

        # -- local expert FFN (column/row split over the model axis) --------
        gph = jnp.einsum("egcd,edf->egcf", expert_in, wg.astype(cdt))
        uph = jnp.einsum("egcd,edf->egcf", expert_in, wu.astype(cdt))
        h = jax.nn.silu(gph) * uph
        out = jnp.einsum("egcf,efd->egcd", h, wd.astype(cdt))
        if tp > 1:
            out = jax.lax.psum(out, model_axis)

        # -- return a2a: capacity slots back to their token's shard ---------
        out = out.reshape(e_loc, dp, g, C, D).transpose(1, 0, 2, 3, 4)
        if dp > 1:
            out = jax.lax.all_to_all(out, data_axis, split_axis=0, concat_axis=0)
        expert_out = out.reshape(E, g, C, D)

        y = jnp.einsum("gsec,egcd->gsd", combine, expert_out)
        y = y.reshape(bl, S, D)

        # -- aux loss: product of GLOBAL means (matches the einsum path;
        # shards hold equal token counts, so pmean of local means is exact)
        me = jax.lax.pmean(jnp.mean(probs, axis=(0, 1)), data_axis)
        ce = jax.lax.pmean(jnp.mean(oh_e[:, :, 0, :], axis=(0, 1)), data_axis)
        aux = moe.router_aux_loss * E * jnp.sum(me * ce)
        load = jax.lax.psum(jnp.sum(oh_e, axis=(0, 1, 2)), data_axis)
        return y, aux, load

    in_specs = (
        P(),                          # router (replicated)
        P(data_axis, None, model_axis),   # wg (E, D, F)
        P(data_axis, None, model_axis),   # wu
        P(data_axis, model_axis, None),   # wd (E, F, D)
        P(data_axis, None, None),         # x  (B, S, D)
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(data_axis, None, None), P(), P()),
        check_vma=False,
    )(p["router"], p["wg"], p["wu"], p["wd"], x)


# ---------------------------------------------------------------------------
# Simulator-facing byte accounting
# ---------------------------------------------------------------------------


def a2a_payload_bytes(
    num_experts: int,
    top_k: int,
    capacity_factor: float,
    group_size: int,
    tokens_local: int,
    d_model: int,
    itemsize: int = 4,
) -> float:
    """Per-device payload of ONE dispatch (or return) all-to-all.

    Each device ships its full dispatched-capacity tensor
    ``(E, groups, C, D)`` through the a2a (the ring model's ``(g-1)/g``
    wire factor is applied by ``repro.core.hardware.wire_bytes``).  Takes
    primitives rather than a MoEConfig so graph-node annotations
    (``repro.core.strategy.moe_a2a_node_meta``) can round-trip through it.
    """
    import math

    group = min(group_size, tokens_local)
    if tokens_local % group:
        group = tokens_local
    g = tokens_local // group
    cap = max(1, int(math.ceil(top_k * group / num_experts * capacity_factor)))
    return float(num_experts * g * cap * d_model * itemsize)


def moe_a2a_bytes(
    moe: MoEConfig, n_tokens_local: int, d_model: int, itemsize: int = 4
) -> float:
    """:func:`a2a_payload_bytes` for a :class:`MoEConfig`."""
    return a2a_payload_bytes(
        moe.num_experts, moe.top_k, moe.capacity_factor, moe.group_size,
        n_tokens_local, d_model, itemsize,
    )
