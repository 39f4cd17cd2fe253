"""Partitioning real models onto the pipeline-schedule executor.

This is the bridge the ROADMAP called for: the ``models/`` transformer /
MoE block stack — until now GSPMD-partitioned only — split into per-stage
pieces and driven through ``repro.dist.pp``'s scheduled executor, so the
*actual* ``apply_block`` math (attention, MoE dispatch, remat policy) runs
under GPipe / 1F1B / interleaved-1F1B step tables with an explicit
scheduled backward.

A :class:`PipelinePlan` names the partition: ``pp`` stage devices times
``vstages`` model chunks per device, each chunk a contiguous run of
``num_layers / (pp * vstages)`` decoder blocks; the token embedding rides
with the first virtual stage (``first_fn``) and the final norm + lm head +
cross-entropy with the last (``loss_fn``), so every parameter's gradient —
embedding and head included — comes out of the scheduled backward.  MoE
router auxiliary losses are emitted per block and cotangent-seeded locally
(see ``repro.dist.pp.make_scheduled_body``).

Loss convention: one pipeline step prices/trains the *mean* over its
``microbatches`` of the model's per-microbatch loss — exactly what
``repro.train.step.make_train_step(grad_accum=M)`` computes for the same
batch split, which makes ``jax.grad`` of :func:`microbatched_reference`
the GSPMD reference the executor must match (tests/test_model_pipeline.py).

The simulator prices the same partition through
``repro.core.strategy.model_pipeline_graph``: boundary hops carry the real
activation payload (:func:`PipelinePlan.hop_bytes` — the executor's
ppermute twin), per-stage gradient all-reduces the exact per-leaf element
counts of :func:`stage_param_trees`, and MoE stages the dispatch
all-to-all payload of ``repro.dist.ep_a2a``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import pp
from repro.dist.schedules import PipelineSchedule, make_schedule
from repro.models import layers as L
from repro.models import transformer

# model families whose block stack is a homogeneous transformer scan the
# executor can chunk (vlm is excluded: the patch projector makes the first
# stage's input heterogeneous; hybrid/ssm mixers are a follow-up)
_PIPELINE_FAMILIES = ("dense", "moe")


@dataclass(frozen=True)
class PipelinePlan:
    """One executable+simulable pipeline partition of an ArchConfig."""

    cfg: ArchConfig
    pp: int
    microbatches: int
    schedule: str = "1f1b"
    vstages: int = 1

    @property
    def n_vstages(self) -> int:
        return self.pp * self.vstages

    @property
    def layers_per_vstage(self) -> int:
        return self.cfg.num_layers // self.n_vstages

    def make_schedule(self) -> PipelineSchedule:
        return make_schedule(
            self.schedule, self.pp, self.microbatches, self.vstages
        )

    def strategy(self, dp: int = 1, compression: str = "none"):
        """The simulator Strategy this plan executes."""
        from repro.core.strategy import Strategy

        return Strategy(
            dp=dp, pp=self.pp, microbatches=self.microbatches,
            schedule=self.schedule, vstages=self.vstages,
            compression=compression,
        )

    def act_shape(self, micro_batch: int, seq: int) -> tuple[int, int, int]:
        """Shape of the activation one boundary hop ships (one microbatch)."""
        return (micro_batch, seq, self.cfg.d_model)

    def hop_bytes(self, micro_batch: int, seq: int) -> float:
        """Per-hop wire payload — the executor's ppermute byte twin."""
        return pp.boundary_bytes(
            self.act_shape(micro_batch, seq), jnp.dtype(self.cfg.compute_dtype)
        )

    def boundary_bytes_per_step(self, micro_batch: int, seq: int) -> float:
        """Total scheduled boundary traffic of one pipeline step."""
        return self.make_schedule().comm_bytes(
            self.hop_bytes(micro_batch, seq)
        )

    def describe(self) -> str:
        sched = self.schedule + (
            f"v{self.vstages}" if self.vstages > 1 else ""
        )
        return (
            f"{self.cfg.name}:pp{self.pp}xmb{self.microbatches}({sched})"
            f" {self.layers_per_vstage}L/vstage"
        )


def check_pipelineable(
    cfg: ArchConfig, pp_stages: int, vstages: int = 1
) -> None:
    """Raise ValueError when this config cannot realize the partition."""
    if cfg.family not in _PIPELINE_FAMILIES:
        raise ValueError(
            f"pipeline partitioning supports families {_PIPELINE_FAMILIES}; "
            f"{cfg.name} is family={cfg.family!r}"
        )
    if cfg.num_patches:
        raise ValueError(
            f"{cfg.name}: vlm patch projector not pipeline-partitionable"
        )
    if cfg.first_k_dense or (cfg.moe is not None and cfg.moe.bias_update_rate):
        raise ValueError(
            f"{cfg.name}: the pipeline partitions one homogeneous block stack "
            f"and carries no router correction bias"
        )
    V = pp_stages * vstages
    if V < 1 or cfg.num_layers % V != 0:
        raise ValueError(
            f"{cfg.name}: num_layers {cfg.num_layers} not divisible by "
            f"pp*vstages = {pp_stages}*{vstages} = {V}"
        )


def make_plan(
    cfg: ArchConfig,
    pp_stages: int,
    microbatches: int,
    schedule: str = "1f1b",
    vstages: int = 1,
) -> PipelinePlan:
    """Validated plan: partitionable config AND realizable schedule."""
    check_pipelineable(cfg, pp_stages, vstages)
    plan = PipelinePlan(
        cfg=cfg, pp=pp_stages, microbatches=microbatches,
        schedule=schedule, vstages=vstages,
    )
    plan.make_schedule().validate()
    return plan


# ---------------------------------------------------------------------------
# Parameter partition: model layout <-> (first, blocks, last)
# ---------------------------------------------------------------------------


def partition_params(cfg: ArchConfig, params):
    """Split a transformer param tree into the executor's three stages.

    ``first`` (embedding) feeds the first virtual stage, ``blocks`` is the
    layer-major stacked stack the schedule chunks, ``last`` (final norm +
    head) closes the last virtual stage.  With tied embeddings the embed
    table appears in BOTH first and last — :func:`merge_grads` sums the two
    gradient contributions, exactly what autodiff does for the shared leaf.
    """
    first = {"embed": params["embed"]}
    last = {"final_norm": params["final_norm"]}
    if "head" in params:
        last["head"] = params["head"]
    elif cfg.tie_embeddings:
        last["embed"] = params["embed"]
    return first, params["blocks"], last


def merge_grads(cfg: ArchConfig, gfirst, gblocks, glast):
    """Inverse of :func:`partition_params` for gradient trees."""
    g_embed = gfirst["embed"]
    if "embed" in glast:
        g_embed = jax.tree_util.tree_map(jnp.add, g_embed, glast["embed"])
    out = {
        "embed": g_embed,
        "blocks": gblocks,
        "final_norm": glast["final_norm"],
    }
    if "head" in glast:
        out["head"] = glast["head"]
    return out


def split_microbatches(batch: dict, microbatches: int) -> dict:
    """(B, ...) leaves -> (M, B/M, ...), same split order as
    ``repro.train.step._split_microbatches`` (consecutive-row blocks)."""

    def split(x):
        b = x.shape[0]
        assert b % microbatches == 0, (
            f"batch {b} % microbatches {microbatches} != 0"
        )
        return x.reshape((microbatches, b // microbatches) + x.shape[1:])

    return {k: split(v) for k, v in batch.items()}


# ---------------------------------------------------------------------------
# Stage callables: the real block math under the schedule
# ---------------------------------------------------------------------------


def stage_fns(cfg: ArchConfig, microbatches: int):
    """(first_fn, layer_fn, loss_fn) for ``repro.dist.pp``'s staged executor.

    * ``first_fn(first_params, xs_m)``: token embedding -> (B, S, D).
    * ``layer_fn(block_params, h) -> (h, aux/M)``: ONE decoder block via
      ``transformer.apply_block`` (attention + dense-or-MoE FFN), wrapped
      in the config's remat policy; the MoE router balance aux is scaled by
      1/M so summed step aux equals the microbatch-mean of the model's.
    * ``loss_fn(last_params, y, loss_m)``: final norm + lm head +
      ``chunked_xent`` on the microbatch labels, scaled by 1/M.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    inv_m = 1.0 / float(microbatches)

    def first_fn(first_p, xs_m):
        return L.embed(first_p["embed"], xs_m["tokens"], cdt)

    def block_fn(block_p, h):
        b, s = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s)
        )
        y, aux, _ = transformer.apply_block(
            block_p, h, cfg, positions=positions
        )
        return y, jnp.asarray(aux, jnp.float32) * inv_m

    if cfg.remat_policy == "dots":
        layer_fn = jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif cfg.remat_policy != "none":
        layer_fn = jax.checkpoint(block_fn)
    else:
        layer_fn = block_fn

    def loss_fn(last_p, y, loss_m):
        h = L.rmsnorm(y, last_p["final_norm"], cfg.norm_eps, cdt)
        if "head" in last_p:
            w, transpose = last_p["head"], False
        else:
            w, transpose = last_p["embed"], True
        ce = L.chunked_xent(
            h, w, loss_m["labels"], transpose=transpose,
            chunk=cfg.loss_chunk, mask=loss_m.get("loss_mask"),
        )
        return ce * inv_m

    return first_fn, layer_fn, loss_fn


def pipeline_loss_and_grads(
    plan: PipelinePlan, params, batch: dict, mesh, axis_name: str = "stage"
):
    """Run one real-model pipeline step: scheduled forward AND backward.

    Returns ``(loss, metrics, grads)`` with ``loss = ce + aux`` (the mean
    over the plan's microbatches), ``metrics = {"ce", "aux"}``, and
    ``grads`` in the model's natural param layout (embedding/head
    included).  The whole-batch math equals ``jax.grad`` of
    :func:`microbatched_reference` — only the execution order (and device
    placement) changes.
    """
    cfg, M = plan.cfg, plan.microbatches
    micro = split_microbatches(batch, M)
    xs = {"tokens": micro["tokens"]}
    loss_inputs = {k: v for k, v in micro.items() if k != "tokens"}
    first, blocks, last = partition_params(cfg, params)
    first_fn, layer_fn, loss_fn = stage_fns(cfg, M)
    ce, aux, _outs, (gf, gb, gl) = pp.pipeline_stage_shard_map(
        first, blocks, last, xs, loss_inputs, layer_fn,
        mesh, plan.make_schedule(),
        first_fn=first_fn, loss_fn=loss_fn, axis_name=axis_name,
    )
    grads = merge_grads(cfg, gf, gb, gl)
    return ce + aux, {"ce": ce, "aux": aux}, grads


def microbatched_reference(model, microbatches: int):
    """The GSPMD reference loss the pipeline executor must reproduce:
    the mean over microbatches of ``model.loss`` — the same math
    ``make_train_step(grad_accum=microbatches)`` accumulates."""

    def ref_loss(params, batch):
        micro = split_microbatches(batch, microbatches)
        total = 0.0
        for m in range(microbatches):
            mb = jax.tree_util.tree_map(lambda a, m=m: a[m], micro)
            lval, _metrics = model.loss(params, mb)
            total = total + lval
        return total / microbatches

    return ref_loss


# ---------------------------------------------------------------------------
# Simulator-facing partition accounting
# ---------------------------------------------------------------------------


def stage_param_trees(
    plan: PipelinePlan, params
) -> list[dict]:
    """Per-stage parameter pytrees (ShapeDtypeStructs) of the partition.

    Stage ``s`` owns its ``vstages`` chunks of every block leaf, plus the
    embedding (stage 0) and the final norm/head (stage S-1; with tied
    embeddings the shared table is carried by both gradient paths, see
    :func:`partition_params`).  Feeds the per-stage gradient all-reduce
    annotations of ``repro.core.strategy.model_pipeline_graph`` — the exact
    per-leaf element counts ``repro.dist.compress.compressed_psum_bytes``
    prices for the same trees.

    Accounting note: the twin counts each stage's OWNED payload — what a
    production transport with stage-scoped reduce groups moves.  The SPMD
    train step (one uniform program over the stage axis) necessarily
    data-reduces the stage-replicated embed/head gradients in every stage
    column; that redundancy is an artifact of the shard_map emulation, the
    same split documented for the executor's fixed-size ppermute registers
    (see ``repro.dist.pp``).
    """
    cfg = plan.cfg
    first, blocks, last = partition_params(cfg, params)
    rows = plan.vstages * plan.layers_per_vstage

    def stage_rows(leaf):
        shape = tuple(jnp.shape(leaf))
        dt = getattr(leaf, "dtype", jnp.float32)
        return jax.ShapeDtypeStruct((rows,) + shape[1:], dt)

    def as_sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(jnp.shape(a)), getattr(a, "dtype", jnp.float32)
            ),
            tree,
        )

    out = []
    for s in range(plan.pp):
        t = {"blocks": jax.tree_util.tree_map(stage_rows, blocks)}
        if s == 0:
            t["first"] = as_sds(first)
        if s == plan.pp - 1:
            t["last"] = as_sds(last)
        out.append(t)
    return out


def moe_layers_per_vstage(plan: PipelinePlan) -> list[int]:
    """How many MoE blocks each virtual stage's chunk contains."""
    cfg = plan.cfg
    per = plan.layers_per_vstage
    out = []
    for k in range(plan.n_vstages):
        lo = k * per
        out.append(
            sum(
                1
                for i in range(lo, lo + per)
                if cfg.is_moe_layer(i)
            )
        )
    return out


def model_layer_cost(
    cfg: ArchConfig, micro_batch: int, seq: int, tp: int = 1
):
    """Per-layer LayerCost with the partition's REAL boundary payload.

    Flops/param bytes come from the analytic
    ``repro.core.autotuner.layer_cost_from_config``; ``boundary_bytes`` is
    replaced by the exact activation payload the scheduled executor
    ppermutes per hop (``pp.boundary_bytes`` of the (B, S, D) microbatch in
    the config's compute dtype) — the byte twin
    tests/test_model_pipeline.py holds the simulator to.
    """
    from repro.core.autotuner import layer_cost_from_config

    base = layer_cost_from_config(cfg, micro_batch, seq, tp=tp)
    hop = pp.boundary_bytes(
        (micro_batch, seq, cfg.d_model), jnp.dtype(cfg.compute_dtype)
    )
    return dataclasses.replace(base, boundary_bytes=hop)
