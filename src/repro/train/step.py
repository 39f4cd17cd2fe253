"""Train-step factory: loss -> grad -> (compressed) reduce -> clip ->
optimizer, with microbatch gradient accumulation (``lax.scan``) and donated
buffers.

The microbatch scan is also the compute/communication overlap vehicle: XLA's
latency-hiding scheduler can overlap microbatch i's gradient reduction with
microbatch i+1's backward once the accumulation is expressed as a loop
(see EXPERIMENTS.md §Perf).

Compressed data parallelism (``compression="int8"``) threads the
error-feedback residual state of :mod:`repro.dist.compress` through
:class:`TrainState`: each step quantizes the (accumulated) local gradient
plus the carried residual, mean-reduces the payload over ``axis_name`` via
``compressed_psum``, and stores the new residual in ``state.comp_state`` —
the same code path runs under ``shard_map`` on a real data mesh
(:func:`make_sharded_train_step`) and standalone with ``axis_name=None``
(dp=1), so the executable numerics the simulator prices are never forked.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.build import Model
from repro.optim.optimizers import Optimizer, clip_by_global_norm


class TrainState(NamedTuple):
    step: jax.Array          # i32 scalar
    params: Any
    opt_state: Any
    # error-feedback residuals for compressed data-parallel training
    # (checkpoint format v2).  None when compression is off — a leafless
    # pytree node, so dense states keep the v1 leaf set.  When on: a pytree
    # matching params with f32 leaves of shape (dp, *param_shape), one
    # residual per data-parallel rank; the launcher shards the leading axis
    # over the "data" mesh axis (see train_state_specs).
    comp_state: Any = None


def _normalize_compression(compression: Optional[str]) -> Optional[str]:
    if compression in (None, "", "none"):
        return None
    if compression != "int8":
        raise ValueError(
            f"executable compression scheme must be 'int8' (got "
            f"{compression!r}; topk is byte-accounting-only, see "
            f"repro.dist.compress)"
        )
    return compression


def train_state_specs(comp_axis: str = "data") -> "TrainState":
    """Per-field PartitionSpecs of a TrainState under data-parallel
    shard_map: everything replicated except the per-rank residuals, whose
    leading axis is split over ``comp_axis``."""
    return TrainState(P(), P(), P(), P(comp_axis))


def init_state(
    model: Model,
    rng,
    optimizer: Optimizer,
    compression: Optional[str] = None,
    dp: int = 1,
) -> tuple[TrainState, Any]:
    params, axes = model.init(rng)
    opt_state = optimizer.init(params)
    comp = None
    if _normalize_compression(compression):
        from repro.dist.compress import init_feedback_state

        comp = init_feedback_state(params, dp)
    return (
        TrainState(jnp.zeros((), jnp.int32), params, opt_state, comp),
        axes,
    )


def abstract_state(
    model: Model,
    optimizer: Optimizer,
    seed: int = 0,
    compression: Optional[str] = None,
    dp: int = 1,
):
    """ShapeDtypeStructs of the full TrainState + the param axes tree."""
    box = {}
    comp_on = _normalize_compression(compression) is not None

    def build(rng):
        p, a = model.init(rng)
        box["axes"] = a
        comp = None
        if comp_on:
            from repro.dist.compress import init_feedback_state

            comp = init_feedback_state(p, dp)
        return TrainState(jnp.zeros((), jnp.int32), p, optimizer.init(p), comp)

    shapes = jax.eval_shape(build, jax.random.PRNGKey(seed))
    return shapes, box["axes"]


def _split_microbatches(batch: dict, accum: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % accum == 0, f"batch {b} % accum {accum} != 0"
        return x.reshape((accum, b // accum) + x.shape[1:])

    return {k: split(v) for k, v in batch.items()}


def make_train_step(
    model: Model,
    optimizer: Optimizer,
    schedule,
    grad_accum: int = 1,
    max_grad_norm: float = 1.0,
    compression: Optional[str] = None,
    axis_name: Optional[str] = None,
    overlap_buckets: int = 0,
):
    """Returns train_step(state, batch) -> (state, metrics).

    grad_accum > 1 scans over microbatches accumulating the mean gradient
    (and the mean of the model's aux metrics) in fp32 before one optimizer
    application.

    With ``compression`` set, the gradient mean over ``axis_name`` runs
    through ``repro.dist.compress.compressed_psum`` — quantize, psum the
    dequantized payload, carry the per-rank error-feedback residual in
    ``state.comp_state``.  ``axis_name=None`` executes the identical
    numerics without a mesh (dp=1).  When ``axis_name`` is set the step
    must run inside ``shard_map`` (see :func:`make_sharded_train_step`);
    batch-level loss/metrics are pmean'd so every rank returns the global
    value.

    ``overlap_buckets >= 2`` groups the compressed reduction's per-leaf
    payloads into that many reverse-order buckets — one psum per bucket,
    launchable as backward produces them — via the ``buckets`` path of
    ``compressed_psum``; bit-identical numerics, fewer collectives.
    """
    cfg: ArchConfig = model.cfg
    compression = _normalize_compression(compression)

    def loss_fn(params, microbatch):
        loss, metrics = model.loss(params, microbatch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch: dict):
        params = state.params

        if grad_accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = _split_microbatches(batch, grad_accum)

            # metric structure (without compute) to seed the scan carry —
            # per-microbatch means are accumulated alongside the gradient
            # so accumulation never drops the model's aux metrics
            mb0 = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), micro
            )
            (_, mshapes), _ = jax.eval_shape(grad_fn, params, mb0)
            mzero = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.float32), mshapes
            )

            def accum_body(carry, mb):
                gsum, lsum, msum = carry
                (l, m), g = grad_fn(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g
                )
                msum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), msum, m
                )
                return (gsum, lsum + l, msum), None

            gzero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum, msum), _ = jax.lax.scan(
                accum_body, (gzero, 0.0, mzero), micro
            )
            grads = jax.tree_util.tree_map(
                lambda g: (g / grad_accum), gsum
            )
            loss = lsum / grad_accum
            metrics = jax.tree_util.tree_map(lambda s: s / grad_accum, msum)

        comp_state = state.comp_state
        if compression is not None:
            from repro.dist.compress import compressed_psum

            # local residual: this rank's (1, ...) slice of the carried state
            res = jax.tree_util.tree_map(lambda r: r[0], state.comp_state)
            grads, new_res = compressed_psum(
                grads, axis_name, res, buckets=overlap_buckets
            )
            comp_state = jax.tree_util.tree_map(lambda r: r[None], new_res)
            if axis_name is not None:
                loss = jax.lax.pmean(loss, axis_name)
                metrics = jax.tree_util.tree_map(
                    lambda m: jax.lax.pmean(m, axis_name), metrics
                )

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = schedule(state.step)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, params, lr
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
            params,
            updates,
        )
        if cfg.moe is not None and cfg.moe.bias_update_rate:
            new_params = move_router_bias(
                new_params, params, metrics["expert_tokens"],
                cfg.moe.bias_update_rate,
            )
        out_metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": lr,
            **{k: v for k, v in (metrics or {}).items()},
        }
        return (
            TrainState(state.step + 1, new_params, opt_state, comp_state),
            out_metrics,
        )

    return train_step


def move_router_bias(new_params, params, load, rate: float):
    """DeepSeek-V3's auxiliary-loss-free balancing (§2.1.2): after the
    step, each MoE layer's correction bias moves by
    ``rate * sign(mean load - load_i)``, the loads (MoE layers, experts)
    counted over every expert on this step's tokens.  The bias takes no
    gradient (the router reads it under ``stop_gradient``) and no weight
    decay: whatever the optimizer did to it is replaced."""
    moe = params["blocks"]["moe"]
    bias = moe["bias"] + rate * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load
    )
    blocks = dict(new_params["blocks"], moe=dict(new_params["blocks"]["moe"], bias=bias))
    return dict(new_params, blocks=blocks)


def make_pipeline_train_step(
    model: Model,
    optimizer: Optimizer,
    schedule,
    mesh,
    plan,
    grad_accum: int = 1,
    max_grad_norm: float = 1.0,
    compression: Optional[str] = None,
    data_axis: str = "data",
    stage_axis: str = "stage",
    overlap_buckets: int = 0,
    overlap_comm: bool = False,
):
    """Train step executing the REAL model through the pipeline schedule.

    One ``shard_map`` over the (data x stage) mesh: each data replica runs
    its batch shard through the scheduled pipeline executor
    (``repro.dist.pp.make_scheduled_body`` with the model's own
    embed/block/head stage callables from ``repro.models.pipeline``), then
    the gradients are mean-reduced over ``data_axis`` — dense ``pmean`` or
    int8 ``compressed_psum`` with the error-feedback residuals carried in
    ``TrainState.comp_state`` (block residuals are re-chunked to the
    schedule's device-major rows, so each stage quantizes exactly the
    parameters it owns).  Clip + optimizer run outside on the merged
    model-layout gradients — identical to the GSPMD path's tail.

    ``grad_accum > 1`` scans ``grad_accum`` pipeline passes per step (the
    accumulation path of :func:`make_train_step`, one level up): the step
    trains the mean over ``grad_accum * plan.microbatches`` microbatches.

    TrainState layout (params, opt_state, comp_state) is unchanged —
    checkpoints are interchangeable with the GSPMD path.

    Overlap knobs (both bit-exact, see repro.dist): ``overlap_buckets >= 2``
    buckets the gradient reduction (compressed via ``compressed_psum``'s
    bucket path, dense via ``bucketed_pmean``) so per-bucket collectives
    launch as backward retires their chunks; ``overlap_comm`` runs the
    scheduled executor with statically-elided dead-tick ppermutes
    (``make_scheduled_body(overlap=True)``).
    """
    from jax import shard_map
    from repro.dist import pp as _pp
    from repro.models.pipeline import partition_params, stage_fns
    from repro.models.sharding import use_sharding

    cfg: ArchConfig = model.cfg
    compression = _normalize_compression(compression)
    sched = plan.make_schedule()
    M, A = plan.microbatches, grad_accum
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert sizes.get(stage_axis) == plan.pp, (sizes, plan.pp)
    dp = sizes.get(data_axis, 1)
    first_fn, layer_fn, loss_fn = stage_fns(cfg, M)

    def _extras_grads(gf, gl):
        """Model's non-block gradient leaves via the canonical merge
        (tied embeddings: sum the two paths of the shared table)."""
        from repro.models.pipeline import merge_grads

        merged = merge_grads(cfg, gf, None, gl)
        del merged["blocks"]
        return merged

    def _extras_of(tree):
        """The non-block subtree of a params-shaped tree, model keys."""
        return {k: v for k, v in tree.items() if k != "blocks"}

    def train_step(state: TrainState, batch: dict):
        params = state.params
        first, blocks, last = partition_params(cfg, params)
        arranged = _pp.arrange_params_for_schedule(blocks, sched)

        b_lead = {v.shape[0] for v in batch.values()}
        (B,) = b_lead
        assert B % (dp * A * M) == 0, (
            f"batch {B} % (dp {dp} * grad_accum {A} * microbatches {M}) != 0"
        )
        bm = B // (dp * A * M)
        tok_sds = jax.ShapeDtypeStruct(
            (bm,) + batch["tokens"].shape[1:], batch["tokens"].dtype
        )
        act_sds = jax.eval_shape(first_fn, first, {"tokens": tok_sds})
        sched_body = _pp.make_scheduled_body(
            sched, layer_fn, act_sds,
            first_fn=first_fn, loss_fn=loss_fn, axis_name=stage_axis,
            overlap=overlap_comm,
        )

        comp_on = compression is not None
        if comp_on:
            res_extras = _extras_of(state.comp_state)
            res_blocks = _pp.arrange_params_for_schedule(
                state.comp_state["blocks"], sched, axis=1
            )

        def body(arranged, first, last, batch_local, *res):
            with use_sharding(None):
                micro = {
                    k: v.reshape((A, M, bm) + v.shape[1:])
                    for k, v in batch_local.items()
                }

                def one_pass(carry, mb):
                    ce_s, aux_s, gb_s, gf_s, gl_s = carry
                    xs = {"tokens": mb["tokens"]}
                    li = {k: v for k, v in mb.items() if k != "tokens"}
                    ce, aux, _outs, gb, gf, gl = sched_body(
                        arranged, first, last, xs, li
                    )
                    add = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
                        jnp.add, a, b
                    )
                    return (
                        ce_s + ce, aux_s + aux,
                        add(gb_s, gb), add(gf_s, gf), add(gl_s, gl),
                    ), None

                zero = (
                    jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(jnp.zeros_like, arranged),
                    jax.tree_util.tree_map(jnp.zeros_like, first),
                    jax.tree_util.tree_map(jnp.zeros_like, last),
                )
                (ce, aux, gb, gf, gl), _ = jax.lax.scan(one_pass, zero, micro)
                scale = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda x: x / A, t
                )
                ce, aux = ce / A, aux / A
                gb, gf, gl = scale(gb), scale(gf), scale(gl)

                gtree = {"extras": _extras_grads(gf, gl), "blocks": gb}
                if comp_on:
                    from repro.dist.compress import compressed_psum

                    re_, rb_ = res
                    rtree = {
                        "extras": jax.tree_util.tree_map(
                            lambda r: r[0], re_
                        ),
                        "blocks": jax.tree_util.tree_map(
                            lambda r: r[0], rb_
                        ),
                    }
                    gtree, new_res = compressed_psum(
                        gtree, data_axis, rtree, buckets=overlap_buckets
                    )
                    new_res = jax.tree_util.tree_map(
                        lambda r: r[None], new_res
                    )
                else:
                    from repro.dist.compress import bucketed_pmean

                    gtree = bucketed_pmean(
                        gtree, data_axis, buckets=overlap_buckets
                    )
                    new_res = None
                ce = jax.lax.pmean(ce, data_axis)
                aux = jax.lax.pmean(aux, data_axis)
                if comp_on:
                    return (ce, aux, gtree["extras"], gtree["blocks"],
                            new_res["extras"], new_res["blocks"])
                return ce, aux, gtree["extras"], gtree["blocks"]

        in_specs = [P(stage_axis), P(), P(), P(data_axis)]
        out_specs = [P(), P(), P(), P(stage_axis)]
        args = [arranged, first, last, batch]
        if comp_on:
            in_specs += [P(data_axis), P(data_axis, stage_axis)]
            out_specs += [P(data_axis), P(data_axis, stage_axis)]
            args += [res_extras, res_blocks]
        out = shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            check_vma=False,
        )(*args)
        if comp_on:
            ce, aux, g_extras, gb_rows, nres_extras, nres_blocks = out
            comp_state = dict(nres_extras)
            comp_state["blocks"] = _pp.unarrange_params_for_schedule(
                nres_blocks, sched, axis=1
            )
        else:
            ce, aux, g_extras, gb_rows = out
            comp_state = state.comp_state

        grads = dict(g_extras)
        grads["blocks"] = _pp.unarrange_params_for_schedule(gb_rows, sched)
        loss = ce + aux

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = schedule(state.step)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, params, lr
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: (
                p.astype(jnp.float32) + u.astype(jnp.float32)
            ).astype(p.dtype),
            params,
            updates,
        )
        metrics = {
            "loss": loss, "ce": ce, "aux": aux,
            "grad_norm": gnorm, "lr": lr,
        }
        return (
            TrainState(state.step + 1, new_params, opt_state, comp_state),
            metrics,
        )

    return train_step


def make_sharded_train_step(
    model: Model,
    optimizer: Optimizer,
    schedule,
    mesh,
    grad_accum: int = 1,
    max_grad_norm: float = 1.0,
    compression: Optional[str] = None,
    axis_name: str = "data",
    pipeline=None,
    overlap_buckets: int = 0,
    overlap_comm: bool = False,
):
    """The train step wrapped for a data mesh — the launcher's entry point.

    Dense training returns the plain step (GSPMD handles the gradient mean
    under jit).  Compressed training needs explicit per-device gradients,
    so the *same* :func:`make_train_step` body is wrapped in ``shard_map``:
    batch split over ``axis_name``, state replicated except the per-rank
    ``comp_state`` slice.  With a ``pipeline`` plan
    (:class:`repro.models.pipeline.PipelinePlan`), the step instead runs
    the real model through the scheduled pipeline executor on the
    (data x stage) mesh — see :func:`make_pipeline_train_step`.  One entry
    point, all strategies — the simulator's priced :class:`Strategy` always
    has an executable counterpart.
    """
    if pipeline is not None:
        return make_pipeline_train_step(
            model, optimizer, schedule, mesh, pipeline,
            grad_accum=grad_accum, max_grad_norm=max_grad_norm,
            compression=compression, data_axis=axis_name,
            overlap_buckets=overlap_buckets, overlap_comm=overlap_comm,
        )
    compression = _normalize_compression(compression)
    step = make_train_step(
        model, optimizer, schedule,
        grad_accum=grad_accum, max_grad_norm=max_grad_norm,
        compression=compression,
        axis_name=axis_name if compression else None,
        overlap_buckets=overlap_buckets,
    )
    if compression is None:
        return step
    from jax import shard_map
    from repro.models.sharding import use_sharding

    def body(state, batch):
        # inside shard_map the mesh axes are manual — the ambient sharding
        # context's with_sharding_constraint hints must not fire
        with use_sharding(None):
            return step(state, batch)

    specs = train_state_specs(axis_name)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(specs, P(axis_name)),
        out_specs=(specs, P()),
        check_vma=False,
    )


def make_eval_step(model: Model):
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch)
        return {"loss": loss, **metrics}

    return eval_step


def run_timed_step(jitted, state, batch, recorder, name: str, **labels):
    """Execute one jitted train step under a recorder interval.

    The measurement boundary is the ``float(metrics["loss"])`` host sync —
    the same boundary the launcher's ad-hoc ``time.perf_counter`` pair
    used before the recorder existed, and the recorder's interval
    primitive reads the clock exactly once on each side whether or not
    recording is enabled, so the measured durations are bit-identical to
    the old code path (see repro.obs.record).

    Under a profiler session the step is the span ``train.step``.  An
    enabled recorder also takes the step's counters: ``attn.flash_layers``
    (the layers whose attention ran the flash kernel) and, with MoE layers,
    ``moe.held_assignments`` (assignments computed by the held experts)
    and ``moe.expert_tokens``, one sample per expert (device
    ``expert<i>``) of the tokens routed to it, summed over the layers.

    Returns ``(state, metrics, loss, dt_seconds)``.
    """
    iv = recorder.interval(name, "host", kind="train-step",
                           layer="train.step", **labels)
    state, metrics = jitted(state, batch)
    loss = float(metrics["loss"])  # host sync: the step is truly done
    dt = iv.stop()
    if recorder.enabled and "attn_flash_layers" in metrics:
        recorder.counter("attn.flash_layers", "host",
                         float(metrics["attn_flash_layers"]))
    if recorder.enabled and "expert_tokens" in metrics:
        recorder.counter("moe.held_assignments", "host",
                         float(metrics["held_assignments"]))
        for i, n in enumerate(jax.device_get(metrics["expert_tokens"]).sum(0)):
            recorder.counter("moe.expert_tokens", f"expert{i}", float(n))
    return state, metrics, loss, dt
