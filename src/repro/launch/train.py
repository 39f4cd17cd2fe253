"""End-to-end training driver.

Wires together every substrate layer: config -> model -> sharding -> data
pipeline -> train step -> checkpoint/restart -> telemetry (heartbeat, step
times, straggler policy).  On the CPU container it runs reduced configs for
real (examples/train_small_lm.py trains a ~100M model a few hundred steps);
on a TPU fleet the same driver runs the full configs over the production
mesh.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 50 --seq 256 --batch 8 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile

import jax
from jax import make_mesh
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.ckpt import AsyncCheckpointer, restore
from repro.configs.base import ShapeConfig, get_config, smoke_variant
from repro.data import make_train_iterator
from repro.ft import HeartbeatMonitor, StepTimeMonitor, StragglerPolicy
from repro.launch import spec as runspec
from repro.models import build_model
from repro.models.sharding import data_axis_size, make_ctx, use_sharding
from repro.optim import cosine_with_warmup, make_optimizer
from repro.obs.record import Recorder
from repro.train import make_sharded_train_step
from repro.train.step import init_state, run_timed_step, train_state_specs


def build_mesh(pp: int = 0):
    """(data, model) GSPMD mesh; ``pp >= 1`` builds the (data, stage)
    pipeline-executor mesh with ``pp`` stage devices instead."""
    n = jax.device_count()
    if pp >= 1:
        if n % pp != 0:
            raise ValueError(
                f"--pp {pp} needs a device count divisible by it (have {n})"
            )
        return make_mesh(
            (n // pp, pp), ("data", "stage"),
            axis_types=(AxisType.Auto,) * 2,
        )
    return make_mesh(
        (n, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def comm_report(
    cfg, mesh, params, *, batch: int, seq: int,
    compression: str = "none", log_fn=print,
) -> None:
    """Log the per-step comm volumes the dist layer would move on this mesh.

    The sim-vs-real loop at a glance: raw vs int8-compressed gradient
    all-reduce payload — priced per leaf via the same executor byte twin
    (``compressed_psum_bytes``) the simulator's annotated graph resolves
    to, per-tensor scale metadata included — and, for ep_a2a MoE configs,
    the per-device dispatch all-to-all payload (repro.dist.ep_a2a).
    """
    from repro.dist.compress import compressed_psum_bytes

    dp = data_axis_size(mesh)
    raw = compressed_psum_bytes(params, scheme="none")
    int8 = compressed_psum_bytes(params, scheme="int8")
    active = " (ACTIVE: error-feedback psum)" if compression == "int8" else ""
    log_fn(
        f"[comm] dp={dp} grad all-reduce/step: raw {raw / 2**20:.1f} MiB; "
        f"an int8+feedback ring would move {int8 / 2**20:.1f} MiB "
        f"({raw / int8:.1f}x less){active}"
    )
    if cfg.moe is not None and cfg.moe.impl == "ep_a2a":
        from repro.dist.ep_a2a import moe_a2a_bytes

        tokens_local = batch // max(dp, 1) * seq
        a2a = moe_a2a_bytes(cfg.moe, tokens_local, cfg.d_model)
        log_fn(
            f"[comm] moe ep_a2a dispatch/layer: {a2a / 2**20:.2f} MiB "
            f"per device each way ({tokens_local} local tokens)"
        )


# one launch loads and fits a calibration DB once: plan report and parity
# report share the estimator (and its provenance ledger) instead of
# re-parsing the DB and re-training every model per report
_NETPROF_CACHE: dict = {}


def netprof_estimator(db_path: str, log_fn=print):
    """(estimator, platform) priced from a calibrated interconnect DB.

    Loads the ProfileDB written by ``scripts/calibrate_net.py``, picks the
    calibrated platform (``cpu_host`` when present, else the DB's single
    platform), and builds an :class:`OpTimeEstimator` whose collectives go
    through the measured chain (exact DB hit -> fitted CollectiveModel ->
    ring; repro.netprof).  ``cpu_host`` platforms are re-calibrated from
    the DB's compute entries too (``repro.core.profiler.calibrate_host``),
    so a fully profiled host prices compute AND comm from measurements.
    Memoized per (path, mtime, size) — repeated calls within one launch
    reuse the fitted estimator and log its banner once.
    """
    from repro.core.database import ProfileDB

    st = os.stat(db_path)
    cache_key = (os.path.abspath(db_path), st.st_mtime_ns, st.st_size)
    hit = _NETPROF_CACHE.get(cache_key)
    if hit is not None:
        return hit
    from repro.core.estimator import OpTimeEstimator
    from repro.core.hardware import PLATFORMS
    from repro.core.profiler import calibrate_host
    from repro.netprof.pricing import netprof_meta

    db = ProfileDB.load(db_path)
    plats = db.platforms()
    name = "cpu_host" if "cpu_host" in plats else (plats[0] if plats else "")
    if not name:
        raise ValueError(f"--netprof-db {db_path}: no platforms in DB")
    if name in PLATFORMS and name != "cpu_host":
        platform = PLATFORMS[name]
    else:
        # cpu_host and custom --platform names: derive the spec from the
        # DB's own measurements (falls back to CPU_HOST constants for
        # anything unprofiled)
        platform = calibrate_host(db, name)
    stamp = netprof_meta(db, name)
    if stamp:
        log_fn(
            f"[netprof] {db_path}: platform {name}, "
            f"{stamp.get('entries', 0)} collective measurements, "
            f"groups {stamp.get('groups')}, "
            f"collectives {len(stamp.get('collectives', []))}"
        )
    else:
        log_fn(f"[netprof] {db_path}: platform {name} "
               f"(no netprof sweep stamp — collectives may ring-fall back)")
    out = (OpTimeEstimator(platform, db), platform)
    _NETPROF_CACHE[cache_key] = out
    return out


def plan_analysis_report(
    cfg, strategy, *, micro_batch: int, seq: int, estimator=None,
    run_spec=None, log_fn=print,
):
    """Statically verify the launch plan before a single step executes.

    Runs the full ``repro.analysis`` pass over the model-derived plan —
    schedule table legality and ppermute pairing, graph structure and
    accounting completeness (with netprof provenance audit when
    ``--netprof-db`` supplied an estimator), and the DES timeline audit.
    Raises :class:`repro.analysis.PlanVerificationError` on any
    error-level finding: a plan that would deadlock the executor or price
    garbage never reaches the mesh.
    """
    from repro.analysis import analyze_training_plan

    report = analyze_training_plan(
        cfg, strategy, micro_batch=micro_batch, seq=seq,
        estimator=estimator, use_model_graph=True,
    )
    runspec.attach(report, run_spec)
    for line in report.summary_lines():
        log_fn(f"[analyze] {line}")
    report.raise_on_errors()
    return report


def pipeline_plan_report(
    cfg, *, pp: int, schedule: str, vstages: int, microbatches: int,
    batch: int, seq: int, netprof_db: str | None = None, log_fn=print,
):
    """Simulate the requested pipeline schedule for this config and log it.

    The sim side of the sim-vs-real loop at launch time: the same
    ``repro.dist.schedules`` step table the shard_map executor would run is
    priced by the DES — bubble fraction, comm share, and the scheduled
    boundary traffic — so a schedule choice is visible before any chip is
    committed.  Reuses ``Autotuner.evaluate`` so the launch report can
    never drift from what the tuner would score.  Falls back with a log
    line (instead of failing the launch) when the config cannot realize the
    schedule, e.g. layers not divisible by pp*vstages.
    """
    from repro.core.autotuner import Autotuner
    from repro.core.strategy import Strategy
    from repro.models.pipeline import model_layer_cost

    strategy = Strategy(pp=pp, microbatches=microbatches, schedule=schedule,
                        vstages=vstages)
    est = platform = None
    if netprof_db:
        est, platform = netprof_estimator(netprof_db, log_fn=log_fn)
    tuner = Autotuner(cfg, chips=pp, global_batch=max(batch, microbatches),
                      seq=seq,
                      **({"platform": platform, "estimator": est}
                         if est is not None else {}))
    try:
        result = tuner.evaluate(strategy)
    except (ValueError, AssertionError, ZeroDivisionError) as e:
        log_fn(f"[pp-plan] {strategy.describe()} not realizable: {e}")
        return None
    if est is not None and est.collective_pricer is not None:
        for line in est.collective_pricer.report_lines():
            log_fn(f"[netprof] {line}")
        ring = est.collective_pricer.ring_fallbacks_for_profiled()
        log_fn(f"[netprof] ring-fallback nodes for profiled collectives: "
               f"{ring}")
    micro_bs = max(batch // microbatches, 1)
    # boundary payload from the model's own activation shape/dtype — the
    # executor's ppermute byte twin, not the analytic bf16 default
    cost = model_layer_cost(cfg, micro_bs, seq, tp=1)
    hops = strategy.make_pipeline_schedule().comm_bytes(cost.boundary_bytes)
    log_fn(
        f"[pp-plan] {strategy.describe()}: simulated step "
        f"{result.makespan_s * 1e3:.2f}ms, "
        f"bubble {result.bubble_fraction * 100:.1f}%, "
        f"comm share {result.comm_fraction * 100:.1f}%, "
        f"boundary traffic {hops / 2**20:.2f} MiB/step"
    )
    return result


def pipeline_parity_report(
    plan, *, micro_batch: int, seq: int, dp: int = 1,
    compression: str = "none", estimator=None, log_fn=print,
) -> float:
    """Model-derived sim bytes vs the executor's byte twin; raises on drift.

    The launch-time incarnation of the tests/test_model_pipeline.py parity
    gate: the simulator's collective-permute nodes over
    ``repro.core.strategy.model_pipeline_graph`` must sum to exactly the
    scheduled ppermute traffic the executor will put on the wire
    (``PipelinePlan.boundary_bytes_per_step``).
    """
    from repro.core.estimator import dist_comm_bytes
    from repro.core.strategy import model_pipeline_graph

    g = model_pipeline_graph(
        plan.cfg, plan.strategy(dp=dp, compression=compression),
        micro_batch, seq,
    )
    sim = sum(
        dist_comm_bytes(n) for n in g.nodes
        if n.kind == "collective-permute"
    )
    ex = plan.boundary_bytes_per_step(micro_batch, seq)
    ok = abs(sim - ex) <= 1e-6 * max(ex, 1.0)
    log_fn(
        f"[pp-exec] {plan.describe()}: boundary bytes/step "
        f"sim={sim:.0f} exec={ex:.0f} "
        f"({'parity ok' if ok else 'PARITY MISMATCH'})"
    )
    if not ok:
        raise AssertionError(
            f"pipeline byte parity drift: sim {sim} != exec {ex}"
        )
    if estimator is not None:
        # price every comm node through the measured chain and report the
        # per-kind provenance next to the byte parity it complements: bytes
        # twin-exact AND time measured == the full sim-vs-real loop closed
        from repro.netprof.pricing import PROV_RING, graph_provenance

        for n in g.nodes:
            if n.is_collective:
                estimator.duration(n)
        prov = graph_provenance(g)
        for kind in sorted(prov):
            s = prov[kind]
            log_fn(
                f"[netprof] {kind}: "
                + " / ".join(f"{v} {k}" for k, v in sorted(s.items()))
            )
        rings = sum(s.get(PROV_RING, 0) for s in prov.values())
        log_fn(f"[netprof] comm nodes ring-priced: {rings}")
    return sim


def _obs_report(
    rec, cfg, plan, mesh, params, *, batch: int, seq: int, dp: int,
    grad_accum: int, compression: str, overlap_buckets: int,
    netprof_db: str | None, trace_out: str, run_spec=None, log_fn=print,
) -> None:
    """The --obs post-pass: price the plan, replay its ops for real,
    attribute the sim-vs-real gap, and export the overlay trace.

    Per-op spans cannot be host-timed inside the executor's shard_map, so
    the real side of each op comes from :func:`repro.obs.replay`'s
    instrumented standalone re-execution on the live mesh — the offline
    profiling the paper's estimator is built from, turned into spans
    under the simulator's own node uids (docs/observability.md).
    """
    from repro.obs import (
        divergence_report,
        overlay_chrome_trace,
        replay_pipeline_ops,
    )

    sim_res = graph = None
    measured = None
    step_spans = [
        s for s in rec.spans if s.labels.get("role") == "step"
    ]
    if step_spans:
        measured = sum(s.duration for s in step_spans) / len(step_spans)
    if plan is not None:
        from repro.core.estimator import OpTimeEstimator
        from repro.core.hardware import platform_for_device
        from repro.core.simulator import simulate
        from repro.core.strategy import model_pipeline_graph

        micro_bs = max(batch // (dp * grad_accum * plan.microbatches), 1)
        strat = plan.strategy(dp=dp, compression=compression)
        if overlap_buckets:
            strat = dataclasses.replace(
                strat, overlap_buckets=overlap_buckets
            )
        graph = model_pipeline_graph(cfg, strat, micro_bs, seq)
        if netprof_db:
            est, _ = netprof_estimator(netprof_db, log_fn=log_fn)
        else:
            est = OpTimeEstimator(platform_for_device(mesh.devices.flat[0]))
        sim_res = simulate(graph, est.duration, record_events=True)
        replay_pipeline_ops(
            rec, graph, cfg=cfg, plan=plan, mesh=mesh, params=params,
            micro_batch=micro_bs, seq=seq, log_fn=log_fn,
        )
        report = divergence_report(rec, sim_res, graph, name="train-obs")
        if measured is not None:
            report.metrics["obs_step_mean_s"] = float(measured)
            log_fn(
                f"[obs] mean real step {measured * 1e3:.1f}ms vs simulated "
                f"makespan {sim_res.makespan * 1e3:.2f}ms (the step also "
                f"carries executor dispatch overhead the per-op "
                f"attribution below excludes)"
            )
        runspec.attach(report, run_spec)
        for line in report.summary_lines():
            log_fn(f"[obs] {line}")
    else:
        report = None
        log_fn(
            "[obs] no pipeline plan (--pp 1): recorded "
            f"{len(rec.spans)} spans; overlay will carry real tracks only"
        )
    if trace_out:
        overlay_chrome_trace(sim_res, rec, trace_out, graph=graph)
        log_fn(f"[obs] overlay trace written to {trace_out}")
        if report is not None:
            rpath = os.path.splitext(trace_out)[0] + "_report.json"
            report.to_json(rpath)
            log_fn(f"[obs] divergence report written to {rpath}")


def train(
    cfg,
    *,
    steps: int,
    seq: int,
    batch: int,
    ckpt_dir: str | None = None,
    restore_from: bool = True,
    lr: float = 3e-4,
    warmup: int = 20,
    grad_accum: int = 1,
    compression: str = "none",
    pp: int = 0,
    pp_schedule: str = "1f1b",
    vstages: int = 1,
    microbatches: int = 0,
    overlap_buckets: int = 0,
    overlap_comm: bool = False,
    netprof_db: str | None = None,
    analyze: bool = False,
    obs: bool = False,
    trace_out: str = "",
    run_spec=None,
    log_every: int = 10,
    ckpt_every: int = 50,
    host_id: int = 0,
    num_hosts: int = 1,
    seed: int = 0,
    log_fn=print,
):
    shape = ShapeConfig("train_driver", seq, batch, "train")
    pipeline_on = pp > 1 or vstages > 1
    plan = None
    if pipeline_on:
        from repro.models.pipeline import make_plan

        pp = max(pp, 1)
        mb = microbatches or max(pp, 1)
        plan = make_plan(
            cfg, pp, mb, schedule=pp_schedule, vstages=vstages
        )
        mesh = build_mesh(pp)
    else:
        mesh = build_mesh()
    dp = data_axis_size(mesh)
    if analyze:
        from repro.core.strategy import Strategy

        mb_count = plan.microbatches if plan is not None else 1
        est = None
        if netprof_db:
            est, _ = netprof_estimator(netprof_db, log_fn=log_fn)
        plan_analysis_report(
            cfg,
            Strategy(
                dp=dp,
                pp=plan.pp if plan is not None else 1,
                microbatches=mb_count,
                schedule=pp_schedule if pipeline_on else "1f1b",
                vstages=vstages if pipeline_on else 1,
                compression=compression,
                overlap_buckets=overlap_buckets,
            ),
            micro_batch=max(batch // (dp * grad_accum * mb_count), 1),
            seq=seq, estimator=est, run_spec=run_spec, log_fn=log_fn,
        )
    ctx = make_ctx(mesh, overrides=cfg.sharding_overrides)
    model = build_model(cfg)
    opt = make_optimizer(cfg.optimizer)
    sched = cosine_with_warmup(lr, warmup, max(steps, warmup + 1))
    # one factory for every strategy: dense returns the plain jit-able
    # step; compressed wraps the same body in shard_map over "data" with
    # the per-rank error-feedback residuals threaded through TrainState;
    # a pipeline plan runs the REAL model through the scheduled executor
    # on the (data, stage) mesh (repro.models.pipeline)
    step_fn = make_sharded_train_step(
        model, opt, sched, mesh,
        grad_accum=grad_accum, compression=compression,
        pipeline=plan,
        overlap_buckets=overlap_buckets, overlap_comm=overlap_comm,
    )
    if overlap_buckets >= 2 or overlap_comm:
        log_fn(
            f"[overlap] bucketed grad all-reduce x{overlap_buckets}"
            f"{', unrolled pipeline comm' if overlap_comm else ''} "
            f"(bit-exact rewrites; repro.dist)"
        )
    if plan is not None:
        micro_bs = batch // (dp * grad_accum * plan.microbatches)
        log_fn(
            f"[pp-exec] executing {plan.describe()} on mesh "
            f"dp{dp}xpp{plan.pp} ({micro_bs} seqs/microbatch)"
        )
        est = None
        if netprof_db:
            est, _ = netprof_estimator(netprof_db, log_fn=log_fn)
        pipeline_parity_report(
            plan, micro_batch=micro_bs, seq=seq, dp=dp,
            compression=compression, estimator=est, log_fn=log_fn,
        )

    with use_sharding(ctx):
        state, axes = init_state(
            model, jax.random.PRNGKey(seed), opt,
            compression=compression, dp=dp,
        )
        comm_report(cfg, mesh, state.params, batch=batch, seq=seq,
                    compression=compression, log_fn=log_fn)
        start_step = 0
        ckpt = None
        if ckpt_dir:
            ckpt = AsyncCheckpointer(ckpt_dir)
            if restore_from:
                out = restore(state, ckpt_dir)
                if out is not None:
                    state, start_step = out
                    log_fn(f"[restore] resumed from step {start_step}")
        # place the state on the mesh (replicated; per-rank residuals split
        # over "data") and each batch split over "data", rather than leave
        # them on the first device for every step to copy out
        state = jax.device_put(state, jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), train_state_specs(),
            is_leaf=lambda x: isinstance(x, P),
        ))
        batch_sharding = NamedSharding(mesh, P("data"))
        jitted = jax.jit(step_fn, donate_argnums=(0,))

        data = make_train_iterator(
            cfg, shape, num_hosts=num_hosts, host_id=host_id,
            seed=seed, start_step=start_step,
        )
        hb = HeartbeatMonitor(
            os.path.join(ckpt_dir, "hb") if ckpt_dir
            else os.path.join(tempfile.gettempdir(), "repro_hb"),
            num_hosts=num_hosts,
        )
        mon = StepTimeMonitor()
        pol = StragglerPolicy()

        losses = []
        # telemetry recorder: disabled it is a pure pass-through whose
        # interval primitive makes the exact two clock reads the old
        # ad-hoc perf_counter arithmetic made (repro.obs.record)
        rec = Recorder(enabled=obs)
        t_train0 = rec.clock()
        for i in range(start_step, steps):
            host_batch = next(data)
            dev_batch = jax.device_put(host_batch, batch_sharding)
            state, metrics, loss, dt = run_timed_step(
                jitted, state, dev_batch, rec, f"train_step{i}",
                role="step", step=i,
            )
            mon.record(host_id, dt)
            hb.beat(host_id, i)
            losses.append(loss)
            if (i + 1) % log_every == 0 or i == start_step:
                tok_s = batch * seq / dt
                log_fn(
                    f"[step {i + 1:5d}] loss={loss:.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e} "
                    f"{dt * 1e3:.0f}ms {tok_s:,.0f} tok/s"
                )
            if ckpt and (i + 1) % ckpt_every == 0:
                ckpt.save(state, i + 1)
            verdicts = pol.assess(mon)
            if verdicts.get(host_id) == "evict":  # pragma: no cover
                log_fn(f"[straggler] host {host_id} flagged for eviction")
        data.close()
        if ckpt:
            ckpt.save(state, steps)
            ckpt.wait()
        wall = rec.clock() - t_train0
        log_fn(
            f"[done] {steps - start_step} steps in {wall:.1f}s; "
            f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
        )
        if obs:
            _obs_report(
                rec, cfg, plan, mesh, state.params,
                batch=batch, seq=seq, dp=dp, grad_accum=grad_accum,
                compression=compression, overlap_buckets=overlap_buckets,
                netprof_db=netprof_db, trace_out=trace_out,
                run_spec=run_spec, log_fn=log_fn,
            )
        return state, losses


def main() -> None:
    ap = argparse.ArgumentParser()
    # shared launch surface lives in repro.launch.spec (one declaration,
    # every driver); only truly train-local knobs are declared here
    runspec.add_args(ap, "model", "train", "obs")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--no-restore", action="store_true")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override d_model (with --smoke)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--moe-impl", choices=["dropless", "ep_a2a"], default=None,
                    help="MoE execution strategy (ep_a2a = explicit "
                         "all-to-all expert parallelism, repro.dist.ep_a2a)")
    args = ap.parse_args()
    runspec.use_compile_cache()
    spec = runspec.from_args(args)

    cfg = get_config(spec.arch)
    if spec.smoke:
        cfg = smoke_variant(cfg)
    if args.moe_impl and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=args.moe_impl)
        )
    if args.d_model:
        cfg = dataclasses.replace(
            cfg, d_model=args.d_model,
            head_dim=args.d_model // cfg.num_heads,
        )
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    pipeline_on = spec.pp > 1 or spec.vstages > 1
    if pipeline_on:
        pipeline_plan_report(
            cfg,
            pp=spec.pp,
            schedule=spec.pp_schedule,
            vstages=spec.vstages,
            microbatches=spec.microbatches or max(spec.pp, 1),
            batch=spec.batch,
            seq=spec.seq,
            netprof_db=spec.netprof_db or None,
        )
    train(
        cfg,
        steps=spec.steps,
        seq=spec.seq,
        batch=spec.batch,
        lr=args.lr,
        grad_accum=spec.grad_accum,
        compression=spec.compression,
        pp=spec.pp if pipeline_on else 0,
        pp_schedule=spec.pp_schedule,
        vstages=spec.vstages,
        microbatches=spec.microbatches,
        overlap_buckets=spec.overlap_buckets,
        overlap_comm=spec.overlap_comm,
        netprof_db=spec.netprof_db or None,
        analyze=spec.analyze,
        obs=spec.obs,
        trace_out=spec.trace_out,
        run_spec=spec,
        ckpt_dir=args.ckpt_dir,
        restore_from=not args.no_restore,
        seed=spec.seed,
    )


if __name__ == "__main__":
    main()
