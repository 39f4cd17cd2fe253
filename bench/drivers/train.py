"""Training cell: the compiled step that ``repro.launch.train`` builds.

Set-up builds the step (``make_sharded_train_step`` on ``build_mesh``), puts
the benchmark's weights and a fresh AdamW state on the mesh, and runs the
first three steps through the window's own compiled call and feed (the
program's ``Prefetcher`` over the benchmark's token rows).  Those steps are
what the reference checks; the window then goes on with the same state.
Each step is synced on its loss, as the launcher does.
"""
from __future__ import annotations

import gc
import math
import time

CHECK_STEPS = 3


def run(cell, env) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import counts, program, traffic
    from bench.check import train as tcheck
    from bench.stats import durations_line
    from repro.data.pipeline import Prefetcher
    from repro.launch.train import build_mesh
    from repro.models import build_model
    from repro.models.sharding import make_ctx, use_sharding
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train import make_sharded_train_step
    from repro.train.step import TrainState, train_state_specs

    conf, job = cell.config, cell.traffic
    m, opt = conf["model"], conf["optimizer"]
    fam = cell.family()
    pdtype = conf["program"]["param_dtype"]
    seed = env.seed
    cfg = program.arch_config(conf)
    mesh = build_mesh()
    model = build_model(cfg)
    optimizer = make_optimizer(
        "adamw", b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
    )
    sched = cosine_with_warmup(opt["lr"], opt["warmup"], opt["total_steps"],
                               opt["min_ratio"])
    step_fn = env.hooks.get("step_fn", lambda f: f)(make_sharded_train_step(
        model, optimizer, sched, mesh, grad_accum=1,
        max_grad_norm=opt["max_grad_norm"],
    ))
    V = m["vocab_size"]
    tokens_per_step = job["batch"] * job["seq"]

    with use_sharding(make_ctx(mesh, overrides=cfg.sharding_overrides)):
        program.check_params(model, fam.abstract(m, pdtype))
        replicated = NamedSharding(mesh, P())
        params = jax.device_put(fam.init(m, seed, pdtype), replicated)
        state = TrainState(jnp.zeros((), jnp.int32), params,
                           optimizer.init(params), None)
        del params
        state = jax.device_put(state, jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), train_state_specs(),
            is_leaf=lambda x: isinstance(x, P),
        ))
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        data = Prefetcher(traffic.train_batches(job, V, seed), depth=2)
        batch_sharding = NamedSharding(mesh, P("data"))

        def one_step(state):
            with jax.profiler.TraceAnnotation("bench.data"):
                t = time.perf_counter()
                batch = jax.device_put(next(data), batch_sharding)
                wait = time.perf_counter() - t
            with jax.profiler.TraceAnnotation("bench.step"):
                state, met = jitted(state, batch)
            with jax.profiler.TraceAnnotation("bench.readback"):
                loss = float(met["loss"])
            return state, met, loss, wait

        # the first steps, checked against the reference after the window
        prog = {"losses": []}
        for i in range(CHECK_STEPS):
            state, met, loss, _ = one_step(state)
            prog["losses"].append(loss)
            if i == 0:
                prog["grad_norm"] = float(met["grad_norm"])
                prog["grad_leaf"] = tcheck.leaf_norms(
                    state.opt_state["m"]) / (1.0 - opt["b1"])
        p0 = jax.device_put(fam.init(m, seed, pdtype), replicated)
        prog["update_leaf"] = tcheck.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, state.params, p0))
        del p0
        compiled = program.compiled_bytes(jitted.lower(state, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=batch_sharding),
            traffic.train_batch(job, V, seed, 0))))
        env.log(f"[memory] train step, compiled: {compiled} B")

        rec = {"steps": 0, "failed": 0, "data_wait_s": [], "traced_steps": 0,
               "ends": []}
        t0 = env.begin_window()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= env.seconds:
                break
            traced = env.tick(elapsed)
            state, _, loss, wait = one_step(state)
            rec["steps"] += 1
            rec["traced_steps"] += int(traced)
            rec["failed"] += 0 if math.isfinite(loss) else 1
            rec["data_wait_s"].append(wait)
            rec["ends"].append(time.perf_counter() - t0)
        window_s = time.perf_counter() - t0
        reduced = env.end_window()
        data.close()
        del state, jitted
    peak = env.memory_peak()
    gc.collect()

    shape = counts.Shape.from_model(m)
    run_rec = {
        "kind": "train", "chips": env.chips, "peaks": env.peaks,
        "trace": reduced,
        "train": {
            "tokens_per_step": tokens_per_step,
            "flops_per_token": counts.train_flops_per_token(shape, job["seq"]),
            "traced_steps": rec["traced_steps"],
            "data_wait_s": rec["data_wait_s"],
        },
    }
    metrics = {
        "train_tokens_per_s": rec["steps"] * tokens_per_step / window_s,
    }

    # the reference, once the program's state is freed
    batches = [traffic.train_batch(job, V, seed, i) for i in range(CHECK_STEPS)]
    ref = tcheck.reference(fam, m, opt, lambda: fam.init(m, seed, pdtype), batches)
    numbers = tcheck.compare(prog, ref)
    env.hooks.get("reference", lambda r: None)(ref)
    env.log(f"[train] window: {durations_line(rec['ends'])}")
    env.log(f"[train] program losses {prog['losses']} reference {ref['losses']}")
    env.log(f"[train] grad norm program {prog['grad_norm']} reference {ref['grad_norm']}")
    return {
        "attempted": rec["steps"], "failed": rec["failed"],
        "metrics": metrics, "run": run_rec, "numbers": numbers,
        "memory_peak_bytes": peak, "memory_compiled_bytes": compiled,
    }
