"""Training cell of a latent-attention MoE model holding one chip's share of
its experts: the compiled step that ``repro.launch.train`` builds.

As ``bench/drivers/train.py`` (set-up builds ``make_sharded_train_step`` on
``build_mesh``, puts the benchmark's weights and a fresh AdamW state on the
mesh, and runs the first three steps through the window's own compiled
call and feed; each step is synced on its loss), with what such a model
adds:

* the program's configuration is its registry entry cut as the file says
  (layers, the held experts, the vocabulary slice), and every published
  width, routing setting and balancing constant is checked against the
  file;
* each step's ``held_assignments`` (the program's ``moe.held_assignments``
  counter: assignments computed by the held experts) is kept on the device
  and read after the window, so that the model FLOPs of the traced steps
  count the routed experts at the work they were given;
* the reference's step moves the router's correction bias as the program's
  does, and the biases after the check steps are compared too
  (``bench/check/train_moe.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

CHECK_STEPS = 3


def arch_config(conf: dict):
    """The registry entry, changed as ``program.overrides`` says (its
    ``moe`` entry changes the expert settings) and cut to the file's share,
    then checked against the file."""
    from repro.configs.base import get_config

    prog = conf["program"]
    over = dict(prog["overrides"])
    base = get_config(prog["registry"])
    moe = dataclasses.replace(
        base.moe, **over.pop("moe", {}),
        experts_held=(conf["experts_held_first"], conf["n_routed_experts"]))
    cfg = dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"], vocab_size=conf["vocab_size"],
        moe=moe, **over)
    want = {
        "d_model": conf["hidden_size"], "num_heads": conf["num_attention_heads"],
        "num_kv_heads": conf["num_key_value_heads"], "d_ff": conf["intermediate_size"],
        "kv_lora_rank": conf["kv_lora_rank"], "qk_nope_head_dim": conf["qk_nope_head_dim"],
        "qk_rope_head_dim": conf["qk_rope_head_dim"], "v_head_dim": conf["v_head_dim"],
        "rope_theta": conf["rope_theta"], "norm_eps": conf["rms_norm_eps"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "first_k_dense": conf["first_k_dense_replace"], "family": "moe",
        "moe.num_experts": conf["experts_routed"],
        "moe.top_k": conf["num_experts_per_tok"],
        "moe.d_ff_expert": conf["moe_intermediate_size"],
        "moe.num_shared_experts": conf["n_shared_experts"],
        "moe.score_func": conf["scoring_func"],
        "moe.routed_scaling_factor": conf["routed_scaling_factor"],
        "moe.router_aux_loss": conf["seq_aux_alpha"],
        "moe.bias_update_rate": conf["bias_update_speed"],
    }
    got = {k: getattr(cfg.moe, k[4:]) if k.startswith("moe.") else getattr(cfg, k)
           for k in want}
    if (got != want or conf["q_lora_rank"] is not None or conf["n_group"] != 1
            or not conf["norm_topk_prob"]
            or conf["topk_method"] != "noaux_tc" or not conf["seq_aux"]):
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def run(cell, env) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import moe_work, program, traffic
    from bench.check import train_moe as tcheck
    from bench.stats import durations_line
    from repro.data.pipeline import Prefetcher
    from repro.launch.train import build_mesh
    from repro.models import build_model
    from repro.models.sharding import make_ctx, use_sharding
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train import make_sharded_train_step
    from repro.train.step import TrainState, train_state_specs

    conf, job = cell.config, cell.traffic
    m, opt = conf, conf["optimizer"]
    fam = cell.family()
    pdtype = conf["program"]["param_dtype"]
    seed = env.seed
    cfg = arch_config(conf)
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

        prog = {"losses": []}
        for i in range(CHECK_STEPS):
            state, met, loss, _ = one_step(state)
            prog["losses"].append(loss)
            if i == 0:
                prog["grad_norm"] = float(met["grad_norm"])
                prog["grad_leaf"] = tcheck.leaf_norms(
                    state.opt_state["m"]) / (1.0 - opt["b1"])
        prog["bias"] = np.asarray(state.params["blocks"]["moe"]["bias"], np.float64)
        p0 = jax.device_put(fam.init(m, seed, pdtype), replicated)
        prog["update_leaf"] = tcheck.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, state.params, p0))
        del p0
        compiled = program.compiled_bytes(jitted.lower(state, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=batch_sharding),
            traffic.train_batch(job, V, seed, 0))))
        env.log(f"[memory] train step, compiled: {compiled} B")

        rec = {"steps": 0, "failed": 0, "data_wait_s": [], "traced": [],
               "ends": []}
        t0 = env.begin_window()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= env.seconds:
                break
            traced = env.tick(elapsed)
            state, met, loss, wait = one_step(state)
            rec["steps"] += 1
            if traced:
                rec["traced"].append(met["held_assignments"])
            rec["failed"] += 0 if math.isfinite(loss) else 1
            rec["data_wait_s"].append(wait)
            rec["ends"].append(time.perf_counter() - t0)
        window_s = time.perf_counter() - t0
        reduced = env.end_window()
        data.close()
        held = [float(h) for h in jax.device_get(rec["traced"])]
        load = jax.device_get(met["expert_tokens"])
        del state, jitted, met
    peak = env.memory_peak()
    gc.collect()

    per_seq = sum(held) / len(held) / job["batch"] if held else 0.0
    run_rec = {
        "kind": "train", "chips": env.chips, "peaks": env.peaks,
        "trace": reduced,
        "train": {
            "tokens_per_step": tokens_per_step,
            "flops_per_token": moe_work.train_flops_per_token(m, job["seq"], per_seq),
            "traced_steps": len(held),
            "data_wait_s": rec["data_wait_s"],
        },
        "moe": {"held_assignments": held},
    }
    metrics = {
        "train_tokens_per_s": rec["steps"] * tokens_per_step / window_s,
    }

    batches = [traffic.train_batch(job, V, seed, i) for i in range(CHECK_STEPS)]
    ref = tcheck.reference(fam, m, opt, lambda: fam.init(m, seed, pdtype), batches,
                           gamma=m["bias_update_speed"])
    numbers = tcheck.compare(prog, ref, m["bias_update_speed"])
    env.hooks.get("reference", lambda r: None)(ref)
    env.log(f"[train] window: {durations_line(rec['ends'])}")
    env.log(f"[train] program losses {prog['losses']} reference {ref['losses']}")
    env.log(f"[train] grad norm program {prog['grad_norm']} reference {ref['grad_norm']}")
    env.log(f"[moe] last step's assignments per expert, summed over layers: "
            f"{[int(x) for x in load.sum(0)]}; held per traced step {held}")
    return {
        "attempted": rec["steps"], "failed": rec["failed"],
        "metrics": metrics, "run": run_rec, "numbers": numbers,
        "memory_peak_bytes": peak, "memory_compiled_bytes": compiled,
    }
