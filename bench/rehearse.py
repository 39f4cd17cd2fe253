"""Compile a cell's programs at their real sizes for a described TPU v5e,
without the chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py <workload> [<workload> ...]

For a training cell: the train step on one device and the reference's
step; for a serving cell: the weight draw, the decode batch, the widest
prefill bucket and the reference's pass.  Nothing runs: this shows what the compiler refuses and the bytes it plans,
never a time.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

GiB = 2 ** 30


def _report(name, compiled):
    ma = compiled.memory_analysis()
    tot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"[rehearse] {name}: args {ma.argument_size_in_bytes / GiB:.2f} GiB, "
          f"out {ma.output_size_in_bytes / GiB:.2f}, temp {ma.temp_size_in_bytes / GiB:.2f}, "
          f"alias {ma.alias_size_in_bytes / GiB:.2f}, total {tot / GiB:.2f} GiB "
          f"({tot} B)", flush=True)


def _sds(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def train_programs(cell, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, SingleDeviceSharding

    from bench import program
    from repro.models import build_model
    from repro.models.sharding import make_ctx, use_sharding
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train import make_sharded_train_step
    from repro.train.step import TrainState

    conf, job = cell.config, cell.traffic
    m, opt = conf["model"], conf["optimizer"]
    fam = cell.family()
    cfg = program.arch_config(conf)
    model = build_model(cfg)
    optimizer = make_optimizer("adamw", b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                               weight_decay=opt["weight_decay"])
    sched = cosine_with_warmup(opt["lr"], opt["warmup"], opt["total_steps"], opt["min_ratio"])
    mesh = Mesh([topo.devices[0]], ("data",))
    one = SingleDeviceSharding(topo.devices[0])
    pdtype = conf["program"]["param_dtype"]
    params = fam.abstract(m, pdtype)
    step = make_sharded_train_step(model, optimizer, sched, mesh, grad_accum=1,
                                   max_grad_norm=opt["max_grad_norm"])
    state = jax.eval_shape(lambda p: TrainState(jnp.zeros((), jnp.int32), p,
                                                optimizer.init(p), None), params)
    batch = {k: jax.ShapeDtypeStruct((job["batch"], job["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    with use_sharding(make_ctx(mesh, overrides=cfg.sharding_overrides)):
        c = jax.jit(step, donate_argnums=(0,)).lower(
            _sds(state, one), _sds(batch, one)).compile()
    _report(f"{cell.name} train step", c)
    # the reference's step, compiled as the check runs it
    p32 = fam.abstract(m, "float32")

    def ref_step(p, mu, nu, tok, lab):
        loss, g = jax.value_and_grad(lambda q: fam.loss(m, q, tok, lab))(p)
        return jax.tree_util.tree_map(lambda a, b, c, d: a - 1e-4 * (b + c + d), p, mu, nu, g), loss
    c = jax.jit(ref_step, donate_argnums=(0, 1, 2)).lower(
        _sds(p32, one), _sds(p32, one), _sds(p32, one), *_sds(list(batch.values()), one)).compile()
    _report(f"{cell.name} reference step (approximation: one update, as many live trees)", c)


def serve_programs(cell, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench import program
    from bench.check import serve as scheck
    from repro.serve import paged
    from repro.serve.policy import ServeConfig

    conf, tr = cell.config, cell.traffic
    m, e = conf["model"], conf["engine"]
    fam = cell.family()
    cfg = program.arch_config(conf)
    one = SingleDeviceSharding(topo.devices[0])
    pdtype = conf["program"]["param_dtype"]
    scfg = ServeConfig(slots=e["slots"], max_len=e["max_len"], block_size=e["block_size"],
                       chunk=e["chunk"])
    from bench.check.families.dense import seed_key

    key = jax.eval_shape(lambda: seed_key(0))
    c = jax.jit(fam.draw_fn(m, pdtype)).lower(_sds(key, one)).compile()
    _report(f"{cell.name} weight draw", c)
    params = _sds(fam.abstract(m, pdtype), one)
    pool = _sds(jax.eval_shape(lambda: paged.init_pool(cfg, scfg)), one)
    S, mb = e["slots"], scfg.max_blocks_per_slot
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    c = jax.jit(lambda p, pl, t, ln, tb: paged.decode_batch(p, pl, t, ln, tb, cfg, scfg)).lower(
        params, pool, i32(S, 1), i32(S), i32(S, mb)).compile()
    _report(f"{cell.name} decode batch ({S} slots, view {scfg.view_len})", c)
    c = jax.jit(lambda p, pl, t, st, w, row: paged.prefill_chunk(
        p, pl, t, st, w, row, 0, cfg, scfg)).lower(
        params, pool, i32(1, e["chunk"]), i32(), i32(), i32(mb)).compile()
    _report(f"{cell.name} prefill chunk (bucket {e['chunk']})", c)
    out_max = tr["output"]["max"]
    for control in (False, True):
        fn = scheck._gap_fn(fam, tuple(sorted(m.items())), control)
        c = fn.lower(params, i32(1, e["max_len"]), i32(out_max), i32(out_max),
                     jax.ShapeDtypeStruct((out_max,), jnp.bool_, sharding=one)).compile()
        _report(f"{cell.name} reference pass{' with control' if control else ''} "
                f"({e['max_len']} positions)", c)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workloads", nargs="+")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies

    from bench import manifest

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for w in args.workloads:
        cell = manifest.load_cell(w)
        if cell.config["kind"] == "train":
            train_programs(cell, topo)
        else:
            serve_programs(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
