import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step for train
shapes, prefill/serve_step for inference shapes) against ShapeDtypeStruct
inputs and abstract parameters (``jax.eval_shape`` — nothing is allocated),
with explicit in/out shardings resolved by the divisibility-aware rules in
``repro.models.sharding``, then:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
    compiled = lowered.compile()
    compiled.memory_analysis()   # proves the per-device footprint
    compiled.cost_analysis()     # XLA's own FLOPs/bytes (loop bodies x1)
    module_summary(as_text)      # loop-expanded FLOPs/bytes/collectives

and writes one JSON record per cell to experiments/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--cells-from file]
"""
import argparse
import gc
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, get_config, list_archs, shape_applicable
from repro.core.hlo_parser import module_summary
from repro.core.roofline import build_report, to_row
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.models import batch_logical_axes, build_model, input_specs
from repro.models.sharding import make_ctx, tree_specs, use_sharding
from repro.optim import make_optimizer
from repro.optim.schedules import cosine_with_warmup
from repro.train.step import abstract_state, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# shape-specific sharding-rule overrides (see DESIGN.md §5).  Decode cells
# shard the KV cache along the SEQUENCE dimension (split-KV / FlashDecoding
# adapted to SPMD): the resolver walks the candidates outside-in, skipping
# axes already consumed by the batch dim, so decode_32k lands on ("model",)
# and the batch=1 long_500k cell claims every idle axis.
_KV_SEQ = (("pod", "data", "model"), ("data", "model"), ("model",), ())
# decode activations replicate the head dim: with the cache sharded on seq,
# head-sharded q would force GSPMD into involuntary resharding of the
# repeated KV block (observed "full rematerialization" warning); per-token
# attention compute is tiny, so seq-parallel + replicated heads wins.
_DECODE = {"kv_seq": _KV_SEQ, "act_heads": ((),)}
SHAPE_RULE_OVERRIDES = {
    "decode_32k": _DECODE,
    "long_500k": _DECODE,
}


def _opt_state_axes(opt_name: str, params_axes):
    """Logical-axes tree for the optimizer state (mirrors optimizers.py)."""
    tup = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x
    )
    if opt_name == "adamw":
        return {
            "m": params_axes,
            "v": params_axes,
            "count": (),
        }
    if opt_name == "adafactor":
        def one(a):
            if len(a) >= 2:
                return {"vr": a[:-1], "vc": a[:-2] + a[-1:]}
            return {"v": a}

        return {
            "f": jax.tree_util.tree_map(one, params_axes, is_leaf=tup),
            "count": (),
        }
    raise ValueError(opt_name)


def _state_axes(opt_name: str, params_axes):
    # TrainState(step, params, opt_state)
    return ((), params_axes, _opt_state_axes(opt_name, params_axes))


def build_cell(arch: str, shape_name: str, multi_pod: bool, cfg=None):
    """Returns (fn, arg_shapes, in_shardings, out_shardings, ctx, meta)."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(cfg.sharding_overrides or {})
    overrides.update(SHAPE_RULE_OVERRIDES.get(shape_name, {}))
    ctx = make_ctx(mesh, overrides=overrides)
    model = build_model(cfg)
    batch_specs = input_specs(cfg, shape)
    batch_axes = batch_logical_axes(cfg, shape)
    sh = lambda spec: NamedSharding(mesh, spec)
    batch_sh = {
        k: sh(ctx.spec_for(batch_axes[k], v.shape, k))
        for k, v in batch_specs.items()
    }

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        state_shapes, params_axes = abstract_state(model, opt)
        # params: TP (+FSDP over data for >=100B configs); optimizer state:
        # always ZeRO-1 sharded over the data axis.
        params_specs = tree_specs(
            ctx, state_shapes.params, params_axes, zero1=cfg.fsdp_params
        )
        opt_specs = tree_specs(
            ctx,
            state_shapes.opt_state,
            _opt_state_axes(cfg.optimizer, params_axes),
            zero1=True,
        )
        state_specs = type(state_shapes)(P(), params_specs, opt_specs)
        state_sh = jax.tree_util.tree_map(
            sh, state_specs, is_leaf=lambda x: isinstance(x, P)
        )
        sched = cosine_with_warmup(3e-4, 100, 10_000)
        accum = cfg.grad_accum if shape.global_batch % max(cfg.grad_accum, 1) == 0 else 1
        step_fn = make_train_step(model, opt, sched, grad_accum=accum)
        metrics_sh = {
            k: sh(P()) for k in ("loss", "grad_norm", "lr", "ce", "aux")
        }

        def fn(state, batch):
            new_state, metrics = step_fn(state, batch)
            return new_state, {
                k: metrics.get(k, jnp.zeros(())) for k in metrics_sh
            }

        return (
            fn,
            (state_shapes, batch_specs),
            (state_sh, batch_sh),
            (state_sh, metrics_sh),
            ctx,
            {"donate": (0,), "kind": "train", "grad_accum": accum},
        )

    params_shapes, params_axes = model.abstract_params()
    # serving weights are bf16 (production checkpoints are served quantized
    # or half precision; the model casts to compute dtype at use anyway)
    params_shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape,
            jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype,
        ),
        params_shapes,
    )
    # >=100B-class configs additionally shard serving weights over the data
    # axis (weight-gathered serving) — TP alone leaves 50+ GB per chip.
    params_specs = tree_specs(
        ctx, params_shapes, params_axes, zero1=cfg.fsdp_params
    )
    params_sh = jax.tree_util.tree_map(
        sh, params_specs, is_leaf=lambda x: isinstance(x, P)
    )
    cache_dtype = jnp.bfloat16

    if shape.kind == "prefill":
        total_len = shape.seq_len  # patches included in the budget (vlm)

        def fn(params, batch):
            return model.prefill(params, batch, total_len)

        cache_shapes = jax.eval_shape(
            lambda p, b: model.prefill(p, b, total_len)[1],
            params_shapes, batch_specs,
        )
        cache_sh = jax.tree_util.tree_map(
            sh,
            tree_specs(ctx, cache_shapes, model.cache_axes()),
            is_leaf=lambda x: isinstance(x, P),
        )
        b = shape.global_batch
        logits_sh = sh(ctx.spec_for(("batch", None, "vocab"), (b, 1, cfg.vocab_size), "logits"))
        return (
            fn,
            (params_shapes, batch_specs),
            (params_sh, batch_sh),
            (logits_sh, cache_sh),
            ctx,
            {"donate": (), "kind": "prefill"},
        )

    # decode: one token against a cache of seq_len
    cache_shapes = model.abstract_cache(
        shape.global_batch, shape.seq_len, dtype=cache_dtype
    )
    cache_sh = jax.tree_util.tree_map(
        sh,
        tree_specs(ctx, cache_shapes, model.cache_axes()),
        is_leaf=lambda x: isinstance(x, P),
    )
    cache_len = shape.seq_len - 1  # write position of the new token

    def fn(params, cache, token):
        return model.decode(params, cache, token, cache_len)

    b = shape.global_batch
    logits_sh = sh(ctx.spec_for(("batch", None, "vocab"), (b, 1, cfg.vocab_size), "logits"))
    return (
        fn,
        (params_shapes, cache_shapes, batch_specs["token"]),
        (params_sh, cache_sh, batch_sh["token"]),
        (logits_sh, cache_sh),
        ctx,
        {"donate": (1,), "kind": "decode"},  # cache updated in place
    )


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             cfg=None, tag: str = "", run_spec=None) -> dict:
    multi_pod = mesh_name == "multi"
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": 512 if multi_pod else 256, "status": "",
        "variant": tag or "baseline",
    }
    if run_spec is not None:
        rec["run_spec"] = run_spec.to_dict()
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    )
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(path, rec)
        return rec
    try:
        t0 = time.time()
        fn, shapes, in_sh, out_sh, ctx, meta = build_cell(
            arch, shape_name, multi_pod, cfg=cfg
        )
        mesh = ctx.mesh
        with use_sharding(ctx):
            jitted = jax.jit(
                fn,
                in_shardings=in_sh,
                out_shardings=out_sh,
                donate_argnums=meta.get("donate", ()),
            )
            lowered = jitted.lower(*shapes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        t0 = time.time()
        text = compiled.as_text()
        summary = module_summary(text, mesh_info(mesh))
        t_parse = time.time() - t0
        report = build_report(
            cfg, shape, mesh_name, rec["chips"], summary,
            xla_cost={k: ca.get(k, 0.0) for k in ("flops", "bytes accessed")},
        )
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            parse_s=round(t_parse, 2),
            hlo_bytes=len(text),
            memory={
                "argument_size_in_bytes": ma.argument_size_in_bytes,
                "output_size_in_bytes": ma.output_size_in_bytes,
                "temp_size_in_bytes": ma.temp_size_in_bytes,
                "alias_size_in_bytes": ma.alias_size_in_bytes,
                "generated_code_size_in_bytes": ma.generated_code_size_in_bytes,
            },
            xla_cost={
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            },
            summary={
                k: v for k, v in summary.items() if k != "graph"
            },
            roofline=to_row(report),
            sharding_drops=[str(d) for d in ctx.drops[:40]],
            num_drops=len(ctx.drops),
            meta=meta,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _write(path, rec)
    return rec


def _write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main() -> None:
    from repro.launch import spec as runspec

    ap = argparse.ArgumentParser()
    # shared launch surface (repro.launch.spec): --arch/--smoke/--seed plus
    # the dryrun cell selectors --shape/--mesh
    runspec.add_args(ap, "model", "dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cells-from", default=None,
                    help="file with one 'arch|shape|mesh' per line")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    spec = runspec.from_args(args)

    cells: list[tuple[str, str, str]] = []
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    if args.cells_from:
        with open(args.cells_from) as f:
            for line in f:
                line = line.strip()
                if line:
                    a, s, m = line.split("|")
                    cells.append((a, s, m))
    elif args.all:
        for a in list_archs():
            for s in SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        assert args.arch and args.shape
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    for a, s, m in cells:
        path = os.path.join(args.out, f"{a}__{s}__{m}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip existing] {a} {s} {m}", flush=True)
            continue
        t0 = time.time()
        rec = run_cell(a, s, m, args.out, run_spec=spec)
        dt = time.time() - t0
        msg = rec["status"]
        if msg == "ok":
            mem = rec["memory"]["temp_size_in_bytes"] / 2**30
            msg += (
                f" compile={rec['compile_s']}s temp={mem:.2f}GiB "
                f"flops/dev={rec['summary']['flops']:.3g} "
                f"coll(ici/dcn)={rec['summary']['collective_bytes_ici']:.3g}/"
                f"{rec['summary']['collective_bytes_dcn']:.3g}"
            )
        elif msg == "error":
            msg += " " + rec["error"][:160]
        print(f"[{dt:7.1f}s] {a} {s} {m}: {msg}", flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
