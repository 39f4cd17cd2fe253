"""Multi-device correctness via subprocess (8 forced host devices).

Exercises the collectives-dependent layers that single-device tests cannot:
gradient compression over a real psum, the shard_map pipeline with real
ppermutes, and elastic re-meshing across device counts.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    # --- compressed_psum over a real 8-way mesh ---
    from repro.dist.compress import compressed_psum, init_compression_state
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    local = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

    def body(g):
        mean, _ = compressed_psum({"g": g}, "data", None)
        return mean["g"]

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")))(local)
    true_mean = np.mean(np.asarray(local), axis=0)
    got = np.asarray(out)[0]
    err = np.max(np.abs(got - true_mean)) / (np.max(np.abs(true_mean)) + 1e-9)
    assert err < 0.02, f"compressed mean err {err}"
    print("compress_ok")

    # --- shard_map pipeline over 4 real stages == sequential ---
    from repro.dist.pp import pipeline_step_shard_map
    mesh4 = jax.make_mesh((4,), ("stage",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    L, M, B, D = 8, 6, 2, 16
    w = jnp.asarray(rng.standard_normal((L, D, D)), jnp.float32) * 0.2
    xs = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)
    layer_fn = lambda p, x: jnp.tanh(x @ p["w"])
    out = pipeline_step_shard_map({"w": w}, xs, layer_fn, mesh4)

    def seq(x):
        for i in range(L):
            x = jnp.tanh(x @ w[i])
        return x

    expect = jax.vmap(seq)(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    print("pipeline_ok")

    # --- gradient of the pipeline matches sequential gradient ---
    g1 = jax.grad(lambda w_: pipeline_step_shard_map(
        {"w": w_}, xs, layer_fn, mesh4).sum())(w)
    def seq_loss(w_):
        def s(x):
            for i in range(L):
                x = jnp.tanh(x @ w_[i])
            return x
        return jax.vmap(s)(xs).sum()
    g2 = jax.grad(seq_loss)(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)
    print("pipeline_grad_ok")

    # --- scheduled executor (explicit 1F1B / interleaved backward) over
    # real stages: loss + grads == sequential autodiff ---
    from repro.dist.pp import pipeline_schedule_shard_map
    from repro.dist.schedules import make_schedule
    M2 = 4
    xs2 = jnp.asarray(rng.standard_normal((M2, B, D)), jnp.float32)

    def seq_sched_loss(w_):
        def s(x):
            for i in range(L):
                x = jnp.tanh(x @ w_[i])
            return x
        ys = jax.vmap(s)(xs2)
        return 0.5 * jnp.sum(ys * ys)

    ref_loss, ref_grad = seq_sched_loss(w), jax.grad(seq_sched_loss)(w)
    mesh2s = jax.make_mesh((2,), ("stage",),
                           axis_types=(jax.sharding.AxisType.Auto,))
    for name, S, v, msh in (("gpipe", 4, 1, mesh4),
                            ("1f1b", 4, 1, mesh4),
                            ("interleaved_1f1b", 2, 2, mesh2s)):
        sch = make_schedule(name, S, M2, v)
        loss, outs, grads = jax.jit(
            lambda p, x, sch=sch, msh=msh: pipeline_schedule_shard_map(
                p, x, layer_fn, msh, sch
            )
        )({"w": w}, xs2)
        assert abs(float(loss) - float(ref_loss)) < 1e-4 * abs(float(ref_loss))
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(ref_grad),
                                   rtol=1e-4, atol=1e-4)
    print("scheduled_pp_ok")

    # --- explicit a2a expert parallelism == the dropless layer (no drops) ---
    import dataclasses
    from repro.configs.base import MoEConfig
    from repro.models import moe as moe_mod
    from repro.models.moe import init_moe, moe_ffn
    moe_mod.INTERPRET = True   # the grouped product, op by op on the CPU
    from repro.models.sharding import make_ctx, use_sharding
    mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    base = MoEConfig(num_experts=8, top_k=2, d_ff_expert=64,
                     capacity_factor=8.0, group_size=32)
    a2a = dataclasses.replace(base, impl="ep_a2a")
    pm, _ = init_moe(jax.random.PRNGKey(0), 32, base, jnp.float32)
    xm = jnp.asarray(rng.standard_normal((8, 16, 32)), jnp.float32)
    y_ref, _, load_ref = moe_ffn(pm, xm, base, jnp.float32)
    ctx = make_ctx(mesh2)
    with use_sharding(ctx), mesh2:
        xs2 = jax.device_put(xm, NamedSharding(mesh2, P("data", None, None)))
        ps = {
            "router": jax.device_put(pm["router"], NamedSharding(mesh2, P())),
            "wg": jax.device_put(pm["wg"], NamedSharding(mesh2, P("data", None, "model"))),
            "wu": jax.device_put(pm["wu"], NamedSharding(mesh2, P("data", None, "model"))),
            "wd": jax.device_put(pm["wd"], NamedSharding(mesh2, P("data", "model", None))),
        }
        y2, _, load2 = jax.jit(lambda p_, x_: moe_ffn(p_, x_, a2a, jnp.float32))(ps, xs2)
        ge = jax.jit(jax.grad(lambda p_: moe_ffn(p_, xs2, a2a, jnp.float32)[0].sum()))(ps)
    assert float(jnp.max(jnp.abs(y2 - y_ref))) < 1e-3
    np.testing.assert_array_equal(np.asarray(load2), np.asarray(load_ref))
    assert float(jnp.sum(jnp.abs(ge["wg"]))) > 0
    print("ep_a2a_ok")

    # --- the dropless layer on the mesh: tokens over data, held experts
    # over model, each device its part, summed == the one-device layer ---
    r = jnp.asarray(rng.standard_normal(xm.shape), jnp.float32)
    lossf = lambda p_, x_: jnp.sum(moe_ffn(p_, x_, base, jnp.float32)[0] * r)
    g_ref = jax.grad(lossf)(pm, xm)
    with use_sharding(ctx), mesh2:
        pd = {k: jax.device_put(v, NamedSharding(mesh2, P("model") if k != "router" else P()))
              for k, v in pm.items()}
        y3, _, load3 = jax.jit(lambda p_, x_: moe_ffn(p_, x_, base, jnp.float32))(pd, xs2)
        g3 = jax.jit(jax.grad(lossf))(pd, xs2)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(load3), np.asarray(load_ref))
    for k in ("router", "wg", "wu", "wd"):
        np.testing.assert_allclose(np.asarray(g3[k]), np.asarray(g_ref[k]),
                                   rtol=1e-4, atol=1e-5)
    print("dropless_mesh_ok")

    # --- elastic re-mesh: move a sharded tree 8 -> 4 devices ---
    from repro.ft import apply_remesh, plan_remesh
    from repro.models.sharding import make_ctx
    plan = plan_remesh((4, 2), ("data", "model"), available_chips=4,
                       global_batch=8)
    assert plan.new_chips == 4 and plan.new_shape[-1] == 2
    small = jax.make_mesh(plan.new_shape, plan.axis_names,
                          axis_types=(jax.sharding.AxisType.Auto,) * 2,
                          devices=jax.devices()[:4])
    ctx = make_ctx(small)
    tree = {"emb": jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)}
    axes = {"emb": ("vocab", "embed")}
    moved = apply_remesh(tree, axes, ctx)
    np.testing.assert_array_equal(np.asarray(moved["emb"]),
                                  np.asarray(tree["emb"]))
    print("remesh_ok")
    """
)


@pytest.mark.slow
def test_multidevice_stack():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("compress_ok", "pipeline_ok", "pipeline_grad_ok",
                   "scheduled_pp_ok", "ep_a2a_ok", "dropless_mesh_ok",
                   "remesh_ok"):
        assert marker in out.stdout, (marker, out.stdout, out.stderr[-1500:])
