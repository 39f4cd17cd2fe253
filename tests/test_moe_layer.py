"""MoE layer properties: the dropless one-chip layer, and the capacity
dispatch of the all-to-all layer (on a one-device mesh)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.dist.ep_a2a import capacity, moe_ffn_ep_a2a
from repro.models.moe import init_moe, moe_ffn


def _ep_a2a(p, x, moe):
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return moe_ffn_ep_a2a(p, x, moe, jnp.float32, mesh)


def make(num_experts=8, top_k=2, cf=8.0, group=64):
    return MoEConfig(
        num_experts=num_experts, top_k=top_k, d_ff_expert=32,
        capacity_factor=cf, group_size=group,
    )


def test_capacity_formula():
    moe = make(num_experts=8, top_k=2, cf=1.0, group=64)
    assert capacity(moe, 64) == 16
    assert capacity(make(num_experts=512, top_k=1, cf=1.0), 64) == 1


def test_moe_output_finite_and_shaped(rng):
    moe = make()
    p, axes = init_moe(jax.random.PRNGKey(0), 64, moe, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 64, 64)), jnp.float32)
    y, aux, load = jax.jit(lambda p, x: moe_ffn(p, x, moe, jnp.float32))(p, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert float(aux) > 0
    assert float(jnp.sum(load)) == 2 * 64 * moe.top_k


def test_group_size_invariance_without_drops(rng):
    """With capacity high enough for zero drops, the all-to-all layer's
    grouping must not change the output (each token's expert set is
    group-independent), and it equals the dropless layer."""
    p, _ = init_moe(jax.random.PRNGKey(0), 32, make(), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    y1, _, load1 = _ep_a2a(p, x, make(group=32))
    y2, _, _ = _ep_a2a(p, x, make(group=128))
    y3, _, load3 = moe_ffn(p, x, make(), jnp.float32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), rtol=1e-4, atol=1e-5)
    # the load counts every assignment, dropped or not, as the dropless layer's
    np.testing.assert_array_equal(np.asarray(load1), np.asarray(load3))


def test_capacity_drops_zero_tokens(rng):
    """With capacity 1 and many tokens per expert, most tokens are dropped
    (output rows become zero), never NaN."""
    moe = dataclasses.replace(make(cf=0.01), router_aux_loss=0.0)
    p, _ = init_moe(jax.random.PRNGKey(0), 16, moe, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 64, 16)), jnp.float32)
    y, _, _ = _ep_a2a(p, x, moe)
    assert bool(jnp.isfinite(y).all())
    zero_rows = int(jnp.sum(jnp.all(y == 0.0, axis=-1)))
    assert zero_rows > 0


def test_moe_gradients_flow(rng):
    moe = make()
    p, _ = init_moe(jax.random.PRNGKey(0), 32, moe, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 64, 32)), jnp.float32)

    def loss(p):
        y, aux, _ = moe_ffn(p, x, moe, jnp.float32)
        return jnp.sum(jnp.square(y)) + aux

    g = jax.grad(loss)(p)
    for k in ("wg", "wu", "wd", "router"):
        assert float(jnp.sum(jnp.abs(g[k]))) > 0, k
