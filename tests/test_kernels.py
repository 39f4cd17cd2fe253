"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, fused_rmsnorm, ssd_scan
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def tol(dtype):
    if dtype == jnp.bfloat16:
        return {"rtol": 2e-2, "atol": 2e-2}
    return {"rtol": 2e-5, "atol": 2e-5}


# -- flash attention -------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,kh,d,dv", [
    (1, 128, 4, 4, 64, 64),       # MHA, exact block
    (2, 256, 8, 2, 64, 64),       # GQA, group 4, two batch rows
    (1, 384, 6, 3, 128, 128),     # head_dim 128, group 2, 3 x 3 blocks
    (2, 128, 2, 1, 32, 32),       # MQA, small
    (1, 256, 4, 2, 192, 128),     # latent attention widths, GQA group 2
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(rng, b, s, h, kh, d, dv, dtype, causal):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kh, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kh, dv)), dtype)
    out = flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128, interpret=True
    )
    ref = attention_ref(q, k, v, causal=causal)
    assert out.shape == (b, s, h, dv) and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("b,s,h,kh,d,dv", [
    (1, 128, 2, 2, 32, 32),       # MHA, one block
    (1, 256, 4, 2, 192, 128),     # latent attention widths, GQA, 2 x 2 blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_matches_ref(rng, b, s, h, kh, d, dv, dtype):
    """Gradients of q, k and v through the kernel's dq and dkv kernels."""
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kh, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kh, dv)), dtype)
    w = jnp.asarray(rng.standard_normal((b, s, h, dv)), jnp.float32)

    def grads(fn):
        loss = lambda *qkv: jnp.sum(fn(*qkv).astype(jnp.float32) * w)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda *qkv: flash_attention(
        *qkv, block_q=128, block_kv=128, interpret=True))
    want = grads(attention_ref)
    rel = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for g1, g2 in zip(got, want):
        assert g1.dtype == dtype
        g1, g2 = np.asarray(g1, np.float32), np.asarray(g2, np.float32)
        np.testing.assert_allclose(g1, g2, rtol=0, atol=rel * np.abs(g2).max())


def test_flash_attention_cross_lengths(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    out = flash_attention(
        q, k, v, causal=False, block_q=128, block_kv=128, interpret=True
    )
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_refuses_unaligned_length(rng):
    q = jnp.zeros((1, 200, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, q, q, interpret=True)


# -- rmsnorm ------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 64), (3, 37, 512), (2, 4, 8, 128), (1, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rng, shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    out = fused_rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_rmsnorm_grad(rng):
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
    g1 = jax.grad(lambda x_: fused_rmsnorm(x_, w, interpret=True).sum())(x)
    g2 = jax.grad(lambda x_: rmsnorm_ref(x_, w).sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-5)


# -- ssd scan ------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 32, 32, 32),
    (2, 256, 4, 32, 64, 64),
    (1, 192, 1, 64, 128, 64),   # odd chunk count
    (2, 64, 8, 16, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(rng, b, s, h, p, n, chunk, dtype):
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), dtype)
    B = jnp.asarray(rng.standard_normal((b, s, h, n)), dtype)
    C = jnp.asarray(rng.standard_normal((b, s, h, n)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (b, s, h)), jnp.float32)
    A = jnp.asarray(-np.exp(rng.standard_normal(h)), jnp.float32)
    y, st = ssd_scan(x, B, C, dt, A, chunk=chunk, interpret=True)
    yr, str_ = ssd_scan_ref(x, B, C, dt, A, chunk)
    if dtype == jnp.bfloat16:
        t = {"rtol": 5e-2, "atol": 5e-2}
    else:
        t = {"rtol": 2e-4, "atol": 2e-4}
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **t
    )
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), **t)


def test_ssd_state_equals_sequential_recurrence(rng):
    """The chunked kernel's final state == token-by-token recurrence."""
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
    A = jnp.asarray(-np.exp(rng.standard_normal(h)), jnp.float32)
    _, st = ssd_scan(x, B, C, dt, A, chunk=16, interpret=True)
    state = np.zeros((b, h, n, p), np.float32)
    for t in range(s):
        decay = np.exp(np.asarray(dt[:, t] * A))  # (b,h)
        contrib = np.einsum(
            "bhn,bhp->bhnp",
            np.asarray(B[:, t] * dt[:, t][..., None]),
            np.asarray(x[:, t]),
        )
        state = state * decay[:, :, None, None] + contrib
    np.testing.assert_allclose(np.asarray(st), state, rtol=2e-4, atol=2e-4)
