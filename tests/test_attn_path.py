"""The attention path selector (``layers.attn_path``) and the flash route.

The selector is a pure function of the platform, the device count and the
shapes, so its choices are tested here on the CPU.  The flash route itself
runs interpreted: a stand-in placement says "one TPU" and the kernel is
asked for in interpret mode.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_variant
from repro.kernels import flash_attention
from repro.models import build_model
from repro.models import layers as L
from repro.models import transformer as T

CFG = get_config("moonlight-16b-a3b")
TPU = {"platform": "tpu", "devices": 1}


@pytest.mark.parametrize("case,want", [
    (dict(sq=8192, skv=8192), "flash"),
    (dict(sq=16384, skv=16384), "flash"),
    (dict(sq=8192, skv=8192, platform="cpu"), "blockwise"),
    (dict(sq=8192, skv=8192, devices=4), "blockwise"),
    (dict(sq=8192, skv=8192, bidirectional=True), "blockwise"),
    (dict(sq=8192, skv=8192, q_offset=128), "blockwise"),
    (dict(sq=8192, skv=8192, mask=np.ones((1, 1, 8192, 8192), bool)), "dense"),
    (dict(sq=4096, skv=8192), "blockwise"),          # not self-attention
    (dict(sq=8192 + 64, skv=8192 + 64), "blockwise"),  # not a multiple of 128
    (dict(sq=2048, skv=2048), "dense"),              # below LONG_KV
    (dict(sq=1, skv=8192), "dense"),                 # decode
])
def test_attn_path_chooses_flash_only_where_sound(case, want):
    case = dict(case)
    placed = {k: case.pop(k, v) for k, v in TPU.items()}
    assert L.attn_path(case.pop("sq"), case.pop("skv"), **placed, **case) == want


def test_attn_path_decode_offset_is_traced():
    """Decode passes its cache length as a traced offset: never flash."""
    seen = []
    jax.make_jaxpr(lambda n: seen.append(
        L.attn_path(8192, 8192, q_offset=n, **TPU)))(jnp.int32(0))
    assert seen == ["blockwise"]


def _tiny(monkeypatch):
    """The smoke-size latent-attention config, attention leaving the dense
    path from 128 keys."""
    monkeypatch.setattr(L, "LONG_KV", 128)
    return smoke_variant(CFG)


@pytest.mark.parametrize("q_offset,sq,bidirectional", [
    (0, 64, False), (32, 32, False), (0, 64, True),
], ids=["causal", "q_offset", "bidirectional"])
def test_blockwise_matches_dense(q_offset, sq, bidirectional, monkeypatch):
    """The blockwise scan (4 key blocks of 16 over 64 keys) against one
    dense softmax under the same mask: 2 KV heads of 3 queries each,
    query/key width 24 against value width 16."""
    monkeypatch.setattr(L, "BLOCK_KV", 16)
    rng = np.random.default_rng(1)
    b, skv, kh, g = 2, 64, 2, 3
    qg = jnp.asarray(rng.standard_normal((b, sq, kh, g, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kh, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kh, 16)), jnp.float32)
    if bidirectional:
        mask = jnp.ones((1, 1, 1, 1, 1), bool)
    else:
        mask = L.causal_mask(sq, skv, offset=q_offset)[:, :, None]
    with jax.default_matmul_precision("highest"):
        got = L._sdpa_blockwise(qg, k, v, q_offset=q_offset,
                                bidirectional=bidirectional)
        want = L._sdpa_dense(qg, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_layers_zero_off_tpu(monkeypatch):
    """The ``attn.flash_layers`` counter that ``run_timed_step`` records
    from the step's metrics reads 0 on the CPU, even at a sequence the
    kernel would take; on one TPU the count is every layer."""
    from repro.obs.record import Recorder
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train.step import TrainState, make_train_step, run_timed_step

    cfg = _tiny(monkeypatch)
    model = build_model(cfg)
    opt = make_optimizer("adamw")
    params, _ = model.init(jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params), None)
    step = jax.jit(make_train_step(model, opt, cosine_with_warmup(1e-3, 1, 10)))
    batch = {k: jnp.zeros((1, 128), jnp.int32) for k in ("tokens", "labels")}
    rec = Recorder(enabled=True)
    run_timed_step(step, state, batch, rec, "s0")
    assert [(c.device, c.value) for c in rec.counters
            if c.name == "attn.flash_layers"] == [("host", 0.0)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(L, "placement", lambda: TPU)
        assert int(T.flash_layers(params, cfg, 128)) == cfg.num_layers
        assert int(T.flash_layers(params, cfg, 64)) == 0


def test_sdpa_flash_route_matches_dense(monkeypatch):
    """Latent attention (query/key width 24 against value width 16) through
    ``_sdpa``'s flash route, the kernel interpreted, against the dense route
    of the same inputs: forward and gradients."""
    cfg = smoke_variant(CFG)
    rng = np.random.default_rng(0)
    b, s, h = 2, 256, cfg.num_heads
    q = jnp.asarray(rng.standard_normal((b, s, h, cfg.qk_head_dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, cfg.qk_head_dim)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, cfg.v_dim)), jnp.float32)

    def run():
        f = lambda *qkv: L._sdpa(*qkv, None)
        out = f(q, k, v)
        grads = jax.grad(lambda *qkv: jnp.sum(f(*qkv) ** 2), argnums=(0, 1, 2))(q, k, v)
        return out, grads

    with jax.default_matmul_precision("highest"):
        assert L.attn_path(s, s, **L.placement()) == "dense"
        want = run()
        _tiny(monkeypatch)
        monkeypatch.setattr(L, "placement", lambda: TPU)
        monkeypatch.setattr(L, "flash_attention",
                            functools.partial(flash_attention, interpret=True))
        assert L.attn_path(s, s, **L.placement()) == "flash"
        got = run()
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(w).max()))
