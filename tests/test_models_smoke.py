"""Per-arch smoke tests: reduced config, one train step on CPU, shapes +
finiteness; prefill/decode consistency against full-forward logits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    SHAPES,
    ShapeConfig,
    get_config,
    list_archs,
    shape_applicable,
    smoke_shape,
    smoke_variant,
)
from repro.models import build_model, make_concrete_batch

ARCHS = list_archs()


def test_all_archs_registered():
    assert len(ARCHS) == 11


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, smoke_shape("train"))
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(lambda p, b: model.loss(p, b), has_aux=True)
    )(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    gnorm = sum(
        float(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g in jax.tree_util.tree_leaves(grads)
    )
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    cfg = smoke_variant(get_config(arch))
    if cfg.moe is not None:  # avoid capacity-drop nondeterminism (see moe.py)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1), dtype=np.int32))
    batch = {"tokens": toks[:, :S]}
    if cfg.num_patches:
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.num_patches, cfg.vision_dim)), jnp.float32
        )
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.frontend_dim)), jnp.float32
        )
    max_len = S + cfg.num_patches + 8
    _, cache = jax.jit(lambda p, b: model.prefill(p, b, max_len))(params, batch)
    cache_len = S + cfg.num_patches
    dec_logits, _ = jax.jit(model.decode)(
        params, cache, toks[:, S:S + 1], cache_len
    )
    batch2 = dict(batch, tokens=toks)
    full_logits, _ = jax.jit(lambda p, b: model.prefill(p, b, max_len))(
        params, batch2
    )
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, -1]),
        np.asarray(full_logits[:, -1]),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_cells(arch):
    from repro.models import input_specs

    cfg = get_config(arch)
    for shape in SHAPES.values():
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            assert "long_500k" in why or shape.name == "long_500k"
            continue
        specs = input_specs(cfg, shape)
        assert "tokens" in specs or "token" in specs


def test_param_counts_match_assignment():
    """Analytic parameter counts land near the advertised sizes."""
    expect = {
        "phi4-mini-3.8b": (3.8e9, 0.35),
        "qwen1.5-110b": (110e9, 0.25),
        "llama3.2-1b": (1.24e9, 0.35),
        "granite-3-2b": (2.5e9, 0.45),
        "pixtral-12b": (12e9, 0.30),
        "kimi-k2-1t-a32b": (1.0e12, 0.25),
        "qwen3-moe-235b-a22b": (235e9, 0.25),
        "jamba-1.5-large-398b": (398e9, 0.30),
        "mamba2-2.7b": (2.7e9, 0.35),
    }
    for arch, (target, tol) in expect.items():
        n = get_config(arch).num_params()
        assert abs(n - target) / target < tol, (arch, n, target)


def test_blockwise_attention_matches_dense(monkeypatch):
    """The blockwise path (4 key blocks of 64 over 256 positions, through
    the whole model) must equal dense attention numerically."""
    from repro.models import layers as L

    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    batch = make_concrete_batch(cfg, ShapeConfig("blockwise_train", 256, 2, "train"))
    l1, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    monkeypatch.setattr(L, "LONG_KV", 256)
    monkeypatch.setattr(L, "BLOCK_KV", 64)
    assert L.attn_path(256, 256, **L.placement()) == "blockwise"
    l2, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
