"""Compile rehearsals for a TPU v5e, without the chip.

The TPU compiler that ships with libtpu compiles for a described
``v5e:2x2`` topology on this CPU-only host: the main-path programs at
granite-3-2b's published widths (depth cut to 2 layers) and each Pallas
kernel at one real shape.  What the chip's compiler refuses (tile
alignment, VMEM, programs that do not fit HBM) fails here at no chip time.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and the test workers all import this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import get_config

HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it cannot describe v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip; keep them out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    jax.config.update("jax_enable_compilation_cache", prev)


def _granite_2l():
    return dataclasses.replace(get_config("granite-3-2b"), num_layers=2)


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_serve_decode_and_prefill_compile(one_chip):
    """The engine's two programs (paged.decode_batch over 8 slots, one
    256-token paged.prefill_chunk) at the smoke run's serve shape."""
    from repro.models import build_model
    from repro.serve import paged
    from repro.serve.policy import ServeConfig

    cfg = _granite_2l()
    scfg = ServeConfig(slots=8, max_len=1024, block_size=16, chunk=256)
    rep = NamedSharding(one_chip, P())
    params = _placed(build_model(cfg).abstract_params()[0], rep)
    pool = _placed(jax.eval_shape(lambda: paged.init_pool(cfg, scfg)), rep)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    mb = scfg.max_blocks_per_slot

    # donated, as the engine's decode program donates it
    decode = jax.jit(lambda p, pl, t, ln, tb: paged.decode_batch(
        p, pl, t, ln, tb, cfg, scfg), donate_argnums=(1,))
    compiled = decode.lower(
        params, pool, i32(8, 1), i32(8), i32(8, mb)
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes

    prefill = jax.jit(lambda p, pl, t, s, w, row: paged.prefill_chunk(
        p, pl, t, s, w, row, 0, cfg, scfg))
    compiled = prefill.lower(
        params, pool, i32(1, scfg.chunk), i32(), i32(), i32(mb)
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_train_step_compiles(one_chip):
    """The launcher's train step (make_sharded_train_step under the
    sharding context train() uses) at seq 2048, batch 4, state donated."""
    from repro.models import build_model
    from repro.models.sharding import make_ctx, use_sharding
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train import make_sharded_train_step
    from repro.train.step import abstract_state

    cfg = _granite_2l()
    model = build_model(cfg)
    opt = make_optimizer(cfg.optimizer)
    step = make_sharded_train_step(
        model, opt, cosine_with_warmup(3e-4, 20, 21), one_chip
    )
    rep = NamedSharding(one_chip, P())
    with use_sharding(make_ctx(one_chip, overrides=cfg.sharding_overrides)):
        state = _placed(abstract_state(model, opt)[0], rep)
        batch = {k: jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=rep)
                 for k in ("tokens", "labels")}
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch
        ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_moonlight_share_train_step_compiles(one_chip, monkeypatch):
    """The train-moonlight-ep8-8k step: moonlight-16b-a3b at published
    widths, 1 dense + 5 MoE layers holding experts 0-7 of 64, the
    20480-id vocabulary slice, seq 8192, batch 1, fp32 master weights and
    AdamW, remat ``dots``.  The compiler's own peak (``peak_memory_in_bytes``,
    what its HBM check holds against the chip) fits 16 GiB; ``temp_size``
    counts more than that peak, so the sum ``_device_bytes`` gives is not
    asserted.  The held experts run as the grouped-product kernels
    (``gmm``, and ``tgmm`` for the weights' gradients), not as capacity
    one-hots; the CPU tests run the kernels interpreted, so this test asks
    for the compiled ones.  On the one-chip mesh, attention at 8k takes the
    flash kernel: the splash forward, dq and dkv kernels."""
    from repro.models import build_model
    from repro.models import moe
    from repro.models.sharding import make_ctx, use_sharding
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train import make_sharded_train_step
    from repro.train.step import abstract_state

    monkeypatch.setattr(moe, "INTERPRET", False)
    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(
        base, num_layers=6, vocab_size=20480, compute_dtype="bfloat16",
        moe=dataclasses.replace(base.moe, experts_held=(0, 8)),
    )
    model = build_model(cfg)
    opt = make_optimizer("adamw", b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    step = make_sharded_train_step(
        model, opt, cosine_with_warmup(3e-4, 20, 100000), one_chip
    )
    rep = NamedSharding(one_chip, P())
    with use_sharding(make_ctx(one_chip, overrides=cfg.sharding_overrides)):
        state = _placed(abstract_state(model, opt)[0], rep)
        batch = {k: jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=rep)
                 for k in ("tokens", "labels")}
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch
        ).compile()
    mem = compiled.memory_analysis()
    # weights, Adam's two moments: 12 B for each of the 669M parameters
    assert mem.argument_size_in_bytes == pytest.approx(12 * 669.2e6, rel=0.01)
    assert mem.peak_memory_in_bytes < HBM_BYTES
    text = compiled.as_text()
    assert "%gmm" in text and "%tgmm" in text
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mha_{phase}" in text


def _fwd_bwd(fn):
    """fn's output and the gradients of q, k and v for that output as the
    cotangent: the forward and every backward kernel in one program."""
    def f(q, k, v):
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp(out)
    return f


def _moonlight_attention(sds):
    """Latent attention of the train-moonlight-ep8-8k cell: batch 1, seq
    8192, 16 heads, query/key width 192 (128 + 64 rotary), value 128."""
    return [sds((1, 8192, 16, 192), jnp.bfloat16),
            sds((1, 8192, 16, 192), jnp.bfloat16),
            sds((1, 8192, 16, 128), jnp.bfloat16)]


def _flash(sds):
    from repro.kernels import flash_attention

    return (
        _fwd_bwd(lambda q, k, v: flash_attention(q, k, v, causal=True)),
        _moonlight_attention(sds),
    )


def _rmsnorm(sds):
    from repro.kernels import fused_rmsnorm

    return (
        lambda x, w: fused_rmsnorm(x, w),
        [sds((4, 2048, 2048), jnp.bfloat16), sds((2048,), jnp.float32)],
    )


# ssd_scan is left out: the TPU lowering has no cumsum (ROADMAP C2)
@pytest.mark.parametrize("kernel", [_flash, _rmsnorm], ids=["flash", "rmsnorm"])
def test_pallas_kernel_compiles_to_tpu_custom_call(one_chip, kernel):
    """Flash attention forward and backward at the moonlight cell's
    attention shapes; the norm at granite-3-2b's, seq 2048."""
    rep = NamedSharding(one_chip, P())
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    fn, args = kernel(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_temporaries_below_blockwise(one_chip):
    """At the moonlight cell's attention shapes, forward and backward, the
    blockwise scan (fp32 score tiles of every key block against every query,
    through HBM) needs more temporary memory than the flash kernel, whose
    score tiles stay in VMEM."""
    from repro.kernels import flash_attention
    from repro.models import layers as L

    rep = NamedSharding(one_chip, P())
    args = _moonlight_attention(
        lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep))

    def temp(fn):
        compiled = jax.jit(_fwd_bwd(fn)).lower(*args).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    flash = temp(lambda q, k, v: flash_attention(q, k, v, causal=True))
    blockwise = temp(lambda q, k, v: L._sdpa_blockwise(
        L._group_q(q, k.shape[2]), k, v, q_offset=0))
    assert blockwise > 2 * flash
