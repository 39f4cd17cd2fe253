"""Public flash-attention op: a thin layer over JAX's splash attention.

Splash attention (``jax.experimental.pallas.ops.tpu.splash_attention``) is
the TPU FlashAttention (arXiv:2205.14135): score tiles live in VMEM and never
reach HBM, a block mask skips the fully masked (query, key) block pairs, and
its custom VJP runs Pallas dq and dkv kernels that skip the same blocks.  It
reads K/V by KV-head group (no repeat of K/V to the query heads) and takes a
value width other than the query/key width (latent attention's 128 against
192).

This op adds the layout of the model code, ``(B, S, H, D)``, to the kernel's
``(H, S, D)`` (batch through ``vmap``), and folds the softmax scale into q,
since the kernel takes none.  Kernels are built once per shape.  They compile
for the TPU unless the caller passes ``interpret=True`` (the CPU tests do).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# Largest query and key blocks; each is cut to the largest power-of-two share
# of it (down to 128) that divides the sequence.  The block sweep at the
# Moonlight training shape (S 8192, 16 heads, qk 192, v 128) is PERF.md §6's.
BLOCK_Q = 512
BLOCK_KV = 512
LANES = 128


def _block(n: int, cap: int) -> int:
    b = cap
    while b > LANES and n % b:
        b //= 2
    if n % b:
        raise ValueError(f"sequence {n} is not a multiple of {LANES}")
    return b


@functools.lru_cache(maxsize=16)
def _kernel(heads, sq, skv, causal, block_q, block_kv, interpret):
    if causal:  # query i sees key j iff j <= i + (skv - sq), as ref.py
        mask = splash.CausalMask((sq, skv), offset=skv - sq)
    else:
        mask = splash.FullMask((sq, skv))
    sizes = splash.BlockSizes(
        block_q=block_q, block_kv=block_kv, block_kv_compute=block_kv,
        block_q_dkv=block_q, block_kv_dkv=block_kv,
        block_kv_dkv_compute=block_kv,
        block_q_dq=block_q, block_kv_dq=block_kv,
    )
    # the block masks are built from numpy; keep them concrete when the first
    # call comes from inside a trace, so that the cached kernel holds no tracer
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash.MultiHeadMask([mask] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret,
        )


def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = BLOCK_Q,
    block_kv: int = BLOCK_KV, interpret: bool = False,
):
    """q: (B, Sq, H, D); k: (B, Skv, K, D); v: (B, Skv, K, Dv) with H % K == 0
    and both lengths multiples of 128.  Returns (B, Sq, H, Dv) in q.dtype.

    Scores are q.k / sqrt(D).  ``causal`` aligns the last query with the last
    key, as ``ref.attention_ref`` does.
    """
    _, sq, h, d = q.shape
    skv = k.shape[1]
    kernel = _kernel(h, sq, skv, causal, _block(sq, block_q),
                     _block(skv, block_kv), interpret)
    q = (q.astype(jnp.float32) / math.sqrt(d)).astype(q.dtype)
    heads_major = lambda t: jnp.swapaxes(t, 1, 2)
    out = jax.vmap(kernel)(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)
