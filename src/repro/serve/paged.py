"""Paged-KV device kernels: block pool + chunked prefill + batched decode.

The device-side half of the paged cache (host bookkeeping lives in
``repro.serve.blocks``).  One KV *pool* replaces the seed engine's per-slot
``(slots, max_len)`` cache:

    pool["k"], pool["v"]: (num_layers, num_blocks, block_size, K, hd)

A request's cache positions map through its block table — a row of
``max_blocks_per_slot`` pool indices, padded with the scratch block — so
view index ``v`` of the gathered per-slot cache

    pool[layer][table_row].reshape(view_len, K, hd)

is exactly logical position ``v``.  Two kernels, both mirroring the
``repro.models.transformer`` scan-over-blocks structure (same rmsnorm /
attention / mlp body, so a priced serve node is the same math the training
graphs price):

* :func:`prefill_chunk` — one prompt chunk of one request (batch 1, padded
  to a pow2 ``bucket``), scatter-writes the chunk's K/V into the pool and
  attends over the gathered view with an absolute-position causal mask;
* :func:`decode_batch` — one token for ALL slots (static batch = slots);
  inactive lanes are routed to the scratch block with length 0, so the
  jitted function never needs data-dependent shapes.

Numerical parity with the sequential reference (``transformer.prefill`` +
``decode_step``) comes from ``_sdpa_dense`` masking with
``jnp.finfo(f32).min``: masked view positions contribute *exactly* 0.0 to
softmax sums, so the padded gathered view computes the same numbers as the
reference's contiguous cache (asserted token-for-token in
tests/test_serve_engine.py).

The pool stores ``cfg.compute_dtype``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve.policy import ServeConfig

SUPPORTED_FAMILIES = ("dense", "moe")


def check_family(cfg: ArchConfig) -> None:
    if cfg.family not in SUPPORTED_FAMILIES or cfg.num_patches:
        raise ValueError(
            f"paged serving supports text-only {SUPPORTED_FAMILIES} "
            f"families, not {cfg.family!r}"
            + (" with patches" if cfg.num_patches else "")
        )
    if cfg.is_mla or cfg.first_k_dense:
        raise ValueError(
            f"paged serving has no latent cache and one homogeneous layer "
            f"stack: {cfg.name} has latent attention or leading dense layers"
        )


def init_pool(cfg: ArchConfig, scfg: ServeConfig) -> dict[str, jax.Array]:
    """Zero-initialized paged KV pool for every layer."""
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (
        cfg.num_layers,
        scfg.resolved_num_blocks(),
        scfg.block_size,
        K,
        hd,
    )
    dt = jnp.dtype(cfg.compute_dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _ffn(block_p, h, cfg: ArchConfig):
    return T.ffn(block_p, h, cfg)[0]


def _paged_attention(attn_p, h, cfg, pool_k, pool_v, *,
                     positions, write_bi, write_off, tables, mask):
    """Project, scatter-write into the pool, attend over gathered views.

    h: (B, S, d); write_bi/write_off: (B*S,) flat pool coordinates for each
    new token's K/V; tables: (B, max_blocks) pool block ids; mask:
    (B, 1, S, view_len) attendable positions.  Returns (attn_out, k, v
    pool layers after the write).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = h.shape
    q, k, v = L._project_qkv(attn_p, h, cfg, positions)
    khd = k.shape[-2:]
    new_k = pool_k.at[write_bi, write_off].set(
        k.reshape((b * s,) + khd).astype(pool_k.dtype)
    )
    new_v = pool_v.at[write_bi, write_off].set(
        v.reshape((b * s,) + khd).astype(pool_v.dtype)
    )
    bs = pool_k.shape[1]
    view = tables.shape[1] * bs
    kv_shape = (b, view) + khd
    k_view = new_k[tables].reshape(kv_shape)
    v_view = new_v[tables].reshape(kv_shape)
    out = L._sdpa(q, k_view, v_view, mask)
    y = jnp.einsum("bqhk,hkd->bqd", out, attn_p["wo"].astype(cdt))
    return y, new_k, new_v


def prefill_chunk(params, pool, tokens, start, width, table_row,
                  scratch_block, cfg: ArchConfig, scfg: ServeConfig,
                  ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One prompt chunk of one request through the whole stack.

    tokens: (1, bucket) int32, right-padded with zeros beyond ``width``;
    start/width: traced scalars (chunk covers prompt positions
    [start, start+width)); table_row: (max_blocks_per_slot,) int32.
    Returns (last-real-token logits (1, 1, vocab), new pool).
    """
    bucket = tokens.shape[1]
    bs = scfg.block_size
    idx = jnp.arange(bucket, dtype=jnp.int32)
    pos = start + idx                       # absolute prompt positions
    positions = pos[None, :]                # (1, bucket)
    real = idx < width                      # padded lanes -> scratch
    write_bi = jnp.where(real, table_row[pos // bs], scratch_block)
    write_off = jnp.where(real, pos % bs, 0)
    kv_pos = jnp.arange(scfg.view_len, dtype=jnp.int32)
    # causal over absolute positions; earlier chunks are already in the pool
    mask = (kv_pos[None, :] <= pos[:, None])[None, None]  # (1,1,bucket,view)

    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], tokens, cdt)
    tables = table_row[None]

    def body(hh, xs):
        block_p, (lk, lv) = xs
        n = L.rmsnorm(hh, block_p["norm1"], cfg.norm_eps, cdt)
        a, nk, nv = _paged_attention(
            block_p["attn"], n, cfg, lk, lv,
            positions=positions, write_bi=write_bi, write_off=write_off,
            tables=tables, mask=mask,
        )
        hh = hh + a
        n = L.rmsnorm(hh, block_p["norm2"], cfg.norm_eps, cdt)
        hh = hh + _ffn(block_p, n, cfg)
        return hh, (nk, nv)

    h, (pk, pv) = jax.lax.scan(
        body, h, (params["blocks"], (pool["k"], pool["v"]))
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    last = jax.lax.dynamic_slice_in_dim(h, width - 1, 1, axis=1)
    w, transpose = _head_weight(params, cfg)
    logits = L.logits_head(w, last, transpose=transpose)
    return logits, {"k": pk, "v": pv}


def _decode_attention(attn_p, h, cfg, pool_k, pool_v, layer, *,
                      positions, write_bi, write_off, tables, mask):
    """One new token per slot: write its K/V into layer ``layer`` of the
    whole stacked pool in place, and attend over each slot's gathered view
    by KV-head group (``_sdpa_dense`` on ``_group_q``, no repeat of the
    view to H heads; with K == H it is plain MHA).

    h: (S, 1, d); pool_k/pool_v: (num_layers, num_blocks, bs, K, hd);
    write_bi/write_off: (S,); tables: (S, max_blocks); mask: (S, 1, 1, 1,
    view_len).  Returns (attn_out, pool_k, pool_v).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    s = h.shape[0]
    q, k, v = L._project_qkv(attn_p, h, cfg, positions)
    pool_k = pool_k.at[layer, write_bi, write_off].set(
        k[:, 0].astype(pool_k.dtype)
    )
    pool_v = pool_v.at[layer, write_bi, write_off].set(
        v[:, 0].astype(pool_v.dtype)
    )
    kv_heads = k.shape[2]
    kv_shape = (s, tables.shape[1] * pool_k.shape[2]) + k.shape[2:]
    k_view = pool_k[layer, tables].reshape(kv_shape)
    v_view = pool_v[layer, tables].reshape(kv_shape)
    out = L._sdpa_dense(L._group_q(q, kv_heads), k_view, v_view, mask)
    y = jnp.einsum(
        "bqhk,hkd->bqd", out.reshape(q.shape), attn_p["wo"].astype(cdt)
    )
    return y, pool_k, pool_v


def decode_batch(params, pool, tokens, lengths, tables,
                 cfg: ArchConfig, scfg: ServeConfig,
                 ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One decode token for every slot lane (static batch = slots).

    tokens: (S, 1) int32; lengths: (S,) cache positions already written
    (the new token lands at position ``lengths[s]``); tables:
    (S, max_blocks_per_slot) int32.  Inactive lanes must come in with
    length 0 and an all-scratch table row — they compute garbage that only
    ever writes to the scratch block.  Returns (logits (S, 1, vocab),
    new pool).

    The whole pool is the layer scan's carry, not its scanned input and
    output: each layer writes one token per slot into it and gathers its
    view from it, so a caller that donates ``pool`` gets the update in
    place (the engine's decode program does).
    """
    s = tokens.shape[0]
    bs = scfg.block_size
    positions = lengths[:, None]            # (S, 1)
    write_bi = tables[jnp.arange(s), lengths // bs]
    write_off = lengths % bs
    kv_pos = jnp.arange(scfg.view_len, dtype=jnp.int32)
    # reference parity: attention_decode masks ki <= cache_len (the
    # just-written position inclusive)
    mask = (kv_pos[None, :] <= lengths[:, None])[:, None, None, None]

    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], tokens, cdt)

    def body(carry, xs):
        hh, pk, pv = carry
        block_p, layer = xs
        n = L.rmsnorm(hh, block_p["norm1"], cfg.norm_eps, cdt)
        a, pk, pv = _decode_attention(
            block_p["attn"], n, cfg, pk, pv, layer,
            positions=positions, write_bi=write_bi, write_off=write_off,
            tables=tables, mask=mask,
        )
        hh = hh + a
        n = L.rmsnorm(hh, block_p["norm2"], cfg.norm_eps, cdt)
        hh = hh + _ffn(block_p, n, cfg)
        return (hh, pk, pv), None

    layers = jnp.arange(pool["k"].shape[0], dtype=jnp.int32)
    (h, pk, pv), _ = jax.lax.scan(
        body, (h, pool["k"], pool["v"]), (params["blocks"], layers)
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = _head_weight(params, cfg)
    logits = L.logits_head(w, h, transpose=transpose)
    return logits, {"k": pk, "v": pv}


def _head_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"], True
    return params["head"], False
