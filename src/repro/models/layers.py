"""Shared model substrate: norms, RoPE, GQA and latent attention (+KV
cache), MLPs.

Parameter convention: every ``init_*`` returns ``(params, axes)`` where
``axes`` mirrors ``params`` and holds the logical-axis tuple of each leaf
(consumed by ``repro.models.sharding``).  All functions are pure.

Dtype convention: parameters live in ``cfg.param_dtype``; compute casts to
``cfg.compute_dtype``; normalization statistics, RoPE tables, softmax and the
loss are fp32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import LANES, flash_attention
from repro.models.sharding import current_ctx, shard_hint

# From this many keys up, attention leaves the dense path (:func:`attn_path`).
LONG_KV = 8192
# Keys per step of the blockwise scan (:func:`_sdpa_blockwise`).
BLOCK_KV = 512


def _init_dense(key, shape, scale_dim, dtype):
    scale = 1.0 / math.sqrt(scale_dim)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype):
    return jnp.ones((d,), dtype=dtype), ("embed",)


def rmsnorm(x, scale, eps: float = 1e-5, compute_dtype=jnp.bfloat16):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(compute_dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (head_dim/2,)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional bias, optional KV cache)
# ---------------------------------------------------------------------------


def init_attention(key, cfg):
    if cfg.is_mla:
        return _init_mla(key, cfg)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    params = {
        "wq": _init_dense(ks[0], (d, H, hd), d, dt),
        "wk": _init_dense(ks[1], (d, K, hd), d, dt),
        "wv": _init_dense(ks[2], (d, K, hd), d, dt),
        "wo": _init_dense(ks[3], (H, hd, d), H * hd, dt),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((H, hd), dt)
        params["bk"] = jnp.zeros((K, hd), dt)
        params["bv"] = jnp.zeros((K, hd), dt)
        axes["bq"] = ("heads", "head_dim")
        axes["bk"] = ("kv_heads", "head_dim")
        axes["bv"] = ("kv_heads", "head_dim")
    return params, axes


def _init_mla(key, cfg):
    """Latent attention (DeepSeek-V2 §2.1) without a query latent."""
    d, H = cfg.d_model, cfg.num_heads
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    params = {
        "wq": _init_dense(ks[0], (d, H, nope + rope), d, dt),
        "wkv_a": _init_dense(ks[1], (d, r + rope), d, dt),
        "kv_norm": jnp.ones((r,), dt),
        "wkv_b": _init_dense(ks[2], (r, H, nope + vd), r, dt),
        "wo": _init_dense(ks[3], (H, vd, d), H * vd, dt),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wkv_a": ("embed", None),
        "kv_norm": (None,),
        "wkv_b": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _project_mla(p, x, cfg, positions):
    """q = W_Q h; [c_KV; k_R] = W_DKV h; c_KV = RMSNorm(c_KV);
    [k_C; v] = W_UKV c_KV; rotary on q_R and on the one k_R every head
    shares.  Returns per-head q, k = [q_C; q_R], [k_C; k_R] (nope + rope
    wide, so that q.k / sqrt(nope + rope) is the published score) and v."""
    cdt = jnp.dtype(cfg.compute_dtype)
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(cdt))
    c = rmsnorm(ckv[..., :r], p["kv_norm"], cfg.norm_eps, cdt)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(cdt))
    k_c, v = kv[..., :nope], kv[..., nope:]
    q_r = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_r = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(
        [k_c, jnp.broadcast_to(k_r, k_c.shape[:-1] + k_r.shape[-1:])], axis=-1
    )
    q = shard_hint(q, ("batch", "seq_q", "act_heads", None), "q")
    return q, k, v


def _project_qkv(p, x, cfg, positions):
    if cfg.is_mla:
        return _project_mla(p, x, cfg, positions)
    cdt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_hint(q, ("batch", "seq_q", "act_heads", None), "q")
    return q, k, v


def repeat_kv(kv, num_heads: int):
    """(B,S,K,hd) -> (B,S,H,hd) by repeating each KV head H//K times."""
    b, s, k, hd = kv.shape
    if k == num_heads:
        return kv
    reps = num_heads // k
    kv = jnp.broadcast_to(kv[:, :, :, None, :], (b, s, k, reps, hd))
    return kv.reshape(b, s, num_heads, hd)


def _group_q(q, k_eff: int):
    """(B,S,H,hd) -> (B,S,K_eff,G,hd) with query head h -> kv head h//G."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, k_eff, h // k_eff, hd)


def _sdpa_dense(qg, k, v, mask):
    """Grouped attention.  qg: (B,Sq,K,G,hd); k,v: (B,Skv,K,hd);
    mask broadcastable to (B,1,1,Sq,Skv).  G=1 == plain MHA."""
    scale = 1.0 / math.sqrt(qg.shape[-1])
    scores = (
        jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    )
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(qg.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return shard_hint(
        out, ("batch", "seq_q", "act_heads", None, None), "attn_out"
    )


def _sdpa_blockwise(qg, k, v, *, q_offset: int, bidirectional: bool = False):
    """Grouped online-softmax attention, scanning KV in blocks (jnp flash).

    qg: (B,Sq,K,G,hd); k: (B,Skv,K,hd); v: (B,Skv,K,vd).  Memory is
    O(Sq * BLOCK_KV) instead of O(Sq * Skv); the block body is
    rematerialized, so the backward pass keeps each block's carry and not
    its (Sq, BLOCK_KV) scores.  Causal masking uses global positions: query i
    attends to kv j iff j <= i + q_offset.  It computes every (query, key)
    block pair, the fully masked ones included; on one TPU, causal
    self-attention takes the flash kernel instead (:func:`attn_path`).
    """
    scale = 1.0 / math.sqrt(qg.shape[-1])
    b, sq, kh, g, hd = qg.shape
    skv, vd = k.shape[1], v.shape[-1]
    blk = min(BLOCK_KV, skv)
    assert skv % blk == 0, f"kv len {skv} % block {blk} != 0"
    nblk = skv // blk
    kb = jnp.moveaxis(k.reshape(b, nblk, blk, kh, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nblk, blk, kh, vd), 1, 0)
    qi = jnp.arange(sq) + q_offset  # global query positions

    @jax.checkpoint
    def body(carry, inputs):
        m, l, acc = carry  # (B,K,G,Sq), (B,K,G,Sq), (B,K,G,Sq,vd)
        jblk, kj, vj = inputs
        s = (
            jnp.einsum("bqkgd,bskd->bkgqs", qg, kj).astype(jnp.float32)
            * scale
        )
        kj_pos = jblk * blk + jnp.arange(blk)
        if bidirectional:
            mask = jnp.ones((1, 1, 1, 1, blk), bool)
        else:
            mask = (
                kj_pos[None, None, None, None, :]
                <= qi[None, None, None, :, None]
            )
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == -inf): scale of 0 keeps them empty
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        p = jnp.where(
            jnp.isfinite(m_new)[..., None], jnp.exp(s - m_new[..., None]), 0.0
        )
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(qg.dtype), vj
        ).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kh, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kh, g, sq, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nblk), kb, vb)
    )
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qg.dtype)
    out = jnp.einsum("bkgqd->bqkgd", out)
    return shard_hint(
        out, ("batch", "seq_q", "act_heads", None, None), "attn_out"
    )


def attn_path(
    sq: int, skv: int, *, platform: str, devices: int, mask=None,
    q_offset=0, bidirectional: bool = False,
) -> str:
    """Which attention :func:`_sdpa` runs: "flash", "blockwise" or "dense",
    from the shapes and the placement alone.

    "flash" (``repro.kernels.flash_attention``: scores stay in VMEM, fully
    masked blocks are skipped forward and backward) where the input shows it
    is sound: one TPU device, causal self-attention with no mask or offset,
    ``sq == skv >= LONG_KV`` and a multiple of 128.  On a mesh of several
    devices the blockwise scan stays, since the kernel is not partitioned.
    "blockwise" (online softmax over ``BLOCK_KV`` keys at a time) for other
    long queries without a mask (bidirectional, an offset), and "dense"
    otherwise: decode, masked chunks, short sequences.
    """
    causal_self = (
        mask is None and not bidirectional
        and isinstance(q_offset, int) and q_offset == 0 and sq == skv
    )
    if (platform == "tpu" and devices == 1 and causal_self
            and skv >= LONG_KV and skv % LANES == 0):
        return "flash"
    # blockwise only pays off for long query blocks (train/prefill): for
    # decode (Sq=1) dense scores are tiny and, crucially, a lax.scan over a
    # sequence-sharded KV cache would force XLA to gather every block on
    # every device, defeating split-KV sharding.
    if sq >= 256 and skv >= LONG_KV and mask is None:
        return "blockwise"
    return "dense"


def placement() -> dict:
    """The platform and device count of the mesh the program is placed on:
    the sharding context's, else the default backend's (inside a
    ``shard_map`` body, which drops the context, that counts every device)."""
    ctx = current_ctx()
    if ctx is None:
        return {"platform": jax.default_backend(), "devices": jax.device_count()}
    devs = ctx.mesh.devices
    return {"platform": devs.flat[0].platform, "devices": devs.size}


def _sdpa(q, k, v, mask, *, q_offset: int = 0, bidirectional=False):
    """q: (B,Sq,H,hd); k: (B,Skv,K,hd), v: (B,Skv,K,vd) with K the
    *stored* kv-head count.  Returns (B,Sq,H,vd).

    Runs the path :func:`attn_path` picks.  The flash kernel reads K/V by
    KV-head group; the blockwise and dense paths repeat KV to H heads.
    """
    b, sq, h, _ = q.shape
    skv = k.shape[1]
    path = attn_path(
        sq, skv, mask=mask, q_offset=q_offset, bidirectional=bidirectional,
        **placement(),
    )
    if path == "flash":
        with jax.named_scope("attn.flash"):
            return flash_attention(q, k, v, causal=True)
    k = repeat_kv(k, h)
    v = repeat_kv(v, h)
    qg = _group_q(q, h)
    if path == "blockwise":
        out = _sdpa_blockwise(
            qg, k, v, q_offset=q_offset, bidirectional=bidirectional
        )
        return out.reshape(b, sq, h, -1)
    if mask is None:
        if bidirectional:
            mask = jnp.ones((1, 1, 1, 1), bool)
        else:
            mask = causal_mask(sq, skv, offset=q_offset)
    # grouped mask shape: (B, 1[K], 1[G], Sq, Skv)
    out = _sdpa_dense(qg, k, v, mask[:, :, None])
    return out.reshape(b, sq, h, -1)


def causal_mask(sq: int, skv: int, offset: int = 0):
    """True where attendable. offset = number of cached tokens before q[0]."""
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(skv)[None, :]
    return (ki <= qi)[None, None, :, :]


def attention(p, x, cfg, *, positions, mask=None, cross_kv=None, bidirectional=False):
    """Full-sequence attention (train / prefill, no cache read).

    cross_kv: optional (k, v) tuple for cross-attention (encoder memory);
    implies bidirectional visibility over the memory.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    if cross_kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
        if "bq" in p:
            q = q + p["bq"].astype(cdt)
        k, v = cross_kv
        bidirectional = True
    out = _sdpa(q, k, v, mask, bidirectional=bidirectional)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(cdt))


def cross_kv_from_memory(p, memory, cfg):
    """Project encoder memory to (k, v) once (reused across decode steps)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(cdt))
    if "bk" in p:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    return k, v


# -- KV cache ----------------------------------------------------------------


def init_kv_cache(batch: int, max_len: int, cfg, dtype):
    """Per-position keys and values; latent attention caches its expanded
    per-head keys (nope + rope wide) and values."""
    K = cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, max_len, K, cfg.qk_head_dim), dtype),
        "v": jnp.zeros((batch, max_len, K, cfg.v_dim), dtype),
    }


KV_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
}


def _cache_write(cache, k, v, pos: int | jax.Array):
    """Write k/v (B,S,K,hd) into the cache at sequence offset ``pos``."""
    return {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)
        ),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)
        ),
    }


def attention_prefill(p, x, cfg, *, positions, cache):
    """Compute full attention AND write k/v into the cache at [0, S)."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = _sdpa(q, k, v, None)
    cdt = jnp.dtype(cfg.compute_dtype)
    new_cache = _cache_write(cache, k, v, 0)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(cdt)), new_cache


def attention_decode(p, x, cfg, *, cache, cache_len):
    """One-token decode: x (B,1,D), attend over cache[0:cache_len] + self.

    The new token's k/v are written at position ``cache_len`` (static-shape
    dynamic_update_slice); the mask hides positions > cache_len.
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_len, dtype=jnp.int32)
    cdt = jnp.dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(p, x, cfg, positions)
    new_cache = _cache_write(cache, k, v, cache_len)
    new_cache = {
        kk: shard_hint(vv, KV_CACHE_AXES[kk], f"cache_{kk}")
        for kk, vv in new_cache.items()
    }
    ck, cv = new_cache["k"].astype(cdt), new_cache["v"].astype(cdt)
    out = _sdpa(q, ck, cv, None, q_offset=cache_len)
    y = jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(cdt))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    params = {
        "wg": _init_dense(ks[0], (d, d_ff), d, dtype),
        "wu": _init_dense(ks[1], (d, d_ff), d, dtype),
        "wd": _init_dense(ks[2], (d_ff, d), d_ff, dtype),
    }
    axes = {
        "wg": ("embed", "ffn"),
        "wu": ("embed", "ffn"),
        "wd": ("ffn", "embed"),
    }
    return params, axes


def mlp(p, x, compute_dtype):
    cdt = jnp.dtype(compute_dtype)
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cdt))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cdt))
    h = jax.nn.silu(g) * u
    h = shard_hint(h, ("batch", "seq", "act_ffn"), "mlp_h")
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(cdt))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype):
    emb = _init_dense(key, (vocab, d), d, dtype)
    return emb, ("vocab", "embed")


def embed(emb, tokens, compute_dtype):
    return jnp.take(emb, tokens, axis=0).astype(compute_dtype)


def logits_head(emb_or_w, x, *, transpose: bool):
    """Final projection to vocab; fp32 logits."""
    w = emb_or_w.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if transpose:  # tied embeddings: w is (vocab, d)
        out = jnp.einsum("bsd,vd->bsv", xf, w)
    else:
        out = jnp.einsum("bsd,dv->bsv", xf, w)
    return shard_hint(out, ("batch", "seq", "vocab"), "logits")


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross-entropy, fp32. labels: int32 (B,S)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_xent(hidden, head_w, labels, *, transpose: bool, chunk: int, mask=None):
    """Cross-entropy without materializing full (B,S,V) fp32 logits.

    Scans over sequence chunks; each chunk computes logits -> logsumexp ->
    label gather and is rematerialized in the backward pass
    (``jax.checkpoint``), bounding live logits to (B, chunk, V).  Used when
    the vocab cannot be sharded (e.g. granite's 49155) or is simply huge.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} % loss chunk {chunk} != 0"
    n = s // chunk
    hc = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)
    if mask is None:
        mc = jnp.ones((n, b, chunk), jnp.float32)
    else:
        mc = jnp.moveaxis(mask.reshape(b, n, chunk), 1, 0).astype(jnp.float32)

    @jax.checkpoint
    def body(carry, inputs):
        nll_sum, cnt = carry
        h, lab, mk = inputs
        logits = logits_head(head_w, h, transpose=transpose)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * mk
        return (nll_sum + jnp.sum(nll), cnt + jnp.sum(mk)), None

    (nll_sum, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hc, lc, mc))
    return nll_sum / jnp.maximum(cnt, 1.0)
