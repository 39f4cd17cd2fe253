"""Decoder-only transformer stack (dense / moe / vlm families).

Each layer stack is a ``lax.scan`` over parameters stacked on a leading
``layers`` dim (initialized with ``jax.vmap``), so HLO size and compile time
are depth-independent — essential for dry-running 80-layer models on a CPU
host.  Remat policy wraps the scan body.  A config with
``first_k_dense`` leading dense layers has two stacks, scanned in turn:
``params["dense_blocks"]`` (dense FFN) then ``params["blocks"]``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models.sharding import shard_hint


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ArchConfig, dense: bool = False):
    """One decoder block: attention + FFN (dense or MoE [+ shared expert])."""
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    attn_p, attn_a = L.init_attention(ks[0], cfg)
    n1, n1a = L.init_rmsnorm(cfg.d_model, dt)
    n2, n2a = L.init_rmsnorm(cfg.d_model, dt)
    params = {"attn": attn_p, "norm1": n1, "norm2": n2}
    axes = {"attn": attn_a, "norm1": n1a, "norm2": n2a}
    if cfg.moe is not None and cfg.moe.every_k == 1 and not dense:
        moe_p, moe_a = M.init_moe(ks[1], cfg.d_model, cfg.moe, dt)
        params["moe"] = moe_p
        axes["moe"] = moe_a
        if cfg.moe.num_shared_experts:
            sh_p, sh_a = L.init_mlp(ks[2], cfg.d_model, cfg.moe.d_ff_shared, dt)
            params["shared_mlp"] = sh_p
            axes["shared_mlp"] = sh_a
    else:
        mlp_p, mlp_a = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, dt)
        params["mlp"] = mlp_p
        axes["mlp"] = mlp_a
    return params, axes


def _prefix_layers(axes):
    """Prepend the scan 'layers' axis to every logical-axes tuple."""
    return jax.tree_util.tree_map(
        lambda a: ("layers",) + a,
        axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x
        ),
    )


def _init_stack(key, cfg: ArchConfig, n: int, dense: bool):
    box = {}

    def one_block(k):
        p, a = init_block(k, cfg, dense)
        box["axes"] = a
        return p

    blocks = jax.vmap(one_block)(jax.random.split(key, n))
    return blocks, _prefix_layers(box["axes"])


def init_params(key, cfg: ArchConfig):
    dt = jnp.dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head, k_proj = jax.random.split(key, 4)
    emb, emb_a = L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dt)
    nd = cfg.first_k_dense
    blocks, blocks_a = _init_stack(k_blocks, cfg, cfg.num_layers - nd, False)
    fn, fn_a = L.init_rmsnorm(cfg.d_model, dt)
    params = {"embed": emb, "blocks": blocks, "final_norm": fn}
    axes = {"embed": emb_a, "blocks": blocks_a, "final_norm": fn_a}
    if nd:
        params["dense_blocks"], axes["dense_blocks"] = _init_stack(
            jax.random.fold_in(k_blocks, 1), cfg, nd, True
        )
    if not cfg.tie_embeddings:
        params["head"] = L._init_dense(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dt
        )
        axes["head"] = ("embed", "vocab")
    if cfg.num_patches:  # vlm multimodal projector
        kp1, kp2 = jax.random.split(k_proj)
        params["projector"] = {
            "w1": L._init_dense(kp1, (cfg.vision_dim, cfg.d_model), cfg.vision_dim, dt),
            "w2": L._init_dense(kp2, (cfg.d_model, cfg.d_model), cfg.d_model, dt),
        }
        axes["projector"] = {
            "w1": ("frontend", "embed"),
            "w2": ("embed", "embed"),
        }
    return params, axes


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def ffn(p, h, cfg: ArchConfig):
    """A block's FFN on normed ``h``: (y, aux, load); ``load`` (the MoE
    layer's assignments per expert) is None in a dense block."""
    cdt = cfg.compute_dtype
    if "moe" not in p:
        return L.mlp(p["mlp"], h, cdt), 0.0, None
    y, aux, load = M.moe_ffn(p["moe"], h, cfg.moe, cdt)
    if "shared_mlp" in p:
        y = y + L.mlp(p["shared_mlp"], h, cdt)
    return y, aux, load


def apply_block(p, x, cfg: ArchConfig, *, positions, mask=None):
    """One block: (x, aux, load) as :func:`ffn` gives them."""
    cdt = cfg.compute_dtype
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps, cdt)
    x = x + L.attention(p["attn"], h, cfg, positions=positions, mask=mask)
    h = L.rmsnorm(x, p["norm2"], cfg.norm_eps, cdt)
    y, aux, load = ffn(p, h, cfg)
    return shard_hint(x + y, ("batch", "seq", "embed"), "block_out"), aux, load


def _remat(fn, cfg: ArchConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def stacks(params):
    """The layer stacks in order: the leading dense one, if any, then the
    main one."""
    return [params[k] for k in ("dense_blocks", "blocks") if k in params]


def run_stack(params, x, cfg: ArchConfig, *, positions, mask=None):
    """Scan the block stacks. Returns (hidden, aux_loss_sum, load): ``load``
    (MoE layers, experts) counts each expert's assignments, None without
    MoE layers."""

    def body(carry, block_p):
        h, aux = carry
        h, a, load = apply_block(block_p, h, cfg, positions=positions, mask=mask)
        return (h, aux + a), load

    body = _remat(body, cfg)
    aux, load = 0.0, None
    for blocks in stacks(params):
        (x, aux), load_s = jax.lax.scan(body, (x, aux), blocks)
        load = load_s if load_s is not None else load
    return x, aux, load


# ---------------------------------------------------------------------------
# Embedding / loss front-ends (shared with vlm)
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg: ArchConfig):
    """Returns (h, positions, text_start).  For vlm, prepends projected
    patch embeddings; text occupies positions [num_patches, num_patches+S)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], batch["tokens"], cdt)
    b = h.shape[0]
    if cfg.num_patches:
        pr = params["projector"]
        pe = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(cdt), pr["w1"].astype(cdt))
        pe = jax.nn.gelu(pe)
        pe = jnp.einsum("bpd,de->bpe", pe, pr["w2"].astype(cdt))
        h = jnp.concatenate([pe, h], axis=1)
    h = shard_hint(h, ("batch", "seq", "embed"), "embed_out")
    s = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return h, positions, cfg.num_patches


def _head_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"], True
    return params["head"], False


def flash_layers(params, cfg: ArchConfig, seq: int):
    """How many layers' attention takes the flash kernel at this sequence
    length: every layer's self-attention has the same shapes, so all or
    none (``L.attn_path``)."""
    path = L.attn_path(seq, seq, **L.placement())
    layers = sum(jax.tree_util.tree_leaves(b)[0].shape[0] for b in stacks(params))
    return jnp.asarray(layers if path == "flash" else 0, jnp.int32)


def loss_fn(params, batch, cfg: ArchConfig):
    h, positions, text_start = _embed_inputs(params, batch, cfg)
    h, aux, load = run_stack(params, h, cfg, positions=positions)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cfg.compute_dtype)
    if text_start:
        h = h[:, text_start:]
    w, transpose = _head_weight(params, cfg)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    ce = L.chunked_xent(
        h, w, labels, transpose=transpose, chunk=cfg.loss_chunk, mask=mask
    )
    loss = ce + aux
    metrics = {"ce": ce, "aux": aux,
               "attn_flash_layers": flash_layers(params, cfg, positions.shape[1])}
    if load is not None:
        first, count = cfg.moe.held
        metrics["expert_tokens"] = load
        metrics["held_assignments"] = jnp.sum(load[:, first:first + count])
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def init_cache(batch: int, max_len: int, cfg: ArchConfig, dtype):
    one = L.init_kv_cache(batch, max_len, cfg, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one
    )


def cache_axes(cfg: ArchConfig):
    return _prefix_layers(L.KV_CACHE_AXES)


def _scan_cached(body, h, params, cache):
    """Scan ``body`` over each stack with its layers' slice of the cache
    (layers stacked on dim 0, the dense stack first)."""
    out, lo = [], 0
    for blocks in stacks(params):
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        part = jax.tree_util.tree_map(lambda c: c[lo:lo + n], cache)
        h, part = jax.lax.scan(body, h, (blocks, part))
        out.append(part)
        lo += n
    if len(out) == 1:
        return h, out[0]
    return h, jax.tree_util.tree_map(lambda *c: jnp.concatenate(c), *out)


def prefill(params, batch, cfg: ArchConfig, max_len: int):
    """Forward pass writing the KV cache; returns (last-token logits, cache)."""
    h, positions, _ = _embed_inputs(params, batch, cfg)
    cache = init_cache(h.shape[0], max_len, cfg, jnp.dtype(cfg.compute_dtype))

    def body(carry, xs):
        hh = carry
        block_p, layer_cache = xs
        n = L.rmsnorm(hh, block_p["norm1"], cfg.norm_eps, cfg.compute_dtype)
        a, new_cache = L.attention_prefill(
            block_p["attn"], n, cfg, positions=positions, cache=layer_cache
        )
        hh = hh + a
        n = L.rmsnorm(hh, block_p["norm2"], cfg.norm_eps, cfg.compute_dtype)
        hh = hh + ffn(block_p, n, cfg)[0]
        hh = shard_hint(hh, ("batch", "seq", "embed"), "block_out")
        return hh, new_cache

    h, cache = _scan_cached(body, h, params, cache)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cfg.compute_dtype)
    w, transpose = _head_weight(params, cfg)
    logits = L.logits_head(w, h[:, -1:], transpose=transpose)
    return logits, cache


def decode_step(params, cache, token, cache_len, cfg: ArchConfig):
    """token: (B,1) int32; cache_len: int32 scalar. Returns (logits, cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], token, cdt)

    def body(carry, xs):
        hh = carry
        block_p, layer_cache = xs
        n = L.rmsnorm(hh, block_p["norm1"], cfg.norm_eps, cdt)
        a, new_cache = L.attention_decode(
            block_p["attn"], n, cfg, cache=layer_cache, cache_len=cache_len
        )
        hh = hh + a
        n = L.rmsnorm(hh, block_p["norm2"], cfg.norm_eps, cdt)
        return hh + ffn(block_p, n, cfg)[0], new_cache

    h, cache = _scan_cached(body, h, params, cache)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = _head_weight(params, cfg)
    logits = L.logits_head(w, h, transpose=transpose)
    return logits, cache
