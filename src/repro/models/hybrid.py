"""Hybrid attention/Mamba stack (Jamba-style) + pure-SSM stack (Mamba-2).

Jamba interleaves 1 attention : 7 mamba layers per period-8 block and swaps
the dense FFN for MoE on every other layer.  The stack scans over
*superblocks* (one interleave period) whose inner structure is a static
8-sublayer unroll — HLO stays depth/8-sized while the interleave pattern is
preserved exactly.

The pure-SSM family (mamba2) scans homogeneous mixer-only layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mamba as MB
from repro.models import moe as M
from repro.models.sharding import shard_hint
from repro.models.transformer import _head_weight, _prefix_layers, _remat


# ---------------------------------------------------------------------------
# Jamba superblocks
# ---------------------------------------------------------------------------


def _sublayer_kinds(cfg: ArchConfig):
    """Static description of one interleave period: list of (mixer, ffn)."""
    period = cfg.attn_every
    kinds = []
    for j in range(period):
        mixer = "attn" if j == cfg.attn_offset else "mamba"
        if cfg.moe is not None and j % cfg.moe.every_k == cfg.moe.offset:
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "mlp"
        else:
            ffn = "none"
        kinds.append((mixer, ffn))
    return kinds


def init_superblock(key, cfg: ArchConfig):
    dt = jnp.dtype(cfg.param_dtype)
    kinds = _sublayer_kinds(cfg)
    n_mamba = sum(1 for m, _ in kinds if m == "mamba")
    n_attn = sum(1 for m, _ in kinds if m == "attn")
    n_mlp = sum(1 for _, f in kinds if f == "mlp")
    n_moe = sum(1 for _, f in kinds if f == "moe")
    ks = jax.random.split(key, 6)
    params, axes = {}, {}

    def stack(init_fn, n, k):
        box = {}

        def one(kk):
            p, a = init_fn(kk)
            box["a"] = a
            return p

        return jax.vmap(one)(jax.random.split(k, n)), box["a"]

    if n_attn:
        params["attn"], a = stack(lambda k: L.init_attention(k, cfg), n_attn, ks[0])
        axes["attn"] = _prefix_layers(a)
    if n_mamba:
        params["mamba"], a = stack(lambda k: MB.init_mamba(k, cfg), n_mamba, ks[1])
        axes["mamba"] = _prefix_layers(a)
    if n_mlp:
        params["mlp"], a = stack(
            lambda k: L.init_mlp(k, cfg.d_model, cfg.d_ff, dt), n_mlp, ks[2]
        )
        axes["mlp"] = _prefix_layers(a)
    if n_moe:
        params["moe"], a = stack(
            lambda k: M.init_moe(k, cfg.d_model, cfg.moe, dt), n_moe, ks[3]
        )
        axes["moe"] = _prefix_layers(a)
    period = len(kinds)
    norm1 = jnp.ones((period, cfg.d_model), dt)
    norm2 = jnp.ones((period, cfg.d_model), dt)
    params["norm1"], params["norm2"] = norm1, norm2
    axes["norm1"] = ("layers", "embed")
    axes["norm2"] = ("layers", "embed")
    return params, axes


def _take(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def apply_superblock(p, x, cfg: ArchConfig, *, positions, caches=None, decode_len=None):
    """Apply one interleave period.

    caches: optional dict {"kv": one-layer kv cache, "ssm": stacked (n_mamba)
    mamba caches}; when given, attention uses prefill/decode cache paths.
    Returns (x, aux, new_caches).
    """
    kinds = _sublayer_kinds(cfg)
    cdt = cfg.compute_dtype
    aux = 0.0
    i_attn = i_mamba = i_mlp = i_moe = 0
    new_kv = None
    new_ssm = []
    for j, (mixer, ffn) in enumerate(kinds):
        h = L.rmsnorm(x, p["norm1"][j], cfg.norm_eps, cdt)
        if mixer == "attn":
            ap = _take(p["attn"], i_attn)
            if caches is None:
                y = L.attention(ap, h, cfg, positions=positions)
            elif decode_len is None:
                y, new_kv = L.attention_prefill(
                    ap, h, cfg, positions=positions, cache=caches["kv"]
                )
            else:
                y, new_kv = L.attention_decode(
                    ap, h, cfg, cache=caches["kv"], cache_len=decode_len
                )
            i_attn += 1
        else:
            mp = _take(p["mamba"], i_mamba)
            if caches is None:
                y, _ = MB.mamba_forward(mp, h, cfg)
            elif decode_len is None:
                y, st = MB.mamba_forward(mp, h, cfg)
                new_ssm.append(st)
            else:
                y, st = MB.mamba_step(mp, h, cfg, _take(caches["ssm"], i_mamba))
                new_ssm.append(st)
            i_mamba += 1
        x = x + y
        if ffn == "none":
            continue
        h = L.rmsnorm(x, p["norm2"][j], cfg.norm_eps, cdt)
        if ffn == "moe":
            y, a, _ = M.moe_ffn(_take(p["moe"], i_moe), h, cfg.moe, cdt)
            aux = aux + a
            i_moe += 1
        else:
            y = L.mlp(_take(p["mlp"], i_mlp), h, cdt)
            i_mlp += 1
        x = x + y
        x = shard_hint(x, ("batch", "seq", "embed"), "block_out")
    new_caches = None
    if caches is not None:
        new_caches = {
            "kv": new_kv,
            "ssm": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_ssm
            ),
        }
    return x, aux, new_caches


# ---------------------------------------------------------------------------
# Full models (shared by hybrid + ssm families)
# ---------------------------------------------------------------------------


def _n_superblocks(cfg: ArchConfig) -> int:
    assert cfg.num_layers % cfg.attn_every == 0
    return cfg.num_layers // cfg.attn_every


def init_params(key, cfg: ArchConfig):
    dt = jnp.dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    emb, emb_a = L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dt)
    if cfg.family == "ssm":
        box = {}

        def one(k):
            mp, ma = MB.init_mamba(k, cfg)
            n, na = L.init_rmsnorm(cfg.d_model, dt)
            box["a"] = {"mixer": ma, "norm": na}
            return {"mixer": mp, "norm": n}

        blocks = jax.vmap(one)(jax.random.split(k_blocks, cfg.num_layers))
        blocks_a = _prefix_layers(box["a"])
    else:
        box = {}

        def one(k):
            p, a = init_superblock(k, cfg)
            box["a"] = a
            return p

        blocks = jax.vmap(one)(jax.random.split(k_blocks, _n_superblocks(cfg)))
        blocks_a = jax.tree_util.tree_map(
            lambda a: ("layers",) + a,
            box["a"],
            is_leaf=lambda x: isinstance(x, tuple)
            and all(e is None or isinstance(e, str) for e in x),
        )
    fn, fn_a = L.init_rmsnorm(cfg.d_model, dt)
    params = {"embed": emb, "blocks": blocks, "final_norm": fn}
    axes = {"embed": emb_a, "blocks": blocks_a, "final_norm": fn_a}
    if not cfg.tie_embeddings:
        params["head"] = L._init_dense(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dt
        )
        axes["head"] = ("embed", "vocab")
    return params, axes


def run_stack(params, x, cfg: ArchConfig, *, positions):
    if cfg.family == "ssm":

        def body(carry, bp):
            h, aux = carry
            n = L.rmsnorm(h, bp["norm"], cfg.norm_eps, cfg.compute_dtype)
            y, _ = MB.mamba_forward(bp["mixer"], n, cfg)
            h = shard_hint(h + y, ("batch", "seq", "embed"), "block_out")
            return (h, aux), None

    else:

        def body(carry, bp):
            h, aux = carry
            h, a, _ = apply_superblock(bp, h, cfg, positions=positions)
            return (h, aux + a), None

    body = _remat(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["blocks"])
    return x, aux


def loss_fn(params, batch, cfg: ArchConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], batch["tokens"], cdt)
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h, aux = run_stack(params, h, cfg, positions=positions)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = _head_weight(params, cfg)
    ce = L.chunked_xent(
        h, w, batch["labels"], transpose=transpose, chunk=cfg.loss_chunk
    )
    return ce + aux, {"ce": ce, "aux": aux}


# -- serving ----------------------------------------------------------------


def init_cache(batch: int, max_len: int, cfg: ArchConfig, dtype):
    if cfg.family == "ssm":
        one = MB.init_mamba_cache(batch, cfg, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one
        )
    n_sb = _n_superblocks(cfg)
    n_mamba = sum(1 for m, _ in _sublayer_kinds(cfg) if m == "mamba")
    kv = L.init_kv_cache(batch, max_len, cfg, dtype)
    ssm = MB.init_mamba_cache(batch, cfg, dtype)
    one = {
        "kv": kv,
        "ssm": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_mamba,) + a.shape), ssm
        ),
    }
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_sb,) + a.shape), one
    )


def cache_axes(cfg: ArchConfig):
    if cfg.family == "ssm":
        return _prefix_layers(dict(MB.MAMBA_CACHE_AXES))
    return _prefix_layers(
        {
            "kv": L.KV_CACHE_AXES,
            "ssm": _prefix_layers(dict(MB.MAMBA_CACHE_AXES)),
        }
    )


def prefill(params, batch, cfg: ArchConfig, max_len: int):
    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], batch["tokens"], cdt)
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = init_cache(b, max_len, cfg, cdt)
    if cfg.family == "ssm":

        def body(carry, xs):
            hh = carry
            bp, _cache_in = xs
            n = L.rmsnorm(hh, bp["norm"], cfg.norm_eps, cdt)
            y, st = MB.mamba_forward(bp["mixer"], n, cfg)
            return hh + y, st

    else:

        def body(carry, xs):
            hh = carry
            bp, cache_in = xs
            hh, _, new_caches = apply_superblock(
                bp, hh, cfg, positions=positions, caches=cache_in
            )
            return hh, new_caches

    h, cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = _head_weight(params, cfg)
    return L.logits_head(w, h[:, -1:], transpose=transpose), cache


def decode_step(params, cache, token, cache_len, cfg: ArchConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    h = L.embed(params["embed"], token, cdt)
    if cfg.family == "ssm":

        def body(carry, xs):
            hh = carry
            bp, cache_in = xs
            n = L.rmsnorm(hh, bp["norm"], cfg.norm_eps, cdt)
            y, st = MB.mamba_step(bp["mixer"], n, cfg, cache_in)
            return hh + y, st

    else:

        def body(carry, xs):
            hh = carry
            bp, cache_in = xs
            hh, _, new_caches = apply_superblock(
                bp, hh, cfg, positions=None, caches=cache_in, decode_len=cache_len
            )
            return hh, new_caches

    h, cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = _head_weight(params, cfg)
    return L.logits_head(w, h, transpose=transpose), cache
