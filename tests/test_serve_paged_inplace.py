"""Paged decode keeps the KV pool whole and in place, and attends by
KV-head group.

``paged.decode_batch`` carries the stacked pool through its layer scan, so
the engine's decode program (which donates the pool) aliases it: no layer
of the pool is sliced out, copied or written back, and the gathered view
is never repeated from K to H heads.  The compiled program is checked on
the CPU; its numbers are checked against a plain jnp decode written here
(gather the whole view, repeat it to H heads, dense masked attention).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_variant
from repro.models import build_model
from repro.models import layers as L
from repro.serve import ServeEngine, paged
from repro.serve.policy import ServeConfig

# (KV heads) of a 4-head, 2-layer smoke granite: GQA and MHA
KV_HEADS = {"gqa": 2, "mha": 4}

_INSTR = re.compile(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _tiny(kv_heads: int):
    cfg = dataclasses.replace(smoke_variant(get_config("granite-3-2b")),
                              num_layers=2, num_kv_heads=kv_heads)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ops(hlo: str):
    """(opcode, dims) of every instruction, fused computations included."""
    for m in _INSTR.finditer(hlo):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        yield m.group(2), dims


@pytest.mark.parametrize("kind", sorted(KV_HEADS))
def test_engine_decode_program_updates_the_pool_in_place(kind):
    cfg, model, params = _tiny(KV_HEADS[kind])
    slots = 4
    eng = ServeEngine(model, params, slots=slots, max_len=64, block_size=8,
                      chunk=8)
    compiled = eng._decode.lower(
        eng.params, eng.pool, jnp.zeros((slots, 1), jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.asarray(eng._tables),
    ).compile()
    pool_bytes = sum(a.nbytes for a in eng.pool.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes

    n_layers, n_blocks, bs, kv, hd = eng.pool["k"].shape
    layer = (n_blocks, bs, kv, hd)
    per_layer = {layer, (1,) + layer}
    view = eng.serve_cfg.view_len
    repeated = slots * view * cfg.num_heads * hd
    ops = list(_ops(compiled.as_text()))
    assert ops, "no instruction parsed from the optimized HLO"
    for op, dims in ops:
        if op in ("dynamic-slice", "dynamic-update-slice"):
            assert dims not in per_layer, (op, dims)
            assert dims != (n_layers,) + layer, (op, dims)
        if op == "broadcast":
            assert int(np.prod(dims)) != repeated, (op, dims)


# -- numbers -------------------------------------------------------------------


def _reference_decode(params, pool, tokens, lengths, tables, cfg, scfg):
    """One token per slot, layer by layer in plain jnp: write the token's
    K/V, gather each slot's whole view, repeat it to H heads, attend with a
    dense mask over positions <= length."""
    cdt = jnp.dtype(cfg.compute_dtype)
    pk, pv = np.array(pool["k"]), np.array(pool["v"])
    s = tokens.shape[0]
    bs = scfg.block_size
    lengths, tables = np.asarray(lengths), np.asarray(tables)
    write_bi = tables[np.arange(s), lengths // bs]
    write_off = lengths % bs
    mask = np.arange(scfg.view_len)[None, :] <= lengths[:, None]   # (S, V)
    reps = cfg.num_heads // cfg.num_kv_heads
    h = L.embed(params["embed"], jnp.asarray(tokens), cdt)
    for i in range(cfg.num_layers):
        bp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
        n = L.rmsnorm(h, bp["norm1"], cfg.norm_eps, cdt)
        q, k, v = L._project_qkv(bp["attn"], n, cfg,
                                 jnp.asarray(lengths)[:, None])
        pk[i, write_bi, write_off] = np.asarray(k[:, 0])
        pv[i, write_bi, write_off] = np.asarray(v[:, 0])
        kview = jnp.repeat(pk[i][tables].reshape(s, scfg.view_len,
                                                 cfg.num_kv_heads, -1),
                           reps, axis=2)
        vview = jnp.repeat(pv[i][tables].reshape(s, scfg.view_len,
                                                 cfg.num_kv_heads, -1),
                           reps, axis=2)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, kview).astype(jnp.float32)
        scores = scores / np.sqrt(q.shape[-1])
        scores = jnp.where(mask[:, None, None, :], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, vview)
        h = h + jnp.einsum("bqhk,hkd->bqd", out, bp["attn"]["wo"].astype(cdt))
        n = L.rmsnorm(h, bp["norm2"], cfg.norm_eps, cdt)
        h = h + paged._ffn(bp, n, cfg)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps, cdt)
    w, transpose = paged._head_weight(params, cfg)
    return L.logits_head(w, h, transpose=transpose), {"k": pk, "v": pv}


def _scattered_pool_state(cfg, scfg, seed=0):
    """A pool of random K/V; slot 0 and slot 1 own blocks scattered over the
    pool, slot 2 is an inactive lane (length 0, every block the scratch
    block 0)."""
    rng = np.random.default_rng(seed)
    shape = paged.init_pool(cfg, scfg)["k"].shape
    pool = {kk: jnp.asarray(rng.standard_normal(shape), cfg.compute_dtype)
            for kk in ("k", "v")}
    mb = scfg.max_blocks_per_slot
    tables = np.zeros((scfg.slots, mb), np.int32)
    tables[0, :3] = [7, 2, 11]
    tables[1, :4] = [3, 9, 5, 12]
    lengths = np.array([7, 13, 0], np.int32)
    return pool, tables, lengths


@pytest.mark.parametrize("kind", sorted(KV_HEADS))
def test_decode_batch_matches_plain_reference(kind):
    """Three consecutive calls, each on the pool the last one returned
    (slot 0 crosses a block boundary): logits and every pool position
    match the plain reference, and nothing else of the pool moves."""
    cfg, _, params = _tiny(KV_HEADS[kind])
    scfg = ServeConfig(slots=3, max_len=16, block_size=4, chunk=4)
    pool, tables, lengths = _scattered_pool_state(cfg, scfg)
    dec = jax.jit(
        lambda p, pl, t, ln, tb: paged.decode_batch(p, pl, t, ln, tb, cfg,
                                                    scfg),
        donate_argnums=(1,),
    )
    ref_pool = {kk: np.array(a) for kk, a in pool.items()}
    rng = np.random.default_rng(1)
    for _ in range(3):
        tokens = rng.integers(1, cfg.vocab_size, (scfg.slots, 1),
                              dtype=np.int32)
        want, ref_pool = _reference_decode(params, ref_pool, tokens, lengths,
                                           tables, cfg, scfg)
        got, pool = dec(params, pool, jnp.asarray(tokens),
                        jnp.asarray(lengths), jnp.asarray(tables))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        for kk in ("k", "v"):
            np.testing.assert_allclose(np.asarray(pool[kk]), ref_pool[kk],
                                       rtol=1e-5, atol=1e-5)
        lengths = lengths + np.array([1, 1, 0], np.int32)

    # positions no call wrote are bit-for-bit the starting pool
    start, _, _ = _scattered_pool_state(cfg, scfg)
    written = np.zeros(start["k"].shape[1:3], bool)   # (blocks, block_size)
    for s, (first, n) in enumerate([(7, 3), (13, 3), (0, 1)]):
        for pos in range(first, first + n):
            written[tables[s, pos // 4], pos % 4] = True
    for kk in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pool[kk])[:, ~written],
                                      np.asarray(start[kk])[:, ~written])


def test_decode_batch_reuses_an_undonated_pool():
    """``serve/cost.calibrate_serve``'s pattern: several timed decode calls
    on one pool that is not donated give the same logits and leave the
    pool as it was."""
    cfg, _, params = _tiny(KV_HEADS["gqa"])
    scfg = ServeConfig(slots=3, max_len=16, block_size=4, chunk=4)
    pool, tables, lengths = _scattered_pool_state(cfg, scfg)
    before = {kk: np.array(a) for kk, a in pool.items()}
    dec = jax.jit(lambda p, pl, t, ln, tb: paged.decode_batch(
        p, pl, t, ln, tb, cfg, scfg))
    tokens = jnp.ones((scfg.slots, 1), jnp.int32)
    outs = [np.asarray(dec(params, pool, tokens, jnp.asarray(lengths),
                           jnp.asarray(tables))[0]) for _ in range(3)]
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])
    for kk in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pool[kk]), before[kk])
