"""Latent attention and the held-experts MoE layer against plain references.

Tiny widths, seeded random weights, float32 throughout: every tolerance
below covers only a different order of float32 summation (blockwise
online softmax against one softmax, a grouped product against a per-expert
loop), never an approximation.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, get_config, smoke_variant
from repro.models import layers as L
from repro.models import moe as M
from repro.train.step import move_router_bias


def _mla_cfg(**kw):
    return dataclasses.replace(smoke_variant(get_config("moonlight-16b-a3b")), **kw)


def _rope_ref(x, pos, theta):
    """Dimension i of the rotary part turned with dimension i + rd/2."""
    rd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, rd, 2) / rd)
    ang = pos[:, None] * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., : rd // 2], x[..., rd // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla_ref(p, x, cfg):
    """DeepSeek-V2 §2.1 without a query latent, one sequence, in numpy."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    s = x.shape[0]
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pos = np.arange(s, dtype=np.float64)
    q = np.einsum("sd,dhk->shk", x, p["wq"])
    ckv = np.einsum("sd,dr->sr", x, p["wkv_a"])
    c = ckv[:, :r] / np.sqrt(np.mean(ckv[:, :r] ** 2, -1, keepdims=True)
                             + cfg.norm_eps) * p["kv_norm"]
    kv = np.einsum("sr,rhk->shk", c, p["wkv_b"])
    k_c, v = kv[..., :nope], kv[..., nope:]
    q_r = _rope_ref(q[..., nope:], pos, cfg.rope_theta)
    k_r = _rope_ref(ckv[:, None, r:], pos, cfg.rope_theta)[:, 0]
    scores = (np.einsum("qhk,thk->hqt", q[..., :nope], k_c)
              + np.einsum("qhk,tk->hqt", q_r, k_r)) / math.sqrt(cfg.qk_head_dim)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("hqt,thk->qhk", w, v)
    return np.einsum("qhk,hkd->qd", o, p["wo"])


@pytest.mark.parametrize("impl", ["dense", "blockwise"])
def test_mla_attention_matches_reference(impl, monkeypatch):
    """Both score paths: one softmax over the causal mask, and the online
    softmax over key blocks of 16 (query/key width 24 against value width
    16)."""
    cfg = _mla_cfg()
    monkeypatch.setattr(L, "BLOCK_KV", 16)
    monkeypatch.setattr(L, "attn_path", lambda *a, **kw: impl)
    p, _ = L.init_attention(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    with jax.default_matmul_precision("highest"):
        got = L.attention(p, x, cfg, positions=pos)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(got[b]), _mla_ref(p, x[b], cfg),
                                   rtol=2e-5, atol=2e-5)


def _moe_cfg(**kw):
    base = dict(num_experts=8, top_k=3, d_ff_expert=16, num_shared_experts=1,
                score_func="sigmoid", routed_scaling_factor=2.446,
                router_aux_loss=1e-4, bias_update_rate=1e-3)
    base.update(kw)
    return MoEConfig(**base)


def _mlp_ref(wg, wu, wd, x):
    g, u = x @ wg, x @ wu
    return (g / (1 + np.exp(-g)) * u) @ wd


def _layer_ref(p, shared, x, moe):
    """The whole layer, every expert held, one token at a time: sigmoid
    scores, top-k on scores plus bias, chosen scores normalised and scaled,
    plus the shared expert."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    out = []
    for t in np.asarray(x, np.float64):
        s = 1 / (1 + np.exp(-(t @ p["router"])))
        chosen = np.argsort(-(s + p["bias"]), kind="stable")[: moe.top_k]
        w = s[chosen] / s[chosen].sum() * moe.routed_scaling_factor
        y = sum(wi * _mlp_ref(p["wg"][e], p["wu"][e], p["wd"][e], t)
                for wi, e in zip(w, chosen))
        out.append(y + _mlp_ref(*(np.asarray(shared[k], np.float64)
                                  for k in ("wg", "wu", "wd")), t))
    return np.stack(out)


def _moe_params(moe, d, seed=0):
    p, _ = M.init_moe(jax.random.PRNGKey(seed), d, moe, jnp.float32)
    # a bias that changes some choices, as training would leave it
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), p["bias"].shape)
    shared, _ = L.init_mlp(jax.random.PRNGKey(seed + 2), d,
                           moe.num_shared_experts * moe.d_ff_expert, jnp.float32)
    return p, shared


def test_shares_sum_to_the_layer():
    """A layer of 8 experts cut into 4 shares of 2 (experts_held (0,2),
    (2,2), ...): the shares' routed parts, plus the shared expert counted
    once, add up to the uncut reference layer."""
    d = 32
    moe = _moe_cfg()
    p, shared = _moe_params(moe, d)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, d), jnp.float32)
    total = L.mlp(shared, x, jnp.float32)
    loads = []
    for first in range(0, 8, 2):
        part = dict(p, **{k: p[k][first:first + 2] for k in ("wg", "wu", "wd")})
        y, _, load = M.moe_ffn(part, x, dataclasses.replace(
            moe, experts_held=(first, 2)), jnp.float32)
        total = total + y
        loads.append(load)
    want = _layer_ref(p, shared, x.reshape(-1, d), moe)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, d), want,
                               rtol=2e-5, atol=2e-5)
    # every share routes over all 8 experts, so each sees the same loads
    for load in loads[1:]:
        np.testing.assert_array_equal(np.asarray(load), np.asarray(loads[0]))
    assert float(jnp.sum(loads[0])) == 2 * 24 * moe.top_k


def test_dropless_batch_equals_its_parts():
    """No capacity: a token's output does not depend on what else is in the
    batch, so the batch's output is its rows' outputs, and its loads their
    sum.  Experts (2, 4) of 8 are held."""
    d = 32
    moe = _moe_cfg(experts_held=(2, 4))
    p, _ = _moe_params(moe, d, seed=5)
    p = dict(p, **{k: p[k][:4] for k in ("wg", "wu", "wd")})
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16, d), jnp.float32)
    y, _, load = M.moe_ffn(p, x, moe, jnp.float32)
    parts = [M.moe_ffn(p, x[i:i + 1], moe, jnp.float32) for i in range(4)]
    np.testing.assert_allclose(np.asarray(y),
                               np.concatenate([np.asarray(q[0]) for q in parts]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray(sum(q[2] for q in parts)))


def test_balance_loss_by_hand():
    """DeepSeek-V3's sequence-wise loss on 2 sequences of 2 tokens, E 4,
    k 2: alpha * mean over sequences of sum_i f_i P_i with
    f_i = E / (k T) * (choices of i), P_i = mean_t s_ti / sum_j s_tj."""
    moe = _moe_cfg(num_experts=4, top_k=2, router_aux_loss=0.5)
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.5],     # sequence 0
                          [0.2, 0.2, 0.2, 0.4],
                          [0.1, 0.1, 0.1, 0.7],     # sequence 1
                          [0.5, 0.5, 0.0, 0.0]], jnp.float32)
    choices = jnp.asarray([[0, 2], [3, 0], [3, 1], [0, 1]], jnp.int32)
    # sequence 0: counts (2, 0, 1, 1) -> f = (2, 0, 1, 1);
    #   P = mean((.45, .05, .25, .25), (.2, .2, .2, .4)) = (.325, .125, .225, .325)
    #   sum f P = .65 + .225 + .325 = 1.2
    # sequence 1: counts (1, 2, 0, 1) -> f = (1, 2, 0, 1);
    #   P = mean((.1, .1, .1, .7), (.5, .5, 0, 0)) = (.3, .3, .05, .35)
    #   sum f P = .3 + .6 + .35 = 1.25
    got = float(M.balance_loss(scores, choices, moe, batch=2))
    assert got == pytest.approx(0.5 * (1.2 + 1.25) / 2, rel=1e-6)


def test_router_weights_and_bias_by_hand():
    """The bias moves the choice, never the weights: scores
    sigmoid(0)=.5, sigmoid(2)=.881, sigmoid(-1)=.269, sigmoid(1)=.731;
    with bias (0, 0, 1, 0) the top-2 are experts 2 and 1, weighted by
    their scores normalised (.269, .881)/1.150 times 2.446."""
    moe = _moe_cfg(num_experts=4, top_k=2, d_ff_expert=4)
    eye = jnp.eye(4, dtype=jnp.float32)
    p = {"router": eye, "bias": jnp.asarray([0.0, 0.0, 1.0, 0.0])}
    x = jnp.asarray([[0.0, 2.0, -1.0, 1.0]], jnp.float32)
    scores, choices, w = M.route(p, x, moe)
    assert np.asarray(choices).tolist() == [[2, 1]]
    s = 1 / (1 + np.exp(-np.asarray([-1.0, 2.0])))
    np.testing.assert_allclose(np.asarray(w)[0], s / s.sum() * 2.446, rtol=1e-6)


def test_bias_update_by_hand():
    """b_i <- b_i + gamma * sign(mean load - load_i), per MoE layer; the
    optimizer's change to the bias (its weight decay) is replaced."""
    load = jnp.asarray([[4.0, 0.0, 2.0, 2.0], [1.0, 1.0, 1.0, 5.0]])
    before = jnp.asarray([[0.0, 0.1, -0.1, 0.0], [0.0, 0.0, 0.0, 0.0]])
    params = {"blocks": {"moe": {"bias": before, "router": jnp.zeros(1)}}}
    decayed = {"blocks": {"moe": {"bias": before * 0.9, "router": jnp.ones(1)}}}
    out = move_router_bias(decayed, params, load, 0.001)
    np.testing.assert_allclose(
        np.asarray(out["blocks"]["moe"]["bias"]),
        [[-0.001, 0.101, -0.1, 0.0], [0.001, 0.001, 0.001, -0.001]], rtol=1e-6)
    assert float(out["blocks"]["moe"]["router"][0]) == 1.0


def test_train_step_moves_the_bias_and_records_the_loads():
    """Two steps of the smoke moonlight config (1 dense + 3 MoE layers,
    all 4 experts held) through ``make_train_step`` and
    ``run_timed_step``: the loads come back per MoE layer and expert, the
    bias moves by gamma * sign(mean load - load) from them, and the
    recorder takes ``moe.expert_tokens`` (one sample an expert, summed over
    layers) and ``moe.held_assignments``."""
    from repro.models import build_model
    from repro.obs.record import Recorder
    from repro.optim import cosine_with_warmup, make_optimizer
    from repro.train.step import TrainState, make_train_step, run_timed_step

    cfg = _mla_cfg()
    model = build_model(cfg)
    opt = make_optimizer("adamw", weight_decay=0.1)
    params, _ = model.init(jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params, opt.init(params), None)
    step = jax.jit(make_train_step(model, opt, cosine_with_warmup(1e-3, 1, 10)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    rec = Recorder(enabled=True)
    bias0 = np.asarray(state.params["blocks"]["moe"]["bias"])
    state, met, _, _ = run_timed_step(step, state, batch, rec, "s0")
    load = np.asarray(met["expert_tokens"])
    assert load.shape == (3, 4) and (load.sum(1) == 2 * 32 * 2).all()
    assert float(met["held_assignments"]) == load.sum()
    want = bias0 + 1e-3 * np.sign(load.mean(1, keepdims=True) - load)
    np.testing.assert_allclose(np.asarray(state.params["blocks"]["moe"]["bias"]),
                               want, rtol=0, atol=1e-9)
    by_name = {}
    for c in rec.counters:
        by_name.setdefault(c.name, {})[c.device] = c.value
    assert by_name["moe.held_assignments"] == {"host": load.sum()}
    assert by_name["moe.expert_tokens"] == {
        f"expert{i}": n for i, n in enumerate(load.sum(0))}
