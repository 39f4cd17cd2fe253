"""Plain reference of a latent-attention MoE decoder (DeepSeek-V3 block:
multi-head latent attention without a query latent, leading dense layers,
then MoE layers of sigmoid-routed experts with shared experts), holding one
chip's share of the experts, and the weights the benchmark draws for it.

Written from the published equations (DeepSeek-V2, arXiv:2405.04434 §2.1;
DeepSeek-V3, arXiv:2412.19437 §2.1.2), in ``jax.numpy`` and float32 at the
highest matmul precision, with no cache, no sorting, no grouped product and
no kernel.  It imports nothing of the program.  ``m`` is the configuration
file, its model keys at the top level:

* attention: q = W_Q h split into q_C (``qk_nope_head_dim``) and q_R
  (``qk_rope_head_dim``) per head; [c_KV; k_R] = W_DKV h; c_KV normalised
  (RMSNorm with its own scale); [k_C; v] = W_UKV c_KV per head; rotary on
  q_R and on the single k_R all heads share (dimension i paired with
  i + rope/2); score (q_C.k_C + q_R.k_R) / sqrt(nope + rope), causal,
  computed in blocks of queries;
* MoE: s = sigmoid(h W_r) over all ``experts_routed`` experts; the top
  ``num_experts_per_tok`` of s + b (b the correction bias); weights
  s / sum(s) over the chosen, times ``routed_scaling_factor``.  Each of the
  ``n_routed_experts`` experts held here (from ``experts_held_first``)
  runs on every token and is weighted by its routing weight, 0 where it
  was not chosen; chosen experts not held add nothing.  The shared experts
  (width ``n_shared_experts * moe_intermediate_size``) run on every token;
* the sequence-wise balance loss sum_i f_i P_i times ``seq_aux_alpha``
  per MoE layer, f_i = E / (k T) * (tokens choosing i), P_i the mean over
  the sequence of s_i / sum_j s_j.

``loss`` also returns each MoE layer's assignments per expert, from which
the reference's step moves the bias (``bench/check/train_moe.py``).
``cast``, where given, rounds both operands of every matrix product first:
the control runs the reference in a lower precision with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.check.families.dense import _is_spec, _mm, _rms, fp8_cast, seed_key  # noqa: F401

QUERY_BLOCK = 512


# -- weights ---------------------------------------------------------------------


def _attn(n: int, m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    r, nope, rope = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd = m["v_head_dim"]
    return {
        "wq": ((n, d, H, nope + rope), d ** -0.5),
        "wkv_a": ((n, d, r + rope), d ** -0.5),
        "kv_norm": ((n, r), None),
        "wkv_b": ((n, r, H, nope + vd), r ** -0.5),
        "wo": ((n, H, vd, d), (H * vd) ** -0.5),
    }


def _mlp(n: int, d: int, f: int) -> dict:
    return {"wg": ((n, d, f), d ** -0.5), "wu": ((n, d, f), d ** -0.5),
            "wd": ((n, f, d), f ** -0.5)}


def layout(m: dict) -> dict:
    """The parameter tree: each leaf ``(shape, std)``, std ``None`` for ones
    and 0 for zeros; names and nesting as the program's."""
    d, V = m["hidden_size"], m["vocab_size"]
    nd = m["first_k_dense_replace"]
    nm = m["num_hidden_layers"] - nd
    E, held, F = m["experts_routed"], m["n_routed_experts"], m["moe_intermediate_size"]
    moe = {
        "router": ((nm, d, E), d ** -0.5),
        "bias": ((nm, E), 0.0),
        "wg": ((nm, held, d, F), d ** -0.5),
        "wu": ((nm, held, d, F), d ** -0.5),
        "wd": ((nm, held, F, d), F ** -0.5),
    }
    tree = {
        "embed": ((V, d), d ** -0.5),
        "blocks": {
            "attn": _attn(nm, m), "norm1": ((nm, d), None), "norm2": ((nm, d), None),
            "moe": moe,
            "shared_mlp": _mlp(nm, d, m["n_shared_experts"] * F),
        },
        "final_norm": ((d,), None),
        "head": ((d, V), d ** -0.5),
    }
    if nd:
        tree["dense_blocks"] = {
            "attn": _attn(nd, m), "norm1": ((nd, d), None), "norm2": ((nd, d), None),
            "mlp": _mlp(nd, d, m["intermediate_size"]),
        }
    return tree


def init(m: dict, seed: int, dtype):
    """The weights of seed ``seed`` in ``dtype``, drawn in one compiled
    program on the default device."""
    return jax.jit(draw_fn(m, dtype))(seed_key(seed))


def draw_fn(m: dict, dtype):
    specs, treedef = jax.tree_util.tree_flatten(layout(m), is_leaf=_is_spec)

    def draw(key):
        leaves = []
        for i, (shape, std) in enumerate(specs):
            if std is None:
                leaves.append(jnp.ones(shape, dtype))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                leaves.append((z * std).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return draw


def abstract(m: dict, dtype):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], jnp.dtype(dtype)), layout(m),
        is_leaf=_is_spec,
    )


# -- forward ---------------------------------------------------------------------


def _rope(x, pos, theta):
    """x: (S, heads, rd); dimension i turned with i + rd/2."""
    rd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rd // 2], x[..., rd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, p, h, cast):
    """h: (S, d) -> (S, d), latent attention over one sequence."""
    s = h.shape[0]
    r, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    pos = jnp.arange(s)
    q = _mm("sd,dhk->shk", h, p["wq"], cast)
    ckv = _mm("sd,dr->sr", h, p["wkv_a"], cast)
    c = _rms(ckv[:, :r], p["kv_norm"], m["rms_norm_eps"])
    kv = _mm("sr,rhk->shk", c, p["wkv_b"], cast)
    k_c, v = kv[..., :nope], kv[..., nope:]
    q_c = q[..., :nope]
    q_r = _rope(q[..., nope:], pos, m["rope_theta"])
    k_r = _rope(ckv[:, None, r:], pos, m["rope_theta"])[:, 0]      # (S, rope)
    scale = (nope + m["qk_rope_head_dim"]) ** -0.5
    nq = max(s // QUERY_BLOCK, 1)

    @jax.checkpoint
    def block(_, i):
        qc = jax.lax.dynamic_slice_in_dim(q_c, i * (s // nq), s // nq)
        qr = jax.lax.dynamic_slice_in_dim(q_r, i * (s // nq), s // nq)
        sc = (_mm("qhk,thk->hqt", qc, k_c, cast)
              + _mm("qhk,tk->hqt", qr, k_r, cast)) * scale
        qi = i * (s // nq) + jnp.arange(s // nq)
        sc = jnp.where(pos[None, None, :] <= qi[None, :, None], sc, -jnp.inf)
        return None, _mm("hqt,thk->qhk", jax.nn.softmax(sc, -1), v, cast)

    _, o = jax.lax.scan(block, None, jnp.arange(nq))
    o = o.reshape(s, *o.shape[2:])
    return _mm("shk,hkd->sd", o, p["wo"], cast)


def _mlp_apply(p, h, cast):
    g = _mm("sd,df->sf", h, p["wg"], cast)
    u = _mm("sd,df->sf", h, p["wu"], cast)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["wd"], cast)


def route(m, p, h, cast):
    """(scores (S, E), chosen experts (S, k), their weights (S, k))."""
    sc = jax.nn.sigmoid(_mm("sd,de->se", h, p["router"], cast))
    _, idx = jax.lax.top_k(sc + jax.lax.stop_gradient(p["bias"]), m["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, idx, -1)
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return sc, idx, w * m["routed_scaling_factor"]


def _moe(m, p, h, cast):
    """(output (S, d), balance loss, assignments per expert (E,))."""
    s = h.shape[0]
    E, k = m["experts_routed"], m["num_experts_per_tok"]
    first, held = m["experts_held_first"], m["n_routed_experts"]
    sc, idx, w = route(m, p, h, cast)
    gate = jnp.zeros((s, E), jnp.float32).at[jnp.arange(s)[:, None], idx].add(w)
    y = _mlp_apply(p["shared"], h, cast)

    def expert(y, e):
        pe = {n: jax.lax.dynamic_index_in_dim(p[n], e, keepdims=False)
              for n in ("wg", "wu", "wd")}
        return y + gate[:, first + e, None] * _mlp_apply(pe, h, cast), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), y, jnp.arange(held))
    count = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = count * (E / (k * s))
    pr = jnp.mean(sc / jnp.sum(sc, -1, keepdims=True), axis=0)
    return y, m["seq_aux_alpha"] * jnp.sum(f * pr), count


def _layer(m, p, x, cast):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    x = x + _attention(m, p["attn"], _rms(x, p["norm1"], eps), cast)
    h = _rms(x, p["norm2"], eps)
    if "mlp" in p:
        return x + _mlp_apply(p["mlp"], h, cast), 0.0, None
    moe = dict(p["moe"], shared=p["shared_mlp"])
    y, aux, count = _moe(m, moe, h, cast)
    return x + y, aux, count


def hidden(m: dict, params, tokens, cast=None):
    """One sequence ``tokens`` (S,): final normalised hidden states (S, d),
    the summed balance loss and the MoE layers' assignments (layers, E)."""
    x = params["embed"][tokens].astype(jnp.float32)
    aux = 0.0
    counts = None
    for name in ("dense_blocks", "blocks"):
        if name not in params:
            continue

        @jax.checkpoint
        def body(carry, p):
            x, aux = carry
            x, a, c = _layer(m, p, x, cast)
            return (x, aux + a), c

        (x, aux), counts_s = jax.lax.scan(body, (x, aux), params[name])
        counts = counts_s if counts_s is not None else counts
    h = _rms(x, params["final_norm"].astype(jnp.float32), m["rms_norm_eps"])
    return h, aux, counts


def loss(m: dict, params, tokens, labels, cast=None):
    """Mean next-token cross-entropy over the batch plus the balance loss
    (mean over the sequences), taken one sequence at a time; and the
    assignments per MoE layer and expert, summed over the batch."""
    def row(_, tl):
        t, lab = tl
        h, aux, counts = hidden(m, params, t, cast)
        z = _mm("sd,dv->sv", h, params["head"], cast)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, lab[:, None], -1)[:, 0]
        return None, (jnp.sum(nll), aux, counts)

    _, (sums, aux, counts) = jax.lax.scan(row, None, (tokens, labels))
    return jnp.sum(sums) / labels.size + jnp.mean(aux), jnp.sum(counts, 0)
