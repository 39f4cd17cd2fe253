"""Plain reference of a dense decoder (GQA attention, gated SiLU MLP,
RMSNorm, rotary positions, tied or untied head), and the weights the
benchmark draws for it.

Written from the published description of such a model, in ``jax.numpy``
and float32 at the highest matmul precision, with no cache, no batching of
requests, no chunking and no kernel.  It imports nothing of the program.
What the configuration file states is what it computes:
``embedding_multiplier`` scales the embedded tokens, ``attention_multiplier``
scales the scores, ``residual_multiplier`` each branch added to the
residual, the logits are divided by ``logits_scaling``, and rotary
positions turn the first ``partial_rotary_factor`` of each head's
dimensions (the halves rotated against each other).

``cast``, where given, rounds both operands of every matrix product first:
the control runs the reference in a lower precision with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# -- weights ---------------------------------------------------------------------


def layout(m: dict) -> dict:
    """The parameter tree: each leaf ``(shape, std)``, std ``None`` for ones.

    Names and nesting are those of the program's checkpoint format, so the
    same tree goes into the program and into this reference.
    """
    L, d = m["num_hidden_layers"], m["hidden_size"]
    H, K, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f, V = m["intermediate_size"], m["vocab_size"]
    tree = {
        "embed": ((V, d), d ** -0.5),
        "blocks": {
            "attn": {
                "wq": ((L, d, H, hd), d ** -0.5),
                "wk": ((L, d, K, hd), d ** -0.5),
                "wv": ((L, d, K, hd), d ** -0.5),
                "wo": ((L, H, hd, d), (H * hd) ** -0.5),
            },
            "norm1": ((L, d), None),
            "norm2": ((L, d), None),
            "mlp": {
                "wg": ((L, d, f), d ** -0.5),
                "wu": ((L, d, f), d ** -0.5),
                "wd": ((L, f, d), f ** -0.5),
            },
        },
        "final_norm": ((d,), None),
    }
    if not m["tie_word_embeddings"]:
        tree["head"] = ((d, V), d ** -0.5)
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init(m: dict, seed: int, dtype):
    """The weights of seed ``seed`` in ``dtype``, drawn in one compiled
    program on the default device."""
    return jax.jit(draw_fn(m, dtype))(seed_key(seed))


def draw_fn(m: dict, dtype):
    """key -> weights: every leaf drawn from its own fold of the key."""
    lay = layout(m)
    specs, treedef = jax.tree_util.tree_flatten(lay, is_leaf=_is_spec)

    def draw(key):
        leaves = []
        for i, (shape, std) in enumerate(specs):
            if std is None:
                leaves.append(jnp.ones(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                leaves.append(
                    (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
                )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return draw


def abstract(m: dict, dtype):
    lay = layout(m)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], jnp.dtype(dtype)), lay,
        is_leaf=_is_spec,
    )


# -- forward ---------------------------------------------------------------------


def _mm(spec, a, b, cast):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta, frac):
    """x: (B,S,heads,hd); rotate the first ``frac`` of hd, halves paired."""
    hd = x.shape[-1]
    rd = int(hd * frac)
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos[:, :, None].astype(jnp.float32) * inv          # (B,S,rd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, xp], -1)


def _layer(m, p, x, pos, cast):
    eps = m["rms_norm_eps"]
    r = m["residual_multiplier"]
    H, K = m["num_attention_heads"], m["num_key_value_heads"]
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    b, s, _ = x.shape
    h = _rms(x, p["norm1"], eps)
    q = _mm("bsd,dhk->bshk", h, p["attn"]["wq"], cast)
    k = _mm("bsd,dhk->bshk", h, p["attn"]["wk"], cast)
    v = _mm("bsd,dhk->bshk", h, p["attn"]["wv"], cast)
    q = _rope(q, pos, m["rope_theta"], m["partial_rotary_factor"])
    k = _rope(k, pos, m["rope_theta"], m["partial_rotary_factor"])
    q = q.reshape(b, s, K, H // K, q.shape[-1])
    sc = _mm("bskgd,btkd->bkgst", q, k, cast) * m["attention_multiplier"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _mm("bkgst,btkd->bskgd", pr, v, cast).reshape(b, s, H, -1)
    x = x + r * _mm("bshk,hkd->bsd", o, p["attn"]["wo"], cast)
    h = _rms(x, p["norm2"], eps)
    g = _mm("bsd,df->bsf", h, p["mlp"]["wg"], cast)
    u = _mm("bsd,df->bsf", h, p["mlp"]["wu"], cast)
    return x + r * _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp"]["wd"], cast)


def hidden(m: dict, params, tokens, cast=None, remat: bool = False):
    """Final normalised hidden states (B,S,d), float32."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32) * m["embedding_multiplier"]
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, p):
        return _layer(m, p, x, pos, cast), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _rms(x, params["final_norm"].astype(jnp.float32), m["rms_norm_eps"])


def logits_at(m: dict, params, h, cast=None):
    """Logits (..., V) of hidden states ``h`` (..., d)."""
    if m["tie_word_embeddings"]:
        z = _mm("...d,vd->...v", h, params["embed"], cast)
    else:
        z = _mm("...d,dv->...v", h, params["head"], cast)
    return z / m["logits_scaling"]


def loss(m: dict, params, tokens, labels, cast=None):
    """Mean next-token cross-entropy over every position of the batch,
    taken one row at a time so that it fits beside the weights."""
    def row(_, tl):
        t, lab = tl
        h = hidden(m, params, t[None], cast, remat=True)
        z = logits_at(m, params, h[0], cast)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, lab[:, None], -1)[:, 0]
        return None, jnp.sum(nll)

    _, sums = jax.lax.scan(row, None, (tokens, labels))
    return jnp.sum(sums) / labels.size


# -- lower precision -------------------------------------------------------------


def fp8_cast(x):
    """Round to float8 e4m3 under one per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
