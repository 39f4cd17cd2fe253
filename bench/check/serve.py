"""Did the engine serve the tokens the plain reference would?

Once the window has closed, a sample of the requests it finished is drawn
from the seed, with the longest of them always in it, until it holds the
traffic's ``check_tokens`` served tokens.  The reference runs once over
each prompt followed by its served tokens, in float32, as one causal pass
with no cache, and reads at each served position the gap by which the
served token's logit lies below the reference's best there.  The number
compared is the widest such gap (``logit_gap``, in logits): greedy decoding
in the program's precision may pick a token the reference ranks a hair
lower, but not one far below.

The control puts the reference, in a lower precision, in the program's
place: it reads the gap of the token that the lower precision ranks first
at each of the same positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sample(finished, seed: int, budget: int):
    """``finished``: [(traffic request, engine request)]; the longest and
    then others in an order drawn from the seed, up to ``budget`` tokens."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda x: (-(x[0].prompt_len + len(x[1].output)), x[0].rid))
    out = [by_len[0]]
    rest = by_len[1:]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    n = len(out[0][1].output)
    for i in order:
        if n >= budget:
            break
        out.append(rest[i])
        n += len(rest[i][1].output)
    return out


def _case_arrays(prompt, served, max_len: int, out_max: int):
    p = len(prompt)
    seq = np.zeros((1, max_len), np.int32)
    toks = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served[:-1], np.int32)])
    seq[0, : len(toks)] = toks
    pos = np.zeros((out_max,), np.int32)
    tgt = np.zeros((out_max,), np.int32)
    valid = np.zeros((out_max,), bool)
    n = len(served)
    pos[:n] = np.arange(p - 1, p - 1 + n)
    tgt[:n] = served
    valid[:n] = True
    return seq, pos, tgt, valid


@functools.lru_cache(maxsize=4)
def _gap_fn(fam, mkey, control: bool):
    m = dict(mkey)
    cast = fam.fp8_cast if control else None

    @jax.jit
    def gap(params, seq, pos, tgt, valid):
        h = fam.hidden(m, params, seq)[0][pos]
        z = fam.logits_at(m, params, h)
        if control:
            zc = fam.logits_at(m, params, fam.hidden(m, params, seq, cast)[0][pos], cast)
            tgt = jnp.argmax(zc, -1)
        g = jnp.max(z, -1) - jnp.take_along_axis(z, tgt[:, None], -1)[:, 0]
        return jnp.max(jnp.where(valid, g, 0.0))

    return gap


def widest_gap(fam, m: dict, params, cases, max_len: int, out_max: int,
               control: bool = False) -> float:
    fn = _gap_fn(fam, tuple(sorted(m.items())), control)
    worst = 0.0
    for prompt, served in cases:
        a = _case_arrays(prompt, served, max_len, out_max)
        worst = max(worst, float(fn(params, *map(jnp.asarray, a))))
    return worst


def compare(fam, m: dict, seed: int, dtype, cases, max_len: int,
            out_max: int, control: bool = False) -> tuple[dict, dict]:
    """The numbers compared; with ``control``, the control's reading on the
    same cases is in the detail."""
    detail = {"requests": len(cases), "tokens": sum(len(s) for _, s in cases)}
    if not cases:
        return {"logit_gap": None}, detail
    params = fam.init(m, seed, dtype)
    g = widest_gap(fam, m, params, cases, max_len, out_max)
    if control:
        detail["control_logit_gap"] = widest_gap(
            fam, m, params, cases, max_len, out_max, control=True)
    del params
    return {"logit_gap": g}, detail
