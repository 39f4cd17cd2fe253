"""Continuous-batching engine vs the sequential decode reference.

The paged engine (chunked prefill, scatter/gather KV blocks, batched
decode with scratch lanes) must be *token-for-token* identical to the
plain ``Model.prefill`` + ``decode_step`` greedy loop: ``_sdpa_dense``
masks with ``finfo(f32).min``, so masked pool positions contribute exactly
0.0 to the softmax and the padded gathered view computes the same numbers
as the reference's contiguous cache.

Also pins the seed engine's ``slot_len`` off-by-one: capacity is now
exactly ``max_len`` cached positions (``max_len - prompt_len + 1`` output
tokens), where the old engine clamped at ``max_len - 1`` and re-wrote the
final cache position.

The slow tier adds a subprocess test on a forced-8-device host: the
slot-sharded engine must produce the same tokens as the unsharded one.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_variant
from repro.models import build_model

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _tiny(name: str, **kw):
    """2-layer smoke config: same code path, minimal jit time."""
    return dataclasses.replace(smoke_variant(get_config(name)),
                               num_layers=2, **kw)


def _reference_greedy(model, params, prompt, n_tokens, max_len):
    """Sequential whole-prompt prefill + one-token decode_step loop."""
    logits, cache = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, max_len)
    )(params, jnp.asarray(np.asarray(prompt)[None, :]))
    toks = [int(jnp.argmax(logits[0, -1]))]
    clen = len(prompt)
    dec = jax.jit(model.decode)
    for _ in range(n_tokens - 1):
        lg, cache = dec(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32), clen
        )
        toks.append(int(jnp.argmax(lg[0, -1])))
        clen += 1
    return toks


def _build(name, seed=0, **kw):
    cfg = _tiny(name, **kw)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def test_engine_chunked_prefill_matches_reference_dense(rng):
    """Prompts longer than the chunk (incl. a non-pow2 final chunk) and two
    interleaved slots still match the sequential reference exactly."""
    from repro.serve import Request, ServeEngine

    cfg, model, params = _build("llama3.2-1b")
    prompts = [
        rng.integers(1, cfg.vocab_size, 21, dtype=np.int32),  # 8+8+5 chunks
        rng.integers(1, cfg.vocab_size, 9, dtype=np.int32),   # 8+1
    ]
    eng = ServeEngine(model, params, slots=2, max_len=48,
                      block_size=8, chunk=8)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
    done = {r.rid: r.output for r in eng.run_until_done()}
    for rid, p in enumerate(prompts):
        assert done[rid] == _reference_greedy(model, params, p, 5, 48), (
            f"request {rid} diverged from the sequential reference"
        )


def _logits_of_served_tokens(eng):
    """Wrap the engine's programs so that each served token's logits are
    kept: the last prefill chunk's (the first token) and decode lane 0's."""
    seen = {"prefill": [], "decode": []}
    prefill_fn, decode = eng._prefill_fn, eng._decode

    def keep(kind, fn, pick):
        def run(*args):
            logits, pool = fn(*args)
            seen[kind].append(np.asarray(pick(logits), np.float64))
            return logits, pool
        return run

    eng._prefill_fn = lambda b: keep("prefill", prefill_fn(b), lambda lg: lg[0, -1])
    eng._decode = keep("decode", decode, lambda lg: lg[0, -1])
    return seen


def test_engine_matches_reference_moe(rng):
    """Logits, not greedy tokens: the engine's logits for each served token
    (12-token prompt in chunks of 8, then decode through the paged pool)
    against the whole-prompt prefill and one-token decode of the reference
    fed the same tokens.  The MoE layer is dropless, so chunking changes no
    routing; float32 throughout, and the tolerance covers the different
    summation order of chunked and whole-prompt attention."""
    from repro.serve import Request, ServeEngine

    cfg, model, params = _build("qwen3-moe-235b-a22b", seed=1)
    prompt = rng.integers(1, cfg.vocab_size, 12, dtype=np.int32)
    eng = ServeEngine(model, params, slots=2, max_len=32,
                      block_size=8, chunk=8)
    seen = _logits_of_served_tokens(eng)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    out = eng.run_until_done()[0].output
    served = [seen["prefill"][-1]] + seen["decode"]
    assert len(out) == len(served) == 4

    logits, cache = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, 32))(
        params, jnp.asarray(prompt[None, :]))
    ref = [np.asarray(logits[0, -1], np.float64)]
    dec = jax.jit(model.decode)
    for i, tok in enumerate(out[:-1]):
        lg, cache = dec(params, cache, jnp.asarray([[tok]], jnp.int32),
                        len(prompt) + i)
        ref.append(np.asarray(lg[0, -1], np.float64))
    for got, want in zip(served, ref):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_engine_boundary_runs_to_exactly_max_len(rng):
    """Off-by-one regression: a request may fill ALL max_len cache
    positions.  prompt 20 + budget 64 in a 32-position cache must emit
    exactly 32 - 20 + 1 = 13 tokens, all matching the reference (the seed
    engine clamped at max_len - 1, re-writing the last position)."""
    from repro.serve import Request, ServeEngine

    cfg, model, params = _build("llama3.2-1b")
    prompt = rng.integers(1, cfg.vocab_size, 20, dtype=np.int32)
    eng = ServeEngine(model, params, slots=1, max_len=32,
                      block_size=8, chunk=8)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=64))
    done = eng.run_until_done()
    assert len(done[0].output) == 13
    assert done[0].output == _reference_greedy(model, params, prompt, 13, 32)
    # every block (incl. the final one) was written and returned
    assert eng.sched.allocator.num_free == eng.sched.allocator.num_blocks - 1
    assert eng.sched.allocator.blocks_of("__scratch__") == [0]


def test_engine_eos_early_exit(rng):
    from repro.serve import Request, ServeEngine

    cfg, model, params = _build("llama3.2-1b")
    prompt = rng.integers(1, cfg.vocab_size, 8, dtype=np.int32)

    eng = ServeEngine(model, params, slots=1, max_len=32, block_size=8)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    free_run = eng.run_until_done()[0].output
    assert len(free_run) == 8

    eos = free_run[2]
    eng2 = ServeEngine(model, params, slots=1, max_len=32, block_size=8,
                       eos_id=eos)
    eng2.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    got = eng2.run_until_done()[0].output
    assert got == free_run[: got.index(eos) + 1]
    assert eos in got


def test_engine_single_slot_queueing_isolated(rng):
    """Three requests through one slot: sequential occupancy, FIFO order,
    and no KV state leaking between consecutive occupants of the slot."""
    from repro.serve import Request, ServeEngine

    cfg, model, params = _build("llama3.2-1b")
    prompts = [rng.integers(1, cfg.vocab_size, 6 + 3 * r, dtype=np.int32)
               for r in range(3)]
    eng = ServeEngine(model, params, slots=1, max_len=32,
                      block_size=8, chunk=8)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=3))
    done = {r.rid: r for r in eng.run_until_done()}
    for rid, p in enumerate(prompts):
        assert done[rid].output == _reference_greedy(model, params, p, 3, 32)
    # FIFO completion and latency records populated
    e2es = [done[r].e2e_s for r in range(3)]
    assert e2es == sorted(e2es)
    for r in range(3):
        assert done[r].ttft_s is not None
        assert len(done[r].token_times_s) == 3


_SHARD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from jax import make_mesh
    from jax.sharding import AxisType
    from repro.configs.base import get_config, smoke_variant
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 6 + r, dtype=np.int32)
               for r in range(8)]

    def run(mesh):
        eng = ServeEngine(model, params, slots=8, max_len=32,
                          block_size=8, chunk=8, mesh=mesh)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=4))
        return {r.rid: r.output for r in eng.run_until_done()}

    assert jax.device_count() == 8, jax.device_count()
    plain = run(None)
    sharded = run(make_mesh((8,), ("serve",), axis_types=(AxisType.Auto,)))
    assert plain == sharded, (plain, sharded)
    print("shard_parity_ok")
    """
)


@pytest.mark.slow
def test_engine_sharded_8dev_matches_unsharded():
    """Slot-sharded decode on a forced-8-device host is token-identical to
    the single-device engine (the decode batch is data-parallel over
    slots; sharding must not change the math)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "shard_parity_ok" in out.stdout
