"""The serving engine's programs carry stable names, and the serving
metrics tell them apart by those names alone."""
import dataclasses

import _tiny_cells  # noqa: F401  (puts the repository on the path)
import jax
import jax.numpy as jnp

from bench import serve_work, xtrace
from repro.configs.base import get_config, smoke_variant
from repro.models import build_model
from repro.serve import ServeEngine


def _module_name(lowered) -> str:
    """``module @jit_serve_decode attributes ...`` -> ``jit_serve_decode``."""
    first = lowered.as_text().splitlines()[0]
    return first.split("@", 1)[1].split()[0]


def test_engine_programs_are_named_and_each_has_one_kind():
    cfg = dataclasses.replace(smoke_variant(get_config("granite-3-2b")),
                              num_layers=2)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=2, max_len=32, block_size=8, chunk=8)
    tables = jnp.asarray(eng._tables)
    decode = _module_name(eng._decode.lower(
        eng.params, eng.pool, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), tables))
    prefill = _module_name(eng._prefill_fn(8).lower(
        eng.params, eng.pool, jnp.zeros((1, 8), jnp.int32), jnp.int32(0),
        jnp.int32(8), tables[0]))
    assert (decode, prefill) == ("jit_serve_decode", "jit_serve_prefill")
    for name, kind in ((decode, "decode"), (prefill, "prefill")):
        # as the trace names a call: the module name and a fingerprint
        traced = xtrace.base_name(f"{name}(8911407554505906894)")
        assert [k for k, pat in serve_work.PROGRAMS.items()
                if pat.search(traced)] == [kind]
