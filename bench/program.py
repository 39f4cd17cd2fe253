"""The system under test, built from a configuration file.

The program's own registry entry is taken and changed only as the file's
``program.overrides`` say; then every width is checked against the file, so
that a registry that drifts from the published numbers stops the run
instead of changing what is measured.
"""
from __future__ import annotations

import dataclasses


def arch_config(config: dict):
    from repro.configs.base import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["registry"]), **prog["overrides"])
    m = config["model"]
    want = {
        "num_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "num_heads": m["num_attention_heads"],
        "num_kv_heads": m["num_key_value_heads"],
        "resolved_head_dim": m["head_dim"], "d_ff": m["intermediate_size"],
        "vocab_size": m["vocab_size"], "tie_embeddings": m["tie_word_embeddings"],
        "rope_theta": m["rope_theta"], "norm_eps": m["rms_norm_eps"],
        "qkv_bias": False, "moe": None, "family": config["family"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def check_params(model, expected) -> None:
    """The program's parameter tree has the layout the benchmark fills."""
    import jax

    got, _ = model.abstract_params()
    a = jax.tree_util.tree_structure(got)
    b = jax.tree_util.tree_structure(expected)
    if a != b:
        raise ValueError(f"parameter tree differs:\n program {a}\n bench   {b}")
    for g, e in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(expected)):
        if g.shape != e.shape:
            raise ValueError(f"parameter shape {g.shape} != {e.shape}")


def compiled_bytes(lowered) -> int:
    """What the compiled program asks of the device's memory, as the
    compiler plans it: arguments, outputs and temporaries, less what the
    outputs reuse of donated arguments."""
    ma = lowered.compile().memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
