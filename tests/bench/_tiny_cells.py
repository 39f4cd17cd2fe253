"""Cells of the benchmark cut to a size the CPU test run can hold.

They keep the real cells' files (configuration, traffic, limits) and change
only the widths, the depth and the lengths, so that the harness's drivers
run end to end here.  Nothing measured here is a device number.
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import manifest  # noqa: E402
from bench.run import Env  # noqa: E402

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=256, attention_multiplier=0.25)
TINY_PROGRAM = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                    head_dim=16, d_ff=128, vocab_size=256)
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def _load(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    return _load(f"bench/traffic/{name}.json")


def _tiny(config: dict) -> dict:
    c = copy.deepcopy(config)
    c["model"].update(TINY_MODEL)
    c["program"]["overrides"].update(TINY_PROGRAM)
    return c


def train_cell(workload: str = "train-granite-l4-2k") -> manifest.Cell:
    real = manifest.load_cell(workload)
    mix = dict(real.traffic, seq=32, batch=4, trace_seconds=1)
    return manifest.Cell(
        name=workload, chips=1, config_name=real.config_name,
        config=_tiny(real.config), traffic_name=real.traffic_name,
        traffic=mix, end_to_end=real.end_to_end, per_layer=real.per_layer,
        limits=real.limits, root=ROOT,
    )


def serve_cell(workload: str = "serve-phi4mini-docs", max_len: int = 128,
               **mix_changes) -> manifest.Cell:
    """The cell at a tiny size; ``mix_changes`` replace keys of its mix (an
    open-loop mix: ``arrivals="poisson"``, ``rate_per_s=...``)."""
    real = manifest.load_cell(workload)
    config = _tiny(real.config)
    config["engine"] = {"slots": 4, "max_len": max_len, "block_size": 16, "chunk": 32}
    mix = dict(
        real.traffic, count=25,
        prompt={"median": 40, "sigma": 0.5, "min": 8, "max": 80},
        output={"median": 12, "sigma": 0.5, "min": 4, "max": 40},
        check_tokens=40, trace_seconds=1,
    )
    mix.update(mix_changes)
    return manifest.Cell(
        name=workload, chips=1, config_name=real.config_name, config=config,
        traffic_name=real.traffic_name, traffic=mix,
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        limits=real.limits, root=ROOT,
    )


def env(seed: int = 2**31 + 7, seconds: float = 2.0, hooks=None) -> Env:
    return Env(seed=seed, seconds=seconds, trace=False, chips=1, peaks=PEAKS,
               trace_seconds=1.0, hooks=hooks, t_start=time.perf_counter())
