"""Readings that the limits of a ``train_moe`` cell are set from, on the chip.

    python bench/control_moe.py --workload <name> --seconds <s> --seeds <n> ... \\
        [--control-seeds <k>]

As ``bench/control.py`` does for the other kinds: for every seed, one run
of the cell as the benchmark makes it (a short window) gives the program's
numbers and its verdict under the cell's limits.  On the first
``--control-seeds`` seeds it also reads the control, the reference of
``bench/check/train_moe.py`` with float8 operands in every matrix product,
on the same weights and batches, compared with the float32 reference by
``bench/check/train_moe.compare`` and judged under the cell's limits; the
control has to come out not correct.  All runs share one process, so only
the first one compiles.  Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control_numbers(cell, seed: int, ref: dict) -> dict:
    """The control's numbers against the float32 reference ``ref`` of the
    same seed."""
    from bench import traffic
    from bench.check import train_moe as tcheck

    m, job = cell.config, cell.traffic
    fam, pdt = cell.family(), m["program"]["param_dtype"]
    batches = [traffic.train_batch(job, m["vocab_size"], seed, j) for j in range(3)]
    ctl = tcheck.reference(fam, m, m["optimizer"], lambda: fam.init(m, seed, pdt),
                           batches, gamma=m["bias_update_speed"], cast=fam.fp8_cast)
    return tcheck.compare(ctl, ref, m["bias_update_speed"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    import jax

    from bench import manifest
    from bench.check.judge import judge
    from bench.peaks import peaks_for
    from bench.run import Env, execute
    from repro.launch.spec import use_compile_cache

    use_compile_cache()
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print(f"no TPU: {d0.platform}", file=sys.stderr)
        return 2
    cell = manifest.load_cell(args.workload)
    if cell.config["kind"] != "train_moe":
        print(f"{args.workload} is not a train_moe cell", file=sys.stderr)
        return 2
    for i, seed in enumerate(args.seeds):
        box = {}
        env = Env(seed=seed, seconds=args.seconds, trace=False,
                  chips=jax.device_count(), peaks=peaks_for(d0.device_kind),
                  trace_seconds=0, hooks={"reference": lambda r, box=box: box.update(ref=r)},
                  t_start=time.perf_counter())
        out = execute(cell, env)
        row = {"seed": seed, "program": {k: v["value"] for k, v in out["compared"].items()},
               "correct": out["correct"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "memory_compiled_bytes": out["device"]["memory_compiled_bytes"],
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        if i < args.control_seeds:
            ok, compared = judge(control_numbers(cell, seed, box["ref"]), cell.limits)
            row["control"] = {"correct": ok,
                              "numbers": {k: v["value"] for k, v in compared.items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
