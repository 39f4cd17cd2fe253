"""Readings that the limits of a cell are set from, on the chip.

    python bench/control.py --workload <name> --seconds <s> --seeds <n> ... \\
        [--control-seeds <k>]

For every seed, one run of the cell as the benchmark makes it (a short
window at the cell's own load) gives the program's numbers.  On the first
``--control-seeds`` seeds it also reads the control, the reference put in
the program's place in the next lower precision (float8 operands), on the
same inputs; and, for a training cell, the reference with half of each
batch left out, a planted fault.  Each of those readings is judged under
the cell's own limits, as a run judges the program, and its verdict
(``correct``, which the control has to fail) and compared numbers are
printed beside it.  All runs share one process, so only the first one
compiles.  Each reading is one JSON line on standard output.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    import jax

    from bench import manifest, traffic
    from bench.check import train as tcheck
    from bench.check.judge import judge, print_compared
    from bench.peaks import peaks_for
    from bench.run import Env, execute
    from repro.launch.spec import use_compile_cache

    use_compile_cache()
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print(f"no TPU: {d0.platform}", file=sys.stderr)
        return 2
    cell = manifest.load_cell(args.workload)
    kind = cell.config["kind"]

    def judged(numbers: dict) -> dict:
        ok, compared = judge(numbers, cell.limits)
        print_compared(compared)
        return {"correct": ok, "compared": compared}

    for i, seed in enumerate(args.seeds):
        control = i < args.control_seeds
        box = {}
        hooks = {"reference": lambda r, box=box: box.update(ref=r)}
        if kind == "serve":
            hooks["control"] = control
            hooks["detail"] = lambda d, box=box: box.update(detail=d)
        env = Env(seed=seed, seconds=args.seconds, trace=False,
                  chips=jax.device_count(), peaks=peaks_for(d0.device_kind),
                  trace_seconds=0, hooks=hooks, t_start=time.perf_counter())
        out = execute(cell, env)
        row = {"seed": seed, "program": {k: v["value"] for k, v in out["compared"].items()},
               "correct": out["correct"]}
        row["metrics"] = {k: v["value"] for k, v in out["metrics"].items()}
        if kind == "serve" and control:
            row["control"] = judged({"logit_gap": box["detail"].get("control_logit_gap")})
            row["checked"] = {k: box["detail"][k] for k in ("requests", "tokens")}
        if kind == "train" and control:
            conf, job = cell.config, cell.traffic
            m, opt, fam = conf["model"], conf["optimizer"], cell.family()
            pdt = conf["program"]["param_dtype"]
            batches = [traffic.train_batch(job, m["vocab_size"], seed, j) for j in range(3)]
            p0 = lambda: fam.init(m, seed, pdt)  # noqa: E731
            ref = box["ref"]
            ctl = tcheck.reference(fam, m, opt, p0, batches, cast=fam.fp8_cast)
            row["control"] = judged(tcheck.compare(ctl, ref))
            half = tcheck.reference(fam, m, opt, p0, batches, rows=job["batch"] // 2)
            row["half_batch"] = judged(tcheck.compare(half, ref))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
