"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compiling or loading every program the
cell's traffic uses, the steps or requests that are checked) is timed from
the start of this process until the window opens (``setup_s``).  The window
then runs for ``--seconds``.  With ``--trace 0`` the cell's end-to-end
metrics are reported; with ``--trace 1`` the last ``trace_seconds`` of the
window (the traffic file gives it) run under the JAX profiler and the
cell's per-layer metrics are reported from that trace and the benchmark's
own spans.  After the window the plain reference decides ``correct``.

The last line of standard output is one JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error and the last key of that object.  Beside ``memory_peak_bytes`` (the
runtime's ``peak_bytes_in_use``, which on the TPU leaves out part of a
program's temporaries) ``device`` gives ``memory_compiled_bytes``: the
largest of the timed programs' arguments, outputs and temporaries less
aliasing, from ``memory_analysis()``.  Where JAX finds no TPU, or fewer
chips than the cell asks for, the run exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import manifest  # noqa: E402
from bench.check.judge import judge, print_compared  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Env:
    """What a driver needs from the harness: the run's settings, the window
    and its trace, and the device's memory."""

    def __init__(self, *, seed: int, seconds: float, trace: bool, chips: int,
                 peaks: dict, trace_seconds: float, clock=None,
                 keep_trace: str = "", hooks: dict | None = None,
                 t_start: float = T_START):
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips, self.peaks = chips, peaks
        self.trace_seconds = min(trace_seconds, seconds) if trace else 0.0
        self.clock = clock
        self.keep_trace = keep_trace
        self.hooks = hooks or {}
        self.t_start = t_start
        self.setup_s = None
        self._compiles0 = 0
        self._tracing = False
        self._window_span = None
        self._trace_dir = None
        self.compiles_in_window = None
        self.log = log

    def begin_window(self) -> float:
        gc.collect()
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        if self.clock is not None:
            self._compiles0 = self.clock.compiles
            log(f"[setup] {self.setup_s:.3f} s; compile {self.clock.secs:.3f} s, "
                f"{self.clock.compiles} backend compiles, "
                f"{self.clock.hits} persistent-cache hits")
        return time.perf_counter()

    def tick(self, elapsed: float) -> bool:
        """Start the profiler once the traced part of the window begins;
        True while tracing."""
        if self.trace and not self._tracing and \
                elapsed >= self.seconds - self.trace_seconds:
            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._trace_dir)
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
            self._tracing = True
        return self._tracing

    def end_window(self):
        if self.clock is not None:
            self.compiles_in_window = self.clock.compiles - self._compiles0
            log(f"[window] backend compiles inside the window: "
                f"{self.compiles_in_window}")
        if not self._tracing:
            return None
        import jax

        from bench import xtrace

        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        try:
            path = xtrace.find_xplane(self._trace_dir)
            if self.keep_trace:
                os.makedirs(self.keep_trace, exist_ok=True)
                shutil.copy(path, self.keep_trace)
            from jax.profiler import ProfileData

            return xtrace.reduce_profile(ProfileData.from_file(path))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def memory_peak(self) -> int:
        import jax

        peaks = []
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


def execute(cell, env: Env) -> dict:
    """Run the cell's driver and assemble the result object."""
    import jax

    res = cell.driver().run(cell, env)
    env.hooks.get("run_record", lambda r: None)(res["run"])
    env.hooks.get("metrics", lambda r: None)(res["metrics"])
    correct, compared = judge(res["numbers"], cell.limits)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    run_rec = res["run"]
    if env.trace:
        for mdef in cell.per_layer:
            v = cell.metric_reader(mdef["name"]).read(run_rec)
            if v is not None:
                metrics[mdef["name"]] = {"value": float(v), "unit": units[mdef["name"]]}
    else:
        values = dict(res["metrics"], setup_s=env.setup_s)
        for mdef in cell.end_to_end:
            metrics[mdef["name"]] = {"value": float(values[mdef["name"]]),
                                     "unit": units[mdef["name"]]}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(res["memory_peak_bytes"]),
              "memory_compiled_bytes": int(res["memory_compiled_bytes"])}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics, "device": device}
    red = run_rec.get("trace")
    if env.trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.device_ops[:10],
                            "idle_gaps": red.idle_gaps[:10]}
        log(f"[trace] window {red.window_s} s busy {red.busy_s} s; "
            f"programs {red.program_s}; collectives {red.collective_s} s "
            f"(exposed {red.collective_exposed_s} s); spans {red.host_spans_s}")
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced .xplane.pb into this directory")
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload)
    import jax

    from bench.clock import CompileClock
    from bench.peaks import peaks_for
    from repro.launch.spec import use_compile_cache

    use_compile_cache()
    clock = CompileClock(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"[bench] no TPU: JAX reports platform {devices[0].platform!r}; "
            f"this benchmark measures only the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"[bench] cell {cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 2
    env = Env(seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              chips=len(devices), peaks=peaks_for(devices[0].device_kind),
              trace_seconds=float(cell.traffic.get("trace_seconds", 5)),
              clock=clock, keep_trace=args.keep_trace)
    out = execute(cell, env)
    print_compared(out["compared"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
