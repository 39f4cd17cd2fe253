"""The program's own spans in a profiler trace, beside the benchmark's.

``repro.obs`` writes the program's spans into the JAX profiler's trace
while a session runs: ``serve.*`` from the serving engine, ``data.wait``
from the input pipeline, ``train.step`` from the launcher's step loop and
``python.gc`` for the collector's pauses (docs/observability.md).
``bench/xtrace.py`` names idle gaps by the benchmark's own ``bench.`` spans
alone; this reduction reads both, over the same window and device
intervals:

* ``span_calls``: spans of each name that overlap the window;
* ``span_mean_s``: their mean duration;
* ``span_idle_s``: device-idle seconds inside the union of each name's
  spans, clipped to the window, mean over the devices;
* ``idle_by_span``: the idle time split by the innermost span open over
  each part of it (``host`` where none is), mean over the devices;
* ``idle_gaps``: the ten longest idle gaps of the first device, each named
  by the innermost span open at its midpoint;
* ``clock_lag_s``: the smallest lag from the start of a span that
  dispatches a program (``serve.prefill``, ``serve.decode``, ``bench.step``)
  to the start of that program on the device.  A call cannot start before
  it is dispatched, so a negative lag is skew between the host's and the
  device's clocks: the device's events are then moved later by it, the
  least shift that puts every call after its dispatch, before any figure
  here is computed.

Read a trace kept by ``bench/run.py --trace 1 --keep-trace <dir>``:

    python bench/spans.py <dir or .xplane.pb> [...]

prints one JSON object per trace, with ``engine_host_ms`` (device idle
inside ``serve.step`` per engine step) and ``prefetch_wait_ms`` (mean
``data.wait``) where the trace holds those spans.
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import serve_work, xtrace  # noqa: E402

PREFIXES = ("bench.", "serve.", "data.", "train.", "python.")
# each span that dispatches a program, and the programs it dispatches
DISPATCH = {
    "serve.prefill": serve_work.PROGRAMS["prefill"],
    "serve.decode": serve_work.PROGRAMS["decode"],
    "bench.step": re.compile(r"train_step"),
}
# a program call is paired with the first call that starts no earlier than
# this before its dispatch span: far more than any skew, far less than a step
PAIR_SLACK_NS = 5e6


@dataclass
class Spans:
    window_s: float
    span_calls: dict = field(default_factory=dict)
    span_mean_s: dict = field(default_factory=dict)
    span_idle_s: dict = field(default_factory=dict)
    idle_by_span: dict = field(default_factory=dict)
    idle_gaps: list = field(default_factory=list)
    clock_lag_s: float | None = None

    def engine_host_ms(self):
        """Device idle inside ``serve.step`` per engine step, in ms."""
        n = self.span_calls.get("serve.step")
        return 1e3 * self.span_idle_s["serve.step"] / n if n else None

    def prefetch_wait_ms(self):
        """Mean ``data.wait`` span, in ms."""
        s = self.span_mean_s.get("data.wait")
        return None if s is None else 1e3 * s


def host_spans(data) -> list[tuple[str, float, float]]:
    """(name, start, end) in ns of the host spans under ``PREFIXES``."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [ev for ev in xtrace._events(line)
                        if ev[0].startswith(PREFIXES)]
    return out


def device_calls(data) -> list[list[tuple[str, float, float]]]:
    """Per device: (program, start, end) in ns of every program call."""
    return [[(xtrace.base_name(n), s, e)
             for line in plane.lines if line.name == "XLA Modules"
             for n, s, e in xtrace._events(line)]
            for plane in xtrace._device_planes(data)]


def clock_lag_ns(spans, calls) -> float | None:
    """The smallest start of a program call after the start of the span
    that dispatched it.  Spans and calls of one program are paired in
    order, each span with the first unpaired call that starts no more than
    ``PAIR_SLACK_NS`` before it."""
    lags = []
    for name, pattern in DISPATCH.items():
        starts = sorted(s for n, s, _ in spans if n == name)
        runs = sorted(s for p, s, _ in calls if pattern.search(p))
        i = 0
        for s in starts:
            while i < len(runs) and runs[i] < s - PAIR_SLACK_NS:
                i += 1
            if i == len(runs):
                break
            lags.append(runs[i] - s)
            i += 1
    return min(lags) if lags else None


def _innermost(spans, t0: float, t1: float) -> str:
    """Name of the shortest span covering all of ``[t0, t1]``."""
    best = None
    for n, s, e in spans:
        if s <= t0 and e >= t1 and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "host"


def reduce_spans(data) -> Spans:
    """``data``: a ``jax.profiler.ProfileData`` with a ``bench.window``."""
    spans = host_spans(data)
    win = [(s, e) for n, s, e in spans if n == xtrace.WINDOW]
    if not win:
        raise ValueError(f"trace holds no {xtrace.WINDOW!r} span")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    spans = [sp for sp in spans if sp[0] != xtrace.WINDOW]
    calls = device_calls(data)
    if not calls:
        raise ValueError("trace holds no device plane")

    lag = clock_lag_ns(spans, [c for dev in calls for c in dev])
    shift = -lag if lag is not None and lag < 0 else 0.0
    idle = [xtrace.subtract([(w0, w1)], xtrace.union(
        xtrace.clip([(s + shift, e + shift) for _, s, e in dev], w0, w1)))
        for dev in calls]
    n_dev = len(idle)

    by_name = defaultdict(list)
    for n, s, e in spans:
        if e > w0 and s < w1:
            by_name[n].append((s, e))
    out = Spans(window_s=(w1 - w0) / 1e9,
                clock_lag_s=None if lag is None else lag / 1e9)
    for n, ivs in sorted(by_name.items()):
        out.span_calls[n] = len(ivs)
        out.span_mean_s[n] = sum(e - s for s, e in ivs) / len(ivs) / 1e9
        u = xtrace.union(xtrace.clip(ivs, w0, w1))
        out.span_idle_s[n] = sum(
            xtrace.total(gaps) - xtrace.total(xtrace.subtract(gaps, u))
            for gaps in idle) / n_dev / 1e9

    split: dict = defaultdict(float)
    for gaps in idle:
        for s, e in gaps:
            near = [sp for sp in spans if sp[1] < e and sp[2] > s]
            cuts = sorted({s, e} | {t for _, a, b in near for t in (a, b)
                                    if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                split[_innermost(near, a, b)] += (b - a) / n_dev / 1e9
    out.idle_by_span = dict(sorted(split.items(), key=lambda kv: -kv[1]))
    for s, e in sorted(idle[0], key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        out.idle_gaps.append([_innermost(spans, mid, mid), (e - s) / 1e9])
    return out


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    for path in (argv if argv is not None else sys.argv[1:]):
        if os.path.isdir(path):
            found = [os.path.join(path, f) for f in sorted(os.listdir(path))
                     if f.endswith(".xplane.pb")]
            path = found[-1] if found else xtrace.find_xplane(path)
        sp = reduce_spans(ProfileData.from_file(path))
        print(json.dumps(dict(
            trace=path, engine_host_ms=sp.engine_host_ms(),
            prefetch_wait_ms=sp.prefetch_wait_ms(), **sp.__dict__)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
