"""Reduce a profiler trace (``.xplane.pb``) to the figures the per-layer
metrics read.

* busy and idle: busy is the union of the intervals in which a program
  (XLA module) runs on a device, inside the traced window; idle is the
  rest of it.  On a TPU v5e the trace's per-operation line does not hold
  every operation inside a loop body, so the operations' union would read
  the device idle inside a running program;
* device seconds per program (XLA module), and of collectives, with the
  part of the collectives during which no other operation runs (exposed);
* the breakdown: the operations that took most device time, and the
  longest idle gaps, each named by the benchmark's own host span
  (``jax.profiler.TraceAnnotation``) that was open in the gap.

The traced window is the host span named ``WINDOW`` that the driver opens
around the traced part of its run.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|collectivepermute|alltoall"
    r"|send|recv",
    re.IGNORECASE,
)
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")


def base_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion``;
    ``jit_step(3)`` -> ``jit_step``."""
    return _SUFFIX.sub("", name.split(" = ")[0].lstrip("%"))


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of union ``a`` not covered by union ``b`` (both sorted)."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, w0: float, w1: float):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


@dataclass
class Reduced:
    window_s: float
    devices: int
    busy_s: float                           # mean over devices
    program_s: dict = field(default_factory=dict)    # module -> s, summed
    program_calls: dict = field(default_factory=dict)
    collective_s: float = 0.0               # mean over devices
    collective_exposed_s: float = 0.0       # mean over devices
    device_ops: list = field(default_factory=list)   # [(name, s)] top 10
    idle_gaps: list = field(default_factory=list)    # [(span, s)] top 10
    host_spans_s: dict = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _device_planes(data):
    """Planes of devices that ran XLA programs (not the host's, and not
    the runtime's own planes such as ``/device:CUSTOM:...``)."""
    return [p for p in data.planes if p.name.startswith("/device:")
            and any(ln.name in ("XLA Modules", "XLA Ops") for ln in p.lines)]


def reduce_profile(data) -> Reduced:
    """``data``: a ``jax.profiler.ProfileData``."""
    host_spans = []                          # (name, start, end)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name.startswith(SPAN_PREFIX):
                    host_spans.append((name, s, e))
    win = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not win:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    window_ns = w1 - w0

    planes = _device_planes(data)
    if not planes:
        raise ValueError("trace holds no device plane")
    busy = coll = exposed = 0.0
    op_time: dict = defaultdict(float)
    program_s: dict = defaultdict(float)
    program_calls: dict = defaultdict(int)
    busy_all = []
    for plane in planes:
        ops, colls, comps, mods = [], [], [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for name, s, e in _events(line):
                    c = clip([(s, e)], w0, w1)
                    if c:
                        mods.append(c[0])
                        program_s[base_name(name)] += total(c) / 1e9
                        program_calls[base_name(name)] += 1
            elif line.name == "XLA Ops":
                for name, s, e in _events(line):
                    c = clip([(s, e)], w0, w1)
                    if not c:
                        continue
                    ops.append(c[0])
                    op_time[base_name(name)] += total(c) / 1e9
                    (colls if is_collective(base_name(name)) else comps).append(c[0])
        u = union(mods or ops)
        busy_all.append(u)
        busy += total(u)
        cu = union(colls)
        coll += total(cu)
        exposed += total(subtract(cu, union(comps)))
    n = len(planes)

    # idle gaps of the first device, named by the innermost span open there
    gaps = subtract([(w0, w1)], busy_all[0])
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        open_ = [(sn, ss, se) for sn, ss, se in host_spans
                 if ss <= mid <= se and sn != WINDOW]
        label = min(open_, key=lambda x: x[2] - x[1])[0] if open_ else "host"
        named.append([label, (e - s) / 1e9])
    span_s: dict = defaultdict(float)
    for sn, ss, se in host_spans:
        c = clip([(ss, se)], w0, w1)
        if c and sn != WINDOW:
            span_s[sn] += total(c) / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(
        window_s=window_ns / 1e9, devices=n, busy_s=busy / n / 1e9,
        program_s=dict(program_s), program_calls=dict(program_calls),
        collective_s=coll / n / 1e9, collective_exposed_s=exposed / n / 1e9,
        device_ops=[[k, v] for k, v in top_ops], idle_gaps=named,
        host_spans_s=dict(span_s),
    )


def reduce_dir(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))


def describe(data, limit: int = 6) -> list[str]:
    """Planes, lines and a few events of a trace: for reading one by hand."""
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                out.append(
                    f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                    f"stats={dict(list(e.stats)[:6])}"
                )
    return out
