"""Shared arithmetic of the serving metrics: the needed work of the traced
steps over the device time of the programs that did it.

A program is told apart by its XLA module name in the trace.
"""
from __future__ import annotations

import re

PROGRAMS = {
    "prefill": re.compile(r"prefill|^jit_fn$"),
    "decode": re.compile(r"decode|lambda"),
}


def program_seconds(reduced, kind: str) -> float:
    pat = PROGRAMS[kind]
    return sum(s for name, s in reduced.program_s.items() if pat.search(name))


def roofline_pct(run: dict, kind: str):
    """Least time of the traced ``kind`` calls over their device time, in
    percent; ``None`` where the trace holds none of them."""
    red = run.get("trace")
    work = traced_work(run, kind)
    if red is None or not work:
        return None
    secs = program_seconds(red, kind)
    if secs <= 0:
        return None
    pk = run["peaks"]
    least = sum(max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"]) for f, b in work)
    return 100.0 * least / secs


def traced_work(run: dict, kind: str) -> list:
    """(FLOPs, bytes) of the ``kind`` calls made in the traced window."""
    work = run.get("serve", {}).get("work", {}).get(kind, [])
    return [(f, b) for f, b, traced in work if traced]


def bound(run: dict, kind: str) -> str:
    pk = run["peaks"]
    work = traced_work(run, kind)
    tc = sum(f for f, _ in work) / pk["flops_bf16"]
    tm = sum(b for _, b in work) / pk["hbm_bytes_per_s"]
    return "compute" if tc >= tm else "memory"
