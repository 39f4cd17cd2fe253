"""Hold each number compared against its limit, and print the pairs."""
from __future__ import annotations

import math
import sys


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}`` for every limit.

    A number that is missing, not finite or above its limit fails.
    """
    out = {}
    ok = True
    for name, lim in limits["limits"].items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= lim["limit"]
        ok = ok and good
        out[name] = {"value": v, "limit": lim["limit"]}
    return ok, out


def print_compared(compared: dict, stream=sys.stderr) -> None:
    for name, vl in compared.items():
        print(f"[check] {name} {vl['value']!r} limit {vl['limit']!r}",
              file=stream, flush=True)
