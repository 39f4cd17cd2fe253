"""The chip's published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: str = _PATH) -> dict:
    """Peaks of one chip of this kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}"
        )
    return dict(table[device_kind])
