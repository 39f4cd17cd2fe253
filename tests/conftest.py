"""Shared test config.

NOTE: no XLA_FLAGS here by design — tests must see the real (single) CPU
device; only the dry-run subprocess uses the 512-device override.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _interpret_pallas_moe(monkeypatch):
    """The MoE layer's grouped product is compiled for the TPU; on the CPU
    the tests run it interpreted (a test may set it back, as the TPU
    compile rehearsals do)."""
    from repro.models import moe

    monkeypatch.setattr(moe, "INTERPRET", True)
