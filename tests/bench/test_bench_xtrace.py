"""The reduction from a profiler trace to busy time, program time,
collectives and the breakdown."""
import os

import _tiny_cells  # noqa: F401  (puts the repository on the path)
import pytest

from bench import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_subtract_clip():
    u = xtrace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert u == [(0, 3), (5, 9)]
    assert xtrace.total(u) == 7
    assert xtrace.subtract(u, [(1, 2), (6, 20)]) == [(0, 1), (2, 3), (5, 6)]
    assert xtrace.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_names():
    assert xtrace.base_name("fusion.12") == "fusion"
    assert xtrace.base_name("jit_train_step(3)") == "jit_train_step"
    assert xtrace.is_collective("all-reduce-start.3")
    assert xtrace.is_collective("collective-permute-done")
    assert not xtrace.is_collective("fusion.7")


class _E:
    def __init__(self, name, s, d):
        self.name, self.start_ns, self.duration_ns = name, s, d


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _D:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_synthetic_two_devices():
    host = _P("/host:CPU", [_L("python", [
        _E("bench.window", 0, 100), _E("bench.step", 0, 40),
        _E("bench.wait", 60, 30)])])
    dev = lambda n, ops: _P(f"/device:TPU:{n}", [  # noqa: E731
        _L("XLA Modules", [_E("jit_step(1)", 0, 50)]),
        _L("XLA Ops", ops)])
    d0 = dev(0, [_E("fusion.1", 0, 30), _E("all-reduce.2", 20, 20)])
    d1 = dev(1, [_E("fusion.1", 0, 10), _E("all-reduce.2", 10, 30)])
    r = xtrace.reduce_profile(_D([host, d0, d1]))
    assert r.window_s == 100e-9 and r.devices == 2
    # busy is where a program runs: 0..50 on both devices
    assert r.busy_s == pytest.approx(50e-9)
    assert r.collective_s == pytest.approx((20 + 30) / 2 * 1e-9)
    assert r.collective_exposed_s == pytest.approx((10 + 30) / 2 * 1e-9)
    assert r.program_s == {"jit_step": pytest.approx(100e-9)}
    assert r.device_ops[0][0] == "all-reduce"
    # the idle gap on device 0 is 50..100, its midpoint under bench.wait
    assert r.idle_gaps[0][0] == "bench.wait"
    assert r.idle_gaps[0][1] == pytest.approx(50e-9)


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three runs of one small program, each
    followed by a 10 ms sleep under the benchmark's spans.  The device's
    clock runs about a millisecond behind the host's in it, so the first
    run falls just before the window opens."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    r = xtrace.reduce_profile(data)
    assert r.devices == 1
    assert 0.03 < r.window_s < 1.0
    assert 0 < r.busy_s < r.window_s
    assert r.idle_share > 0.2
    assert r.program_calls == {"jit__lambda": 2}
    assert r.device_ops[0][0] == "fusion"
    assert r.idle_gaps and r.idle_gaps[0][0] == "bench.wait"
    assert r.collective_s == 0.0
