"""The reduction of the program's own spans (``bench/spans.py``): span
counts, device idle inside each span, the idle split by innermost span, the
clock-lag check and its correction, and the two figures read from them."""
import os

import _tiny_cells  # noqa: F401  (puts the repository on the path)
import pytest

from bench import spans, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


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


def _trace(host, *devices):
    planes = [_P("/host:CPU", [_L("python", [_E(*h) for h in host])])]
    for i, calls in enumerate(devices):
        planes.append(_P(f"/device:TPU:{i}", [
            _L("XLA Modules", [_E(*c) for c in calls]), _L("XLA Ops", [])]))
    return _D(planes)


# one engine step inside the benchmark's span: the decode is dispatched at
# 1 and runs 20..40 on the device; the readback waits 40..90
STEP = [("bench.window", 0, 100), ("bench.engine_step", 0, 100),
        ("serve.step", 1, 97), ("serve.decode", 1, 10),
        ("serve.readback", 40, 50), ("python.gc", 95, 30)]


def test_a_program_span_inside_a_bench_span_names_the_gap():
    data = _trace(STEP, [("jit_serve_decode(7)", 20, 20)])
    sp = spans.reduce_spans(data)
    assert sp.idle_gaps[0] == ["serve.readback", pytest.approx(60e-9)]
    # the benchmark's own reduction still names it by its own span
    assert xtrace.reduce_profile(data).idle_gaps[0][0] == "bench.engine_step"
    assert sp.window_s == pytest.approx(100e-9)
    assert sp.span_calls == {"bench.engine_step": 1, "serve.step": 1,
                             "serve.decode": 1, "serve.readback": 1,
                             "python.gc": 1}
    assert sp.span_idle_s["serve.step"] == pytest.approx(77e-9)
    assert sp.span_idle_s["bench.engine_step"] == pytest.approx(80e-9)
    assert sp.span_idle_s["serve.decode"] == pytest.approx(10e-9)
    assert sp.span_idle_s["serve.readback"] == pytest.approx(50e-9)
    assert sp.span_idle_s["python.gc"] == pytest.approx(5e-9)   # clipped
    assert sp.idle_by_span == {
        "serve.readback": pytest.approx(50e-9),
        "serve.step": pytest.approx(14e-9),
        "serve.decode": pytest.approx(10e-9),
        "python.gc": pytest.approx(5e-9),
        "bench.engine_step": pytest.approx(1e-9)}
    assert sp.clock_lag_s == pytest.approx(19e-9)
    assert sp.engine_host_ms() == pytest.approx(77e-9 * 1e3)
    assert sp.prefetch_wait_ms() is None


def test_negative_lag_moves_the_device_later():
    """A call that starts before its dispatch reads the device's clock
    behind the host's: its events move later by the lag."""
    host = [("bench.window", 0, 100), ("serve.step", 0, 100),
            ("serve.decode", 10, 5), ("serve.prefill", 50, 5)]
    data = _trace(host, [("jit_serve_decode(1)", 7, 20),
                         ("jit_serve_prefill(2)", 52, 20)],
                  [("jit_serve_decode(1)", 9, 20),
                   ("jit_serve_prefill(2)", 52, 20)])
    sp = spans.reduce_spans(data)
    assert sp.clock_lag_s == pytest.approx(-3e-9)
    # busy after the move: 10..30 and 55..75 on device 0, 12..32 and 55..75
    # on device 1; idle inside serve.step is the mean of 60 and 60
    assert sp.span_idle_s["serve.step"] == pytest.approx(60e-9)
    assert sp.span_idle_s["serve.decode"] == pytest.approx((0 + 2) / 2 * 1e-9)


def test_calls_pair_with_dispatches_in_order():
    """Two chunks dispatched back to back run one after the other: each
    pairs with its own dispatch, not with the later one."""
    starts = [("serve.prefill", 0, 1), ("serve.prefill", 2, 1)]
    calls = [("jit_serve_prefill", 4, 30), ("jit_serve_prefill", 34, 30)]
    assert spans.clock_lag_ns(starts, calls) == 4
    assert spans.clock_lag_ns(starts, []) is None


def test_prefetch_wait_is_the_mean_data_wait():
    host = [("bench.window", 0, 100), ("bench.data", 0, 10),
            ("data.wait", 1, 2), ("bench.data", 50, 10), ("data.wait", 51, 4)]
    sp = spans.reduce_spans(_trace(host, [("jit_train_step(1)", 12, 30)]))
    assert sp.prefetch_wait_ms() == pytest.approx(3e-9 * 1e3)
    assert sp.engine_host_ms() is None
    assert sp.span_calls["data.wait"] == 2


def test_recorded_tpu_trace_agrees_with_xtrace():
    """On the recorded v5e trace (benchmark spans only, no program call
    paired with a dispatch) the split adds up to xtrace's idle time and the
    gaps carry xtrace's names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    red, sp = xtrace.reduce_profile(data), spans.reduce_spans(data)
    assert sp.clock_lag_s is None
    assert sp.window_s == red.window_s
    assert sum(sp.idle_by_span.values()) == pytest.approx(red.window_s - red.busy_s)
    assert sp.idle_gaps == red.idle_gaps
    assert sp.span_calls == {"bench.step": 3, "bench.wait": 3}


def test_recorded_docs_window_on_tpu():
    """The host spans and program calls of a whole traced window of the
    docs cell on a TPU v5e (the profiler trace reduced to what the
    reduction reads): the engine's phases name every long gap that the
    benchmark's own reduction calls ``bench.engine_step``."""
    import gzip
    import json

    with gzip.open(os.path.join(DATA, "docs_tpu_spans.json.gz"), "rt") as f:
        doc = json.load(f)
    data = _trace([(n, s, e - s) for n, s, e in doc["spans"]],
                  *[[(n, s, e - s) for n, s, e in dev] for dev in doc["calls"]])
    sp = spans.reduce_spans(data)
    assert sp.span_calls["serve.step"] == 93
    for name in ("serve.plan", "serve.execute", "serve.commit"):
        assert sp.span_calls[name] == 93
    assert 1e-3 < sp.clock_lag_s < 2e-3
    assert 3.0 < sp.engine_host_ms() < 4.5
    assert list(sp.idle_by_span)[:2] == ["serve.prefill", "serve.readback"]
    assert not any(label == "bench.engine_step" for label, _ in sp.idle_gaps)
    red = xtrace.reduce_profile(data)
    assert sp.window_s == red.window_s
    assert sum(sp.idle_by_span.values()) == pytest.approx(red.window_s - red.busy_s)
    assert [g[0] for g in red.idle_gaps].count("bench.engine_step") >= 5
