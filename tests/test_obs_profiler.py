"""The recorder's profiler sink: spans on the JAX profiler's clock.

With no profiler session the sink costs nothing the recorder's own tests
could see: the disabled path returns the cached singleton and reads no
clock.  Under a session (``jax.profiler.start_trace``, which works on the
CPU) every recorder span, the serving engine's phases, the prefetcher's
waits, the train step and the collector's pauses land in the trace under
stable layer names, with their uids, labels and counters as event stats —
and nothing the program measures or serves changes.
"""
import dataclasses
import gc
import glob
import os
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_variant
from repro.obs import Recorder
from repro.obs import record
from repro.obs.record import _NULL_SPAN, profiler_span

PREFIXES = ("serve", "data.", "train.", "python.", "test.", "fwd")


class FakeClock:
    """Deterministic clock: returns 0.0, 1.0, 2.0, ... and counts reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        t = float(self.reads)
        self.reads += 1
        return t


@contextmanager
def session(tmp_path):
    """A profiler session; yields a list filled on exit with the host
    events ``(name, start_ns, end_ns, stats)`` under ``PREFIXES``."""
    from jax.profiler import ProfileData

    events: list = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, dict(e.stats)))
    events.sort(key=lambda ev: (ev[1], -ev[2]))


def named(events, name):
    return [ev for ev in events if ev[0] == name]


# -- the sink ------------------------------------------------------------------


def test_profiler_span_off_is_the_null_span_and_hooks_nothing(monkeypatch):
    monkeypatch.setattr(record, "_gc_hooked", False)
    assert profiler_span("test.a", k=1) is _NULL_SPAN
    with profiler_span("test.a") as span:
        span.set_metadata(k=2)
    assert record._gc_hooked is False


def test_profiler_span_on_writes_name_and_stats(tmp_path):
    with session(tmp_path) as events:
        with profiler_span("test.outer", index=3, of="decode", final=True):
            span = profiler_span("test.inner")
            span.set_metadata(collected=7)
            span.__exit__(None, None, None)
    (outer,), (inner,) = named(events, "test.outer"), named(events, "test.inner")
    assert outer[3] == {"index": 3, "of": "decode", "final": 1}
    assert inner[3] == {"collected": 7}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]


def test_disabled_recorder_under_a_session_opens_spans_reads_no_clock(tmp_path):
    clk = FakeClock()
    rec = Recorder(enabled=False, clock=clk)
    with session(tmp_path) as events:
        with rec.span("step1", kind="serve-step", role="step"):
            rec.begin("F0.1", "stage0", kind="fwd", layer="test.fwd")
            rec.end("F0.1")
        rec.interval("train_step0", kind="train-step", layer="train.step",
                     step=0).stop()
    assert rec.spans == [] and clk.reads == 2  # the interval's two reads only
    (step,) = named(events, "serve-step")
    assert step[3] == {"uid": "step1", "role": "step"}
    (fwd,) = named(events, "test.fwd")
    assert fwd[3] == {"uid": "F0.1"}
    assert step[1] <= fwd[1] and fwd[2] <= step[2]
    (train,) = named(events, "train.step")
    assert train[3] == {"uid": "train_step0", "step": 0}


def _record(rec):
    with rec.span("step0", kind="serve-step", layer="test.step", role="step"):
        rec.begin("F0.0", "stage0", kind="fwd", vstage=1)
        rec.emit("sendF0.0", "link:pp", 10.0, 10.5, kind="send")
        rec.end("F0.0")
    iv = rec.interval("train_step0", kind="train-step", layer="train.step")
    return iv.stop()


def test_enabled_recorder_keeps_spans_exactly_under_a_session(tmp_path):
    off = Recorder(clock=FakeClock())
    dur_off = _record(off)
    on = Recorder(clock=FakeClock())
    with session(tmp_path) as events:
        dur_on = _record(on)
    assert dur_on == dur_off
    assert on.to_dict() == off.to_dict()
    assert on.clock.reads == off.clock.reads
    # begin/end and interval reach the profiler; emit's post-measured span
    # cannot be opened afterwards
    assert [ev[0] for ev in events if ev[0] != "python.gc"] == [
        "test.step", "fwd", "train.step"]
    assert named(events, "fwd")[0][3] == {"uid": "F0.0", "vstage": 1}


def test_collector_pauses_are_python_gc_spans(tmp_path):
    with session(tmp_path) as events:
        profiler_span("test.first").__exit__(None, None, None)
        gc.collect()
    assert gc.callbacks.count(record._gc_span) == 1
    full = [ev for ev in named(events, "python.gc")
            if ev[3].get("generation") == 2]
    assert full and isinstance(full[-1][3]["collected"], int)


# -- the executors ---------------------------------------------------------------


def test_prefetcher_wait_is_data_wait(tmp_path):
    from repro.data import Prefetcher

    pf = Prefetcher(iter(range(5)), depth=2)
    with session(tmp_path) as events:
        got = [next(pf) for _ in range(3)]
    pf.close()
    assert got == [0, 1, 2]
    waits = named(events, "data.wait")
    assert len(waits) == 3
    assert all(0 <= ev[3]["depth"] <= 2 for ev in waits)


def test_run_timed_step_is_train_step(tmp_path):
    import jax.numpy as jnp

    from repro.train.step import run_timed_step

    jitted = jax.jit(lambda s, b: (s + b, {"loss": (s * b).sum()}))
    rec = Recorder(enabled=False)
    state = jnp.ones(4)
    state, _, _, _ = run_timed_step(jitted, state, jnp.ones(4), rec, "train_step0")
    with session(tmp_path) as events:
        run_timed_step(jitted, state, jnp.ones(4), rec, "train_step1",
                       role="step", step=1)
    (ev,) = named(events, "train.step")
    assert ev[3] == {"uid": "train_step1", "role": "step", "step": 1}


class _Ticker:
    """Deterministic engine clock: 1 ms per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import build_model

    cfg = dataclasses.replace(smoke_variant(get_config("llama3.2-1b")),
                              num_layers=2)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _serve(tiny_model, recorder=None):
    from repro.serve import Request, ServeEngine

    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, slots=2, max_len=32, block_size=8,
                      chunk=8, clock=_Ticker(), recorder=recorder)
    rng = np.random.default_rng(0)
    for rid, (plen, n, at) in enumerate([(11, 4, 0.0), (6, 3, 2.5e-3),
                                         (17, 2, 0.0)]):
        eng.submit(Request(
            rid=rid, prompt=rng.integers(1, cfg.vocab_size, plen, dtype=np.int32),
            max_new_tokens=n, arrival_s=at))
    eng.run_until_done()
    return eng


def test_engine_spans_under_a_session_change_nothing(tiny_model, tmp_path):
    base = _serve(tiny_model, Recorder(clock=_Ticker()))
    with session(tmp_path) as events:
        eng = _serve(tiny_model, Recorder(clock=_Ticker()))
    assert eng.step_log == base.step_log
    assert eng.step_durations == base.step_durations
    assert ({r.rid: r.output for r in eng.finished}
            == {r.rid: r.output for r in base.finished})
    assert eng._rec.to_dict() == base._rec.to_dict()

    steps = named(events, "serve.step")
    assert len(steps) == len(eng.step_log)
    assert [ev[3]["index"] for ev in steps] == [sig[0] for sig in eng.step_log]
    assert all(set(ev[3]) == {"index", "queued", "live", "free_blocks"}
               for ev in steps)
    children = [ev for ev in events if ev[0] != "serve.step"
                and ev[0].startswith("serve.")]
    for ev in children:  # each phase nests inside one step
        assert sum(s[1] <= ev[1] and ev[2] <= s[2] for s in steps) == 1, ev
    assert len(named(events, "serve.plan")) == len(steps)
    assert len(named(events, "serve.commit")) == len(steps)
    assert [ev[3]["uid"] for ev in named(events, "serve.execute")] == [
        f"step{sig[0]}" for sig in eng.step_log]

    prefills = named(events, "serve.prefill")
    want = [sig[2] for sig in eng.step_log if sig[2] is not None]
    assert [(ev[3]["rid"], ev[3]["start"], ev[3]["width"], bool(ev[3]["final"]))
            for ev in prefills] == [(rid, start, width, final)
                                    for _slot, rid, start, width, final in want]
    assert all(ev[3]["bucket"] >= ev[3]["width"] for ev in prefills)
    decodes = named(events, "serve.decode")
    assert [ev[3]["slots"] for ev in decodes] == [
        len(sig[3]) for sig in eng.step_log if sig[3]]
    assert all(ev[3]["kv_tokens"] >= ev[3]["slots"] for ev in decodes)
    reads = [ev[3]["of"] for ev in named(events, "serve.readback")]
    assert reads.count("decode") == len(decodes)
    assert reads.count("prefill") == sum(1 for p in want if p[4])


def test_engine_with_no_recorder_serves_the_same_under_a_session(
        tiny_model, tmp_path):
    base = _serve(tiny_model)
    with session(tmp_path) as events:
        eng = _serve(tiny_model)
    assert eng.step_log == base.step_log
    assert eng.step_durations == base.step_durations
    assert ({r.rid: r.output for r in eng.finished}
            == {r.rid: r.output for r in base.finished})
    assert len(named(events, "serve.step")) == len(eng.step_log)
