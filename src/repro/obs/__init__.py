"""repro.obs — unified runtime telemetry: spans, counters, overlays, diffs.

The paper's claim is that an offline-profiled simulation predicts real
system timelines; this package makes that claim *inspectable* instead of a
single parity percentage.  Three pieces:

* :mod:`repro.obs.record` — a structured span/counter recorder
  (:class:`Recorder`) with a monotonic clock, device/stage/request labels,
  nesting, and a zero-cost disabled mode.  The real executors — the train
  step loop (``launch/train.py``), the scheduled pipeline replay
  (:mod:`repro.obs.replay`) and the :class:`~repro.serve.engine.ServeEngine`
  host loop — emit spans under the *same node-uid vocabulary* the
  simulator's :class:`~repro.core.graph.DataflowGraph` /
  :class:`~repro.serve.policy.StepPlan` use, so a real run produces a
  timeline in the same schema as :class:`~repro.core.simulator.SimResult`.
  While a JAX profiler session runs, the same spans (and the executors'
  own :func:`~repro.obs.record.profiler_span` phases) also land in the
  profiler's trace, on the device events' clock.

* :mod:`repro.obs.overlay` — one Perfetto/Chrome JSON with aligned
  ``sim:`` and ``real:`` tracks per device, pricing provenance and byte
  twins as trace args, and counter tracks (in-flight microbatches, KV
  blocks, link concurrency).

* :mod:`repro.obs.diff` — the divergence attributor: joins real spans to
  simulated intervals by uid and emits a ranked
  :class:`~repro.analysis.Report` — per-op and per-provenance-class
  absolute/relative error, the top-k ops responsible for the step-time
  gap, and the O-code diagnostic family (O001 real span with no simulated
  twin, O002 simulated node never observed, O003 provenance-class error
  over tolerance).

Entry points: ``launch/train.py --pp 2 --obs --trace-out t.json`` and
``launch/serve.py --trace ... --obs --trace-out s.json``; see
docs/observability.md.
"""
from repro.obs.diff import divergence_report  # noqa: F401
from repro.obs.overlay import (  # noqa: F401
    derive_sim_counters,
    overlay_chrome_trace,
)
from repro.obs.record import (  # noqa: F401
    Counter,
    Recorder,
    Span,
    SpanError,
)
from repro.obs.replay import replay_pipeline_ops  # noqa: F401
