"""Continuous-batching serving engine over a paged KV pool.

The engine is a host loop around two jitted kernels
(``repro.serve.paged``): one prefill *chunk* (batch 1, pow2-bucketed
width) and one full-batch decode step (static batch = slots).  All
scheduling decisions — admission, block reservation, chunk selection, the
decode batch — come from :class:`repro.serve.policy.ServeScheduler`, the
exact object the DES twin (``repro.serve.sim``) drives, so a simulated
timeline replays the engine's step compositions verbatim (the house
parity convention, serve edition).

Per-request latency is recorded against the *scheduler clock*: each step's
real (measured) duration is accumulated into ``sched.clock``, and the
clock fast-forwards over idle gaps while waiting for open-loop arrivals (a
trace replay never sleeps).  Driving admission off accumulated measured
time — not raw wall time — means inter-step host overhead never drifts
the scheduling clock away from the recorded ``step_durations``, so
``repro.serve.sim.replay_schedule(trace, cfg, engine.step_durations)``
reproduces the engine's step compositions AND its latency report exactly,
for any trace (the hard half of the serve parity gate).  TTFT / per-token
gaps / e2e land on the :class:`Request` and feed
``repro.serve.report.latency_report``.

The seed engine's lockstep slot loop (single shared ``cache_len``,
left-padded prefill, the ``slot_len`` clamp at ``max_len - 1`` that
silently re-wrote the last cache position at the boundary) is gone;
capacity is now exact — a request may run to *exactly* ``max_len`` cached
positions (regression-tested in tests/test_serve_engine.py).
``splice_cache`` survives below: it still splices whole-sequence caches
for the non-paged ``Model.prefill``/``decode`` path and is property-tested
on arbitrary pytrees.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.build import Model
from repro.obs.record import profiler_span
from repro.serve import paged
from repro.serve.policy import ServeConfig, ServeScheduler, StepPlan


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new_tokens: int = 16
    arrival_s: float = 0.0       # open-loop arrival offset (trace replay)
    output: list[int] = field(default_factory=list)
    done: bool = False
    # latency record (virtual-clock seconds, filled by the engine)
    ttft_s: Optional[float] = None
    e2e_s: Optional[float] = None
    token_times_s: list[float] = field(default_factory=list)


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params,
        slots: int = 4,
        max_len: int = 256,
        eos_id: Optional[int] = None,
        block_size: int = 16,
        chunk: int = 32,
        num_blocks: int = 0,
        mesh=None,
        clock: Callable[[], float] = time.perf_counter,
        recorder=None,
    ):
        paged.check_family(model.cfg)
        self.model = model
        self.cfg = model.cfg
        self.serve_cfg = ServeConfig(
            slots=slots, max_len=max_len, block_size=block_size,
            num_blocks=num_blocks, chunk=chunk,
        )
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.mesh = mesh
        self.sched = ServeScheduler(self.serve_cfg)
        self.requests: dict[int, Request] = {}
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.finished: list[Request] = []
        # per-step records for the parity report / latency attribution
        self.step_log: list[tuple] = []
        self.step_durations: list[float] = []

        mb = self.serve_cfg.max_blocks_per_slot
        self._tables = np.full(
            (slots, mb), self.sched.scratch_block, np.int32
        )
        self.params = self._replicated(params)
        self.pool = self._replicated(paged.init_pool(self.cfg, self.serve_cfg))

        scfg = self.serve_cfg

        def serve_decode(p, pool, t, ln, tb):
            return paged.decode_batch(p, pool, t, ln, tb, self.cfg, scfg)

        # the programs' names (``jit_serve_decode``, ``jit_serve_prefill``)
        # are what a profiler trace tells them apart by.  Decode donates the
        # pool (every call site rebinds ``self.pool`` to the result), so
        # its layer loop updates the pool in place
        self._decode = jax.jit(serve_decode, donate_argnums=(1,))
        self._prefill_cache: dict[int, Callable] = {}
        # duration source only — scheduling time is sched.clock (see module
        # docstring); injectable for deterministic tests.  Telemetry goes
        # through a repro.obs Recorder; without one, a disabled recorder
        # over the same clock measures step durations through the exact
        # same two clock reads the ad-hoc arithmetic used to make (the
        # PR-7 replay parity tests pin this bit-identical).
        if recorder is None:
            from repro.obs.record import Recorder

            recorder = Recorder(enabled=False, clock=clock)
        self._rec = recorder
        self._clock = recorder.clock

    # -- sharding --------------------------------------------------------------

    def _replicated(self, tree):
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _slot_sharded(self, arr):
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P

        ax = self.mesh.axis_names[0]
        return jax.device_put(arr, NamedSharding(self.mesh, P(ax)))

    # -- warmup ----------------------------------------------------------------

    def warmup(self) -> None:
        """Compile every kernel this engine can dispatch (decode + all pow2
        prefill buckets) on throwaway inputs, so first-call jit time never
        lands inside a measured step duration.  The dummy tables point at
        the scratch block, whose contents are never read (positions past a
        slot's length are masked), so the returned pool replaces the old one
        with no visible change, and no second pool stays alive."""
        scfg = self.serve_cfg
        scratch = self.sched.scratch_block
        row = jnp.full((scfg.max_blocks_per_slot,), scratch, jnp.int32)
        bucket = 1
        while bucket <= scfg.chunk:
            toks = jnp.zeros((1, bucket), jnp.int32)
            logits, self.pool = self._prefill_fn(bucket)(
                self.params, self.pool, toks, jnp.int32(0),
                jnp.int32(bucket), row,
            )
            # the greedy readback compiles its own tiny executable — run it
            # too, or its first-use cost lands in a measured step
            int(jnp.argmax(logits[0, -1]))
            bucket *= 2
        tables = jnp.full_like(jnp.asarray(self._tables), scratch)
        logits, self.pool = self._decode(
            self.params, self.pool,
            self._slot_sharded(jnp.zeros((self.slots, 1), jnp.int32)),
            self._slot_sharded(jnp.zeros((self.slots,), jnp.int32)),
            self._slot_sharded(tables),
        )
        np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)

    # -- admission -------------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.sched.submit(
            req.rid, len(req.prompt), req.max_new_tokens, req.arrival_s
        )
        self.requests[req.rid] = req

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            scfg = self.serve_cfg
            scratch = self.sched.scratch_block

            def serve_prefill(p, pool, toks, start, width, row):
                return paged.prefill_chunk(
                    p, pool, toks, start, width, row, scratch, self.cfg, scfg
                )

            self._prefill_cache[bucket] = jax.jit(serve_prefill)
        return self._prefill_cache[bucket]

    # -- one engine step -------------------------------------------------------

    def step(self) -> bool:
        """Execute one scheduler step; False if nothing can progress.

        Under a profiler session the step is the span ``serve.step``, and
        each phase of it a span of its own (docs/observability.md)."""
        sched = self.sched
        if not sched.outstanding():
            return False
        with profiler_span(
            "serve.step", index=sched.step_index, queued=len(sched.queue),
            live=sum(r is not None for r in self.slot_req),
            free_blocks=sched.allocator.num_free,
        ):
            with profiler_span("serve.plan"):
                plan = self._plan()
            if plan is None:
                return False
            self._execute(plan)
            return True

    def _plan(self) -> Optional[StepPlan]:
        plan = self.sched.plan_step()
        if plan.empty:
            nxt = self.sched.next_arrival()
            if nxt is None:
                return None
            # open-loop replay: jump the clock to the next arrival instead
            # of sleeping through the gap
            self.sched.skip_to(nxt)
            plan = self.sched.plan_step()
            if plan.empty:
                return None
        return plan

    def _execute(self, plan: StepPlan) -> None:
        rec = self._rec
        iv = rec.interval(
            f"step{plan.index}", "host", kind="serve-step",
            layer="serve.execute", role="step",
        )
        scratch = self.sched.scratch_block
        for rid, slot in plan.admitted:
            req = self.requests[rid]
            self.slot_req[slot] = req
            state = self.sched.slot_state(slot)
            if state is None:
                raise RuntimeError(
                    f"step {plan.index}: request {rid} admitted to slot "
                    f"{slot} but the scheduler holds no slot state "
                    f"(statically detectable as R006)"
                )
            blocks = state.blocks
            self._tables[slot] = scratch
            self._tables[slot, : len(blocks)] = blocks

        new_tokens: dict[int, int] = {}
        eos_slots: set[int] = set()
        if plan.prefill is not None:
            pf = plan.prefill
            req = self.slot_req[pf.slot]
            if req is None or req.rid != pf.rid:
                raise RuntimeError(
                    f"step {plan.index}: prefill chunk targets request "
                    f"{pf.rid} in slot {pf.slot}, but the slot holds "
                    f"{'no request' if req is None else f'request {req.rid}'} "
                    f"(statically detectable as R006)"
                )
            with profiler_span(
                "serve.prefill", rid=pf.rid, start=pf.start, width=pf.width,
                bucket=pf.bucket, final=pf.final,
            ):
                toks = np.zeros((1, pf.bucket), np.int32)
                toks[0, : pf.width] = req.prompt[pf.start : pf.start + pf.width]
                t0 = rec.clock() if rec.enabled else 0.0
                logits, self.pool = self._prefill_fn(pf.bucket)(
                    self.params, self.pool, jnp.asarray(toks),
                    jnp.int32(pf.start), jnp.int32(pf.width),
                    jnp.asarray(self._tables[pf.slot]),
                )
            if pf.final:
                with profiler_span("serve.readback", of="prefill"):
                    tok = int(jnp.argmax(logits[0, -1]))
                new_tokens[pf.slot] = tok
                if tok == self.eos_id:
                    eos_slots.add(pf.slot)
            if rec.enabled:
                jax.block_until_ready(logits)
                rec.emit(
                    f"step{plan.index}/prefill"
                    f"[r{pf.rid}@{pf.start}+{pf.width}]",
                    "chip", t0, rec.clock(), kind="prefill",
                    rid=pf.rid, slot=pf.slot, bucket=pf.bucket,
                )

        if plan.decode_slots:
            with profiler_span(
                "serve.decode", slots=len(plan.decode_slots)
            ) as span:
                toks = np.zeros((self.slots, 1), np.int32)
                lengths = np.zeros((self.slots,), np.int32)
                tables = np.full_like(self._tables, scratch)
                for s in plan.decode_slots:
                    req = self.slot_req[s]
                    state = self.sched.slot_state(s)
                    if req is None or state is None:
                        raise RuntimeError(
                            f"step {plan.index}: decode batch includes slot "
                            f"{s} with no admitted request (statically "
                            f"detectable as R006)"
                        )
                    toks[s, 0] = req.output[-1]
                    lengths[s] = state.length
                    tables[s] = self._tables[s]
                span.set_metadata(kv_tokens=int(lengths.sum()))
                t0 = rec.clock() if rec.enabled else 0.0
                logits, self.pool = self._decode(
                    self.params, self.pool,
                    self._slot_sharded(jnp.asarray(toks)),
                    self._slot_sharded(jnp.asarray(lengths)),
                    self._slot_sharded(jnp.asarray(tables)),
                )
            with profiler_span("serve.readback", of="decode"):
                nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
            if rec.enabled:
                rec.emit(
                    f"step{plan.index}/decode[{len(plan.decode_slots)}]",
                    "chip", t0, rec.clock(), kind="decode",
                    slots=len(plan.decode_slots),
                )
            for s in plan.decode_slots:
                tok = int(nxt[s])
                new_tokens[s] = tok
                if tok == self.eos_id:
                    eos_slots.add(s)

        with profiler_span("serve.commit"):
            res = self.sched.commit(plan, frozenset(eos_slots))
        dur = iv.stop()
        self.sched.advance(dur)
        t_end = self.sched.clock
        self.step_log.append(plan.signature())
        self.step_durations.append(dur)
        for slot, tok in new_tokens.items():
            req = self.slot_req[slot]
            if req is None:
                raise RuntimeError(
                    f"step {plan.index}: token produced for slot {slot} "
                    f"with no admitted request (statically detectable "
                    f"as R006)"
                )
            req.output.append(tok)
            req.token_times_s.append(t_end)
            if len(req.output) == 1:
                req.ttft_s = t_end - req.arrival_s
        for rid in res.finished:
            req = self.requests[rid]
            req.done = True
            req.e2e_s = t_end - req.arrival_s
            self.finished.append(req)
            for s, r in enumerate(self.slot_req):
                if r is not None and r.rid == rid:
                    self.slot_req[s] = None
                    self._tables[s] = scratch
        if rec.enabled:
            rec.counter(
                "kv_free_blocks", "chip", self.sched.allocator.num_free
            )
            rec.counter(
                "live_slots", "chip",
                sum(r is not None for r in self.slot_req),
            )

    def run_until_done(self, max_steps: int = 100_000) -> list[Request]:
        steps = 0
        while self.sched.outstanding():
            if not self.step():
                queued = [q.rid for q in self.sched.queue]
                live = [r.rid for r in self.slot_req if r is not None]
                raise RuntimeError(
                    f"serving stalled at step {len(self.step_log)} with "
                    f"work outstanding (queued requests {queued}, live "
                    f"requests {live})"
                )
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"serving did not converge within {max_steps} steps "
                    f"({len(self.finished)}/{len(self.requests)} requests "
                    f"finished)"
                )
        return self.finished


# -- cache splicing helpers ----------------------------------------------------


def _batch_axis(full, one) -> int:
    """First axis where the shapes differ (slots vs 1: the batch axis)."""
    for i, (f, o) in enumerate(zip(full.shape, one.shape)):
        if o != f:
            return i
    return 0


def splice_cache(full, one, slot: int) -> object:
    """Functional helper: write sequence-0 of `one` into slot `slot` of
    `full` (non-paged whole-cache path; kept separate for unit testing)."""

    def leaf(f, o):
        ax = _batch_axis(f, o)
        start = [0] * f.ndim
        start[ax] = slot
        return jax.lax.dynamic_update_slice(f, o.astype(f.dtype), tuple(start))

    return jax.tree_util.tree_map(leaf, full, one)
