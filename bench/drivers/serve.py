"""Serving cell: ``ServeEngine.submit``/``step`` under open-loop traffic.

Set-up draws the weights on the device in one compiled program, builds the
engine and runs ``engine.warmup()`` (every prefill bucket up to the chunk,
and the decode batch).  In the window the harness submits each request at
its due time on its own wall clock and calls ``engine.step()``; when there
is nothing to do it waits for the next due time.  A request is timed from
when it was due: its first token and every later one are stamped when
``step()`` returns with them (the engine reads each token back to the host
inside the step).  The engine's own scheduler clock is not used.

Requests due in the window that have no token when it closes are served on
(at most ``DRAIN_S``) so that their time to first token is known; work done
after the close does not count towards a rate.

After the window a sample of the finished requests, drawn from the seed and
with the longest among them, is compared with the reference.
"""
from __future__ import annotations

import gc
import time
from collections import deque

DRAIN_S = 60.0


def _compiled_bytes(engine, eng_conf: dict, env) -> int:
    """The larger of what the engine's decode program and its widest
    prefill program ask of the device's memory, compiled for the arguments
    its warm-up gives them (the same programs, so the warm-up finds them in
    the compile cache)."""
    import jax.numpy as jnp

    from bench import program

    S, chunk = eng_conf["slots"], eng_conf["chunk"]
    tables = jnp.asarray(engine._tables)
    decode = program.compiled_bytes(engine._decode.lower(
        engine.params, engine.pool, jnp.zeros((S, 1), jnp.int32),
        jnp.zeros((S,), jnp.int32), tables))
    prefill = program.compiled_bytes(engine._prefill_fn(chunk).lower(
        engine.params, engine.pool, jnp.zeros((1, chunk), jnp.int32),
        jnp.int32(0), jnp.int32(chunk), tables[0]))
    env.log(f"[memory] compiled: decode {decode} B, prefill chunk {prefill} B")
    return max(decode, prefill)


def run(cell, env) -> dict:
    import jax
    import numpy as np

    from bench import counts, program, traffic
    from bench.check import serve as scheck
    from bench.stats import durations_line, nearest_rank
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    conf, tr = cell.config, cell.traffic
    m, eng_conf = conf["model"], conf["engine"]
    fam = cell.family()
    pdtype = conf["program"]["param_dtype"]
    seed, seconds = env.seed, env.seconds
    cfg = program.arch_config(conf)
    model = build_model(cfg)
    program.check_params(model, fam.abstract(m, pdtype))
    params = fam.init(m, seed, pdtype)
    engine = ServeEngine(
        model, params, slots=eng_conf["slots"], max_len=eng_conf["max_len"],
        eos_id=None, block_size=eng_conf["block_size"], chunk=eng_conf["chunk"],
    )
    del params
    compiled = _compiled_bytes(engine, eng_conf, env)
    env.hooks.get("engine", lambda e: None)(engine)
    engine.warmup()

    reqs = traffic.requests(tr, seconds)
    V = m["vocab_size"]
    for r in reqs:
        if r.prompt_len + r.output_len - 1 > eng_conf["max_len"]:
            raise ValueError(f"request {r.rid} does not fit max_len")
    prompts = {r.rid: traffic.prompt_tokens(seed, r.rid, r.prompt_len, V)
               for r in reqs}
    backlog = tr["arrivals"] == "backlog"
    due_in_window = [r for r in reqs if r.due_s < seconds]
    pending = deque(due_in_window)
    objs: dict = {}
    seen: dict = {}
    tok_t: dict = {}
    admitted_t: dict = {}
    late: list = []
    steps_window = []              # (t_end, signature, decode lengths, traced)
    shape = counts.Shape.from_model(m)

    t0 = env.begin_window()
    now = 0.0
    closed_at = None
    n_done = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            if closed_at is None:
                closed_at = now
                queued_at_close = len(engine.sched.queue)
                reduced = env.end_window()
            waiting = [r for r in due_in_window
                       if r.rid in objs and not tok_t.get(r.rid)]
            if backlog or not waiting or now >= seconds + DRAIN_S:
                break
        traced = env.tick(now) if closed_at is None else False
        with jax.profiler.TraceAnnotation("bench.submit"):
            while pending and pending[0].due_s <= now:
                r = pending.popleft()
                req = Request(rid=r.rid, prompt=prompts[r.rid],
                              max_new_tokens=r.output_len)
                engine.submit(req)
                objs[r.rid] = req
                seen[r.rid] = 0
                late.append(now - r.due_s)
        lens = [s.length if s is not None else 0 for s in engine.sched.slots]
        t_before = now
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            progressed = engine.step()
        t_after = time.perf_counter() - t0
        if not progressed:
            nxt = pending[0].due_s if pending else seconds
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt - t_after, 0.002)))
            continue
        sig = engine.step_log[-1]
        steps_window.append((t_after, sig, [lens[s] for s in sig[3]], traced))
        for rid, _slot in sig[1]:
            admitted_t[rid] = t_before
        fin = engine.finished[n_done:]
        n_done = len(engine.finished)
        live = [r for r in engine.slot_req if r is not None] + fin
        for req in live:
            k = len(req.output)
            if k > seen[req.rid]:
                tok_t.setdefault(req.rid, []).extend([t_after] * (k - seen[req.rid]))
                seen[req.rid] = k
    if closed_at is None:
        queued_at_close = len(engine.sched.queue)
        reduced = env.end_window()
    peak = env.memory_peak()

    # -- end-to-end numbers ------------------------------------------------------
    # a request due in the window that never got a token counts as missing,
    # with the time it waited until serving stopped; a backlog is not
    # expected to be finished by the window
    ttft = [tok_t[r.rid][0] - r.due_s if tok_t.get(r.rid) else now - r.due_s
            for r in due_in_window]
    missing = 0 if backlog else sum(1 for r in due_in_window if not tok_t.get(r.rid))
    gaps = []
    for ts in tok_t.values():
        gaps += [b - a for a, b in zip(ts, ts[1:]) if b <= seconds]
    done_in = [s for s in steps_window if s[0] <= seconds]
    prefilled = sum(s[1][2][3] for s in done_in if s[1][2] is not None)
    generated = sum(1 for ts in tok_t.values() for t in ts if t <= seconds)
    metrics = {"serve_tokens_per_s": (prefilled + generated) / seconds}
    if ttft and not backlog:
        metrics["ttft_p90_ms"] = 1e3 * nearest_rank(ttft, 90)
    if gaps:
        metrics["tbt_p95_ms"] = 1e3 * nearest_rank(gaps, 95)
    env.log(f"[serve] {sum(1 for r in due_in_window if tok_t.get(r.rid))} first "
            f"tokens, {len(gaps)} token gaps, "
            f"{prefilled} prompt tokens and {generated} tokens in the window; "
            f"{queued_at_close} requests queued at its close")
    env.log(f"[serve] window: "
            f"{durations_line([s[0] for s in steps_window if s[0] <= seconds])}")
    if late:
        env.log(f"[generator] {len(late)} requests submitted; lateness max "
                f"{max(late) * 1e3:.3f} ms, p99 {nearest_rank(late, 99) * 1e3:.3f} ms")
    waits = [admitted_t[r.rid] - r.due_s for r in due_in_window if r.rid in admitted_t]

    # -- needed work of every step, for the rooflines ---------------------------------
    wbytes = shape.params * np.dtype(pdtype).itemsize
    work = {"prefill": [], "decode": []}
    for _t, sig, dlens, traced in steps_window:
        if sig[2] is not None:
            _slot, _rid, start, width, _final = sig[2]
            work["prefill"].append(counts.prefill_chunk(
                shape, start, width, weight_bytes=wbytes) + (traced,))
        if sig[3]:
            work["decode"].append(counts.decode_step(
                shape, dlens, weight_bytes=wbytes) + (traced,))
    from bench import serve_work

    run_rec = {
        "kind": "serve", "chips": env.chips, "peaks": env.peaks,
        "trace": reduced,
        "serve": {"queue_wait_s": waits, "work": work,
                  "traced_steps": sum(1 for s in steps_window if s[3]),
                  "queued_at_close": queued_at_close},
    }

    for kind in ("prefill", "decode"):
        if reduced is not None and serve_work.traced_work(run_rec, kind):
            env.log(f"[roofline] {kind}: {len(serve_work.traced_work(run_rec, kind))} "
                    f"calls traced, {serve_work.program_seconds(reduced, kind)} s on "
                    f"the device, bound by {serve_work.bound(run_rec, kind)}")

    # -- the reference, once the engine is gone -------------------------------------
    attempted = len(objs) if backlog else len(due_in_window)
    finished = [(r, objs[r.rid]) for r in due_in_window
                if r.rid in objs and objs[r.rid].done]
    sample = scheck.sample(finished, seed, tr["check_tokens"])
    cases = [(prompts[r.rid], list(req.output)) for r, req in sample]
    del engine, objs, finished, sample
    gc.collect()
    numbers, detail = scheck.compare(
        fam, m, seed, pdtype, cases, eng_conf["max_len"], tr["output"]["max"],
        control=env.hooks.get("control", False))
    env.hooks.get("detail", lambda d: None)(detail)
    env.log(f"[serve] checked {detail['requests']} requests, "
            f"{detail['tokens']} served tokens: {detail}")
    return {
        "attempted": attempted, "failed": missing, "metrics": metrics,
        "run": run_rec, "numbers": numbers, "memory_peak_bytes": peak,
        "memory_compiled_bytes": compiled, "detail": detail,
    }
